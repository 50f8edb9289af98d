"""device: ``peak_bytes_in_use`` of the fullest chip as the backend
reports it, in GB.  Doubtful (PERF.md section 7): recorded, never a
bound."""


def read(data):
    peak = data.get("memory_peak_bytes")
    return peak / 1e9 if peak else None
