"""collectives: milliseconds per step in which a collective operation
(all-reduce, all-gather, reduce-scatter, ...) ran on device 0."""


def read(data):
    trace = data["trace"]
    if not trace.get("steps") or not trace.get("collective_s"):
        return None
    return trace["collective_s"] / trace["steps"] * 1e3
