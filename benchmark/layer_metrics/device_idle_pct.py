"""device: share of the traced window in which no operation ran, averaged
over the cell's chips."""


def read(data):
    trace = data["trace"]
    if not trace.get("window_s") or not trace.get("busy_s"):
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
