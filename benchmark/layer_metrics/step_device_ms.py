"""train step: device-busy milliseconds per training step, the union of
the intervals in which an operation ran, averaged over the cell's
chips, over the steps traced."""


def read(data):
    trace = data["trace"]
    if not trace.get("steps") or not trace.get("busy_s"):
        return None
    return trace["busy_s"] / trace["steps"] * 1e3
