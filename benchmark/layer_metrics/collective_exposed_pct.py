"""collectives: the part of device 0's collective time during which no
other operation ran there, as a share of the traced window (the step
time it costs)."""


def read(data):
    trace = data["trace"]
    if not trace.get("window_s") or "collective_exposed_s" not in trace \
            or not trace.get("collective_s"):
        return None
    return 100.0 * trace["collective_exposed_s"] / trace["window_s"]
