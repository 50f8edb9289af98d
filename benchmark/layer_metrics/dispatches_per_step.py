"""train step: programs run on device 0 per training step, counted on
the ``XLA Modules`` line of the trace.  Where the step object counts its
own launches (the fused step, the scanned window, the spmd step) the two
must agree to within one program per sync; a disagreement is reported as
not available rather than as either number."""


def read(data):
    trace, counters = data["trace"], data["counters"]
    if not trace.get("steps") or "program_runs" not in trace:
        return None
    traced = trace["program_runs"] / trace["steps"]
    if "program_launches" in counters and counters.get("steps"):
        own = counters["program_launches"] / counters["steps"]
        # the copies in and the reads out are programs too on some
        # runtimes: the trace may count more, never fewer
        if traced + 1e-9 < own:
            return None
    return traced
