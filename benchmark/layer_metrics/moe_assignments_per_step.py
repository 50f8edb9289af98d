"""expert layer: (token, expert) assignments the routers sent to the
experts held here, all layers summed, a step: the gauge
``mxnet_moe_assignments_held`` as the driver's ``record_expert_load`` call
set it after the window (the mean over all the run's steps).  Fewer rows
walked is a faster step: it says why a seed was fast.  None on a program
without the gauge."""


def read(data):
    from mxnet_tpu import telemetry
    gauge = telemetry.REGISTRY.get("mxnet_moe_assignments_held")
    return None if gauge is None else gauge.value()
