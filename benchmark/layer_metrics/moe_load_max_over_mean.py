"""expert layer: the busiest held expert's assignments over the mean of
the held experts, in the layer where that ratio is largest, of the sums
over all the run's steps: the gauge
``mxnet_moe_expert_load_max_over_mean`` as the driver's
``record_expert_load`` call set it after the window (1 = even).  None on
a program without the gauge."""


def read(data):
    from mxnet_tpu import telemetry
    gauge = telemetry.REGISTRY.get("mxnet_moe_expert_load_max_over_mean")
    return None if gauge is None else gauge.value()
