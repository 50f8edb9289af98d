"""expert layer: the share of the rows the routed experts' grouped
products ran that were padding, every held expert's last tile counted
whole: 100 x (1 - ``mxnet_moe_assignments_held`` /
``mxnet_moe_rows_computed``), the two gauges as the driver's
``record_expert_load`` call set them after the window (means over all the
run's steps).  None on a program without the gauges, or where no row was
computed."""


def read(data):
    from mxnet_tpu import telemetry
    held, rows = (telemetry.REGISTRY.get(name) for name in (
        "mxnet_moe_assignments_held", "mxnet_moe_rows_computed"))
    if held is None or rows is None or not rows.value():
        return None
    return 100.0 * (1.0 - held.value() / rows.value())
