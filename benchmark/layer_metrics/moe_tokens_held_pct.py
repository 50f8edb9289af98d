"""expert layer: of the tokens of a step, all layers counted, the share
whose ONE expert lies here: the gauge ``mxnet_moe_assignments_held`` (the
mean a step the driver's ``record_expert_load`` call set after the window)
over tokens a step × layers × experts a token.  50 under an even router
with half the experts held; what a seed reads says why it was fast.
``better: lower`` by convention only: it is a reading of the routing, not
a goal.  None on a program without the gauge."""
import benchcore as C


def read(data):
    from mxnet_tpu import telemetry
    gauge = telemetry.REGISTRY.get("mxnet_moe_assignments_held")
    if gauge is None:
        return None
    cell = data["cell"]
    cfg = C.Cell(cell["name"]).config
    slots = cell["batch"] * (int(cfg["image"][0]) - 1) \
        * cfg["num_hidden_layers"] * cfg["num_experts_per_tok"]
    return 100.0 * gauge.value() / slots
