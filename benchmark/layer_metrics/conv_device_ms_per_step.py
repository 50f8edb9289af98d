"""operators: device milliseconds a step under ``op/Convolution``,
forward and backward together.  A fusion is named for what XLA fused
into it: one that holds a convolution and a BatchNorm gives each half
its time."""
import scoperead


def read(data):
    return scoperead.scopes().ms_per_step(data, "classes", "conv")
