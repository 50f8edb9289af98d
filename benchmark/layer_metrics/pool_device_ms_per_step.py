"""operators: device milliseconds a step under ``op/Pooling`` (ResNet's
max pool, whose backward is a ``select-and-scatter``, and the global
average pools), forward and backward together."""
import scoperead


def read(data):
    return scoperead.scopes().ms_per_step(data, "classes", "pool")
