"""operators: device milliseconds a step under ``op/BatchNorm``
(statistics, normalisation and their backward), forward and backward
together; a fusion shared with a convolution gives each half."""
import scoperead


def read(data):
    return scoperead.scopes().ms_per_step(data, "classes", "batchnorm")
