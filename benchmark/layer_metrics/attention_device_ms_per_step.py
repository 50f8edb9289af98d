"""operators: device milliseconds a step under an ``*/attention`` scope
(``granite/attention``, with ``solar/attention`` or
``nemotron/attention`` around it): the projections, the three
``mx_flash_attention_*`` kernels, the output gate; forward, backward
and recomputation together."""
import scoperead


def read(data):
    return scoperead.scopes().ms_per_step(data, "classes", "attention")
