"""operators: device milliseconds a step in the language models' head and
loss: ops under ``*/head`` (the final norm and the product with the
vocabulary's rows) or ``step/loss``; forward, backward (the table's
weight gradient) and recomputation together."""
import scoperead


def read(data):
    return scoperead.scopes().ms_per_step(data, "classes", "head")
