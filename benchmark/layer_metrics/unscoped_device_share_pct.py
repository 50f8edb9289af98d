"""device: the share of the device's op time a step that ran under no
scope of the program's (``unscoped`` of ``benchmark/trace/scopes.py``):
copies and what XLA put in itself, ``jit(step)/mul`` glue between
operators, the eager programs of a metric.  What the class metrics
cannot see; None where the trace has no ``op/`` scope at all."""
import scoperead


def read(data):
    return scoperead.scopes().share_pct(data, "classes", "unscoped")
