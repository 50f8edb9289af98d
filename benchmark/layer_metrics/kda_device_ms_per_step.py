"""operators: device milliseconds a step under ``kimi/kda``: the Kimi
Delta Attention layers whole (projections, short convolutions, gates,
the chunked delta-rule scan, the gated norm and the output projection);
forward, backward and recomputation together.  What sets the pace of
Kimi Linear's cell; its ``scan`` class in ``trace/scopes.py`` is a part
of it.  None on a program without that scope."""
import scopepath


def read(data):
    return scopepath.ms_per_step(data, "kimi/kda")
