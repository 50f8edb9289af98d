"""operators: device milliseconds a step under ``kimi/attention``: the
multi-head latent attention layer whole (the projections to the queries
and the latent, the latent's norm and its expansion to every head's keys
and values, the three flash kernels, the output projection); forward,
backward and recomputation together.  The ``attention`` class of
``trace/scopes.py`` in Kimi Linear's cell.  None on a program without
that scope."""
import scopepath


def read(data):
    return scopepath.ms_per_step(data, "kimi/attention")
