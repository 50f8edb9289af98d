"""expert layer: device milliseconds a step under ``zaya/moe/router``: the
router that is a network with a state of its own (a down-projection, the
depth averaging, an RMSNorm, an MLP of two GELU layers), in float32 at the
highest precision; forward, backward and recomputation together.  A part of
``moe``'s class in ``trace/scopes.py``; the scores, the top-k and the plan
inside the routed-expert op are not in it.  None on a program without that
scope."""
import scopepath


def read(data):
    return scopepath.ms_per_step(data, "zaya/moe/router")
