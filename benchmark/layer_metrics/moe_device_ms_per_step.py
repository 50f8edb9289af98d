"""expert layer: device milliseconds a step under a ``*/moe/`` scope
(``solar/moe``, ``nemotron/moe``): the router, the dispatch, the walk
over the held experts' tiles, the shared expert and the combine;
forward, backward and recomputation together."""
import scoperead


def read(data):
    return scoperead.scopes().ms_per_step(data, "classes", "moe")
