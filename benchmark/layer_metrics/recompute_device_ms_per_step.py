"""train step: device milliseconds a step spent re-running the forward
inside the backward: ops whose name stack holds ``jax.checkpoint``'s
``rematted_computation`` (``spmd.TrainStep(remat=True)``, one boundary a
declared layer), whatever their scope.  The price of the memory the
remat boundaries save."""
import scoperead


def read(data):
    return scoperead.scopes().ms_per_step(data, "phases", "recompute")
