"""train step: device milliseconds a step in the optimizer's update: ops
under ``step/optimizer`` (the fused steps' ``fused_update``, the
``spmd.TrainStep`` loop over its functional updates) or under an
``op/*_update`` operator (the Gluon trainer's ``multi_sgd_mom_update``
programs).  An op fused from several classes gives each an equal part."""
import scoperead


def read(data):
    return scoperead.scopes().ms_per_step(data, "classes", "optimizer")
