"""gluon loop: updater calls (one optimizer program each) per
``gluon.Trainer.step``, from the deltas of
``mxnet_trainer_update_calls_total`` in the records of the span
``gluon/trainer/update``.  A count: it repeats exactly."""
import spanread


def read(data):
    return spanread.counter_per_step(
        data, "mxnet_trainer_update_calls_total", ("gluon/trainer/update",))
