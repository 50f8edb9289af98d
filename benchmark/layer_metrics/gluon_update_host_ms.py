"""gluon loop: median host milliseconds of one ``gluon.Trainer.step``
call in the window, from the driver's own clock around the call."""
import statistics


def read(data):
    spans = data["counters"].get("trainer_step_s")
    if not spans:
        return None
    return statistics.median(spans) * 1e3
