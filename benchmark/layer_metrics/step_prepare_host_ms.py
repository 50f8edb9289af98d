"""train step: median host milliseconds per training step the step
object spends around the call of its program, as self time of the
program's spans: ``fit/step/prepare`` + ``fit/step/writeback``, the
scanned window's ``fit/window/prepare`` + ``fit/window/writeback`` over
K, ``spmd/step/prepare``."""
import spanread

AROUND = ("fit/step/prepare", "fit/step/writeback", "fit/window/prepare",
          "fit/window/writeback", "spmd/step/prepare")


def read(data):
    return spanread.median_ms_per_step(data, AROUND, self_time=True)
