"""What the readers of the device's time by scope share:
``benchmark/trace/scopes.py`` (beside ``reduce.py``, whose window it
keeps), loaded once a process so that eleven readers parse the trace
once."""
import functools
import os

import benchcore as C


@functools.lru_cache(maxsize=None)
def scopes():
    return C.load_py(os.path.join(C.CHECKOUT, "benchmark", "trace",
                                  "scopes.py"), "benchmark_trace_scopes")
