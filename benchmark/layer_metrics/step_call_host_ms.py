"""train step: median host milliseconds per training step inside the
call of the jitted step program, from the program's own dispatch span
(``fit/step/fused_dispatch``; ``fit/step/scan_dispatch`` over K;
``spmd/step/dispatch``).  The device waits for whatever the call does
before its program starts; ``step_host_args`` counts what it has to copy."""
import spanread


def read(data):
    return spanread.median_ms_per_step(data, spanread.DISPATCH)
