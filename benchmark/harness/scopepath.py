"""Device time under one of the program's own scope paths: what
``benchmark/trace/scopes.py`` does by class, for a reader that wants a part
of a class (``zaya/attention/mix`` inside ``attention``, ``zaya/moe/router``
inside ``moe``).  The same trace, the same window (first to last
``bench/sync``), the same rows (``mxnet_tpu.profiler.device_ops``, ops that
only hold other ops dropped), the same division: an op fused from
primitives of several scopes gives each an equal part of its time.

Returns None, and never raises, wherever ``scopes.read`` has nothing to
read (no trace, no TPU plane, no ``op/`` scope, fewer than two syncs): a
program from before the scope was named then leaves the metric out."""
import functools

import scoperead


@functools.lru_cache(maxsize=1)     # one traced run a process
def _ops(path):
    from mxnet_tpu import profiler
    return profiler.device_ops(path)


def ms_per_step(data, *components):
    """Device milliseconds a step of the ops under a scope path that holds
    one of ``components`` whole; 0.0 where the trace has the program's
    scopes and nothing under these."""
    scopes = scoperead.scopes()
    try:
        whole = scopes.read(data)
        if whole is None:
            return None
        path = scopes.newest_trace()
        syncs = scopes.syncs_of(path)
        lo, hi = syncs[0], syncs[-1]
        ops = _ops(path)
        used = sorted({op.device for op in ops})[:int(data["cell"]["chips"])]
        wanted = scopes._has(*components)
        ns = 0.0
        for op in ops:
            a, b = max(op.start_ns, lo), min(op.start_ns + op.duration_ns, hi)
            named = [s for s in op.scopes if s]
            if op.device not in used or b <= a or not named \
                    or scopes.reduce.CONTAINER.match(op.name):
                continue
            ns += (b - a) / len(used) \
                * sum(1 for s in named if wanted.search(s)) / len(named)
        return ns * 1e-6 / whole["steps"]
    except Exception as e:   # a reader never takes the run down
        print(f"  device time under {'|'.join(components)}: not read "
              f"({type(e).__name__}: {e})", flush=True)
        return None
