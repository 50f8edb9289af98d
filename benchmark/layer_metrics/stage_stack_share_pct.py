"""input staging: share of the scanned window's staging spent stacking
the K host batches (``io/stage_super/host_stack``: pulling them out of
their arrays and ``np.stack``) rather than handing the stack to
``jax.device_put`` (``io/stage_super/device_put``)."""
import spanread

STACK = ("io/stage_super/host_stack",)
PUT = ("io/stage_super/device_put",)


def read(data):
    stack = spanread.median_ms_per_step(data, STACK)
    put = spanread.median_ms_per_step(data, PUT)
    if stack is None or put is None or stack + put <= 0:
        return None
    return 100.0 * stack / (stack + put)
