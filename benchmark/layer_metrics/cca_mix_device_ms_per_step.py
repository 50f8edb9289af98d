"""operators: device milliseconds a step under ``zaya/attention/mix`` or
``zaya/attention/rope``: what compressed convolutional attention puts
around its projections and the flash kernels (the two causal convolutions
over the q/k latents, the mean of q and k, the L2 norms and temperature,
the value's shift, the partial rotary turn); forward, backward and
recomputation together.  A part of ``attention``'s class in
``trace/scopes.py``.  None on a program without those scopes."""
import scopepath


def read(data):
    return scopepath.ms_per_step(data, "zaya/attention/mix",
                                 "zaya/attention/rope")
