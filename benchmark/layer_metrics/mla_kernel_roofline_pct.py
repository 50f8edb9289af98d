"""operators: the flash attention kernels' share of their roofline in Kimi
Linear's cell, where they run at keys of 192 over values of 128 in its
latent attention layer.  Read as ``attention_kernel_roofline_pct`` reads
SDAR's: the least time the chip could take (the larger of the
configuration's ``attention_kernel_flops`` over the table's bf16 peak and
its ``attention_kernel_bytes`` over the HBM rate) over the device seconds
a step of the ops named ``mx_flash_attention_*``.  The backward kernels
recompute the scores, 9 products run for the 6 counted, so it cannot pass
67 % while compute bounds it.  None where the configuration has no such
functions, the trace no such op, or the device no table peak."""
import os

import benchcore as C

_SHARE = C.load_py(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "attention_kernel_roofline_pct.py"),
                   "benchmark_metric_attention_kernel_roofline_pct")


def read(data):
    return _SHARE.read(data)
