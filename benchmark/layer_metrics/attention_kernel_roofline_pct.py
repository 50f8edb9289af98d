"""operators: the flash attention kernels' share of their roofline.  The
least time the chip could take for a step's attention (the larger of
``attention_kernel_flops`` over the table's bf16 peak and
``attention_kernel_bytes`` over its HBM rate, both functions of the
configuration's shapes alone, kept in its ``.py``: the work the step
NEEDS, whatever computes it) over the device seconds a step spent in the
ops named ``mx_flash_attention_*``, forward, backward and recomputation
together.  The kernels recompute the scores in the backward and run the
forward again under a remat boundary, 11 products for the 6 counted, so
it cannot pass 55 % while compute bounds it.  None where the configuration
has no such functions, the trace no such op, or the device no table
peak."""
import benchcore as C

KERNELS = "mx_flash_attention_"


def read(data):
    trace, cell = data["trace"], data["cell"]
    if not cell.get("peak_flops") or not trace.get("steps"):
        return None
    found = C.Cell(cell["name"])
    mod = found.config_module()
    counts = [getattr(mod, name, None) for name in (
        "attention_kernel_flops", "attention_kernel_bytes")]
    seconds = sum(s for name, s in trace["device_ops"] if KERNELS in name) \
        / trace["steps"]
    if None in counts or not seconds:
        return None
    flops, nbytes = (count(found.config) for count in counts)
    least = flops / cell["peak_flops"]
    # the table's HBM rate of the chip whose peak the harness handed over
    for peak in C.load_json(C.CHECKOUT + "/benchmark/harness/peaks.json")[
            "peaks"].values():
        if peak["bf16_flops"] == cell["peak_flops"]:
            least = max(least, nbytes / peak["hbm_bytes_per_s"])
    return 100.0 * least / seconds
