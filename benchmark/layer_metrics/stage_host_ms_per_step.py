"""input staging: median host milliseconds per training step handing the
batch to the device, from the program's own spans: ``io/stage_batch``,
the scanned window's ``io/stage_super/host_stack`` +
``io/stage_super/device_put`` over K, or ``spmd/step/shard_batch``."""
import spanread

STAGE = ("io/stage_batch", "io/stage_super/host_stack",
         "io/stage_super/device_put", "spmd/step/shard_batch")


def read(data):
    return spanread.median_ms_per_step(data, STAGE)
