"""input staging: megabytes (1e6 bytes) per training step handed to
``jax.device_put`` by the program's staging, from the deltas of
``mxnet_io_stage_bytes_total`` in the span records.  A count: the batch's
bytes, labels included, and it repeats exactly."""
import spanread

STAGE = ("io/stage_batch", "io/stage_super", "spmd/step/shard_batch")


def read(data):
    got = spanread.counter_per_step(data, "mxnet_io_stage_bytes_total",
                                    STAGE)
    return None if got is None else got / 1e6
