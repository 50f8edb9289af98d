"""train step: of the positions whose loss weights a step was handed
(block-diffusion training: the T positions of the noisy copy), the share
that carry loss, the masked ones: 100 x the delta of
``mxnet_diffusion_masked_positions_total`` over that of
``mxnet_diffusion_positions_total`` inside ``spmd/step/shard_batch``, over
the profiled steps.  About half under a level t ~ U(0, 1) a block.  None
on a program without the counters."""
import spanread

SPAN = ("spmd/step/shard_batch",)


def read(data):
    masked, positions = (spanread.counter_per_step(data, name, SPAN)
                         for name in (
                             "mxnet_diffusion_masked_positions_total",
                             "mxnet_diffusion_positions_total"))
    if masked is None or not positions:
        return None
    return 100.0 * masked / positions
