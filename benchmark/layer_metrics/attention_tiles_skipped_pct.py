"""operators: of the (query tile, key tile) pairs a head of the flash
attention kernels' grid, the share the mask leaves empty: neither computed
nor copied in.  The gauge ``mxnet_flash_attention_tiles{mask, kind}``, set
as the program is traced, summed over the masks' kinds: 100 x empty /
(empty + partial + full).  ``better: higher`` by convention only: it is a
fact about the mask and the tile, not a goal.  None on a program without
the gauge, or where no call was traced."""

MASKS = ("none", "causal", "block_causal", "block_diffusion")
KINDS = ("empty", "partial", "full")


def read(data):
    from mxnet_tpu import telemetry
    gauge = telemetry.REGISTRY.get("mxnet_flash_attention_tiles")
    if gauge is None:
        return None
    tiles = {kind: sum(gauge.value({"mask": mask, "kind": kind})
                       for mask in MASKS) for kind in KINDS}
    if not sum(tiles.values()):
        return None
    return 100.0 * tiles["empty"] / sum(tiles.values())
