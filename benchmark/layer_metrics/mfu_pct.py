"""device: model FLOP/s utilisation.  images/s of the un-profiled part
of the traced run's window, times the FLOPs one image's training step
needs (2 per multiply-accumulate of the configuration's convolutions and
dense layers, three forward passes' worth), over chips times the table
peak.  The same number as ``images_per_s`` against the chip's ceiling."""


def read(data):
    cell = data["cell"]
    if not cell.get("peak_flops"):
        return None
    return 100.0 * data["window"]["images_per_s"] * cell["flops_per_image"] \
        / (cell["chips"] * cell["peak_flops"])
