"""Whole ResNet training step's share of the chip's bf16 peak: 2 x MACs,
forward + backward, times the images per second of this run's window."""
from benchmarks.flops import resnet


def read(ctx):
    f = ctx["facts"]
    if "images_per_s" not in f:
        return None
    cfg = f["config"]
    flops = resnet.train_flops_per_image(cfg["depth"], cfg["image_size"],
                                         cfg["class_num"])
    return 100.0 * flops * f["images_per_s"] / (
        ctx["cell"].chips * ctx["peaks"]["bf16_flops_per_s"])
