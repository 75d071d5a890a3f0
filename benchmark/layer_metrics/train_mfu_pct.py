"""Model FLOP/s utilisation: the FLOPs forward and backward require per image
(``rooflines.train_flops_per_image``) x images/s over chips x the peak."""
from benchmark import rooflines


def read(run):
    if run.peaks is None:
        return None
    flops = rooflines.train_flops_per_image(run.dalle_cfg)
    return 100.0 * flops * run.outcome.host["images_per_s"] / (
        len(run.devices) * run.peaks["bf16_flops"])
