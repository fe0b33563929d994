"""Whole-step share of the chip's int8 peak: the model's operations per
image (bench/counts.py) times the images per second of the untraced
window, the profiler off."""
from bench import counts


def read(rec):
    rate = rec["images"] / rec["window_s"]
    return 100.0 * counts.ops_per_image(rec["config"]) * rate / rec["peak"]["int8_ops_per_s"]
