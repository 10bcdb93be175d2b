"""Model FLOPs of the trained samples per second, over the chips' bf16
peak, in percent: ``flops.py`` per sample x the window's samples/s over
chips x peak. Moves ``samples_per_s``."""
from flops import train_flops_per_sample


def read(run):
    per_sample = train_flops_per_sample(run["cell"].config)
    peak = run["chips"] * run["peak"]["bf16_flops_per_s"]
    return 100.0 * per_sample * run["samples_per_s"] / peak
