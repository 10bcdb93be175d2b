"""Model FLOPs of the trained samples per second, over the chips' bf16
peak, in percent: the cell's family's ``train_flops_per_sample`` x the
window's samples/s over chips x peak. Moves ``samples_per_s``."""


def read(run):
    cell = run["cell"]
    per_sample = cell.family.train_flops_per_sample(cell.config,
                                                    cell.traffic)
    peak = run["chips"] * run["peak"]["bf16_flops_per_s"]
    return 100.0 * per_sample * run["samples_per_s"] / peak
