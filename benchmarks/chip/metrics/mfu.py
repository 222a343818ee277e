"""Model FLOP/s utilisation of the whole step over the traced window: the
forward and backward FLOPs per token (three forward passes, no recompute,
counted from the configuration by its reference's
``forward_flops_per_token``) times the window's tokens per second, over the
chips' bf16 peak, in percent."""


def read(run):
    if run.peaks is None:
        return None
    peak = run.peaks["bf16_flops_per_s"] * run.cell.chips
    return 100.0 * run.train_flops_per_token * run.tokens_per_s / peak
