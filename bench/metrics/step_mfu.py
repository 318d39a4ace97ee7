"""step_mfu: the whole training step's share of the chips' bf16 peak,
6 N tokens/s / (chips x peak), from the untraced window's rate."""
from bench.flops import train_flops


def read(r):
    if not r.tokens_per_s:
        return None
    return 100.0 * train_flops(r.n_params, r.tokens_per_s) / (
        r.chips * r.peak["bf16_flops_per_s"])
