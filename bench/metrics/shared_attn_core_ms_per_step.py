"""shared_attn_core_ms_per_step: device time per traced step under the
program's `hybrid.shared.attn` scope (the shared block's scores, mask,
softmax and PV, the part quadratic in the sequence), in forward, recompute
and backward, on the device with the most (bench/scopes_named.py)."""
from bench import scopes_named


def read(r):
    return scopes_named.shared_ms(r.summary, scopes_named.SHARED_ATTN)
