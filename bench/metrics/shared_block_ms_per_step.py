"""shared_block_ms_per_step: device time per traced step under the
program's `hybrid.shared` scope (the hybrid family's shared block: concat,
norms, projections and adapters, attention, MLP and the site's `linear`),
in forward, recompute and backward, on the device with the most
(bench/scopes_named.py)."""
from bench import scopes_named


def read(r):
    return scopes_named.shared_ms(r.summary, scopes_named.SHARED)
