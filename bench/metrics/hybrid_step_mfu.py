"""hybrid_step_mfu: step_mfu's reading in the hybrid cell, 6 N tokens/s /
(chips x bf16 peak), N being the reference's `param_count`: for Zamba2 the
parameters a token's products pass through, the shared block once for each
site that applies it.  The attention's score and PV products are left out,
as the SSD scan's are.  The same number as step_mfu, whose list of cells
names only mamba2-780m.dp1; once that list names the hybrid cell, this
metric is redundant."""
from bench.metrics.step_mfu import read  # noqa: F401
