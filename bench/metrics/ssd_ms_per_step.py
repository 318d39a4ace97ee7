"""ssd_ms_per_step: device time per traced step of the SSD scan (ops under
the program's `ssm.ssd` scope, in forward, recompute and backward alike),
on the device with the most (bench/scopes.py)."""
from bench import scopes


def read(r):
    got = scopes.of_run(r.summary)
    if got is None or got.split is None or not any(got.split.ssd_s.values()):
        return None
    return 1e3 * max(got.split.ssd_s.values()) / got.split.steps
