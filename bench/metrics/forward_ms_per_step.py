"""forward_ms_per_step: device time per traced step of the forward pass:
operations under the program's `train.forward` scope that are neither
recomputed nor transposed, on the device with the most (bench/scopes.py)."""
from bench import scopes


def read(r):
    got = scopes.of_run(r.summary)
    if got is None or got.split is None:
        return None
    return got.split.per_step_ms("forward")
