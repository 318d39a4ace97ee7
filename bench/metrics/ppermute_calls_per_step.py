"""ppermute_calls_per_step: collective-permute operations per step in the
trace ("-done" halves of asynchronous ones not counted again), on the
device that runs most."""


def read(r):
    s = r.summary
    if s is None or not any(s.permute_calls.values()):
        return None
    return max(s.permute_calls.values()) / s.steps
