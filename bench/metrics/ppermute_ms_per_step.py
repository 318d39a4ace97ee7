"""ppermute_ms_per_step: device time of the collective-permute operations
per step, on the busiest device."""


def read(r):
    s = r.summary
    if s is None or not any(s.permute_calls.values()):
        return None
    return 1e3 * max(s.permute_s.values()) / s.steps
