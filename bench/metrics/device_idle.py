"""Share of the traced window in which no op ran on the device, in
percent: 1 - (union of the device op intervals) / window."""

from bench.devtrace import busy_seconds


def read(obs):
    if not obs.device_ops:
        return None
    lo, hi = obs.window
    return 100.0 * (1.0 - busy_seconds(obs.device_ops) / (hi - lo))
