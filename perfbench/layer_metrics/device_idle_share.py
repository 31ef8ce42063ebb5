"""device_idle_share.<mix>, for every mix: 1 - union of device-op intervals
/ traced window, in percent, mean over the chips used. Nothing to read
without a trace."""

from perfbench import trace


def read(res):
    if res.get("trace") is None:
        return None
    busy_s, window_s = trace.busy_and_window(res["trace"])
    if window_s <= 0 or busy_s <= 0:
        return None
    return 100.0 * (1.0 - busy_s / window_s)
