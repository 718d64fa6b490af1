"""Share of the traced serving window in which the device ran nothing:
1 - busy / window, from the profiler trace (device layer)."""


def read(record):
    t = record.get("trace")
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
