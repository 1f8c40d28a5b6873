"""The device: what the profiler's trace and the backend's counters say."""


def _first(ctx):
    tr = ctx["trace"]
    if not tr:
        return None, None
    return tr, tr["devices"][tr["first_device"]]


def idle_pct(ctx):
    tr, dev = _first(ctx)
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - dev["busy_s"] / tr["window_s"])


def device_ms_per_round(ctx):
    tr, dev = _first(ctx)
    if not tr or not tr["rounds"] or dev["busy_s"] <= 0:
        return None
    return 1e3 * dev["busy_s"] / tr["rounds"]


def peak_hbm_gb(ctx):
    if ctx["platform"] != "tpu" or not ctx["memory_peak_bytes"]:
        return None
    return ctx["memory_peak_bytes"] / 1e9
