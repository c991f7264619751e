"""device_idle_pct.fit: 1 - (union of device-op intervals) / window over
the traced whole fit (span ``bench.fit``), in percent."""


def read(obs):
    trace, windows = obs.get("trace"), obs.get("windows", {}).get("fit")
    if trace is None or not windows or not trace.devices:
        return None
    length = sum(hi - lo for lo, hi in windows)
    return 100.0 * (1.0 - trace.busy_ns(windows) / length)
