"""sweep_roofline: (t + 1) times the least time of one sweep on this
chip, max(FLOPs / peak FLOP/s, bytes / peak B/s) from harness.work.sweep,
over the device-busy time of the traced ``falkon_solve`` call, in percent.
The count is the algorithm's, whatever implements the sweep."""


def read(obs):
    trace, windows = obs.get("trace"), obs.get("windows", {}).get("solve")
    if trace is None or not windows or not trace.devices:
        return None
    busy_s = trace.busy_ns(windows) * 1e-9
    if busy_s <= 0:
        return None
    least, _bound = obs["work_sweep"].least_seconds(obs["peaks"])
    return 100.0 * (obs["iterations"] + 1) * least / busy_s
