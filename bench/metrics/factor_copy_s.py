"""factor_copy_s: host seconds of the whole-factor copies between host and
device on the blocked factor route (the program's spans
``falkon.factor.download`` and ``falkon.factor.upload``) inside the traced
fit (span ``bench.fit``). Nothing where the fit made no such copy, or the
program marks none."""

COPIES = ("falkon.factor.download", "falkon.factor.upload")


def read(obs):
    trace, windows = obs.get("trace"), obs.get("windows", {}).get("fit")
    if trace is None or not windows:
        return None
    copies = [(s, e) for name in COPIES for s, e in trace.spans(name)
              if any(lo <= s < hi for lo, hi in windows)]
    if not copies:
        return None
    return sum(e - s for s, e in copies) * 1e-9
