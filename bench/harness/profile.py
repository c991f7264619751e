"""One profiler trace of the benchmark's own spans, reduced in place."""
from __future__ import annotations

import contextlib
import shutil
import tempfile

from . import xtrace


@contextlib.contextmanager
def traced():
    """Trace the body; yields a dict that holds the reduced ``Trace`` under
    ``"trace"`` once the body has ended. The raw trace is written under
    ``TMPDIR`` and deleted once read."""
    import jax
    out: dict = {}
    log_dir = tempfile.mkdtemp(prefix="bench_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    try:
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        try:
            yield out
        finally:
            jax.profiler.stop_trace()
        out["trace"] = xtrace.Trace.from_file(xtrace.find_xplane(log_dir))
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)


def device_summary(trace: xtrace.Trace, windows) -> tuple[float, float, dict]:
    """(busy seconds, window seconds, breakdown) of the traced windows."""
    window_ns = sum(hi - lo for lo, hi in windows)
    busy_ns = trace.busy_ns(windows)
    breakdown = {"device_ops": trace.top_ops(windows),
                 "idle_gaps": trace.idle_gaps(windows)}
    return busy_ns * 1e-9, window_ns * 1e-9, breakdown
