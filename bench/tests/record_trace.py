#!/usr/bin/env python3
"""Record the small chip trace that tests/test_xtrace.py reads.

    python bench/tests/record_trace.py OUT_DIR

Inside one ``bench.test`` span: three calls of a jitted 2048^3 bf16 matmul
chain, a 50 ms host sleep in span ``host.sleep`` with the device idle, then
three more calls. Writes ``OUT_DIR/small.xplane.pb`` and
``OUT_DIR/small.json`` (the host-clock facts the test checks against).
"""
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from harness import xtrace  # noqa: E402


def main(out_dir: str) -> int:
    out = Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)
    f = jax.jit(lambda a: (a @ a) @ a)
    a = jnp.ones((2048, 2048), jnp.bfloat16) * 0.01
    jax.block_until_ready(f(a))
    log_dir = tempfile.mkdtemp(prefix="record_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.test"):
        for _ in range(3):
            jax.block_until_ready(f(a))
        with jax.profiler.TraceAnnotation("host.sleep"):
            time.sleep(0.05)
        for _ in range(3):
            jax.block_until_ready(f(a))
    jax.profiler.stop_trace()
    src = xtrace.find_xplane(log_dir)
    shutil.copy(src, out / "small.xplane.pb")
    shutil.rmtree(log_dir, ignore_errors=True)
    tr = xtrace.Trace.from_file(str(out / "small.xplane.pb"))
    facts = {"device_kind": jax.devices()[0].device_kind, "calls": 6,
             "matmuls_per_call": 2, "n": 2048, "sleep_s": 0.05,
             "planes": sorted(tr.devices), "span": tr.spans("bench.test")}
    (out / "small.json").write_text(json.dumps(facts, indent=1))
    print(json.dumps(facts))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
