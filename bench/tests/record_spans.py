#!/usr/bin/env python3
"""Record the chip trace that tests/test_spans.py reads: program spans with
programs launched inside them.

    python bench/tests/record_spans.py OUT_DIR

Inside one ``bench.test`` span, the root program span ``falkon.test`` holds:
``falkon.test.async``, which launches ASYNC calls of a jitted 4096^3 bf16
matmul chain and does not wait for them, so their device work outlasts the
span; a host span ``host.wait`` that waits for them; ``falkon.test.sync``,
which launches SYNC calls and waits for each; and ``falkon.test.sleep``, a
50 ms host sleep that launches nothing. Each launching span counts its calls
as ``sweeps``. Writes ``OUT_DIR/spans.xplane.pb`` and ``OUT_DIR/spans.json``
(the host facts the test checks against).
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

from harness import spans, xtrace  # noqa: E402

ASYNC, SYNC, N, SLEEP_S = 40, 4, 4096, 0.05


def main(out_dir: str) -> int:
    out = Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)
    f = jax.jit(lambda a: (a @ a) @ a)
    a = jnp.ones((N, N), jnp.bfloat16) * 0.01
    jax.block_until_ready(f(a))
    log_dir = tempfile.mkdtemp(prefix="record_spans_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.test"):
        with jax.profiler.TraceAnnotation("falkon.test"):
            with jax.profiler.TraceAnnotation("falkon.test.async", sweeps=ASYNC):
                outs = [f(a) for _ in range(ASYNC)]
            with jax.profiler.TraceAnnotation("host.wait"):
                jax.block_until_ready(outs)
            with jax.profiler.TraceAnnotation("falkon.test.sync", sweeps=SYNC):
                for _ in range(SYNC):
                    jax.block_until_ready(f(a))
            with jax.profiler.TraceAnnotation("falkon.test.sleep"):
                time.sleep(SLEEP_S)
    jax.profiler.stop_trace()
    shutil.copy(xtrace.find_xplane(log_dir), out / "spans.xplane.pb")
    shutil.rmtree(log_dir, ignore_errors=True)
    tr = spans.SpanTrace.from_file(str(out / "spans.xplane.pb"))
    facts = {"device_kind": jax.devices()[0].device_kind, "n": N, "sleep_s": SLEEP_S,
             "launches": {"falkon.test.async": ASYNC, "falkon.test.sync": SYNC,
                          "falkon.test.sleep": 0},
             "matmuls_per_call": 2, "test": tr.spans("bench.test"),
             "wait": tr.spans("host.wait")}
    (out / "spans.json").write_text(json.dumps(facts, indent=1))
    print(json.dumps(facts))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
