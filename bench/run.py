#!/usr/bin/env python3
"""FALKON chip benchmark: run one cell of BENCHMARK.json.

    python bench/run.py --workload susy_fit --seed 7 --seconds 30 --trace 0

From the root of a checkout. Exits 2 with no result line unless JAX finds a
TPU with at least the cell's chips. ``--trace 0`` reports the cell's
end-to-end metrics, ``--trace 1`` its per-layer metrics from a profiler
trace of the benchmark's own spans. Either way the last line of standard
output is one JSON object (correct, attempted, failed, metrics, device,
breakdown with --trace 1, then the compared numbers beside their limits),
and the last lines of standard error give the same comparisons.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))
# the TPU runtime logs to /tmp/tpu_logs unless told otherwise; keep its
# writes under this run's TMPDIR
os.environ.setdefault("TPU_LOG_DIR", os.path.join(tempfile.gettempdir(), "tpu_logs"))

from harness import cell as cells, compile_cache, output  # noqa: E402


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def run(args, *, require_tpu: bool = True, t0: float = T0, cell=None):
    """Run the cell; returns (result line, Result) or None without a chip.
    Tests pass ``require_tpu=False`` (no look for a chip, no compile cache)
    and a ``cell`` cut to a CPU's size in place of the one BENCHMARK.json
    names."""
    cell = cell or cells.load_cell(args.workload)
    import jax
    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu" or len(devices) < cell.chips):
        print(f"bench: {args.workload} needs {cell.chips} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s). Nothing was run.",
              file=sys.stderr)
        return None
    if require_tpu:
        compile_cache.enable()
    cells.program_path()
    limits = json.loads((cells.BENCH / "limits" / f"{cell.name}.json").read_text())
    readers = {m["name"]: cells.metric_reader(m["name"]).read for m in cell.per_layer}
    result = cells.driver(cell).run(cell, args.seed, args.seconds, bool(args.trace),
                                    t0, limits["limits"])
    line = output.result_line(cell, result, devices, bool(args.trace), readers)
    return line, result


def main(argv=None) -> int:
    out = run(parse(argv))
    if out is None:
        return 2
    line, result = out
    output.print_checks(result)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
