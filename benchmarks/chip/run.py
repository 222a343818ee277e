#!/usr/bin/env python3
"""Chip benchmark of the repository's pipelined training path.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the TPU this process finds and
prints, as the last line of standard output, one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics with
``--trace 0``, its per-layer metrics with ``--trace 1``), ``device``, with
``--trace 1`` a ``breakdown``, and last ``compared``: each number the check
compared with its limit.  The same numbers end standard error.  Without a
TPU, or with fewer chips than the cell asks for, it exits with status 3 and
prints no result; without the program (``src/repro``) beside it, status 2.
"""
from __future__ import annotations

import os
import time


def _process_age() -> float:
    """Seconds since this process started (Linux /proc)."""
    with open("/proc/self/stat") as f:
        ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return max(0.0, uptime - ticks / os.sysconf("SC_CLK_TCK"))


T_START = time.perf_counter() - _process_age()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
# libtpu writes its logs under /tmp unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT / "src"), str(HERE)]
    try:
        import repro  # noqa: F401
    except ImportError as e:
        print(f"chip benchmark: the program is not here ({e})",
              file=sys.stderr)
        return 2
    from chipbench import harness, spec

    cell = spec.load_cell(ROOT, args.workload)
    harness.use_compile_cache(ROOT / ".jax_cache")
    trace_dir = HERE / ".trace"
    shutil.rmtree(trace_dir, ignore_errors=True)
    try:
        result = harness.run(cell, seed=args.seed, seconds=args.seconds,
                             trace=bool(args.trace), t_start=T_START,
                             trace_dir=trace_dir)
    except harness.NoChip as e:
        print(f"chip benchmark: {e}", file=sys.stderr)
        return 3
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
