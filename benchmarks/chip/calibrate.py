#!/usr/bin/env python3
"""Readings from which a cell's limits are set (``limits/<cell>.json``).

    python3 benchmarks/chip/calibrate.py --workload <cell> \
        --seeds 1,2,...,12 --control-seeds 1,2,3 --out readings.jsonl

In one process on the chip, for each seed: the program's first three steps
through the same path a run takes (no warm-up call, a one-step window) and
the three numbers of the check against the float32 reference.  For each
control seed also, against the same reference: the control (the reference
in fp8, the precision below the configuration's bfloat16) and the faults of
a training cell planted in the reference put in the program's place --
half of the batch left out, the exchange between replicas left out (cells
with more than one replica), and one micro-batch's loss altered by 1 %
where the last stage produces it.  A state left unchanged reads 1 on both
norm gaps and needs no run.  One JSON line per seed; the lines a cell's
limits were set from are kept beside them, ``limits/<cell>.readings.jsonl``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
# libtpu writes its logs under /tmp unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def _numbers(check, out, ref):
    return check.compare({"losses": out["losses"], "grads": [out["grads"]],
                          "changes": [out["changes"]]}, ref)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(HERE)]
    from chipbench import check, harness, reference, spec

    cell = spec.load_cell(ROOT, args.workload)
    harness.use_compile_cache(ROOT / ".jax_cache")
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    tr = cell.traffic
    with open(args.out, "a") as out:
        for seed in (int(s) for s in args.seeds.split(",")):
            t0 = time.perf_counter()
            got: dict = {}
            harness.run(cell, seed=seed, seconds=0.0, trace=False,
                        t_start=t0, warmup=False, readings=got)
            ref = got["reference"]
            row = {"workload": cell.name, "seed": seed,
                   "program": got["numbers"],
                   "program_losses": got["program"]["losses"],
                   "reference_losses": ref["losses"]}
            if seed in controls:
                row["control"] = _numbers(
                    check, reference.run(cell, seed, numerics="fp8"), ref)
                row["half_batch"] = _numbers(
                    check, reference.run(cell, seed, fault="half_batch"), ref)
                if tr["dp"] > 1:
                    ne = reference.run(cell, seed, fault="no_exchange")
                    row["no_exchange"] = {"grad_norm_gap": check.compare(
                        {"losses": [], "grads": [ne["grads"]],
                         "changes": [ref["changes"]]}, ref)["grad_norm_gap"]}
                blocks = ref["block_losses"][0]
                row["answer_altered"] = {
                    "loss_gap": 0.01 * blocks[0] / len(blocks)}
            row["seconds"] = time.perf_counter() - t0
            print(json.dumps(row), file=out, flush=True)
            print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
