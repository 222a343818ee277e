"""Calibration-loop gate: a measured profile must predict the run it was
calibrated from far better than the analytic tables do.

Runs the numeric reduced arch (real JAX) through the **process** backend
with ``payload_true`` + ``throttle`` — real OS worker processes moving real
payload bytes through the file store at the plan's modeled per-worker
bandwidth, so spans measure host wall-clock seconds under the plan's own
budget.  The traced run is folded back through
:func:`repro.obs.calibrate.calibrate_profile` and the headline is the max
per-stage relative error of the model's ``stage_aggregates`` terms against
the observed spans, before (analytic profile) vs after (measured profile).

``--check`` enforces the CI gate ``residual <= baseline * 0.5 + 0.02`` —
calibrated re-planning is pointless unless the measured tables at least
halve the predicted-vs-observed error (the 2pp absolute slack covers
wall-clock jitter on runs whose analytic error is already tiny).  A replan
row records how the re-solved deployment prices against the old one on the
measured tables.  Writes ``BENCH_calibration.json`` at the repo root.

    PYTHONPATH=src python -m benchmarks.calibration_bench [--fast] [--check]
"""
from __future__ import annotations

import json
import os

from repro.api import numeric_plan
from repro.obs.calibrate import calibrate_profile, replan
from repro.serverless.execution import ExecutionConfig
from repro.serverless.runtime import run_plan

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT_JSON = os.path.join(_REPO_ROOT, "BENCH_calibration.json")

# gate: residual <= baseline * REL_FACTOR + ABS_SLACK (also quoted in ci.yml)
REL_FACTOR = 0.5
ABS_SLACK = 0.02


def rows(fast: bool = False):
    steps = 2 if fast else 3     # >= 2 so the JIT-compile step-0 warmup drops
    plan, prof, ex = numeric_plan("phi3-mini-3.8b@reduced4", stages=2, dp=2,
                                  batch=8, seq=16)
    rp = plan.resolve(profile=prof)
    res = run_plan(rp.profile, rp.platform, rp.config,
                   rp.total_micro_batches,
                   ExecutionConfig(steps=steps, backend="process",
                                   payload_true=True, throttle=True,
                                   trace=True),
                   pipelined_sync=rp.pipelined_sync, execution=ex)
    cal = calibrate_profile(res.trace, rp.profile, rp.platform, rp.config,
                            rp.total_micro_batches,
                            pipelined_sync=rp.pipelined_sync)
    baseline = cal.baseline["max_rel_err"]
    residual = cal.residual["max_rel_err"]
    rep = replan(cal, plan)
    a1, a2 = rep.alpha
    obj_old = rep.old_on_measured.objective(a1, a2)
    obj_new = rep.new_on_measured.objective(a1, a2)
    limit = baseline * REL_FACTOR + ABS_SLACK
    out = [
        {"bench": "calibration", "backend": "process", "steps": steps,
         "warmup": cal.warmup, "t_iter_s": round(res.t_iter, 4),
         "baseline_max_rel_err": round(baseline, 4),
         "residual_max_rel_err": round(residual, 4),
         "warnings": ";".join(w.name for w in cal.warnings) or "-"},
        {"bench": "replan", "old_stages": rep.old_plan.n_stages,
         "new_stages": rep.new_plan.n_stages, "old_d": rep.old_plan.d,
         "new_d": rep.new_plan.d,
         "objective_old_on_measured": round(obj_old, 8),
         "objective_new_on_measured": round(obj_new, 8),
         "improved_or_equal": obj_new <= obj_old + 1e-12},
        {"bench": "gate", "baseline": round(baseline, 4),
         "residual": round(residual, 4), "limit": round(limit, 4),
         "ok": residual <= limit},
    ]
    with open(OUT_JSON, "w") as f:
        json.dump(out, f, indent=1)
    return out


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(prog="benchmarks.calibration_bench")
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--check", action="store_true",
                    help="exit 1 unless the measured profile at least "
                         "halves the predicted-vs-observed error")
    args = ap.parse_args(argv)
    rs = rows(fast=args.fast)
    for r in rs:
        print(",".join(f"{k}={v}" for k, v in r.items()))
    gate = next(r for r in rs if r["bench"] == "gate")
    if args.check and not gate["ok"]:
        print(f"FAIL: calibrated residual error {gate['residual']} exceeds "
              f"{gate['limit']} ({REL_FACTOR:.0%} of analytic baseline "
              f"{gate['baseline']} + {ABS_SLACK})")
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
