"""Tracing-overhead gate: span recording must be ~free when off and cheap
when on.

Times ``run_plan`` on the **local** execution backend (real daemon threads
over the blocking in-process store — the only backend where host wall-clock
is the measurement, so recording overhead is observable) in three modes:

* ``off``      — no recorder attached; each op still calls
  ``repro.obs.open_span``, which returns the shared no-op ``UNTRACED``
  context (no annotation, no clock read), and enters and leaves it,
* ``on``       — ``trace=True``: every store op and compute block brackets a
  ``perf_counter`` pair inside a ``jax.profiler.TraceAnnotation`` and
  appends a Span (``repro.obs.open_span``),
* ``emulated`` — the virtual-clock backend traced, as a sanity row (its
  "overhead" is pure bookkeeping; the virtual timings are identical by
  construction).

Each mode reports the **min over reps** of host seconds per step — min, not
mean, because scheduler noise only ever adds time.  ``--check`` enforces the
CI gate ``traced_min <= base_min * 1.05 + 0.05`` (5% relative + 50ms
absolute slack for timer/thread-start jitter on tiny runs) and exits 1 on
breach.  Writes ``BENCH_trace_overhead.json`` at the repo root.

    PYTHONPATH=src python -m benchmarks.trace_overhead [--fast] [--check]
"""
from __future__ import annotations

import json
import os
import time

from repro.core.partition import merge_layers
from repro.core.perfmodel import Config
from repro.core.profiler import paper_model_profile
from repro.serverless.platform import AWS_LAMBDA
from repro.serverless.execution import ExecutionConfig
from repro.serverless.runtime import run_plan

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT_JSON = os.path.join(_REPO_ROOT, "BENCH_trace_overhead.json")

# relative + absolute slack of the --check gate (also quoted in ci.yml)
REL_SLACK = 1.05
ABS_SLACK = 0.05


def _plan(d):
    prof = merge_layers(paper_model_profile("bert-large", AWS_LAMBDA), 6)
    L = prof.L
    x = tuple(1 if i == 2 else 0 for i in range(L - 1))
    return prof, Config(x=x, d=d, z=tuple(5 for _ in range(L)))


def _time_once(backend, trace, *, d, M, steps):
    prof, cfg = _plan(d)
    t0 = time.perf_counter()
    res = run_plan(prof, AWS_LAMBDA, cfg, M,
                   ExecutionConfig(steps=steps, backend=backend, trace=trace))
    host = time.perf_counter() - t0
    n_spans = 0 if res.trace is None else len(res.trace.spans)
    return host / steps, n_spans


def rows(fast: bool = False):
    reps = 3 if fast else 5
    d, M, steps = 2, 8, (1 if fast else 2)
    out = []
    for name, backend, trace in (("local_off", "local", False),
                                 ("local_traced", "local", True),
                                 ("emulated_traced", "emulated", True)):
        best, n_spans = min(
            _time_once(backend, trace, d=d, M=M, steps=steps)
            for _ in range(reps))
        out.append({"bench": name, "backend": backend, "traced": trace,
                    "reps": reps, "steps": steps,
                    "min_s_per_step": round(best, 6), "spans": n_spans})
    base = next(r for r in out if r["bench"] == "local_off")
    traced = next(r for r in out if r["bench"] == "local_traced")
    limit = base["min_s_per_step"] * REL_SLACK + ABS_SLACK
    gate = {"bench": "gate", "base_s": base["min_s_per_step"],
            "traced_s": traced["min_s_per_step"], "limit_s": round(limit, 6),
            "ok": traced["min_s_per_step"] <= limit}
    out.append(gate)
    with open(OUT_JSON, "w") as f:
        json.dump(out, f, indent=1)
    return out


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(prog="benchmarks.trace_overhead")
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--check", action="store_true",
                    help="exit 1 if traced local runs breach the overhead "
                         "gate")
    args = ap.parse_args(argv)
    rs = rows(fast=args.fast)
    for r in rs:
        print(",".join(f"{k}={v}" for k, v in r.items()))
    gate = next(r for r in rs if r["bench"] == "gate")
    if args.check and not gate["ok"]:
        print(f"FAIL: traced local step {gate['traced_s']}s exceeds "
              f"{gate['limit_s']}s ({REL_SLACK:.0%} of untraced "
              f"{gate['base_s']}s + {ABS_SLACK}s)")
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
