"""Observability layer: span schema, trace validation, metrics, attribution.

The heavy lifting is shared with test_backends' timing plan (bert-large
merged to 6 layers): traced emulated runs must reproduce the backend's own
StepTiming and StoreStats *exactly* — the trace is a decomposition of the
run, not a parallel estimate of it.
"""
import json

import pytest

from repro.core.partition import merge_layers
from repro.core.perfmodel import Config
from repro.core.profiler import paper_model_profile
from repro.obs import (
    ELAPSED,
    Span,
    Trace,
    TraceValidationError,
    gap_attribution,
    pipeline_health,
    validate_trace,
)
from repro.serverless.platform import AWS_LAMBDA
from repro.serverless.runtime import run_plan
from repro.serverless.runtime.store import classify_key
from repro.serverless.simulator import simulate_funcpipe


def _timing_plan(d=4):
    prof = merge_layers(paper_model_profile("bert-large", AWS_LAMBDA), 6)
    L = prof.L
    x = tuple(1 if i == 2 else 0 for i in range(L - 1))
    return prof, Config(x=x, d=d, z=tuple(5 for _ in range(L)))


@pytest.fixture(scope="module")
def traced_run():
    prof, cfg = _timing_plan(d=4)
    res = run_plan(prof, AWS_LAMBDA, cfg, 8, steps=2, trace=True)
    sim = simulate_funcpipe(prof, AWS_LAMBDA, cfg, 8, trace=True)
    return prof, cfg, res, sim


# ------------------------------------------------------------------ schema
def test_span_schema_roundtrip():
    sp = Span(stage=1, replica=2, step=0, phase="fwd", op="upload",
              start=1.0, end=2.5, nbytes=100.0, key="k0/r2/m0/act1")
    assert sp.worker == "s1r2"
    assert sp.duration == 1.5
    assert sp.resource == "uplink"
    assert Span.from_dict(sp.to_dict()) == sp
    # compute spans carry no key/bytes and map to the cpu lane
    cp = Span(stage=0, replica=0, step=0, phase="bwd", op="compute",
              start=0.0, end=1.0)
    assert cp.resource == "cpu"
    assert "key" not in cp.to_dict() and "nbytes" not in cp.to_dict()


def test_classify_key():
    assert classify_key("k0/r1/m2/act3") == "act"
    assert classify_key("k0/r1/m2/grad3") == "grad"
    assert classify_key("k0/sync1/part/2/0") == "sync"
    assert classify_key("k0/sync1/red/2") == "sync"
    assert classify_key("whatever") == "other"


# ------------------------------------------------ emulated trace invariants
def test_emulated_trace_validates(traced_run):
    _, cfg, res, _ = traced_run
    tr = res.trace
    assert tr is not None and len(tr.spans) > 0
    validate_trace(tr)   # non-overlap per lane + phase ordering
    workers = {sp.worker for sp in tr.spans}
    assert workers == {f"s{s}r{r}" for s in range(sum(cfg.x) + 1)
                       for r in range(cfg.d)}


def test_emulated_span_ends_reproduce_step_timing(traced_run):
    """Per step, the last span end IS the step's StepTiming.end (exact)."""
    _, _, res, _ = traced_run
    tr = res.trace
    for k, end in enumerate(tr.meta["step_ends"]):
        assert max(s.end for s in tr.spans if s.step == k) == end


def test_emulated_span_bytes_reconcile_bit_exact(traced_run):
    """Spans are emitted adjacent to each store op, in the same serial
    order, so the float sums match StoreStats bit for bit."""
    _, _, res, _ = traced_run
    tr, ss = res.trace, res.store_stats

    def fold(op):
        # the store's running `+=`, in emission order (builtin sum() is
        # compensated on Python >= 3.12 and would differ in the last ulp)
        total = 0.0
        for s in tr.spans:
            if s.op == op:
                total += s.nbytes
        return total

    assert fold("upload") == ss.bytes_in
    assert fold("download") == ss.bytes_out
    assert pipeline_health(tr)["reconciliation"]["ok"]


def test_store_stats_class_breakdown(traced_run):
    _, _, res, _ = traced_run
    ss = res.store_stats
    assert set(ss.class_bytes_in) == {"act", "grad", "sync"}
    assert sum(ss.class_bytes_in.values()) == pytest.approx(ss.bytes_in)
    assert sum(ss.class_bytes_deleted.values()) == \
        pytest.approx(ss.bytes_deleted)
    d = ss.as_dict()
    assert d["puts"] == ss.puts and "class_bytes_in" in d


def test_validate_trace_rejects_overlap_and_disorder():
    base = dict(stage=0, replica=0, step=0, phase="fwd", op="compute")
    tr = Trace(spans=[Span(start=0.0, end=2.0, **base),
                      Span(start=1.0, end=3.0, **base)], meta={})
    with pytest.raises(TraceValidationError, match="overlap"):
        validate_trace(tr)
    tr2 = Trace(spans=[
        Span(stage=0, replica=0, step=0, phase="bwd", op="compute",
             start=0.0, end=1.0),
        Span(stage=0, replica=0, step=0, phase="fwd", op="compute",
             start=2.0, end=3.0)], meta={})
    with pytest.raises(TraceValidationError, match="before fwd ends"):
        validate_trace(tr2)


# ----------------------------------------------------------- chrome export
def test_chrome_trace_roundtrip(tmp_path, traced_run):
    _, _, res, sim = traced_run
    tr = res.trace
    tr.predicted = sim.trace.spans
    path = tmp_path / "t.json"
    tr.save(path)
    doc = json.loads(path.read_text())        # valid JSON, object form
    assert isinstance(doc["traceEvents"], list)
    xs = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    # one X event per observed + predicted span, ts/dur in microseconds
    assert len(xs) == len(tr.spans) + len(tr.predicted)
    assert all(e["dur"] >= 0 for e in xs)
    t2 = Trace.load(path)
    assert len(t2.spans) == len(tr.spans)
    assert len(t2.predicted) == len(tr.predicted)
    assert t2.spans[0] == tr.spans[0]
    assert t2.meta["step_ends"] == tr.meta["step_ends"]


# ---------------------------------------------------- predicted + metrics
def test_predicted_trace_validates(traced_run):
    _, cfg, _, sim = traced_run
    tr = sim.trace
    validate_trace(tr)
    S = sum(cfg.x) + 1
    assert {s.stage for s in tr.spans} == set(range(S))
    assert all(s.replica == 0 and s.step == 0 for s in tr.spans)
    assert {s.op for s in tr.spans} == {"download", "compute", "upload",
                                        "sync"}
    # predicted makespan is the simulated t_iter
    assert max(s.end for s in tr.spans) == pytest.approx(sim.t_iter)


def test_pipeline_health_metrics(traced_run):
    _, cfg, res, _ = traced_run
    h = pipeline_health(res.trace)
    S = sum(cfg.x) + 1
    assert [row["stage"] for row in h["stages"]] == list(range(S))
    for row in h["stages"]:
        assert 0.0 <= row["bubble_frac"] <= 1.0
        assert row["compute_frac"] + row["bubble_frac"] == pytest.approx(1.0)
        assert 0.0 <= row["up_bw_util"] <= 1.0
    assert h["straggler_ratio"] >= 1.0
    pb = h["phase_bytes"]
    assert pb["fwd"]["up"] > 0 and pb["sync"]["up"] > 0


def test_gap_attribution_ranks_cells(traced_run):
    _, _, res, sim = traced_run
    tr = res.trace
    bare = Trace(spans=tr.spans, meta=tr.meta)   # no predicted attached
    with pytest.raises(ValueError, match="no predicted"):
        gap_attribution(bare)
    rows = gap_attribution(tr, predicted=sim.trace.spans)
    gaps = [abs(r.gap_s) for r in rows]
    assert gaps == sorted(gaps, reverse=True)
    # busy cells exclude the closed-form sync phase; elapsed rows include it
    assert all(r.phase != "sync" or r.op == ELAPSED for r in rows)
    assert any(r.op == ELAPSED for r in rows)
    # the emulated backend charges the shared cost model: compute cells agree
    for r in rows:
        if r.op == "compute":
            assert r.observed_s == pytest.approx(r.predicted_s, rel=1e-9)


# ------------------------------------------------------------ local backend
def test_local_backend_trace_validates():
    prof, cfg = _timing_plan(d=2)
    res = run_plan(prof, AWS_LAMBDA, cfg, 8, steps=1, backend="local",
                   trace=True)
    tr = res.trace
    assert tr.meta["clock"] == "wall"
    validate_trace(tr)
    ss = res.store_stats
    # modeled byte sums still reconcile (thread order differs: approx)
    up = sum(s.nbytes for s in tr.spans if s.op == "upload")
    dn = sum(s.nbytes for s in tr.spans if s.op == "download")
    assert up == pytest.approx(ss.bytes_in)
    assert dn == pytest.approx(ss.bytes_out)
    # wall-clock traces carry no bandwidth-utilization columns (cross-clock)
    assert "up_bw_util" not in pipeline_health(tr)["stages"][0]


def test_untraced_run_has_no_trace():
    prof, cfg = _timing_plan(d=1)
    res = run_plan(prof, AWS_LAMBDA, cfg, 4, steps=1)
    assert res.trace is None
    sim = simulate_funcpipe(prof, AWS_LAMBDA, cfg, 4)
    assert sim.trace is None


# ------------------------------------------------------- planner + cache
def test_planner_stats_populated():
    from repro.core import planner

    prof = merge_layers(paper_model_profile("bert-large", AWS_LAMBDA), 6)
    alpha = (1.0, 2**16 * 1e-9)
    r = planner.solve(prof, AWS_LAMBDA, alpha=alpha, total_micro_batches=16,
                      d_options=(1, 2), merge_to=6)
    assert r.stats is not None and r.stats.engine == "batch"
    assert r.stats.partitions_polished > 0
    assert "polished" in r.stats.describe()
    r_dp = planner.dp_solve(prof, AWS_LAMBDA, alpha=alpha,
                            total_micro_batches=16, d_options=(1, 2),
                            merge_to=6)
    assert r_dp.stats.engine == "dp"
    assert r_dp.stats.dp_states > 0 and r_dp.stats.dp_rows_kept > 0
    assert "states" in r_dp.stats.describe()


def test_plan_cache_eviction_counter(tmp_path):
    from repro.api.plan_cache import PlanCache

    cache = PlanCache(tmp_path)
    key = "deadbeef"
    path = cache._path(key)
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text("{not json")
    assert cache.get(key) is None
    assert cache.evictions == 1 and cache.misses == 1
    assert not path.exists()
    assert cache.get(key) is None        # plain miss, no eviction
    assert cache.evictions == 1 and cache.misses == 2


# ------------------------------------------------------------- CLI surface
def test_cli_trace_and_inspect(tmp_path, capsys):
    from repro.cli import main as cli_main

    trace = tmp_path / "t.json"
    rc = cli_main(["emulate", "--model", "bert-large", "--fast",
                   "--steps", "1", "--trace", str(trace),
                   "--no-plan-cache"])
    out = capsys.readouterr().out
    assert rc == 0 and trace.exists()
    assert "wrote trace" in out
    assert "store uploads by key class:" in out
    rc = cli_main(["inspect", str(trace)])
    out = capsys.readouterr().out
    assert rc == 0
    assert "trace OK" in out
    assert "gap attribution" in out
    assert "byte reconciliation vs StoreStats: OK" in out


def test_cli_inspect_lists_host_work_of_a_local_numeric_trace(tmp_path,
                                                              capsys):
    from repro.cli import main as cli_main

    trace = tmp_path / "t.json"
    rc = cli_main(["emulate", "--numerics", "--model",
                   "phi3-mini-3.8b@reduced", "--stages", "2", "--dp", "1",
                   "--batch", "8", "--seq", "16", "--steps", "2",
                   "--backend", "local", "--trace", str(trace),
                   "--no-plan-cache"])
    capsys.readouterr()
    assert rc == 0
    rc = cli_main(["inspect", str(trace)])
    out = capsys.readouterr().out
    assert rc == 0 and "trace OK" in out
    assert "host pack: 4 spans" in out and "host update: 4 spans" in out
    # observed host work against the model's predicted 0
    rows = [line.split() for line in out.splitlines()
            if line.split()[1:3] in (["sync", "pack"], ["sync", "update"])]
    assert rows and all(float(r[4]) == 0.0 for r in rows)


def test_cli_inspect_rejects_invalid(tmp_path, capsys):
    from repro.cli import main as cli_main

    bad = tmp_path / "bad.json"
    bad.write_text("[]")
    with pytest.raises(SystemExit, match="not a repro trace"):
        cli_main(["inspect", str(bad)])


# ------------------------------------- host work at the sync boundary (local)
def _numeric_local(d, *, trace, steps=2):
    from repro.api import ExecutionConfig, numeric_plan

    plan, prof, ex = numeric_plan("phi3-mini-3.8b@reduced", stages=2, dp=d,
                                  batch=8, seq=16)
    res = plan.emulate(ExecutionConfig(backend="local", steps=steps,
                                       trace=trace),
                       execution=ex, profile=prof)
    return plan, ex, res


def _stage_param_counts(plan, ex):
    import jax

    from repro.serverless.runtime.worker import (
        StageWorker,
        stage_instance_ranges,
    )

    return [sum(int(a.size) for a in jax.tree.leaves(
                StageWorker(ex.cfg, span, ex.init_params, mu=1,
                            optimizer=ex.optimizer).params))
            for span in stage_instance_ranges(ex.cfg, plan.config.x)]


@pytest.fixture(scope="module", params=[1, 2], ids=["d1", "d2"])
def numeric_local_runs(request):
    d = request.param
    plan, ex, traced = _numeric_local(d, trace=True)
    _, _, plain = _numeric_local(d, trace=False)
    return d, plan, ex, traced, plain


def test_local_numeric_trace_has_pack_then_update_per_worker_step(
        numeric_local_runs):
    d, plan, ex, res, _ = numeric_local_runs
    tr = res.trace
    validate_trace(tr)
    counts = _stage_param_counts(plan, ex)
    for s in range(2):
        for r in range(d):
            for k in range(2):
                mine = sorted((sp for sp in tr.spans if (sp.stage, sp.replica,
                               sp.step) == (s, r, k)),
                              key=lambda sp: sp.start)
                host = [sp for sp in mine if sp.op in ("pack", "update")]
                assert [sp.op for sp in host] == ["pack", "update"]
                assert all(sp.phase == "sync" for sp in host)
                last_bwd = max(sp.end for sp in mine if sp.phase == "bwd")
                assert host[0].start >= last_bwd
                assert host[1].start >= host[0].end
                # bytes that moved: the fp32 host vector, down and back
                assert [sp.nbytes for sp in host] == [4.0 * counts[s]] * 2


def test_tracing_leaves_the_numerics_bit_identical(numeric_local_runs):
    import jax
    import numpy as np

    _, _, _, traced, plain = numeric_local_runs
    assert traced.losses == plain.losses
    for a, b in zip(jax.tree.leaves(traced.params),
                    jax.tree.leaves(plain.params)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_host_work_rows_in_health_and_attribution(numeric_local_runs):
    d, plan, ex, res, _ = numeric_local_runs
    tr = res.trace
    h = pipeline_health(tr)
    n = 2 * d * 2                               # workers x steps
    assert {op: row["count"] for op, row in h["host"].items()} == \
        {"pack": n, "update": n}
    assert h["host"]["pack"]["bytes"] == 4.0 * d * 2 * sum(
        _stage_param_counts(plan, ex))
    for row in h["stages"]:                     # host work is not compute
        assert row["compute_frac"] + row["bubble_frac"] == pytest.approx(1.0)
        assert row["bubble_frac"] > 0.0
    # the perf model has no term for host work: observed rows, predicted 0
    sim = plan.simulate(trace=True)
    rows = gap_attribution(tr, predicted=sim.trace.spans)
    host = [r for r in rows if r.op in ("pack", "update")]
    assert {(r.stage, r.op) for r in host} == \
        {(s, op) for s in range(2) for op in ("pack", "update")}
    assert all(r.phase == "sync" and r.predicted_s == 0.0
               and r.observed_s > 0.0 for r in host)


def test_emulated_numeric_trace_has_no_host_spans():
    from repro.api import ExecutionConfig, numeric_plan

    plan, prof, ex = numeric_plan("phi3-mini-3.8b@reduced", stages=2, dp=1,
                                  batch=8, seq=16)
    res = plan.emulate(ExecutionConfig(steps=1, trace=True), execution=ex,
                       profile=prof)
    ops = {sp.op for sp in res.trace.spans}
    assert "compute" in ops and not ops & {"pack", "update"}


def test_program_spans_are_profiler_host_events_on_one_clock(tmp_path):
    """Every span of a traced local run is also a host event of a
    jax.profiler session, named ``s{stage}r{replica} {phase}.{op}`` with
    its step as an argument; starts differ by one offset and durations
    agree."""
    import glob
    import os

    import jax
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        _, _, res = _numeric_local(1, trace=True)
    finally:
        jax.profiler.stop_trace()
    spans = {}
    for sp in res.trace.spans:
        spans.setdefault((f"{sp.worker} {sp.phase}.{sp.op}", sp.step),
                         []).append(sp)
    labels = {label for label, _ in spans}
    path = sorted(glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                         "*", "*.xplane.pb")))[-1]
    events = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in labels:
                    step = dict(e.stats).get("step")
                    events.setdefault((e.name, step), []).append(
                        (e.start_ns * 1e-9, e.duration_ns * 1e-9))
    offsets, n = [], 0
    for key, mine in spans.items():
        evs = sorted(events.get(key, []))
        assert len(evs) == len(mine), key
        for sp, (start, dur) in zip(sorted(mine, key=lambda s: s.start), evs):
            offsets.append(start - sp.start)
            assert abs(dur - sp.duration) < 2e-3, (key, dur, sp.duration)
            n += 1
    assert n == len(res.trace.spans)
    assert {k[0].split(" ")[1] for k in spans} >= {"sync.pack", "sync.update"}
    assert max(offsets) - min(offsets) < 2e-3
