"""The harness finds a cell's configuration, traffic mix, limits and
per-layer metrics by name, so each can be added as a new file."""
from __future__ import annotations

import json
import math
import re
import shutil
from pathlib import Path

import pytest

from chipbench import spec

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def _copy(tmp_path) -> Path:
    root = tmp_path / "checkout"
    shutil.copytree(HERE, root / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__", ".trace"))
    shutil.copy(ROOT / "BENCHMARK.json", root)
    return root


@pytest.mark.parametrize("cell", [w["name"] for w in json.loads(
    (ROOT / "BENCHMARK.json").read_text())["workloads"]])
def test_every_cell_loads(cell):
    c = spec.load_cell(ROOT, cell)
    assert c.limits is not None, f"limits/{cell}.json"
    assert {m.name for m in c.end_to_end} >= {"setup_s"}
    assert c.per_layer and all(callable(m.read) for m in c.per_layer)
    tr = c.traffic
    assert tr["global_batch"] % (tr["dp"] * tr["micro_batch"]) == 0
    fwd = c.reference.forward_flops_per_token(c.config, tr["seq_len"])
    assert math.isfinite(fwd) and fwd > 0


def test_new_config_traffic_and_metric_are_new_files(tmp_path):
    root = _copy(tmp_path)
    bench = root / "benchmarks" / "chip"
    cfg = json.loads(
        (bench / "configs" / "phi3-mini-3.8b.depth2.json").read_text())
    (bench / "configs" / "phi3-mini-3.8b.depth2.low-lr.json").write_text(
        json.dumps(dict(cfg, optimizer=dict(cfg["optimizer"], lr=1e-6))))
    mix = json.loads((bench / "traffic" / "s2d1.b8x512.json").read_text())
    (bench / "traffic" / "s2d1.b8x1024.json").write_text(
        json.dumps(dict(mix, seq_len=1024)))
    (bench / "metrics" / "steps_in_window.py").write_text(
        "def read(run):\n    return run.window_steps\n")
    doc = json.loads((root / "BENCHMARK.json").read_text())
    doc["configs"].append({"name": "phi3-mini-3.8b.depth2.low-lr",
                           "source": "test",
                           "file": "benchmarks/chip/configs/"
                                   "phi3-mini-3.8b.depth2.low-lr.json",
                           "reduced": [], "why": "test"})
    doc["workloads"].append({"name": "phi3-d2.s2d1.b8x1024",
                             "config": "phi3-mini-3.8b.depth2.low-lr",
                             "traffic": "s2d1.b8x1024", "chips": 1,
                             "why": "test"})
    doc["per_layer"].append({"name": "steps_in_window", "unit": "count",
                             "better": "higher", "source": "host_clock",
                             "layer": "engine", "moves": "train_tokens_per_s",
                             "workloads": ["phi3-d2.s2d1.b8x1024"]})
    (root / "BENCHMARK.json").write_text(json.dumps(doc))

    c = spec.load_cell(root, "phi3-d2.s2d1.b8x1024", bench_dir=bench)
    assert c.config["optimizer"]["lr"] == 1e-6
    assert c.traffic["seq_len"] == 1024
    assert c.limits is None                      # none written yet
    reader = {m.name: m.read for m in c.per_layer}["steps_in_window"]
    assert reader(type("Run", (), {"window_steps": 7})) == 7
    # a metric scoped to other cells is not read here
    other = spec.load_cell(root, "phi3-d2.s2d1.b8x512", bench_dir=bench)
    assert "steps_in_window" not in {m.name for m in other.per_layer}


def test_unknown_device_kind_raises():
    assert spec.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(spec.UnknownDevice):
        spec.peaks_for("cpu")


def test_unknown_cell_raises():
    with pytest.raises(spec.SpecError):
        spec.load_cell(ROOT, "no-such-cell")


def test_benchmark_json_keeps_the_contract():
    doc = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert set(doc) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [x["name"] for x in doc[group]]
        assert len(names) == len(set(names))
        assert all(name.match(n) for n in names)
    e2e = {m["name"] for m in doc["end_to_end"]}
    assert "setup_s" in e2e
    for m in doc["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    for m in doc["per_layer"]:
        assert m["moves"] in e2e
    for c in doc["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert c["file"].startswith(tuple(p + "/" for p in doc["paths"]))
    assert 1 <= doc["run_seconds"] <= 51
