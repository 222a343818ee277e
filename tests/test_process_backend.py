"""The process backend's substrate: FileStore atomicity + accounting across
real processes, mtime leases and poison files, FileBarrier, payload-true
byte charging, bandwidth throttling, registry availability reporting, and
process-backend end-to-end runs (parity itself lives in test_backends.py /
test_faults.py, parametrized over backend='process')."""
import os
import threading
import time

import numpy as np
import pytest

from repro.serverless.backends import (
    ProcessBackend,
    available_backends,
    backend_availability,
    get_backend,
)
from repro.serverless.backends.process_worker import (
    FileBarrier,
    FileStore,
    _true_payload_nbytes,
)
from repro.serverless.runtime.store import (
    ProducerDeadError,
    StoreAbortedError,
    assert_store_drained,
)

pytestmark = pytest.mark.skipif(os.name != "posix",
                                reason="FileStore needs POSIX flock")


def _mkstore(tmp_path, **kw):
    kw.setdefault("timeout", 5.0)
    kw.setdefault("lease_timeout", 0.3)
    return FileStore(str(tmp_path / "store"), **kw)


# ------------------------------------------------------------------ FileStore
def test_file_store_round_trip_and_accounting(tmp_path):
    store = _mkstore(tmp_path)
    store.put("k0/r0/m0/act0", 128.0, value={"x": 1})
    assert "k0/r0/m0/act0" in store and store.live_bytes == 128.0
    value, nb = store.take("k0/r0/m0/act0", return_nbytes=True)
    assert value == {"x": 1} and nb == 128.0
    assert len(store) == 0 and store.live_bytes == 0.0
    assert store.stats.puts == store.stats.deletes == 1
    assert_store_drained(store)


def test_file_store_blocks_until_visible(tmp_path):
    store = _mkstore(tmp_path)
    got = {}

    def consumer():
        got["v"] = store.take("x")

    t = threading.Thread(target=consumer)
    t.start()
    time.sleep(0.05)
    assert t.is_alive()
    store.put("x", 64.0, value="payload")
    t.join(timeout=10.0)
    assert got["v"] == "payload"


def test_file_store_overwrite_counts_implicit_delete(tmp_path):
    store = _mkstore(tmp_path)
    store.put("k", 100.0)
    store.put("k", 40.0)
    assert store.live_bytes == pytest.approx(40.0)
    store.delete("k")
    assert store.stats.puts == store.stats.deletes == 2
    assert store.stats.bytes_deleted == pytest.approx(store.stats.bytes_in)
    assert_store_drained(store)


def test_accounting_survives_a_second_client(tmp_path):
    """stats.json is the shared truth: a second FileStore client over the
    same root (another process, in production) sees the same counters."""
    a = _mkstore(tmp_path)
    a.put("k0/r0/m0/act0", 32.0, value=b"v")
    b = FileStore(str(tmp_path / "store"), timeout=5.0)
    assert b.stats.puts == 1 and b.live_bytes == 32.0
    assert b.take("k0/r0/m0/act0") == b"v"
    assert a.stats.deletes == 1 and a.live_bytes == 0.0


def test_stale_mtime_lease_raises_producer_dead(tmp_path):
    """A producer whose heartbeat file mtime froze (SIGKILL'd process) fails
    its consumers over without burning the get timeout."""
    store = _mkstore(tmp_path, timeout=30.0, lease_timeout=0.2)
    store.heartbeat((0, 0))
    time.sleep(0.4)                      # mtime goes stale by itself
    t0 = time.monotonic()
    with pytest.raises(ProducerDeadError, match="stopped heartbeating"):
        store.get("k0/r0/m0/act0")
    assert time.monotonic() - t0 < 5.0


def test_dead_marker_fails_over_immediately(tmp_path):
    store = _mkstore(tmp_path, timeout=30.0)
    store.mark_dead((0, 0))
    with pytest.raises(ProducerDeadError, match="died"):
        store.get("k0/r0/m0/act0")


def test_poison_file_aborts_waiters_and_revives(tmp_path):
    store = _mkstore(tmp_path, timeout=30.0)
    errs = {}

    def consumer():
        try:
            store.get("k0/r0/m0/act0")
        except BaseException as e:      # noqa: BLE001
            errs["e"] = e

    t = threading.Thread(target=consumer)
    t.start()
    time.sleep(0.05)
    store.abort(RuntimeError("worker s0r0 exploded"))
    t.join(timeout=10.0)
    assert isinstance(errs["e"], StoreAbortedError)
    assert "exploded" in str(errs["e"])
    # first poison wins; revive clears it
    store.abort(RuntimeError("collateral"))
    assert "exploded" in store._poison_text()
    store.revive()
    assert store._poison_text() is None


def test_get_timeout_diagnoses_missing_object(tmp_path):
    store = _mkstore(tmp_path, timeout=0.05)
    with pytest.raises(TimeoutError, match="never became visible"):
        store.get("missing")


def test_file_barrier_meets_across_threads(tmp_path):
    store = _mkstore(tmp_path)
    n, out = 3, []

    def party(i):
        b = FileBarrier(store, "k0-s0", n, i, timeout=10.0)
        b.wait()
        out.append(i)
        b.wait()                         # second generation works too

    ts = [threading.Thread(target=party, args=(i,)) for i in range(n)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=15.0)
    assert sorted(out) == [0, 1, 2]


def test_file_barrier_breaks_on_poison(tmp_path):
    store = _mkstore(tmp_path)
    store.abort(RuntimeError("peer died"))
    b = FileBarrier(store, "k0-s0", 2, 0, timeout=5.0)
    with pytest.raises(threading.BrokenBarrierError):
        b.wait()


# ----------------------------------------------------- payload-true accounting
def test_payload_true_charges_real_nbytes(tmp_path):
    """Charged bytes equal the sum of the *real* payload sizes — the
    calibrated axis the ROADMAP asks for — regardless of the modeled sizes
    the engine passes."""
    store = _mkstore(tmp_path, payload_true=True)
    payloads = {
        "k0/r0/m0/act0": np.arange(1000, dtype=np.float32),     # activation
        "k0/r0/m0/grad0": np.ones((16, 8), dtype=np.float32),   # gradient
        "k0/sync0/red/0": np.zeros(37, dtype=np.float64),       # sync chunk
    }
    for key, arr in payloads.items():
        store.put(key, 1.0, value=arr)   # modeled size deliberately wrong
    want = float(sum(a.nbytes for a in payloads.values()))
    assert store.stats.bytes_in == pytest.approx(want)
    got = 0.0
    for key, arr in payloads.items():
        value, nb = store.take(key, return_nbytes=True)
        np.testing.assert_array_equal(value, arr)
        got += nb
    assert got == pytest.approx(want)
    assert store.stats.bytes_out == pytest.approx(want)
    assert_store_drained(store)


def test_true_payload_nbytes_falls_back_to_wire_size():
    arr = np.arange(10, dtype=np.int64)
    assert _true_payload_nbytes(arr, b"") == arr.nbytes
    assert _true_payload_nbytes(b"12345", b"x") == 5.0
    assert _true_payload_nbytes({"no": "nbytes"}, b"123456") == 6.0


def test_without_payload_true_modeled_sizes_are_charged(tmp_path):
    store = _mkstore(tmp_path)
    store.put("k", 999.0, value=np.zeros(4, dtype=np.float32))
    assert store.stats.bytes_in == 999.0
    store.delete("k")


# ------------------------------------------------------------------- throttle
def test_throttle_transfer_time_tracks_bytes_over_bandwidth(tmp_path):
    """Wall-clock put+take of B real bytes at bandwidth W takes ~B/W each
    way (within scheduling tolerance)."""
    bw = 2e6                             # 2 MB/s
    store = _mkstore(tmp_path, payload_true=True, bandwidth=bw, t_lat=0.0)
    arr = np.zeros(250_000, dtype=np.float32)        # 1 MB -> 0.5 s per leg
    expect = arr.nbytes / bw
    t0 = time.monotonic()
    store.put("k0/r0/m0/act0", 0.0, value=arr)
    up = time.monotonic() - t0
    t0 = time.monotonic()
    store.take("k0/r0/m0/act0")
    down = time.monotonic() - t0
    for leg in (up, down):
        assert leg >= expect * 0.9
        assert leg <= expect * 1.6 + 0.2        # generous: CI schedulers
    assert store.stats.bytes_in == arr.nbytes


def test_unthrottled_transfers_do_not_sleep(tmp_path):
    store = _mkstore(tmp_path, payload_true=True)
    t0 = time.monotonic()
    store.put("k", 0.0, value=np.zeros(250_000, dtype=np.float32))
    store.take("k")
    assert time.monotonic() - t0 < 0.5


# --------------------------------------------------- registry / availability
def test_process_backend_registered_and_available():
    assert "process" in available_backends()
    be = get_backend("process")
    assert isinstance(be, ProcessBackend)
    assert be.wall_clock and be.hosts_programs
    avail = backend_availability()
    assert avail["process"] is None          # posix host (see pytestmark)
    assert avail["emulated"] is None and avail["local"] is None


def test_process_backend_refuses_an_accelerator_host(monkeypatch, tmp_path):
    """One process per chip: a parent holding a TPU must not spawn workers
    that need it too — open() refuses before spawning anything."""
    import jax

    from repro.core.partition import merge_layers
    from repro.core.perfmodel import Config
    from repro.core.profiler import paper_model_profile
    from repro.serverless.backends import BackendUnavailableError
    from repro.serverless.platform import AWS_LAMBDA
    from repro.serverless.simulator import stage_aggregates

    prof = merge_layers(paper_model_profile("bert-large", AWS_LAMBDA), 4)
    config = Config(x=(0, 1, 0), d=1, z=(0,) * 4)
    agg = stage_aggregates(prof, AWS_LAMBDA, config, 2)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    be = ProcessBackend(root=str(tmp_path / "store"))
    with pytest.raises(BackendUnavailableError, match="one process at a time"):
        be.open(agg)
    assert not be._procs
    assert "tpu device" in backend_availability()["process"]


def test_unknown_backend_error_lists_names_and_availability():
    with pytest.raises(KeyError) as ei:
        get_backend("s3-but-misspelled")
    msg = str(ei.value)
    assert "unknown execution backend" in msg
    for name in ("emulated", "local", "process", "aws", "oss"):
        assert name in msg
    import importlib.util

    if importlib.util.find_spec("boto3") is None:
        assert "boto3 not installed" in msg


def test_process_backend_caps_worker_processes():
    from types import SimpleNamespace

    with pytest.raises(ValueError, match="caps at"):
        ProcessBackend().open(SimpleNamespace(S=9, d=8))     # 72 > 64


def test_api_emulate_calibration_flags_require_process_backend():
    from repro.api import session

    s = (session("bert-large", platform="aws", global_batch=64)
         .plan(merge_to=6, d_options=(1, 2)))
    with pytest.raises(ValueError, match="process"):
        s.emulate(steps=1, throttle=True)
    with pytest.raises(ValueError, match="process"):
        s.emulate(steps=1, backend="local", payload_true=True)


# ------------------------------------------------------------------ end to end
def test_throttled_run_is_slower_and_conserved():
    """End-to-end: the same timing-only plan runs measurably slower with the
    bandwidth throttle on, and the byte accounting stays identical."""
    from test_backends import _timing_plan

    from repro.serverless.platform import AWS_LAMBDA
    from repro.serverless.runtime import run_plan

    prof, cfg = _timing_plan(d=2)
    fast = run_plan(prof, AWS_LAMBDA, cfg, 32, steps=1, pipelined_sync=True,
                    backend=ProcessBackend())
    total_bytes = fast.store_stats.bytes_in
    # bandwidth sized so uplink sleeps alone total ~8s across the workers:
    # even spread over the S*d=4 processes leaves ~2s on the critical path
    bw = total_bytes / 8.0
    slow = run_plan(prof, AWS_LAMBDA, cfg, 32, steps=1, pipelined_sync=True,
                    backend=ProcessBackend(throttle=True, bandwidth=bw))
    assert slow.store_stats.bytes_in == pytest.approx(total_bytes)
    assert (slow.store_stats.puts, slow.store_stats.gets) == \
        (fast.store_stats.puts, fast.store_stats.gets)
    assert slow.t_total > fast.t_total + 1.0
