"""Compile the Pallas kernels for a described TPU v5e at phi3-mini shapes.

Nothing runs: the TPU compiler installed beside JAX compiles for a chip that
is described, not attached, and refuses what the chip would (unaligned
tiles, too much VMEM) — which interpret-mode tests cannot show.  The kernels
go through ``repro.kernels.ops`` with ``jax.default_backend`` reporting
"tpu", so the compiled program is the one ops picks on a chip, block sizes
included.  The topology is described inside a fixture: the TPU library may
be loaded by one process at a time, and only the worker that runs these
tests may load it.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compile_for_tpu(monkeypatch, one_chip):
    """AOT-compile ``fn`` at the given shapes for one described chip, with
    the persistent cache off (a TPU program cannot be read back here)."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv("REPRO_KERNEL_MODE", raising=False)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()

    def run(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in shapes]
        return jax.jit(fn).lower(*args).compile()

    yield run
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


BF16, I32 = jnp.bfloat16, jnp.int32


def _flash():
    from repro.kernels import ops

    return functools.partial(ops.flash_attention, causal=True)


def _swiglu():
    from repro.kernels import ops

    return ops.swiglu


def _decode():
    from repro.kernels import ops

    return ops.decode_attention


@pytest.mark.parametrize("kernel,shapes", [
    (_flash, [((1, 512, 32, 96), BF16)] * 3),
    (_swiglu, [((1024, 3072), BF16), ((3072, 8192), BF16),
               ((3072, 8192), BF16)]),
    (_decode, [((1, 32, 96), BF16), ((1, 32, 1024, 96), BF16),
               ((1, 32, 1024, 96), BF16), ((), I32)]),
    # tiles the defaults do not divide: ops picks S-block 104, T-block 200
    (_flash, [((1, 520, 32, 96), BF16)] * 3),
    (_swiglu, [((1000, 3072), BF16), ((3072, 8192), BF16),
               ((3072, 8192), BF16)]),
], ids=["flash-phi3", "swiglu-phi3", "decode-phi3", "flash-S520",
        "swiglu-T1000"])
def test_kernel_compiles_for_v5e(compile_for_tpu, kernel, shapes):
    compiled = compile_for_tpu(kernel(), *shapes)
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 2**30


def test_stage_update_keeps_small_leaves_unpadded(compile_for_tpu, one_chip):
    """The compiled optimizer update of a stage whose leaves end in a dim
    under a tile's 128 lanes (xlstm-7-1-125m's q/k/v blocks [.., 4, 4] and
    gate biases [.., 4]) holds a few flat vectors of temporaries, not the
    whole flat gradient reshaped to [n/4, 4] and padded to 128 lanes (32x)."""
    from repro.api import numeric_plan
    from repro.serverless.runtime.worker import StageWorker, stage_instance_ranges

    # one stage holding both periods: leaves stacked over two instances
    plan, _, ex = numeric_plan("xlstm-7-1-125m@reduced16", stages=1, dp=1,
                               batch=4, seq=16)
    span = stage_instance_ranges(ex.cfg, plan.x)[0]
    w = StageWorker(ex.cfg, span, ex.init_params, mu=2, optimizer=ex.optimizer)
    n = sum(w._sizes)
    on_chip = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,  # noqa: E731
                                             sharding=one_chip)
    compiled = jax.jit(w._optimizer_update, donate_argnums=0).lower(
        jax.tree.map(on_chip, w.opt_state),
        on_chip(jax.ShapeDtypeStruct((n,), jnp.float32)),
        on_chip(jax.ShapeDtypeStruct((), jnp.int32))).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * 4 * n
