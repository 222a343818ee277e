"""Model-level unit tests: decode-vs-forward consistency for every family,
recurrent-vs-parallel form equivalence, MoE routing invariants, blockwise
attention vs naive."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypo import given, settings, st

from repro.configs import get_config
from repro.configs.base import InputShape, LayerSpec
from repro.models import attention, mamba, moe, registry, xlstm
from repro.models.common import (layer_norm, softmax_cross_entropy,
                                 vocab_parallel_cross_entropy)


DECODABLE = ["phi3-mini-3.8b", "gemma3-4b", "qwen2.5-14b", "internlm2-20b",
             "internvl2-26b", "xlstm-125m", "jamba-v0.1-52b", "dbrx-132b",
             "qwen3-moe-235b-a22b"]


@pytest.mark.parametrize("arch_id", DECODABLE)
def test_prefill_decode_matches_forward(arch_id):
    cfg = get_config(arch_id).reduced()
    if cfg.moe is not None:  # no capacity drops for exactness
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=float(cfg.moe.n_experts)))
    params = registry.init_params(cfg, jax.random.PRNGKey(0))
    B, S = 2, 32
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab_size, jnp.int32)
    batch = {"tokens": toks}
    if cfg.frontend == "vision":
        from repro.models.multimodal import synth_patch_embeds
        batch["image_embeds"] = synth_patch_embeds(jax.random.PRNGKey(2), cfg, B)
    h, _ = registry.forward(cfg, params, {**batch, "labels": toks})
    ref_logits = registry._logits(cfg, params, h)
    pre = dict(batch)
    pre["tokens"] = toks[:, : S - 4]
    logits_last, caches = registry.prefill(cfg, params, pre, capacity=S)
    np.testing.assert_allclose(
        np.asarray(logits_last[:, 0]), np.asarray(ref_logits[:, S - 5]),
        rtol=1e-4, atol=1e-4)
    for t in range(S - 4, S):
        sl, caches = registry.decode_step(cfg, params, caches, toks[:, t:t + 1])
        np.testing.assert_allclose(
            np.asarray(sl[:, 0]), np.asarray(ref_logits[:, t]),
            rtol=1e-4, atol=2e-4, err_msg=f"{arch_id} step {t}")


def test_mamba_parallel_vs_recurrent():
    cfg = get_config("jamba-v0.1-52b").reduced()
    p = mamba.init_mamba_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    B, S = 2, 16
    x = 0.5 * jax.random.normal(jax.random.PRNGKey(1), (B, S, cfg.d_model))
    y_par, state = mamba.mamba_forward(p, x, cfg=cfg, return_state=True)
    cache = mamba.init_mamba_cache(B, cfg, cfg.mamba.d_inner(cfg.d_model), jnp.float32)
    ys = []
    for t in range(S):
        y, cache = mamba.mamba_decode(p, x[:, t:t + 1], cache, cfg=cfg)
        ys.append(y)
    y_rec = jnp.concatenate(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_par), np.asarray(y_rec), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(state.h), np.asarray(cache.h), rtol=2e-4, atol=2e-4)


def test_mlstm_chunked_vs_recurrent():
    cfg = get_config("xlstm-125m").reduced()
    p = xlstm.init_mlstm_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    B, S = 2, 16
    x = 0.5 * jax.random.normal(jax.random.PRNGKey(1), (B, S, cfg.d_model))
    y_par = xlstm.mlstm_forward(p, x, cfg=cfg)
    di = int(cfg.d_model * cfg.xlstm.m_proj_factor)
    cache = xlstm.init_mlstm_cache(B, cfg, di, cfg.n_heads, jnp.float32)
    ys = []
    for t in range(S):
        y, cache = xlstm.mlstm_decode(p, x[:, t:t + 1], cache, cfg=cfg)
        ys.append(y)
    y_rec = jnp.concatenate(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_par), np.asarray(y_rec), rtol=3e-4, atol=3e-4)


def test_mlstm_chunk_boundary_invariance():
    """Chunked mLSTM result must not depend on the chunk size."""
    cfg = get_config("xlstm-125m").reduced()
    p = xlstm.init_mlstm_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = 0.5 * jax.random.normal(jax.random.PRNGKey(1), (1, 32, cfg.d_model))
    import repro.models.xlstm as xm
    orig = xm.MLSTM_CHUNK
    try:
        xm.MLSTM_CHUNK = 8
        y8 = xlstm.mlstm_forward(p, x, cfg=cfg)
        xm.MLSTM_CHUNK = 32
        y32 = xlstm.mlstm_forward(p, x, cfg=cfg)
    finally:
        xm.MLSTM_CHUNK = orig
    np.testing.assert_allclose(np.asarray(y8), np.asarray(y32), rtol=3e-4, atol=3e-4)


def test_blockwise_attention_matches_naive():
    from repro.models.attention import _blockwise_attention
    key = jax.random.PRNGKey(0)
    B, S, Hq, Hkv, hd = 2, 1024, 4, 2, 64
    q = jax.random.normal(key, (B, S, Hq, hd))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, S, Hkv, hd))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, S, Hkv, hd))
    pos = jnp.arange(S, dtype=jnp.int32)
    from repro.kernels.ref import flash_attention_ref
    for window in [0, 128]:
        got = _blockwise_attention(q, k, v, pos, True, window)
        want = flash_attention_ref(q, k, v, causal=True, window=window)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_moe_no_drop_equals_dense_mixture():
    """With huge capacity, moe_forward == explicit per-token expert mixture."""
    cfg = get_config("dbrx-132b").reduced()
    cfg = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=float(cfg.moe.n_experts)))
    mc = cfg.moe
    p = moe.init_moe_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    B, S = 2, 8
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, cfg.d_model))
    got, aux = moe.moe_forward(p, x, cfg=cfg)
    # explicit reference
    toks = x.reshape(-1, cfg.d_model)
    logits = toks @ p["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    gates, sel = jax.lax.top_k(probs, mc.top_k)
    gates = gates / gates.sum(-1, keepdims=True)
    outs = []
    for t in range(toks.shape[0]):
        acc = jnp.zeros(cfg.d_model)
        for kk in range(mc.top_k):
            e = int(sel[t, kk])
            h = jax.nn.silu(toks[t] @ p["w_gate"][e]) * (toks[t] @ p["w_up"][e])
            acc = acc + gates[t, kk] * (h @ p["w_down"][e])
        outs.append(acc)
    want = jnp.stack(outs).reshape(B, S, cfg.d_model)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4)
    assert float(aux) >= 0


def test_moe_capacity_drops_tokens():
    cfg = get_config("dbrx-132b").reduced()
    cfg = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=0.1))
    p = moe.init_moe_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, cfg.d_model))
    out, _ = moe.moe_forward(p, x, cfg=cfg)
    assert np.all(np.isfinite(np.asarray(out)))


@given(st.integers(0, 1000))
@settings(max_examples=20, deadline=None)
def test_vocab_parallel_ce_matches(seed):
    key = jax.random.PRNGKey(seed)
    T, V = 8, 32
    logits = jax.random.normal(key, (T, V))
    labels = jax.random.randint(jax.random.fold_in(key, 1), (T,), 0, V)
    want = softmax_cross_entropy(logits, labels)
    # single-shard vocab-parallel (identity psum) must agree
    got = vocab_parallel_cross_entropy(logits, labels, jnp.int32(0), V, lambda x: x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_window_cache_ring_semantics():
    """Decode with a ring cache == decode with a full cache, once warm."""
    cfg = get_config("gemma3-4b").reduced()
    spec_w = cfg.period[0]   # windowed layer spec (window=64 reduced)
    assert spec_w.window > 0
    p = attention.init_attn_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    B, S = 1, 48
    x = 0.3 * jax.random.normal(jax.random.PRNGKey(1), (B, S, cfg.d_model))
    pos = jnp.arange(S, dtype=jnp.int32)
    full = attention.attn_forward(p, x, cfg=cfg, spec=spec_w, positions=pos)
    cache = attention.init_kv_cache(B, cfg.n_kv_heads,
                                    attention.cache_capacity(spec_w, S), cfg.hd,
                                    jnp.float32)
    outs = []
    for t in range(S):
        y, cache = attention.attn_decode(p, x[:, t:t + 1], cache, cfg=cfg, spec=spec_w)
        outs.append(y)
    got = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(full), rtol=2e-4, atol=2e-4)


# ------------------------------------------- the paper's xLSTM[7:1] (xlstm-7-1-125m)
def test_headwise_projection_is_block_diagonal_dense():
    n, b = 6, 4
    w = jax.random.normal(jax.random.PRNGKey(0), (n, b, b))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 5, n * b))
    dense = jax.scipy.linalg.block_diag(*w)          # [n*b, n*b]
    np.testing.assert_allclose(np.asarray(xlstm.headwise(x, w)),
                               np.asarray(x @ dense), rtol=1e-5, atol=1e-5)


def test_paper_slstm_scan_matches_a_loop_over_the_cell():
    from repro.configs import resolve_arch
    from repro.configs.base import SLSTM

    cfg = resolve_arch("xlstm-7-1-125m@reduced")
    assert cfg.xlstm.block == "paper"
    p = xlstm.init_slstm_params(jax.random.PRNGKey(0), cfg, jnp.float32,
                                inner_ff=False)
    assert "w_up_ff" not in p and p["w_gates"].ndim == 4
    B, S = 2, 12
    x = 0.5 * jax.random.normal(jax.random.PRNGKey(1), (B, S, cfg.d_model))
    got = xlstm.slstm_forward(p, x, cfg=cfg)
    xg = xlstm._slstm_gate_inputs(p, x, cfg)
    st = xlstm.init_slstm_cache(B, cfg, x.dtype)
    hs = []
    for t in range(S):
        st, h = xlstm._slstm_cell(p, cfg, xg[:, t], st)
        hs.append(h.reshape(B, cfg.d_model))
    want = layer_norm(jnp.stack(hs, axis=1), p["out_norm"], cfg.norm_eps,
                      groups=cfg.n_heads)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    # the convolution feeds only the input and forget gates: a change of x
    # at t moves the z/o gate inputs of t alone, the i/f inputs of t..t+3
    dx = x.at[:, 5].add(1.0)
    moved = jnp.abs(xlstm._slstm_gate_inputs(p, dx, cfg) - xg).reshape(
        B, S, cfg.n_heads, 4, -1).max(axis=(0, 2, 4))       # [S, gate]
    assert np.all(np.asarray(moved[[5], :]) > 0)
    assert np.all(np.asarray(moved[6:9, [1, 2]]) > 0)
    assert np.all(np.asarray(moved[6:, [0, 3]]) == 0)
    assert cfg.period[7].mixer == SLSTM


def test_slstm_is_blind_to_an_input_gate_bias():
    """Why the paper's sLSTM has no input-gate bias here: a constant added
    to the input gate's pre-activation at every step leaves h as it was, so
    such a bias would get no gradient."""
    from repro.configs import resolve_arch

    cfg = resolve_arch("xlstm-7-1-125m@reduced")
    p = xlstm.init_slstm_params(jax.random.PRNGKey(0), cfg, jnp.float32,
                                inner_ff=False)
    assert p["b_gates"].shape == (3 * cfg.d_model,)
    B, S = 2, 12
    x = 0.5 * jax.random.normal(jax.random.PRNGKey(1), (B, S, cfg.d_model))
    xg = xlstm._slstm_gate_inputs(p, x, cfg)

    def run(shift):
        g = xg.reshape(B, S, cfg.n_heads, 4, -1).at[:, :, :, 1].add(shift)
        st, hs = xlstm.init_slstm_cache(B, cfg, x.dtype), []
        for t in range(S):
            st, h = xlstm._slstm_cell(p, cfg, g[:, t].reshape(B, -1), st)
            hs.append(h)
        return jnp.stack(hs)

    np.testing.assert_allclose(np.asarray(run(2.5)), np.asarray(run(0.0)),
                               rtol=1e-5, atol=1e-6)
    assert float(jnp.max(jnp.abs(run(0.0)))) > 0.01


def test_xlstm_7_1_125m_is_the_papers_model():
    from repro.configs import resolve_arch
    from repro.configs.base import DENSE_FF, MLSTM, NO_FF, SLSTM

    cfg = resolve_arch("xlstm-7-1-125m")
    kinds = [cfg.layer_spec(i).mixer for i in range(cfg.n_layers)]
    assert cfg.n_layers == 24 and cfg.n_periods == 3
    assert kinds == ([MLSTM] * 7 + [SLSTM]) * 3
    assert all(cfg.layer_spec(i).ff == (DENSE_FF if k == SLSTM else NO_FF)
               for i, k in enumerate(kinds))
    xc = cfg.xlstm
    assert (cfg.d_model, cfg.n_heads, int(cfg.d_model * xc.m_proj_factor),
            xc.qkv_block, xc.conv_kernel, cfg.d_ff, cfg.ff_act,
            cfg.vocab_size, cfg.norm) == (768, 4, 1536, 4, 4, 1024, "gelu",
                                          50304, "layer")
    assert not cfg.tie_embeddings
    shapes = jax.eval_shape(lambda k: registry.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    n = sum(a.size for a in jax.tree.leaves(shapes))
    assert n == pytest.approx(163.7e6, rel=0.01)
    assert cfg.param_count() == pytest.approx(n, rel=1e-3)
    # the registry's 1:1 variant is unchanged and still decodes
    old = resolve_arch("xlstm-125m")
    assert old.decodes and not cfg.decodes
    assert not cfg.supports_shape("decode_32k")
    assert cfg.supports_shape("train_4k")


def test_serving_refuses_the_papers_xlstm():
    from repro.serverless.platform import get_platform
    from repro.serving import arch_config_for_model, plan_serving

    with pytest.raises(NotImplementedError, match="trains only"):
        arch_config_for_model("xlstm-7-1-125m@reduced")
    with pytest.raises(NotImplementedError, match="prefill and decode"):
        plan_serving("xlstm-7-1-125m@reduced", get_platform("aws"), slo=60.0)


def test_stage_program_carries_the_xlstm_scopes():
    """A lowered stage forward of the reduced model names the mLSTM, its
    memory, the sLSTM and its recurrence in its HLO op metadata."""
    from repro.api import numeric_plan
    from repro.serverless.runtime.worker import StageWorker, stage_instance_ranges

    plan, _, ex = numeric_plan("xlstm-7-1-125m@reduced16", stages=2, dp=1,
                               batch=4, seq=16)
    span = stage_instance_ranges(ex.cfg, plan.x)[0]
    w = StageWorker(ex.cfg, span, ex.init_params, mu=2, optimizer=ex.optimizer)
    batch = ex.batch_fn(0)
    mb = {k: v[:2] for k, v in batch.items()}
    hlo = jax.jit(w._stage_fn).lower(w.params, None, mb).as_text(
        debug_info=True)
    for scope in ("mlstm/", "mlstm.memory/", "slstm/", "slstm.recurrence/"):
        assert scope in hlo, scope
