"""Per-architecture smoke tests (deliverable f): a REDUCED variant of each
assigned arch runs one forward/train step (and one decode step where the
family supports decoding) on CPU; asserts output shapes and finiteness."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.configs.base import InputShape
from repro.data.synthetic import make_batch
from repro.models import registry
from repro.optim import SGD

TRAIN_SHAPE = InputShape("smoke_train", 64, 4, "train")


@pytest.mark.parametrize("arch_id", ARCH_IDS)
def test_reduced_train_step(arch_id):
    cfg = get_config(arch_id).reduced()
    assert cfg.d_model <= 512
    assert cfg.n_layers <= max(2, cfg.period_len)
    if cfg.moe is not None:
        assert cfg.moe.n_experts <= 4
    params = registry.init_params(cfg, jax.random.PRNGKey(0))
    batch = make_batch(cfg, TRAIN_SHAPE)

    def loss_of(p):
        loss, m = registry.loss_fn(cfg, p, batch)
        return loss, m

    (loss, metrics), grads = jax.value_and_grad(loss_of, has_aux=True)(params)
    assert np.isfinite(float(loss)), arch_id
    assert float(loss) > 0
    # one SGD step moves the loss
    opt = SGD(lr=0.05, momentum=0.0)
    new_params = jax.tree.map(
        lambda p, g: (p.astype(jnp.float32) - 0.05 * g).astype(p.dtype), params, grads
    )
    loss2, _ = registry.loss_fn(cfg, new_params, batch)
    assert np.isfinite(float(loss2))
    assert float(loss2) < float(loss), f"{arch_id}: step did not reduce loss"
    for leaf in jax.tree.leaves(grads):
        assert np.all(np.isfinite(np.asarray(leaf))), arch_id


@pytest.mark.parametrize("arch_id", [a for a in ARCH_IDS
                                     if not get_config(a).is_encoder
                                     and get_config(a).decodes])
def test_reduced_decode_step(arch_id):
    cfg = get_config(arch_id).reduced()
    params = registry.init_params(cfg, jax.random.PRNGKey(0))
    B, ctx = 2, 32
    caches = registry.init_decode_caches(cfg, B, ctx)
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, 1), 0, cfg.vocab_size, jnp.int32)
    logits, caches = registry.decode_step(cfg, params, caches, toks)
    assert logits.shape == (B, 1, cfg.vocab_size)
    assert np.all(np.isfinite(np.asarray(logits)))
    # second step advances the cursor / state
    logits2, _ = registry.decode_step(cfg, params, caches, toks)
    assert np.all(np.isfinite(np.asarray(logits2)))


@pytest.mark.parametrize("arch_id", ARCH_IDS)
def test_full_config_counts(arch_id):
    """The FULL configs (exercised via dry-run only) have sane param counts."""
    cfg = get_config(arch_id)
    n = cfg.param_count()
    expected = {
        "phi3-mini-3.8b": (3.0e9, 5.0e9),
        "hubert-xlarge": (0.7e9, 1.4e9),
        "qwen2.5-14b": (12e9, 17e9),
        "dbrx-132b": (110e9, 150e9),
        "xlstm-125m": (0.08e9, 0.2e9),
        "xlstm-7-1-125m": (0.162e9, 0.165e9),
        "internlm2-20b": (17e9, 24e9),
        "qwen3-moe-235b-a22b": (200e9, 260e9),
        "internvl2-26b": (17e9, 26e9),
        "gemma3-4b": (3.0e9, 5.5e9),
        "jamba-v0.1-52b": (45e9, 60e9),
    }[arch_id]
    assert expected[0] <= n <= expected[1], f"{arch_id}: {n/1e9:.2f}B"
