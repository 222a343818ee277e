"""The plain reference's first training steps, at the cell's own sizes.

The reference takes the cell's configuration and its module beside it
(``init_params``, ``loss``), the same weights and batches from the seed,
and the configuration's optimizer; it imports nothing of the program.  It
runs in float32 at ``highest`` matmul precision, one micro-batch of rows at a
time so that it fits beside nothing else on the chip, and returns what
:func:`chipbench.check.compare` reads.

The optimizer keeps float32 master weights; the model computes with them as
the configuration stores its weights (``torch_dtype``, rounded to nearest),
and the gradient with respect to the stored weights updates the masters.
With bfloat16 weights an update smaller than half a bfloat16 step leaves
the stored weight where it was, in any mixed-precision trainer; a reference
on unrounded weights would move where the configuration cannot.

``numerics`` selects the control (``"fp8"``, see ``refmath``).  ``fault``
plants a fault of the program in the reference put in its place:

``half_batch``    the second half of each batch replaced by the first, so the
                  mean is taken over half the rows;
``no_exchange``   the gradient is replica 0's own, divided by the replica
                  count, as when the scatter-reduce exchanges nothing
                  (first step only: it is read by grad_norm_gap).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from chipbench import check
from chipbench.data import batch_maker, weights_key
from chipbench.refmath import NUMERICS, adamw_step, round_to


def _f32(tree):
    """Each leaf in float32, holding exactly its stored value: a bare
    float32 -> bfloat16 -> float32 pair of conversions inside one program
    may be dropped by XLA, which would start the masters off the stored
    grid."""
    return jax.tree.map(lambda a: round_to(a.astype(jnp.float32), a.dtype),
                        tree)


def run(cell, seed: int, *, numerics: str = "float32", steps: int = 3,
        fault: str = None) -> dict:
    cfg, ref, tr = cell.config, cell.reference, cell.traffic
    opt = cfg["optimizer"]
    nx = NUMERICS[numerics]
    B, mb, d = tr["global_batch"], tr["micro_batch"], tr["dp"]
    init = jax.jit(lambda k: _f32(ref.init_params(cfg, k)))
    make_batch = batch_maker(tr, cfg["vocab_size"], seed)
    if fault == "no_exchange":
        steps = 1

    stored = jnp.dtype(cfg["torch_dtype"])

    def as_stored(a):      # the stored value forward, the gradient straight
        return a + jax.lax.stop_gradient(round_to(a, stored) - a)

    def block(p, acc, tokens, labels):
        loss, g = jax.value_and_grad(lambda q: ref.loss(
            cfg, jax.tree.map(as_stored, q), tokens, labels, nx))(p)
        return loss, jax.tree.map(jnp.add, acc, g)

    block = jax.jit(block, donate_argnums=(1,))
    update = jax.jit(partial(adamw_step, opt=opt), donate_argnums=(0, 2, 3))
    out = {"losses": [], "block_losses": []}
    with jax.default_matmul_precision("highest"):
        p = init(weights_key(seed))
        m = jax.tree.map(jnp.zeros_like, p)
        v = jax.tree.map(jnp.zeros_like, p)
        for k in range(steps):
            batch = make_batch(k)
            tokens, labels = batch["tokens"], batch["labels"]
            if fault == "half_batch":
                tokens = jnp.concatenate([tokens[:B // 2]] * 2)
                labels = jnp.concatenate([labels[:B // 2]] * 2)
            rows = B // d if fault == "no_exchange" else B
            acc = jax.tree.map(jnp.zeros_like, p)
            losses = []
            for lo in range(0, rows, mb):
                loss, acc = block(p, acc, tokens[lo:lo + mb],
                                  labels[lo:lo + mb])
                losses.append(loss)
            n = rows // mb * (d if fault == "no_exchange" else 1)
            grads = jax.tree.map(lambda a: a / n, acc)
            del acc
            losses = [float(x) for x in losses]
            out["block_losses"].append(losses)
            out["losses"].append(sum(losses) / len(losses))
            if k == 0:
                out["grads"] = check.leaf_norms(grads)
            p, m, v = update(p, grads, m, v, jnp.float32(k))
            del grads
        del m, v
        out["changes"] = check.change_norms(p, init(weights_key(seed)))
    return out
