"""Serverless stage workers: real JAX forward/backward for a layer range.

A :class:`StageWorker` owns the contiguous slice of the model that the
planner assigned to one pipeline stage — a range of period instances plus,
for the boundary stages, the embedding table / final norm + LM head — and
executes the same math as the monolithic ``registry.loss_fn`` /
``core.pipeline.pipeline_train_loss`` paths: ``embed_inputs`` ->
``period_forward`` scan -> ``block_norm`` + CE.  Because the instance scan is
simply split at stage boundaries, the engine's pipelined execution is
numerically the monolithic forward, up to fp32 summation order.

Partition bridge: the planner's boundary vector ``x`` indexes the arch
profile produced by ``core.profiler.arch_model_profile`` (layer table
``[embed, layer_0..layer_{n-1}, head]``).  ``stage_instance_ranges`` maps
those cuts onto period-instance ranges; cuts must fall on period boundaries
(always true for ``period_len == 1`` families).

Backward runs through ``jax.vjp``.  With ``jit=True`` (default) the worker
caches a jitted forward and a jitted backward per input-shape signature —
the seed implementation re-traced an un-jitted ``jax.vjp`` closure on every
micro-batch, which dominated engine wall-clock (see the ``walltime`` rows of
``benchmarks/runtime_accuracy.py``).  The jitted forward runs ``jax.vjp``
*inside* the jit and returns the residual-carrying pullback (a
``jax.tree_util.Partial`` pytree), so the backward consumes cached
residuals instead of recomputing the forward inside the VJP — the
recompute variant is kept behind ``remat=True`` for the A/B wall-clock
comparison.  Holding residuals between fwd and bwd is exactly what the
paper's activation-memory term ``mu * a_i`` accounts for.  Gradients are
accumulated in fp32 across micro-batches; ``grad_vector`` flattens them on
the device and hands the storage scatter-reduce one host vector, and
``apply_update`` takes the replicas' sum back, takes the mean and applies the
optimizer on fp32 masters (same math as
``testing.pipeline_equiv.reference_step``); with ``jit=True`` each is one
jitted program per stage, and the update donates the optimizer state.

MoE note: the router aux loss is seeded per micro-batch (weight ``1/mu``),
which matches full-batch routing only when the aux statistic is linear in
the batch — the same caveat as the shard_map pipeline (see
``testing/pipeline_equiv.py``); dense families are exact.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core.partition import stages_of
from repro.models import registry
from repro.models.common import block_norm, softmax_cross_entropy
from repro.models.transformer import period_forward
from repro.optim import Optimizer


@dataclass(frozen=True)
class StageSpan:
    """What pipeline stage ``index`` of ``n_stages`` owns."""

    index: int
    n_stages: int
    inst_lo: int          # first owned period instance
    inst_hi: int          # one past the last owned instance (may equal lo)
    owns_embed: bool
    owns_head: bool


def stage_instance_ranges(cfg: ArchConfig, x) -> List[StageSpan]:
    """Map profile-layer cuts ``x`` (over ``arch_model_profile``'s
    ``[embed, layers..., head]`` table) to period-instance spans."""
    L = len(x) + 1
    expect = cfg.n_layers + 2
    if L != expect:
        raise ValueError(
            f"partition is over {L} profile layers but arch {cfg.name!r} "
            f"profiles to {expect} ([embed] + {cfg.n_layers} layers + [head])")
    plen = cfg.period_len
    spans = []
    stages = stages_of(tuple(x))
    for s, (lo, hi) in enumerate(stages):
        lo_l = max(lo, 1) - 1          # first model layer in the stage
        hi_l = min(hi, cfg.n_layers) - 1   # last model layer (inclusive)
        if lo_l > hi_l:                # embed-only or head-only stage
            inst_lo = inst_hi = 0 if lo == 0 else cfg.n_periods
        else:
            if lo_l % plen != 0:
                raise ValueError(
                    f"stage {s} starts mid-period (layer {lo_l}, period_len={plen}); "
                    "numeric execution needs period-aligned cuts")
            if hi_l != cfg.n_layers - 1 and (hi_l + 1) % plen != 0:
                raise ValueError(
                    f"stage {s} ends mid-period (layer {hi_l}, period_len={plen}); "
                    "numeric execution needs period-aligned cuts")
            inst_lo = lo_l // plen
            inst_hi = -(-(hi_l + 1) // plen)
        spans.append(StageSpan(
            index=s, n_stages=len(stages), inst_lo=inst_lo, inst_hi=inst_hi,
            owns_embed=(lo == 0), owns_head=(hi == L - 1),
        ))
    return spans


#: a TPU tile's minor dimension.  XLA rewrites a slice of the flat gradient
#: followed by a reshape to a leaf whose last dim k is not a multiple of it
#: into a reshape of the whole vector to [n/k, k], tiled and padded to 128
#: lanes: 12.3 GB of temporaries for xlstm-7-1-125m's stage 0, whose q/k/v
#: blocks end in 4.  An optimization barrier keeps such a slice first, and
#: keeps such a leaf's ravel apart from the pack's concatenation.
_LANES = 128


def _narrow(shape) -> bool:
    """A leaf whose last dim does not fill a tile's lanes (see ``_LANES``)."""
    return bool(shape) and shape[-1] % _LANES != 0


def _is_leaf_state(v) -> bool:
    """One param's optimizer state: ``{"master": ..., <moments>}``."""
    return isinstance(v, dict) and "master" in v


class StageWorker:
    """One serverless function: params + optimizer shard for a stage span."""

    def __init__(self, cfg: ArchConfig, span: StageSpan, full_params: dict,
                 *, mu: int, optimizer: Optimizer, replicas: int = 1,
                 jit: bool = True, remat: bool = False):
        if cfg.frontend != "none":
            raise NotImplementedError(
                "runtime numeric execution covers token-LM archs; "
                f"frontend={cfg.frontend!r} is not wired up")
        if cfg.tie_embeddings and span.n_stages > 1:
            raise NotImplementedError(
                "tied embeddings span two stages; untie or use a single stage")
        self.cfg = cfg
        self.span = span
        self.mu = mu
        self.replicas = replicas
        self.optimizer = optimizer
        self.dtype = jnp.dtype(cfg.param_dtype)

        p: Dict[str, Any] = {}
        if span.owns_embed:
            p["embed"] = full_params["embed"]
        if span.owns_head:
            p["final_norm"] = full_params["final_norm"]
            if cfg.tie_embeddings:
                if not span.owns_embed:  # unreachable (guarded above)
                    raise NotImplementedError
            else:
                p["head"] = full_params["head"]
        if span.inst_hi > span.inst_lo:
            p["layers"] = jax.tree.map(
                lambda a: a[span.inst_lo:span.inst_hi], full_params["layers"])
            self.mask = jnp.asarray(
                registry.active_mask(cfg)[span.inst_lo:span.inst_hi])
        else:
            self.mask = None
        self.params = p

        # fp32 masters + optimizer state, per leaf (ZeRO-less: the stage owns
        # its whole shard, replicas hold identical copies).  The update
        # donates this state, so every master is a copy the worker owns:
        # ``astype`` to the dtype a param already has returns the caller's
        # array, and the params may be the caller's own init arrays.
        def leaf_state(a):
            master = jnp.array(a, jnp.float32, copy=True)
            return {"master": master, **optimizer.init_state(master)}

        self.opt_state = jax.tree.map(leaf_state, self.params)

        flat, self._treedef = jax.tree.flatten(self.params)
        self._shapes = [l.shape for l in flat]
        self._sizes = [int(np.prod(l.shape)) for l in flat]
        self._dtypes = [l.dtype for l in flat]
        self.grad_nbytes = float(sum(self._sizes)) * 4  # fp32 sync payload

        self._vjps: Dict[int, Any] = {}
        self._grad_acc = None
        self.jit = jit
        self.remat = remat
        self._saved_inputs: Dict[int, Tuple[Any, Any]] = {}
        self._saved_sigs: Dict[int, Any] = {}
        self._jitted: Dict[Any, Tuple[Any, Any]] = {}  # shape sig -> (fwd, bwd)
        # one compiled pack and one compiled optimizer program per stage;
        # the update donates the state
        self._flatten = jax.jit(self._pack) if jit else self._pack
        self._update = (jax.jit(self._optimizer_update, donate_argnums=0)
                        if jit else self._optimizer_update)

    # ------------------------------------------------------------- stage math
    def _stage_fn(self, params, x, batch_mb):
        cfg = self.cfg
        aux = jnp.zeros((), jnp.float32)
        if self.span.owns_embed:
            x = registry.embed_inputs(cfg, params, batch_mb)
        if self.mask is not None:
            seq = x.shape[1]
            positions = jnp.arange(seq, dtype=jnp.int32)

            def body(h, xs):
                inst_params, act_row = xs
                h, a = period_forward(inst_params, h, act_row, cfg=cfg,
                                      positions=positions)
                return h, a

            x, auxs = jax.lax.scan(body, x, (params["layers"], self.mask))
            aux = aux + jnp.sum(auxs)
        if self.span.owns_head:
            h = block_norm(x, params["final_norm"], cfg)
            head_w = params["embed"] if cfg.tie_embeddings else params["head"]
            logits = h @ head_w.T
            labels = batch_mb["labels"]
            if cfg.causal and not cfg.is_encoder:
                logits = logits[:, :-1]
                labels = labels[:, 1:]
            ce = jnp.mean(softmax_cross_entropy(logits, labels))
            return ce, aux
        return x, aux

    # ------------------------------------------------------------- jit cache
    def _shape_sig(self, x_in, batch_mb):
        leaf = lambda a: (tuple(a.shape), str(jnp.asarray(a).dtype))
        x_sig = None if x_in is None else leaf(x_in)
        b_sig = tuple(sorted((k, leaf(v)) for k, v in batch_mb.items()))
        return (x_sig, b_sig)

    def _get_jitted(self, sig):
        """Jitted (fwd, bwd) pair for one (stage-shape, micro-batch-shape)
        signature, traced once per signature instead of per micro-batch.

        Default (``remat=False``): the forward runs ``jax.vjp`` under jit and
        returns the pullback as a ``jax.tree_util.Partial`` — its leaves ARE
        the residuals, cached in function memory until the backward consumes
        them, so the backward does no forward recompute.  ``remat=True``
        keeps the recompute-inside-VJP variant (no residuals held) for the
        wall-clock A/B in ``benchmarks/runtime_accuracy.py``."""
        fns = self._jitted.get(sig)
        if fns is not None:
            return fns

        def vjp_of(params, x_in, batch_mb):
            if self.span.owns_embed:
                return jax.vjp(lambda p: self._stage_fn(p, None, batch_mb),
                               params)
            return jax.vjp(lambda p, x: self._stage_fn(p, x, batch_mb),
                           params, x_in)

        def unpack(grads):
            g_params = jax.tree.map(lambda g: g.astype(jnp.float32), grads[0])
            g_in = grads[1] if len(grads) > 1 else None
            return g_params, g_in

        def cotangent(g_out):
            seed = jnp.asarray(1.0 / self.mu, jnp.float32)
            return (seed, seed) if self.span.owns_head else (g_out, seed)

        if self.remat:
            def fwd_fn(params, x_in, batch_mb):
                return self._stage_fn(params, x_in, batch_mb)

            def bwd_fn(params, x_in, batch_mb, g_out):
                _, vjp = vjp_of(params, x_in, batch_mb)
                return unpack(vjp(cotangent(g_out)))
        else:
            def fwd_fn(params, x_in, batch_mb):
                out_aux, vjp = vjp_of(params, x_in, batch_mb)
                return out_aux, vjp

            def bwd_fn(vjp, g_out):
                return unpack(vjp(cotangent(g_out)))

        fns = (jax.jit(fwd_fn), jax.jit(bwd_fn))
        self._jitted[sig] = fns
        return fns

    # ---------------------------------------------------------------- fwd/bwd
    def forward(self, m: int, x_in, batch_mb) -> Tuple[Any, float]:
        """Run the stage on micro-batch ``m``.  Returns (output, aux) where
        output is the boundary activation — or the micro-batch CE for the
        last stage."""
        if self.jit:
            x_val = None if self.span.owns_embed else jnp.asarray(x_in)
            sig = self._shape_sig(x_val, batch_mb)
            fwd, _ = self._get_jitted(sig)
            if self.remat:
                out, aux = fwd(self.params, x_val, batch_mb)
                self._saved_inputs[m] = (x_val, batch_mb)
            else:
                (out, aux), vjp = fwd(self.params, x_val, batch_mb)
                self._vjps[m] = vjp          # residuals cached until backward
                self._saved_sigs[m] = sig
            return out, float(aux)
        if self.span.owns_embed:
            out_aux, vjp = jax.vjp(
                lambda p: self._stage_fn(p, None, batch_mb), self.params)
        else:
            out_aux, vjp = jax.vjp(
                lambda p, x: self._stage_fn(p, x, batch_mb), self.params,
                jnp.asarray(x_in))
        self._vjps[m] = vjp
        out, aux = out_aux
        return out, float(aux)

    def _accumulate(self, g_params) -> None:
        if self._grad_acc is None:
            self._grad_acc = g_params
        else:
            self._grad_acc = jax.tree.map(jnp.add, self._grad_acc, g_params)

    def backward(self, m: int, g_out) -> Optional[jax.Array]:
        """VJP for micro-batch ``m``.  ``g_out`` is the cotangent arriving
        from stage s+1 (ignored on the last stage, which seeds the loss).
        Returns the cotangent for stage s-1 (None on stage 0)."""
        if self.jit:
            g_val = None if self.span.owns_head else jnp.asarray(g_out)
            if self.remat:
                x_val, batch_mb = self._saved_inputs.pop(m)
                _, bwd = self._get_jitted(self._shape_sig(x_val, batch_mb))
                g_params, g_in = bwd(self.params, x_val, batch_mb, g_val)
            else:
                vjp = self._vjps.pop(m)      # frees residuals after the call
                _, bwd = self._get_jitted(self._saved_sigs.pop(m))
                g_params, g_in = bwd(vjp, g_val)
            self._accumulate(g_params)
            return g_in
        vjp = self._vjps.pop(m)
        seed = jnp.asarray(1.0 / self.mu, jnp.float32)
        if self.span.owns_head:
            cot = (seed, seed)
        else:
            cot = (jnp.asarray(g_out), seed)
        grads = vjp(cot)
        g_params = grads[0]
        g_in = grads[1] if len(grads) > 1 else None
        g_params = jax.tree.map(lambda g: g.astype(jnp.float32), g_params)
        self._accumulate(g_params)
        return g_in

    # ------------------------------------------------------------ checkpoints
    def export_state(self) -> dict:
        """The stage's full persistent state — params + fp32 masters +
        optimizer moments — as a plain pytree of arrays.  Everything else
        (cached VJP residuals, gradient accumulators, jit caches) is
        per-step transient: a worker restored from this tree at a step
        boundary continues bit-identically."""
        return {"params": self.params, "opt_state": self.opt_state}

    def load_state(self, state: dict) -> None:
        """Restore from :meth:`export_state` (the Function Manager's
        relaunch path).  Resets every transient accumulator — a relaunched
        function starts its step from scratch."""
        treedef = jax.tree.structure(self.params)
        if jax.tree.structure(state["params"]) != treedef:
            raise ValueError(
                f"checkpointed stage state does not match stage {self.span.index}: "
                f"{jax.tree.structure(state['params'])} != {treedef}")
        self.params = jax.tree.map(jnp.asarray, state["params"])
        # copies: the update donates them, and ``state`` stays the caller's
        self.opt_state = jax.tree.map(lambda a: jnp.array(a, copy=True),
                                      state["opt_state"])
        self._vjps.clear()
        self._saved_inputs.clear()
        self._saved_sigs.clear()
        self._grad_acc = None

    # ------------------------------------------------------------------- sync
    def _pack(self, grad_acc):
        """Every fp32 accumulator leaf raveled and concatenated, in leaf
        order: the flat gradient :meth:`grad_vector` hands the sync."""
        with jax.named_scope("pack"):
            flat = []
            for g in jax.tree.leaves(grad_acc):
                narrow = _narrow(g.shape)
                g = g.ravel()
                if narrow:
                    g = jax.lax.optimization_barrier(g)
                flat.append(g)
            return jnp.concatenate(flat)

    def grad_vector(self) -> np.ndarray:
        """Accumulated stage gradient, flattened fp32 (scatter-reduce
        payload): one program on the device, then one copy to the host."""
        assert self._grad_acc is not None, "backward() must run first"
        flat = self._flatten(self._grad_acc)
        self._grad_acc = None       # freed once the pack has read it
        return np.asarray(flat)

    def _optimizer_update(self, opt_state, flat_g, step, replicas):
        """The optimizer on every leaf of the stage: ``flat_g`` (the fp32
        sum of the flat gradient over the stage's ``replicas``) cut into the
        leaves' shapes by static slices and divided by ``replicas``, each
        fp32 master stepped and cast to its param's dtype.  Returns the new
        ``(params, opt_state)``.  :meth:`apply_update` runs it as one
        compiled program, or op by op with ``jit=False``."""
        with jax.named_scope("update"):
            states = jax.tree.leaves(opt_state, is_leaf=_is_leaf_state)
            params, new_states, off = [], [], 0
            for st, shape, size, dtype in zip(states, self._shapes,
                                              self._sizes, self._dtypes):
                g = flat_g[off:off + size]
                if _narrow(shape):
                    g = jax.lax.optimization_barrier(g)
                g = g.reshape(shape) / replicas
                off += size
                moments = {k: v for k, v in st.items() if k != "master"}
                master, moments = self.optimizer.update(
                    g, st["master"], moments, step)
                params.append(master.astype(dtype))
                new_states.append({"master": master, **moments})
        return (jax.tree.unflatten(self._treedef, params),
                jax.tree.unflatten(self._treedef, new_states))

    def apply_update(self, reduced: np.ndarray, step: int) -> None:
        """Optimizer step from the flat gradient summed over the stage's
        replicas: one host -> device copy, then one call of the stage's
        update program, which takes the mean.  The replica count is a traced
        fp32 scalar, so the divide stays a divide (IEEE-exact on the CPU,
        the same bits as numpy's)."""
        assert len(reduced) == sum(self._sizes), (len(reduced), self._sizes)
        self.params, self.opt_state = self._update(
            self.opt_state, jnp.asarray(reduced, jnp.float32),
            jnp.asarray(step, jnp.int32),
            jnp.asarray(self.replicas, jnp.float32))


def assemble_params(cfg: ArchConfig, workers: List[StageWorker]) -> dict:
    """Re-assemble monolithic ``registry.init_params``-layout params from one
    replica's stage workers (for checkpointing / equivalence checks)."""
    out: Dict[str, Any] = {}
    layer_parts = [w.params["layers"] for w in workers if "layers" in w.params]
    if layer_parts:
        out["layers"] = jax.tree.map(
            lambda *parts: jnp.concatenate(parts, axis=0), *layer_parts)
    for w in workers:
        if w.span.owns_embed:
            out["embed"] = w.params["embed"]
        if w.span.owns_head:
            out["final_norm"] = w.params["final_norm"]
            if not cfg.tie_embeddings:
                out["head"] = w.params["head"]
    return out
