"""One pipeline stage's serving math: partitioned prefill + one-token decode.

:class:`ServeStageWorker` is the inference sibling of
``runtime.worker.StageWorker``: it owns a contiguous run of period instances
(plus possibly the embedding and/or the head) and exposes jitted
``prefill``/``decode`` entry points that chain bit-identically to the
monolithic ``registry.prefill`` / ``registry.decode_step`` — both sides run
the same ``lax.scan`` body over the same per-instance parameters, the split
merely chains the scan carry across stages through the object store.
"""
from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.models import registry
from repro.models.common import block_norm
from repro.models.transformer import period_decode, period_prefill
from repro.serverless.runtime.worker import StageSpan


def greedy_token(logits: Any) -> np.ndarray:
    """argmax over the vocab of the last position -> int32 [B, 1].

    The single sampling rule shared by the pipelined engine and the
    monolithic reference loop, so token parity is argmax of bit-identical
    logits on both sides.
    """
    logits = np.asarray(logits)
    return np.argmax(logits[:, -1], axis=-1).astype(np.int32).reshape(-1, 1)


class ServeStageWorker:
    """Stage ``span`` of ``cfg``, serving prefill + decode requests.

    ``prefill(x_in)`` takes the token ids ([B, S] int) when the stage owns
    the embedding, else the upstream hidden state [B, S, d]; it returns
    ``(out, caches)`` where ``out`` is the next stage's input (or last-
    position logits on the head stage) and ``caches`` the stage's decode
    caches (None when the stage owns no layers).  ``decode(caches, x_in)``
    is the single-token analog.
    """

    def __init__(self, cfg: ArchConfig, span: StageSpan, full_params: dict, *,
                 s_ctx: int, jit: bool = True, use_pallas: bool = False):
        if cfg.frontend != "none":
            raise NotImplementedError(
                f"pipelined serving supports token frontends only, "
                f"got frontend={cfg.frontend!r}")
        if cfg.tie_embeddings and span.n_stages > 1:
            raise NotImplementedError(
                "tied embeddings cannot be split across serving stages "
                "(embed and head live in different workers)")
        self.cfg = cfg
        self.span = span
        self.s_ctx = int(s_ctx)
        self.use_pallas = bool(use_pallas)
        self.has_layers = span.inst_hi > span.inst_lo

        p: dict = {}
        if span.owns_embed or cfg.tie_embeddings:
            p["embed"] = full_params["embed"]
        if span.owns_head:
            p["final_norm"] = full_params["final_norm"]
            if not cfg.tie_embeddings:
                p["head"] = full_params["head"]
        if self.has_layers:
            p["layers"] = jax.tree.map(
                lambda a: a[span.inst_lo:span.inst_hi],
                full_params["layers"])
        self.params = p
        self.mask = (jnp.asarray(
            registry.active_mask(cfg)[span.inst_lo:span.inst_hi])
            if self.has_layers else None)

        self._prefill = jax.jit(self._prefill_fn) if jit else self._prefill_fn
        self._decode = jax.jit(self._decode_fn) if jit else self._decode_fn

    # ------------------------------------------------------------- jitted math
    def _embed(self, params, x_in):
        return params["embed"][x_in] if self.span.owns_embed else x_in

    def _head(self, params, h):
        h = block_norm(h, params["final_norm"], self.cfg)
        head = (params["embed"] if self.cfg.tie_embeddings
                else params["head"])
        return h @ head.T

    def _prefill_fn(self, params, x_in):
        h = self._embed(params, x_in)
        positions = jnp.arange(h.shape[1], dtype=jnp.int32)
        caches = None
        if self.has_layers:
            def body(x, scanned):
                pp, act = scanned
                x, cs = period_prefill(pp, x, act, cfg=self.cfg,
                                       positions=positions,
                                       capacity=self.s_ctx)
                return x, cs

            h, caches = jax.lax.scan(body, h, (params["layers"], self.mask))
        if self.span.owns_head:
            # matches registry.prefill: norm + logits on the last position
            return self._head(params, h[:, -1:]), caches
        return h, caches

    def _decode_fn(self, params, caches, x_in):
        h = self._embed(params, x_in)
        if self.has_layers:
            def body(x, scanned):
                pp, cache, act = scanned
                x, nc = period_decode(pp, x, cache, act, cfg=self.cfg,
                                      use_pallas=self.use_pallas)
                return x, nc

            h, caches = jax.lax.scan(
                body, h, (params["layers"], caches, self.mask))
        if self.span.owns_head:
            return self._head(params, h), caches
        return h, caches

    # --------------------------------------------------------------- frontends
    def prefill(self, x_in) -> Tuple[np.ndarray, Optional[Any]]:
        out, caches = self._prefill(self.params, x_in)
        return (np.asarray(out),
                None if caches is None else jax.tree.map(np.asarray, caches))

    def decode(self, caches, x_in) -> Tuple[np.ndarray, Optional[Any]]:
        out, caches = self._decode(self.params, caches, x_in)
        return (np.asarray(out),
                None if caches is None else jax.tree.map(np.asarray, caches))
