"""The comparison that decides ``correct``: the program's first three
training steps against the plain float32 reference of the same steps.

Three numbers are compared, each with a limit of its own per cell
(``limits/<cell>.json``):

``loss_gap``
    the largest |program loss - reference loss| over steps 0, 1 and 2;
``grad_norm_gap``
    the first step's gradient as the optimizer got it, read back from the
    program's AdamW state after one step (m / (1 - b1)); for each leaf
    (each layer's slice of a stacked leaf counts as a leaf) the gap between
    the program's norm and the reference's, over the larger of the
    reference's norm of that leaf and of the median leaf; the worst leaf
    of every worker;
``update_norm_gap``
    the same for the change of the fp32 master parameters over the three
    steps, leaving out leaves whose reference gradient is under a thousandth
    of the median leaf's (they move under Adam by round-off alone).

A leaf of the reference that no worker holds, or that workers hold unevenly,
makes both norm gaps infinite.
"""
from __future__ import annotations

import math
from statistics import median
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

NUMBERS = ("loss_gap", "grad_norm_gap", "update_norm_gap")

#: a leaf whose reference gradient norm is under this share of the median
#: leaf's is left out of update_norm_gap
MOVED_FLOOR = 1e-3

Key = Tuple[str, Optional[int]]      # (leaf path, layer index or None)


def _is_stacked(path) -> bool:
    return getattr(path[0], "key", None) == "layers"


@jax.jit
def _norms(tree):
    def f(path, a):
        a = a.astype(jnp.float32)
        if _is_stacked(path):
            return jnp.sqrt(jnp.sum(a * a, axis=tuple(range(1, a.ndim))))
        return jnp.sqrt(jnp.sum(a * a))
    return jax.tree_util.tree_map_with_path(f, tree)


@jax.jit
def _diff_norms(a_tree, b_tree):
    return _norms(jax.tree.map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
        a_tree, b_tree))


def _keyed(norm_tree, first_layer: int = 0) -> Dict[Key, float]:
    out: Dict[Key, float] = {}
    for path, v in jax.tree_util.tree_flatten_with_path(
            jax.device_get(norm_tree))[0]:
        name = jax.tree_util.keystr(path)
        if _is_stacked(path):
            for i, x in enumerate(v.tolist()):
                out[(name, first_layer + i)] = float(x)
        else:
            out[(name, None)] = float(v)
    return out


def leaf_norms(tree, first_layer: int = 0) -> Dict[Key, float]:
    """Norm of every leaf of ``tree``; stacked layer leaves per layer,
    numbered from ``first_layer``."""
    return _keyed(_norms(tree), first_layer)


def change_norms(after, before, first_layer: int = 0) -> Dict[Key, float]:
    return _keyed(_diff_norms(after, before), first_layer)


def take(tree, like, first: int, stop: int):
    """The leaves of ``tree`` at the paths of ``like``, stacked layer leaves
    cut to layers [first, stop)."""
    def pick(path, _):
        node = tree
        for k in path:
            node = node[getattr(k, "key", getattr(k, "idx", None))]
        return node[first:stop] if _is_stacked(path) else node
    return jax.tree_util.tree_map_with_path(pick, like)


def _gap(p: float, r: float, floor: float) -> float:
    return abs(p - r) / max(r, floor) if max(r, floor) > 0 else math.inf


def compare(prog: dict, ref: dict) -> Dict[str, float]:
    """The three numbers.  ``prog``: {"losses": [3], "grads": [per worker
    {key: norm}], "changes": [per worker {key: norm}]}; ``ref``:
    {"losses": [3], "grads": {key: norm}, "changes": {key: norm}}."""
    out = {n: math.inf for n in NUMBERS}
    losses = list(prog["losses"])
    if len(losses) == len(ref["losses"]):
        out["loss_gap"] = max(abs(a - b) for a, b in zip(losses, ref["losses"]))
    held: Dict[Key, int] = {}
    for w in prog["grads"]:
        for k in w:
            held[k] = held.get(k, 0) + 1
    counts = set(held.values())
    if set(held) != set(ref["grads"]) or len(counts) != 1:
        return out                       # a leaf missing, extra or doubled
    g_med = median(ref["grads"].values())
    out["grad_norm_gap"] = max(
        _gap(w[k], ref["grads"][k], g_med) for w in prog["grads"] for k in w)
    moved = {k for k, g in ref["grads"].items() if g >= MOVED_FLOOR * g_med}
    c_med = median(ref["changes"][k] for k in moved)
    out["update_norm_gap"] = max(
        _gap(w[k], ref["changes"][k], c_med)
        for w in prog["changes"] for k in w if k in moved)
    return {k: (v if math.isfinite(v) else math.inf) for k, v in out.items()}


def verdict(numbers: Dict[str, float], limits: Optional[dict]
            ) -> Tuple[bool, Dict[str, dict]]:
    """(correct, {name: {"value", "limit"}}); without limits nothing is
    correct."""
    shown = {n: {"value": numbers[n],
                 "limit": None if limits is None else limits[n]["limit"]}
             for n in NUMBERS}
    ok = limits is not None and all(
        numbers[n] <= limits[n]["limit"] for n in NUMBERS)
    return ok, shown
