"""The comparison that decides ``correct``.

The program's side is read from the timed path itself (the job hands in
what its own steps or its own served requests produced); the reference
side is computed here from the plain reference the cell's family names
(``manifest.load_family``) and weights regenerated from the seed. Every
number compared is printed beside its limit; the limits and the readings
they were set from live in ``benchmark/limits/<cell>.json``.
"""

from __future__ import annotations

import functools
import json
import statistics
import sys
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import weights as W

ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8


def load_limits(cell) -> Dict[str, float]:
    with open(cell.path("limits", cell.name + ".json")) as f:
        return {k: float(v) for k, v in json.load(f)["limits"].items()}


def judge(numbers: Dict[str, float], limits: Dict[str, float],
          tag: str = "check") -> bool:
    """Print each number beside its limit; True when all are inside.
    A number with no limit, or one that is not finite, fails."""
    ok = True
    for k, v in numbers.items():
        lim = limits.get(k)
        good = lim is not None and np.isfinite(v) and v <= lim
        ok &= bool(good)
        print(f"[{tag}] {k} = {v:.6g}  limit {lim}  "
              f"{'ok' if good else 'FAIL'}", file=sys.stderr, flush=True)
    return ok


# --------------------------------------------------------------------------
# training: first steps' losses, first gradient, parameters' change
# --------------------------------------------------------------------------

def leaf_norms(tree: Dict[str, jax.Array]) -> Dict[str, float]:
    out = _norms(tree)
    return {k: float(v) for k, v in jax.device_get(out).items()}


@jax.jit
def _norms(tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}


def delta_norms(params: Dict[str, jax.Array], seed: int,
                rule) -> Dict[str, float]:
    """Per leaf, the norm of ``params`` minus the start its family's
    ``rule`` gives it (regenerated inside the program, never stored)."""
    shapes = {k: v.shape for k, v in params.items()}
    out = _delta(params, W.seed_arg(seed), W.rules_of(rule, shapes))
    return {k: float(v) for k, v in jax.device_get(out).items()}


@functools.partial(jax.jit, static_argnums=2)
def _delta(params, seed_u32, rules):
    return {k: jnp.sqrt(jnp.sum(jnp.square(
        params[k].astype(jnp.float32)
        - W.leaf(seed_u32, k, params[k].shape, jnp.float32, r))))
        for k, r in zip(sorted(params), rules)}


def adam(w, g, m, v, step: int, lr: float, b1: float = ADAM_B1,
         b2: float = ADAM_B2, eps: float = ADAM_EPS):
    """One Adam update (Kingma & Ba, bias-corrected); ``step`` from 1."""
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * jnp.square(g)
    mhat = m / (1 - b1 ** step)
    vhat = v / (1 - b2 ** step)
    return w - lr * mhat / (jnp.sqrt(vhat) + eps), m, v


def train_reference(seed: int, fam, dims, batches: np.ndarray,
                    lr: float, mode: str = "f32") -> dict:
    """Follow the first ``len(batches)`` (1 or 2) Adam steps in float32,
    one row of the batch at a time so that it fits beside nothing else:
    the family's loss is a mean over rows (``manifest.load_family``).
    Adam's moments after step one are functions of the first gradient,
    so that gradient is kept in their place: one array, not two."""
    if not 1 <= len(batches) <= 2:
        raise ValueError("the reference follows one or two steps")
    rows = batches.shape[1]
    loss = fam.reference.loss

    def row_step(acc, w, row):
        l, g = jax.value_and_grad(
            lambda p: loss(p, row[None], dims, mode, remat=True))(w)
        return l, jax.tree_util.tree_map(
            lambda a, b: a + b / rows, acc, g)

    row_step = jax.jit(row_step, donate_argnums=0)

    def grads_of(w, batch):
        acc = jax.tree_util.tree_map(jnp.zeros_like, w)
        total = 0.0
        for r in range(rows):
            l, acc = row_step(acc, w, jnp.asarray(batch[r]))
            total += float(l)
        return total / rows, acc

    @jax.jit
    def first(w, g):
        return jax.tree_util.tree_map(
            lambda p, a: adam(p, a, 0.0, 0.0, 1, lr)[0], w, g)

    @jax.jit
    def second(w, g1, g2):
        return jax.tree_util.tree_map(
            lambda p, a, b: adam(p, b, (1 - ADAM_B1) * a,
                                 (1 - ADAM_B2) * jnp.square(a), 2, lr)[0],
            w, g1, g2)

    w = W.make_all(seed, fam, dims, jnp.float32)
    l1, g1 = grads_of(w, batches[0])
    losses, gnorm = [l1], leaf_norms(g1)
    w = first(w, g1)
    if len(batches) == 2:
        l2, g2 = grads_of(w, batches[1])
        losses.append(l2)
        w = second(w, g1, g2)
        del g2
    del g1
    return {"losses": losses, "grad_norms": gnorm,
            "delta_norms": delta_norms(w, seed, fam.leaf_rule)}


def worst_leaf_gap(got: Dict[str, float], ref: Dict[str, float]) -> float:
    """Largest |got - ref| over the leaves, each against the reference's
    norm of that leaf or of the median leaf, whichever is larger (some
    gradients are all but zero)."""
    floor = statistics.median(ref.values())
    return max(abs(got[k] - ref[k]) / max(ref[k], floor) for k in ref)


def compare_train(got: dict, ref: dict) -> Dict[str, float]:
    return {
        "loss_gap": max(abs(a - b) / abs(b)
                        for a, b in zip(got["losses"], ref["losses"])),
        "grad_norm_gap": worst_leaf_gap(got["grad_norms"],
                                        ref["grad_norms"]),
        "param_change_gap": worst_leaf_gap(got["delta_norms"],
                                           ref["delta_norms"]),
    }


# --------------------------------------------------------------------------
# serving: how far below the reference's best each served token lies
# --------------------------------------------------------------------------

def pick_sample(finished: Sequence[Tuple[np.ndarray, np.ndarray]],
                n: int, seed: int) -> List[int]:
    """Indices of ``n`` finished requests, drawn from the seed, the
    longest (prompt + served tokens) always among them."""
    if not finished:
        return []
    longest = max(range(len(finished)),
                  key=lambda i: len(finished[i][0]) + len(finished[i][1]))
    rest = [i for i in range(len(finished)) if i != longest]
    rng = np.random.default_rng([int(seed), 3])
    take = rng.permutation(len(rest))[:max(0, n - 1)]
    return [longest] + [rest[i] for i in take]


def serve_reference(seed: int, fam, dims, dtype,
                    sample: Sequence[Tuple[np.ndarray, np.ndarray]],
                    rows: int, capacity: int, max_out: int,
                    mode: str = "f32"):
    """Reference logits at every served position of ``sample``: one
    full forward over prompt + served tokens per request, layer by layer
    with that layer's weights regenerated (in the served ``dtype``, cast
    up) and dropped. Fixed shapes (rows, capacity) and (rows, max_out)
    so one program serves every run. Returns (logits (rows, max_out, V),
    served (rows, max_out), mask (rows, max_out))."""
    toks = np.zeros((rows, capacity), np.int32)
    pos = np.zeros((rows, max_out), np.int32)
    served = np.zeros((rows, max_out), np.int32)
    mask = np.zeros((rows, max_out), bool)
    for r, (prompt, out) in enumerate(sample[:rows]):
        full = np.concatenate([prompt, out])[:capacity]
        toks[r, :len(full)] = full
        n = min(len(out), max_out)
        pos[r, :n] = len(prompt) - 1 + np.arange(n)
        served[r, :n] = out[:n]
        mask[r, :n] = True
    lg = fam.reference.layerwise_logits(
        jnp.asarray(toks), jnp.asarray(pos), dims, mode,
        get=lambda shapes: W.make_leaves(seed, shapes, dtype,
                                         fam.leaf_rule),
        shapes_of_layer=lambda i: fam.layer_shapes(dims, i),
        top_shapes=fam.top_shapes(dims))
    return lg, served, mask


@jax.jit
def _gaps(ref_logits, tokens, mask):
    best = jnp.max(ref_logits, axis=-1)
    took = jnp.take_along_axis(ref_logits, tokens[..., None], -1)[..., 0]
    gap = (best - took) / jnp.std(ref_logits, axis=-1)
    return (jnp.max(jnp.where(mask, gap, 0.0)),
            jnp.sum(jnp.where(mask, gap == 0.0, False)), jnp.sum(mask))


def serve_gap(ref_logits, tokens, mask) -> Tuple[float, int, int]:
    """(widest gap by which a token's reference logit lies below the
    reference's best, in standard deviations of that position's logits;
    tokens that are the reference's best; tokens compared)."""
    g, same, n = jax.device_get(_gaps(ref_logits, jnp.asarray(tokens),
                                      jnp.asarray(mask)))
    return float(g), int(same), int(n)
