"""Mixture-of-Experts FFN — Switch-style top-1 routing over the 'ep'
mesh axis.

Green-field TPU design (the reference has no MoE; its expert-parallel
niche is PSLib's giant sharded embeddings, which this framework covers
with parallel.ShardedEmbedding — SURVEY §2.5). This layer completes the
'ep' axis story for TRANSFORMER compute: expert weights shard
``P('ep', ...)``, routing uses the dense one-hot dispatch/combine
einsum formulation (Mesh-TensorFlow / Switch-Transformer lineage) so the
whole layer is static-shaped, MXU-friendly, and the SPMD partitioner
inserts the token all-to-all between the data-parallel token layout and
the expert-parallel compute layout — no sorting, no ragged shapes, no
host control flow.

Semantics (Switch Transformer, top-1):
- router: softmax over ``num_experts`` logits per token; each token goes
  to its argmax expert with its gate probability as the scale.
- capacity: each expert processes at most ``ceil(tokens/E * cf)``
  tokens; overflow tokens are DROPPED (output zeros — callers keep the
  residual connection, so dropped tokens pass through identity).
- aux loss: ``E * sum_e(fraction_e * mean_prob_e)`` (the Switch
  load-balance loss; 1.0 at perfect balance), returned per call for the
  trainer to weight.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from ..core.dtypes import compute_dtype_of
from ..core.enforce import enforce
from .. import initializer as I
from .layer import Layer
from .layers import Linear
from ..telemetry.scopes import scope

__all__ = ["DroplessMoE", "SwitchFFN", "dropless_moe", "route",
           "switch_moe"]


def switch_moe(x, router_w, w1, b1, w2, b2, *, capacity: int,
               act=jax.nn.gelu, top_k: int = 1):
    """Functional top-k MoE over tokens (k=1: Switch; k=2: GShard).

    x: (S, D) tokens; router_w: (D, E); w1: (E, D, F); b1: (E, F);
    w2: (E, F, D); b2: (E, D). Returns (y (S, D), aux_loss scalar,
    z_loss scalar, kept_fraction scalar — kept = the fraction of
    (token, choice) assignments that fit capacity; z_loss is the ST-MoE
    router stability term mean(logsumexp(logits)^2), weighted ~1e-3 by
    the trainer to keep router logits from drifting large).

    top-2 follows GShard's ordering: every token's FIRST choice claims
    its expert slot before any second choice does, and the two gates are
    renormalized to sum to 1 per token.
    """
    enforce(top_k in (1, 2), "top_k must be 1 or 2, got %s", top_k)
    s = x.shape[0]
    e = router_w.shape[1]
    logits = (x @ router_w).astype(jnp.float32)        # (S, E)
    probs = jax.nn.softmax(logits, axis=-1)
    z = jax.nn.logsumexp(logits, axis=-1)              # (S,)
    z_loss = jnp.mean(z * z)
    top_p, top_i = jax.lax.top_k(probs, top_k)         # (S, k)
    # Switch top-1 scales by the RAW router probability; GShard top-2
    # renormalizes the two gates to sum to 1 per token
    gates = (top_p if top_k == 1
             else top_p / jnp.sum(top_p, axis=-1, keepdims=True))
    onehots = [jax.nn.one_hot(top_i[:, j], e, dtype=jnp.float32)
               for j in range(top_k)]                  # k x (S, E)
    # positions within each expert's queue (arrival order — deterministic,
    # shard-invariant prefix sums); ALL first choices precede second ones
    pos = [jnp.cumsum(onehots[0], axis=0) * onehots[0]]  # (S, E), 1-based
    if top_k == 2:
        first_counts = jnp.sum(onehots[0], axis=0)     # (E,)
        pos.append((jnp.cumsum(onehots[1], axis=0) + first_counts[None, :])
                   * onehots[1])
    dmask = jnp.zeros((s, e, capacity), x.dtype)
    combine = jnp.zeros((s, e, capacity), x.dtype)
    kept_ct = jnp.zeros((), jnp.float32)
    for j in range(top_k):
        keep = (pos[j] > 0) & (pos[j] <= capacity)
        pos_c = jnp.clip(pos[j] - 1, 0, capacity - 1).astype(jnp.int32)
        slot = jax.nn.one_hot(pos_c, capacity, dtype=x.dtype)  # (S, E, C)
        dm = slot * keep.astype(x.dtype)[..., None]
        dmask = dmask + dm
        combine = combine + dm * gates[:, j].astype(x.dtype)[:, None, None]
        # BOOL mask counted in f32: a bf16 dmask sum saturates at 256
        # under the mixed_bf16 policy and would corrupt the metric
        kept_ct = kept_ct + jnp.sum(keep.astype(jnp.float32))
    expert_in = jnp.einsum("sec,sd->ecd", dmask, x)    # (E, C, D)
    h = act(jnp.einsum("ecd,edf->ecf", expert_in, w1) + b1[:, None, :])
    out_e = jnp.einsum("ecf,efd->ecd", h, w2) + b2[:, None, :]
    y = jnp.einsum("sec,ecd->sd", combine, out_e)      # dropped -> zeros
    # load-balance aux over FIRST-choice assignment (Switch/GShard form):
    # E * sum_e(fraction_of_tokens_e * mean_prob_e)
    frac = jnp.mean(onehots[0], axis=0)                # (E,)
    mean_prob = jnp.mean(probs, axis=0)                # (E,)
    aux = e * jnp.sum(frac * mean_prob)
    kept = kept_ct / (s * top_k)
    return (y, aux.astype(jnp.float32), z_loss.astype(jnp.float32),
            kept.astype(jnp.float32))


class SwitchFFN(Layer):
    """Drop-in MoE replacement for the position-wise FFN.

    ``forward(x (B, T, D)) -> (B, T, D)``; the load-balance aux loss
    and kept-token fraction of the call ride the BUFFER mechanism
    (``aux_loss``/``kept_fraction`` — functional callers collect them
    from functional_call's new_buffers, the BatchNorm-stats contract;
    the trainer adds ``aux_weight * aux_loss`` to the objective, 0.01 in
    the Switch paper).

    Expert weights are stacked ``(E, ...)``; under a mesh, place them
    ``P('ep', ...)`` (:func:`expert_param_spec`) and the partitioner
    inserts the token all-to-all between the dp token layout and the
    ep expert layout (golden-HLO tested).
    """

    def __init__(self, d_model: int, d_ff: int, num_experts: int,
                 capacity_factor: float = 1.25,
                 act=jax.nn.gelu, dtype=None, router_top_k: int = 1):
        super().__init__()
        enforce(num_experts >= 2, "SwitchFFN needs >= 2 experts, got %s",
                num_experts)
        enforce(capacity_factor > 0.0,
                "capacity_factor must be > 0, got %s", capacity_factor)
        enforce(router_top_k in (1, 2),
                "router_top_k must be 1 (Switch) or 2 (GShard), got %s",
                router_top_k)
        self.num_experts = num_experts
        self.capacity_factor = float(capacity_factor)
        self.act = act
        self.router_top_k = router_top_k
        self.create_parameter("router_w", (d_model, num_experts),
                              dtype, I.XavierUniform())
        self.create_parameter("w1", (num_experts, d_model, d_ff), dtype,
                              I.XavierUniform())
        self.create_parameter("b1", (num_experts, d_ff), dtype,
                              I.Constant(0.0), is_bias=True)
        self.create_parameter("w2", (num_experts, d_ff, d_model), dtype,
                              I.XavierUniform())
        self.create_parameter("b2", (num_experts, d_model), dtype,
                              I.Constant(0.0), is_bias=True)
        self.register_buffer("aux_loss", jnp.zeros((), jnp.float32))
        self.register_buffer("router_z_loss", jnp.zeros((), jnp.float32))
        self.register_buffer("kept_fraction", jnp.ones((), jnp.float32))

    def capacity(self, tokens: int) -> int:
        # top-k routing makes k*tokens assignments: capacity scales with
        # k (GShard convention) or the second choices would nearly all
        # drop at the default factor
        return max(1, math.ceil(tokens * self.router_top_k
                                / self.num_experts
                                * self.capacity_factor))

    def forward(self, x):
        b, t, d = x.shape
        y, aux, z_loss, kept = switch_moe(
            x.reshape(b * t, d), self.router_w,
            self.w1, self.b1, self.w2, self.b2,
            capacity=self.capacity(b * t), act=self.act,
            top_k=self.router_top_k)
        self.update_buffer("aux_loss", aux)
        self.update_buffer("router_z_loss", z_loss)
        self.update_buffer("kept_fraction", kept)
        return y.reshape(b, t, d)


# Which body the routed experts take, from the static counts of a call.
# Both constants are read off ``tools/moe_bodies.py`` on a v5e at the two
# expert shapes the benchmark holds (PERF.md section 3 has the table).
#
# The dense body does ``experts / top_k`` times the grouped body's
# products (held x rows against rows x top_k x held / experts: the held
# count and both widths cancel), at 0.92 to 0.97 of the bf16 peak once
# it is past the ridge. The grouped body costs 9 to 20 times its own
# products' time at that peak (sort, gathers, a float32 row a pair
# written and read several times, grouped kernels whose groups are a
# few hundred rows), so the dense body is the faster one while it does
# up to about that many times the work: 7.2 (72 experts, 10 a token) is
# 1.2 to 2.9 times faster dense at every row count from 16 to 4096; 16
# (64 experts, 4 a token) is a tie from 4096 rows up, within 2 to 6%.
# (PR 44's readings, of a grouped body that gathered and wrote a row for
# every pair, held or not. Since PR 49 it touches the held pairs alone,
# and at a ratio of 16 with an eighth of the experts held it is 1.3 to
# 1.8 times the faster from 2048 rows up: the rule sends those rows the
# right way with more room than it had.)
DENSE_MAX_WORK = 12
# Whatever the work ratio, few rows stream densely: before its first row
# the grouped body costs about 2.3 times the stream of the held weights,
# and the dense body's products take that long at 2.3 times the chip's
# ridge (v5e: 197e12 / 819e9 = 240 FLOP a byte, so 550 rows; the held
# bytes cancel here too). At a work ratio of 16 the dense body is 3.3 /
# 2.4 / 1.6 times faster at 128 / 256 / 512 rows and level at 1024.
DENSE_MAX_ROWS = 512


# The grouped body keeps nothing of a (token, pick) pair but integers (a
# sort key, its place in the order, the counts): the rows it gathers and
# multiplies are a window of the pairs on HELD experts (:func:`window_rows`).
# What is left that grows with a call's tokens is the float32 result,
# (rows, width), beside a window's rows. Past this many bytes of float32
# rows a (token, pick) pair would have taken, a call's tokens still go
# through the body in equal parts, one after another: a 28672-token
# prefill at 8 picks of 6144 numbers (GLM-5, 5.25 GB by that count) runs
# as 7 parts of 4096 tokens, each with a result of 0.10 GB where the
# whole call's would be 0.70 GB beside 11.5 GB of weights and arena.
# Every other cell's calls stay whole (the largest: 16384 rows x 6 picks
# x 2048 numbers, 0.81 GB; 14336 x 4 x 3584, 0.82 GB).
GROUPED_MAX_BYTES = 1 << 30


def grouped_parts(rows: int, top_k: int, width: int) -> int:
    """The equal parts the grouped body takes ``rows`` tokens of
    ``width`` numbers in: the fewest that leave a part's pairs, counted
    as float32 rows, at most :data:`GROUPED_MAX_BYTES` (one token a part
    at the least)."""
    return next(n for n in range(1, rows + 1) if rows % n == 0 and (
        rows // n * top_k * width * 4 <= GROUPED_MAX_BYTES or n == rows))


# The grouped body's window: the rows of one pass of its three products.
# A quarter over the pairs that land on held experts when every router
# output is picked alike, rounded up to the grouped kernels' row tile: a
# call whose routing is near that runs ONE window, and the room costs
# little (rows past the held pairs belong to no group, and a grouped
# product skips them). Seeded routers are far from even: the trained
# cell's layers hold 0.72 to 1.32 of the even share, so about one layer
# in ten runs a second window there (PERF.md section 6, PR 49).
WINDOW_ROOM = 1.25
WINDOW_TILE = 512


def window_rows(rows: int, top_k: int, experts: int, held: int) -> int:
    """The rows of one window of the grouped body, for ``rows`` tokens
    that each pick ``top_k`` of ``experts`` router outputs of which
    ``held`` are here (static counts: the trace fixes them). All the
    pairs where that is fewer: a call with every expert held, or with
    few rows, is one window."""
    pairs = rows * top_k
    room = math.ceil(pairs * held / experts * WINDOW_ROOM / WINDOW_TILE)
    return min(pairs, room * WINDOW_TILE)


def windows_run(held_pairs, rows: int, top_k: int, experts: int,
                held: int):
    """How many windows the grouped body runs for a call of those static
    counts (:func:`window_rows`) in which ``held_pairs`` (token, pick)
    pairs landed on held experts: ``ceil(held_pairs / window)``, 0 where
    none did (the one-window form has no loop and always runs its
    window; it counts as 1 with a pair and 0 without, like the rest).
    Host arithmetic on a count the call already returns."""
    return -(-int(held_pairs) // window_rows(rows, top_k, experts, held))


def streams_densely(rows: int, top_k: int, experts: int) -> bool:
    """Whether :func:`dropless_moe` takes its dense body for ``rows``
    tokens that each pick ``top_k`` of ``experts`` router outputs
    (static counts: the trace fixes them; how many experts are held and
    how wide they are cancels out of both comparisons above). Under one
    pair an expert (``rows * top_k < experts``: the lone last token of a
    prefill) most held experts get no row; the grouped body skips those
    and the dense one would read them all."""
    return experts <= rows * top_k and (
        rows <= DENSE_MAX_ROWS or experts <= DENSE_MAX_WORK * top_k)


def _experts_dense(x, w_gate, w_up, w_down, local, gates):
    """Every token through every held expert, weighted by ``gate (S,
    held)``: the softmax weight of the pick that chose the expert and
    exactly 0.0 elsewhere, so the terms are the picked pairs' and no
    other. The weights are read once, in place: no sort, no gather. The
    gate weighs the hidden activations, so that the down product sums
    over (expert, width) at once into (S, D): a result a held expert,
    (held, S, D) float32, would be written and read whole at hundreds
    of rows."""
    f32 = jnp.float32
    held = w_gate.shape[0]
    with scope("moe_route"):
        # a pick on an expert that is not held matches no column
        hit = local[:, :, None] == jnp.arange(held, dtype=local.dtype)
        gate = jnp.sum(jnp.where(hit, gates[:, :, None], 0.0), axis=1)
    with scope("moe_experts"):
        xs = x.astype(w_gate.dtype)
        h = (jax.nn.silu(jnp.einsum("sd,edf->sef", xs, w_gate,
                                    preferred_element_type=f32))
             * jnp.einsum("sd,edf->sef", xs, w_up,
                          preferred_element_type=f32))
        h = (h * gate[:, :, None]).astype(w_down.dtype)
        return jnp.einsum("sef,efd->sd", h, w_down,
                          preferred_element_type=f32)


def _owned(a, live):
    """``a`` (R, ...) with the rows no group owns zeroed (``live`` (R,)
    marks the rows some group owns). ``lax.ragged_dot`` leaves the rows
    of its result that belong to no group UNWRITTEN on the TPU (the CPU
    writes zeros there): every grouped product's result goes through
    here, so that neither the result nor, through the masks' own
    transposes, the weights' gradients ever read such a row."""
    return jnp.where(live[:, None], a, 0)


def _window_terms(xs, w_gate, w_up, w_down, gate, sizes, live):
    """One window's rows ``xs`` (R, D), sorted by expert with ``sizes``
    (held,) rows a group from row 0 on, through the three grouped
    products: ``(gate * silu(xs Wg) * (xs Wu)) Wd`` (R, D) float32, the
    hidden activations in the weights' type and the gate on them, as
    the dense body has it."""
    def product(a, w):
        return _owned(jax.lax.ragged_dot(
            a, w, sizes, preferred_element_type=jnp.float32), live)

    h = (jax.nn.silu(product(xs, w_gate)) * product(xs, w_up)
         * gate[:, None])
    return product(h.astype(w_down.dtype), w_down)


def _window_of(w, rows: int, top_k: int, order, edges):
    """Window ``w`` of the sorted pairs: (pair (R,) the pairs' indices
    into the (S k,) pairs, tok (R,) their tokens, sizes (held,) the rows
    each held expert has INSIDE the window, live (R,) the rows that are
    held pairs). ``edges`` (held + 1,) are the groups' boundaries in the
    sorted order: the held pairs are its first ``edges[-1]`` entries."""
    lo = w * rows
    pair = jax.lax.dynamic_slice(order, (lo,), (rows,))
    cut = jnp.clip(edges, lo, lo + rows)
    live = jnp.arange(rows, dtype=edges.dtype) < edges[-1] - lo
    return pair, pair // top_k, cut[1:] - cut[:-1], live


def _over_windows(rows: int, order, edges, one, carry):
    """``one(w, carry)`` over the windows that hold a held pair,
    ``ceil(edges[-1] / rows)`` of them: ONE loop body whatever the
    count, a loop whose trip count the routing decides (real control
    flow on the TPU: a window past the held pairs is never entered).
    Where all the pairs fit one window there is no loop."""
    if order.shape[0] == rows:
        return one(0, carry)
    return jax.lax.fori_loop(0, (edges[-1] + rows - 1) // rows, one, carry)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _grouped_windows(rows: int, top_k: int, x, w_gate, w_up, w_down,
                     pair_gates, order, edges):
    """The held pairs' terms summed by token, (S, D) float32: window by
    window of ``rows`` sorted pairs, gather the window's token rows,
    the three grouped products (:func:`_window_terms`), add the rows
    into the result by token.
    ``pair_gates`` (S k,) float32 is a pair's gate, 0.0 on an absent
    expert; ``order`` the pairs sorted by expert, held ones first,
    padded to whole windows; ``edges`` as :func:`_window_of` takes them.

    Its own reverse mode (a loop whose trip count is traced has none in
    JAX): the same loop again, each window pulled back through its
    products from the rows it gathers anew, so nothing is kept from the
    forward pass but the arguments, and under ``jax.checkpoint`` the
    recompute pass has nothing of the experts to compute. The
    cotangents accumulate in the loop's carry in their primals' types,
    as the transposes JAX writes itself do; a window's rows' cotangent
    is masked past the held pairs before it is added by token (the
    transpose of a grouped product with respect to its left operand is
    a grouped product: rows no group owns are unwritten there too)."""
    def one(w, y):
        pair, tok, sizes, live = _window_of(w, rows, top_k, order, edges)
        return y.at[tok].add(_window_terms(
            x[tok].astype(w_gate.dtype), w_gate, w_up, w_down,
            pair_gates[pair], sizes, live))

    return _over_windows(rows, order, edges, one,
                         jnp.zeros(x.shape, jnp.float32))


def _grouped_windows_fwd(rows, top_k, *args):
    return _grouped_windows(rows, top_k, *args), args


def _grouped_windows_bwd(rows, top_k, args, dy):
    x, w_gate, w_up, w_down, pair_gates, order, edges = args

    def one(w, acc):
        pair, tok, sizes, live = _window_of(w, rows, top_k, order, edges)
        # (the window's own terms are traced here and never read: the
        # compiler drops the third product's forward)
        d_xs, d_gate_w, d_up, d_down, d_gate = jax.vjp(
            lambda *a: _window_terms(*a, sizes, live),
            x[tok].astype(w_gate.dtype), w_gate, w_up, w_down,
            pair_gates[pair])[1](dy[tok])
        return (acc[0].at[tok].add(_owned(d_xs, live).astype(x.dtype)),
                acc[1] + d_gate_w, acc[2] + d_up, acc[3] + d_down,
                acc[4].at[pair].add(jnp.where(live, d_gate, 0)))

    with scope("moe_experts"):
        return (*_over_windows(rows, order, edges, one, tuple(
            jnp.zeros_like(a) for a in args[:5])), None, None)


_grouped_windows.defvjp(_grouped_windows_fwd, _grouped_windows_bwd)


def _experts_grouped(x, w_gate, w_up, w_down, local, gates, here, sizes,
                     experts: int):
    """The (token, pick) pairs on HELD experts, sorted by expert,
    through three grouped matmuls (``lax.ragged_dot``) a window of
    :func:`window_rows` pairs, weighted and added up by token. Nothing
    of a pair on an absent expert is gathered or multiplied, and nothing
    of (pairs, width) is made: what is as long as the pairs is integers
    and the gates."""
    s, top_k = local.shape
    held = w_gate.shape[0]
    rows = window_rows(s, top_k, experts, held)
    with scope("moe_route"):
        # pairs on absent experts sort last, into a group no weight has:
        # the held pairs are the first sum(sizes) of the order
        group = jnp.where(here, local, held).reshape(-1)   # (S k,)
        order = jnp.argsort(group, stable=True).astype(jnp.int32)
        order = jnp.pad(order, (0, -order.shape[0] % rows))
        edges = jnp.concatenate([jnp.zeros((1,), sizes.dtype),
                                 jnp.cumsum(sizes)])
        pair_gates = jnp.where(here, gates, 0.0).reshape(-1)
    with scope("moe_experts"):
        return _grouped_windows(rows, top_k, x, w_gate, w_up, w_down,
                                pair_gates, order, edges)


def route(logits, top_k: int, routing: str = "topk_softmax",
          score_bias=None, scaling: float = 1.0):
    """(gates (S, k) float32, picks (S, k) int) of router ``logits``
    (S, E) float32 under the rule ``routing``:

    - ``"topk_softmax"``: the ``top_k`` largest logits (ties broken as
      ``lax.top_k`` breaks them, lowest index first), gates = softmax
      over those ``top_k`` logits;
    - ``"sigmoid_noaux_tc"`` (DeepSeek-V3's auxiliary-loss-free rule,
      one group): scores = sigmoid(logits); the picks are the ``top_k``
      largest of ``scores + score_bias`` (the bias selects and does not
      weigh); gates = ``scaling`` x the picks' own scores over their
      sum (plus 1e-20)."""
    if routing == "topk_softmax":
        top_l, top_i = jax.lax.top_k(logits, top_k)
        return jax.nn.softmax(top_l, axis=-1), top_i
    enforce(routing == "sigmoid_noaux_tc" and score_bias is not None,
            "routing rule %r is not one of %s, or lacks its bias",
            routing, DroplessMoE.ROUTING)
    scores = jax.nn.sigmoid(logits)
    _, top_i = jax.lax.top_k(scores + score_bias.astype(jnp.float32),
                             top_k)
    picked = jnp.take_along_axis(scores, top_i, axis=-1)
    return (scaling * picked
            / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)), top_i


def dropless_moe(x, router_w, w_gate, w_up, w_down, *, top_k: int,
                 experts_held=None, routing: str = "topk_softmax",
                 score_bias=None, scaling: float = 1.0,
                 with_load: bool = False):
    """Dropless top-k gated experts over tokens, for the experts held
    here.

    x: (S, D) tokens; router_w: (D, E), the router over ALL ``E``
    experts; w_gate, w_up: (held, D, F); w_down: (held, F, D), the
    weights of experts ``first .. first + held - 1`` where
    ``experts_held = (first, held)`` (default: all of them). The
    router's logits are float32 and ``routing`` (with ``score_bias``
    (E,) and ``scaling`` where the rule has them) turns them into
    ``top_k`` picks and gates a token: :func:`route`. No capacity:
    every (token, pick) pair that falls on a held expert is computed,
    as ``gate * (silu(x Wg) * (x Wu)) Wd``; a pair on an expert that is
    not held adds nothing (its part of the result belongs to the chip
    that holds it; nothing stands in for that chip here).

    Two bodies give those terms, chosen by the static shapes alone
    (:func:`streams_densely`): rows that reach every expert (a decode
    step; a prefill whose router picks a large share of its experts) go
    through every held expert as dense products with a gate of 0.0
    where an expert was not picked; many rows that each pick a small
    share, or very few rows (a prefill's last token), are sorted by
    expert and the pairs on HELD experts, the sorted order's head, run
    as grouped matmuls in windows of :func:`window_rows` pairs: one
    window under even routing, as many as the held pairs fill whatever
    the routing (no capacity: :func:`windows_run` says how many a call
    ran). Both run in the WEIGHTS' type with
    float32 sums: the tokens are cast to it, never the experts (a
    float32 copy of bfloat16 experts would be written out whole a call).
    Past :data:`GROUPED_MAX_BYTES` the grouped body takes the rows in
    equal parts, one after another (:func:`grouped_parts`).

    Returns (y (S, D) in x's dtype, tokens (held,) int32: the pairs
    each held expert got) and, ``with_load``, a third: load (E,) int32,
    the pairs each of the router's ``E`` outputs got, held or not (what
    the bias rule of ``"sigmoid_noaux_tc"`` reads,
    :meth:`DroplessMoE.bias_update`).

    Differentiable in ``x`` and every weight but ``score_bias``, which
    selects and does not weigh (its gradient is zero). Going backward
    the grouped body runs its windows again (:func:`_grouped_windows`:
    a window's rows gathered anew, each ``lax.ragged_dot`` transposed
    into two more grouped products, the rows' cotangents added by
    token): in no pass does it touch a pair on an absent expert."""
    e = router_w.shape[1]
    parts = grouped_parts(x.shape[0], top_k, x.shape[1])
    if parts > 1 and not streams_densely(x.shape[0], top_k, e):
        # the body a part takes is the whole call's: past DENSE_MAX_ROWS
        # the rule does not read the rows
        y, *counts = jax.lax.map(
            lambda part: dropless_moe(
                part, router_w, w_gate, w_up, w_down, top_k=top_k,
                experts_held=experts_held, routing=routing,
                score_bias=score_bias, scaling=scaling,
                with_load=with_load),
            x.reshape(parts, x.shape[0] // parts, x.shape[1]))
        return (y.reshape(x.shape), *(jnp.sum(c, axis=0) for c in counts))
    first, held = (0, e) if experts_held is None else experts_held
    with scope("moe_route"):
        logits = jnp.dot(x, router_w, preferred_element_type=jnp.float32)
        gates, top_i = route(logits, top_k, routing, score_bias,
                             scaling)                      # (S, k)
        local = top_i - first
        here = (local >= 0) & (local < held)
        sizes = jnp.bincount(jnp.where(here, local, held).reshape(-1),
                             length=held + 1)[:held].astype(jnp.int32)
        if with_load:
            load = jnp.bincount(top_i.reshape(-1), length=e).astype(
                jnp.int32)
    if streams_densely(x.shape[0], top_k, e):
        y = _experts_dense(x, w_gate, w_up, w_down, local, gates)
    else:
        y = _experts_grouped(x, w_gate, w_up, w_down, local, gates, here,
                             sizes, e)
    y = y.astype(x.dtype)
    return (y, sizes, load) if with_load else (y, sizes)


class DroplessMoE(Layer):
    """Top-k routed gated experts with no capacity and no dropped token,
    told which experts it holds (``experts_held = (first, count)`` of
    ``num_experts``): the layer of an expert-parallel deployment as one
    chip runs it. It routes over all ``num_experts``, computes the part
    of the result its own experts give (:func:`dropless_moe`), and
    leaves the rest to the chips that hold the others: summed over a
    partition of the experts the parts are the whole layer, forward and
    backward (``tests/test_hybrid.py``, ``tests/test_hybrid_train.py``).
    A shared (always-on) MLP is the caller's. ``routing`` names the rule
    that turns router logits into picks and gates (:func:`route`, one
    of :attr:`ROUTING`); ``"sigmoid_noaux_tc"`` brings a parameter
    ``score_bias`` (num_experts,) and multiplies the normalised gates
    by ``scaling``.

    **Trained**: the three expert tensors are declared to
    ``Layer._cast_once`` (``compute_cast``), so under a policy that
    computes narrower than they are stored (``amp="mixed_bf16"`` over
    float32 master weights) a training step casts each once and both
    bodies run in the policy's type with float32 sums; the router reads
    the tokens and its weight as they come, and its logits are float32.
    ``score_bias`` takes no gradient (it selects and does not weigh),
    so an optimizer leaves it where it is. **The bias rule**
    (DeepSeek-V3's auxiliary-loss-free balancing, ``topk_method``
    ``noaux_tc``) is :meth:`bias_update`: with ``bias_update_rate`` =
    ``gamma`` > 0 the layer holds two buffers, ``bias_shift`` (E,)
    float32, zero at the start, and ``expert_load`` (E,) int32; the
    picks are the largest of ``scores + score_bias + bias_shift``, and
    after a training call ``bias_shift += gamma sign(mean(load) -
    load)`` over all ``E`` outputs, from the (token, pick) pairs the
    call's batch sent each way. The state travels as buffers (the path
    batch-norm statistics take through ``parallel.Trainer``), so the
    first step of a run routes by the seed's bias, the parameter is
    never written, and a served model reads the moved bias (the
    buffers are arguments of every serving program). With ``gamma`` 0
    (the default) there is no buffer and no rule.

    ``forward(x (..., D)) -> (..., D)``; ``forward_counted`` also
    returns the (count,) int32 pairs each held expert got and,
    ``with_load``, the (num_experts,) pairs of every router output;
    ``streams_densely(rows)`` says which body that many rows take."""

    ROUTING = ("topk_softmax", "sigmoid_noaux_tc")

    def __init__(self, d_model: int, d_ff: int, num_experts: int,
                 top_k: int, experts_held=None,
                 routing: str = "topk_softmax", dtype=None,
                 scaling: float = 1.0, bias_update_rate: float = 0.0):
        super().__init__()
        first, count = experts_held or (0, num_experts)
        enforce(routing in self.ROUTING,
                "routing rule %r is not one of %s", routing, self.ROUTING)
        enforce(1 <= top_k <= num_experts,
                "top_k %s must lie in 1..num_experts %s", top_k,
                num_experts)
        enforce(0 <= first and count >= 1
                and first + count <= num_experts,
                "experts_held %s is not a range of the %s experts",
                (first, count), num_experts)
        enforce(not bias_update_rate or routing == "sigmoid_noaux_tc",
                "the bias rule belongs to routing 'sigmoid_noaux_tc', "
                "got %r", routing)
        self.num_experts, self.top_k = num_experts, top_k
        self.experts_held = (int(first), int(count))
        self.routing, self.scaling = routing, float(scaling)
        self.bias_update_rate = float(bias_update_rate)
        self.router = Linear(d_model, num_experts, bias_attr=False,
                             dtype=dtype)
        if routing == "sigmoid_noaux_tc":
            self.create_parameter("score_bias", (num_experts,), dtype,
                                  is_bias=True)
        if self.bias_update_rate:
            # concrete even where the model is built under a trace
            # (``jax.eval_shape`` around a constructor): nobody fills a
            # buffer in afterwards, as a loader does the parameters
            with jax.ensure_compile_time_eval():
                self.register_buffer(
                    "bias_shift", jnp.zeros((num_experts,), jnp.float32))
                self.register_buffer(
                    "expert_load", jnp.zeros((num_experts,), jnp.int32))
        init = I.XavierUniform()
        for name, shape in (("w_gate", (count, d_model, d_ff)),
                            ("w_up", (count, d_model, d_ff)),
                            ("w_down", (count, d_ff, d_model))):
            self.create_parameter(name, shape, dtype, init,
                                  compute_cast=True)

    def selection_bias(self):
        """What the picks add to the scores: ``score_bias`` and, under
        the bias rule, what the rule has moved it by so far; None where
        the routing rule has no bias."""
        if self.routing != "sigmoid_noaux_tc":
            return None
        if not self.bias_update_rate:
            return self.score_bias
        return self.score_bias.astype(jnp.float32) + self.bias_shift

    def forward_counted(self, x, with_load: bool = False):
        lead = x.shape[:-1]
        with self._own_code():      # the expert tensors' narrow copies
            narrow = compute_dtype_of(self.w_gate.dtype)
            y, *counts = dropless_moe(
                x.reshape(-1, x.shape[-1]), self.router.weight,
                self.w_gate.astype(narrow), self.w_up.astype(narrow),
                self.w_down.astype(narrow), top_k=self.top_k,
                experts_held=self.experts_held, routing=self.routing,
                score_bias=self.selection_bias(), scaling=self.scaling,
                with_load=with_load)
        return (y.reshape(*lead, -1), *counts)

    def forward(self, x):
        return self.forward_counted(x)[0]

    def bias_update(self, load):
        """The rule's step after a training call whose batch sent
        ``load`` (E,) pairs to each router output: ``bias_shift +=
        gamma sign(mean(load) - load)``, and ``expert_load`` keeps
        ``load``. Both are buffer updates (``functional_call`` hands
        them on as ``new_buffers``); call it outside any
        ``jax.checkpoint`` the call itself ran under. Nothing where the
        layer has no rule."""
        if not self.bias_update_rate:
            return
        pairs = load.astype(jnp.float32)
        self.update_buffer("bias_shift", self.bias_shift + (
            self.bias_update_rate * jnp.sign(jnp.mean(pairs) - pairs)))
        self.update_buffer("expert_load", load.astype(jnp.int32))

    def streams_densely(self, rows: int) -> bool:
        return streams_densely(rows, self.top_k, self.num_experts)

    def windows_run(self, held_pairs, rows: int) -> int:
        """The windows the grouped body ran for a call of ``rows``
        tokens that sent ``held_pairs`` (token, pick) pairs to the
        experts held here (:func:`windows_run`): 1 under even routing,
        more when the held experts drew over :data:`WINDOW_ROOM` of
        their share, 0 where no pair was held or the rows took the
        dense body."""
        if not rows or self.streams_densely(rows):
            return 0
        # a call taken in parts: as if its parts drew alike
        parts = grouped_parts(rows, self.top_k, self.w_gate.shape[1])
        return parts * windows_run(
            -(-int(held_pairs) // parts), rows // parts, self.top_k,
            self.num_experts, self.experts_held[1])


def expert_param_spec(axis: str = "ep"):
    """Sharding rules for SwitchFFN params: experts over ``axis``, the
    router replicated (tiny) — compose with transformer_tp_rules."""
    from jax.sharding import PartitionSpec as P

    return [
        (r"(^|\.)w1$", P(axis, None, None)),
        (r"(^|\.)b1$", P(axis, None)),
        (r"(^|\.)w2$", P(axis, None, None)),
        (r"(^|\.)b2$", P(axis, None)),
    ]
