"""A dropless expert layer that is told which experts it holds.

One chip of an expert-parallel group routes every token over ALL the
router's outputs (:func:`route`), keeps the assignments that name one of
its own experts (:func:`plan`) and computes what those experts add to
the tokens (:func:`routed_experts`). Nothing is dropped whatever the
imbalance: the assignments are sorted by expert, and the sorted rows go
through grouped matrix products (``jax.lax.ragged_dot``, on the TPU a
kernel that visits only the row tiles a group really has) a block of
``chunk_rows`` rows at a time, for as many blocks as the step's routing
filled. The loop's trip count is the only thing that depends on the
routing, so every shape is static, the worst case (every token choosing
only experts held here) runs through the same program, and the memory
is that of one block.

On one chip the layer runs without its exchange: what the absent
experts would add is left out, and nothing stands in for them.

Weights of the experts held are ONE array ``[held, parts, hidden,
width]``, each part stored ``[hidden, width]`` (the down product
contracts over ``width``), so the leading dimension is the expert,
which is what a table shards over the model axis. The parts follow the
expert's activation, a static choice (:data:`PARTS`): ``silu`` (SwiGLU,
``down(silu(gate x) * up x)``) has three, gate, up and down; ``relu2``
(``down(relu(up x)^2)``, the Nemotron-H family's) has two, up and down.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from multiverso_tpu import telemetry

# an expert's parts a stored matrix each, by its activation
PARTS = {"silu": ("gate", "up", "down"), "relu2": ("up", "down")}


class Routing(NamedTuple):
    top_s: jax.Array        # [T, k] float32 weights of the chosen experts
    top_e: jax.Array        # [T, k] int32 chosen experts, of all E
    counts: jax.Array       # [E] int32 real tokens that chose each expert
    balance: jax.Array      # [] float32 mean over sequences of a * sum f.P


class Plan(NamedTuple):
    row_tok: jax.Array      # [R] int32 token of each sorted row
    row_src: jax.Array      # [R] int32 index into top_s.reshape(-1)
    offsets: jax.Array      # [held + 1] int32 first row of each expert
    # rows from offsets[-1] on belong to no expert held here


# a router's score function, and what its published code adds under
# the sum that normalises the chosen scores
_SCORES = {"softmax": (partial(jax.nn.softmax, axis=-1), 1e-20),
           "sigmoid": (jax.nn.sigmoid, 1e-6)}


def route(h, w_router, real, *, top_k: int, norm_topk_prob: bool,
          scaling: float, alpha: float, score: str = "softmax",
          bias=None) -> Routing:
    """Scores over all experts in float32 — ``score``: ``softmax`` over
    them or a ``sigmoid`` each — (the logits at full float32 precision:
    a near-tie decided by bfloat16 inputs would send the token
    elsewhere), the greedy top-k, exact counts, and the per-sequence
    balance loss ``alpha * sum_e f_e P_e`` with ``f_e = E / (k n) *
    (real tokens of the sequence that chose e)`` and ``P_e`` the mean
    score of ``e`` over the sequence's real tokens (0 where ``alpha`` is
    0). With a ``bias`` [E] (auxiliary-loss-free balancing,
    arXiv:2408.15664) the experts chosen are the top-k of ``score +
    bias``, their weights the scores alone: the bias steers the choice,
    takes no gradient and scales nothing."""
    squash, under = _SCORES[score]

    @telemetry.scope("lm.moe.route")
    def run(h, w_router, real, bias):
        B, S, _ = h.shape
        E = w_router.shape[1]
        logits = jnp.einsum("bsd,de->bse", h, w_router,
                            precision=lax.Precision.HIGHEST)
        s = squash(logits)
        if bias is None:
            top_s, top_e = lax.top_k(s, top_k)
        else:
            top_e = lax.top_k(s + lax.stop_gradient(bias), top_k)[1]
            top_s = jnp.take_along_axis(s, top_e, -1)
        if norm_topk_prob:
            top_s = top_s / (jnp.sum(top_s, -1, keepdims=True) + under)
        top_s = top_s * scaling
        chose = jnp.sum(jax.nn.one_hot(top_e, E, dtype=jnp.float32), 2) \
            * real[..., None]                                  # [B, S, E]
        per_seq = chose.sum(1)                                 # [B, E]
        balance = jnp.zeros(())
        if alpha:
            n = jnp.maximum(real.sum(1), 1.0)[:, None]
            f = per_seq * (E / (top_k * n))
            P = (s * real[..., None]).sum(1) / n
            balance = alpha * jnp.mean(jnp.sum(f * P, -1))
        counts = per_seq.sum(0).astype(jnp.int32)
        return Routing(top_s.reshape(B * S, top_k),
                       top_e.reshape(B * S, top_k), counts, balance)
    return run(h, w_router, real, bias)


def bias_delta(counts):
    """What a step's routing asks of an expert layer's selection bias:
    ``sign(c_e - mean c)`` [E] float32 from the real tokens ``counts``
    [E] that chose each expert — a table's delta that is no derivative;
    updater ``sgd`` at rate gamma makes it ``b_e += gamma sign(mean c -
    c_e)``."""
    c = counts.astype(jnp.float32)
    return jnp.sign(c - jnp.mean(c))



def plan(top_e, real, *, first: int, held: int, chunk_rows: int) -> Plan:
    """Sort the (token, choice) assignments by the expert held here
    that they name; assignments of padding and of experts held elsewhere
    sort last and are never visited."""
    @telemetry.scope("lm.moe.permute")
    def run(top_e, real):
        T, k = top_e.shape
        local = top_e - first
        mine = (local >= 0) & (local < held) & (real.reshape(T, 1) > 0)
        key = jnp.where(mine, local, held).reshape(-1)
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        sizes = jnp.sum(jax.nn.one_hot(key, held + 1, dtype=jnp.int32), 0)
        offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                   jnp.cumsum(sizes[:held])])
        pad = -(T * k) % chunk_rows
        order = jnp.pad(order, (0, pad))
        return Plan(order // k, order, offsets.astype(jnp.int32))
    return run(top_e, real)


@telemetry.scope("lm.moe.permute")
def row_weights(top_s, row_src):
    """The weight of each sorted row: ``top_s`` [T, k] read in the plan's
    order (``Plan.row_src``)."""
    return jnp.take(top_s.reshape(-1), row_src)


def _gmm(x, w, sizes, transpose_rhs=False):
    """Grouped product of the rows of ``x`` [m, a] with ``w`` [g, a, b]
    (or [g, b, a] contracted over its last axis), float32
    accumulation."""
    dims = lax.RaggedDotDimensionNumbers(
        dot_dimension_numbers=(((1,), (2 if transpose_rhs else 1,)),
                               ((), ())),
        lhs_ragged_dimensions=[0], rhs_group_dimensions=[0])
    return lax.ragged_dot_general(x, w, sizes, dims,
                                  preferred_element_type=jnp.float32)


def _tgmm(x, y, sizes):
    """Per group, ``x^T y`` over the group's rows: [g, a, b] float32."""
    dims = lax.RaggedDotDimensionNumbers(
        dot_dimension_numbers=(((0,), (0,)), ((), ())),
        lhs_ragged_dimensions=[0], rhs_group_dimensions=[])
    return lax.ragged_dot_general(x, y, sizes, dims,
                                  preferred_element_type=jnp.float32)


def _block(c, chunk_rows, plan_, row_w):
    """Rows ``c * chunk_rows …`` of the sorted assignments: their
    tokens, weights, which of them an expert held here owns (what a
    grouped product leaves in the other rows is never read) and the
    group sizes inside the block."""
    r0 = c * chunk_rows
    tok = lax.dynamic_slice(plan_.row_tok, (r0,), (chunk_rows,))
    w = lax.dynamic_slice(row_w, (r0,), (chunk_rows,))
    rows = r0 + jnp.arange(chunk_rows, dtype=jnp.int32)
    valid = (rows < plan_.offsets[-1])[:, None]
    edges = jnp.clip(plan_.offsets, r0, r0 + chunk_rows)
    return tok, w[:, None], valid, edges[1:] - edges[:-1]


@telemetry.scope("lm.moe.permute")
def _gather_rows(h, tok):
    return jnp.take(h, tok, axis=0)


@telemetry.scope("lm.moe.permute")
def _scatter_rows(y, tok, rows):
    return y.at[tok].add(rows)


@telemetry.scope("lm.moe.experts")
def _experts_forward(x, wb, sizes):
    g = _gmm(x, wb[:, 0], sizes)
    u = _gmm(x, wb[:, 1], sizes)
    a = (jax.nn.silu(g) * u).astype(x.dtype)
    return g, u, a, _gmm(a, wb[:, 2], sizes, transpose_rhs=True)


@telemetry.scope("lm.moe.experts")
def _experts_backward(x, wb, sizes, g, u, a, rw, d_rows):
    d_out = (rw * d_rows).astype(x.dtype)
    d_a = _gmm(d_out, wb[:, 2], sizes)
    sig = jax.nn.sigmoid(g)
    d_g = (d_a * u * sig * (1.0 + g * (1.0 - sig))).astype(x.dtype)
    d_u = (d_a * g * sig).astype(x.dtype)
    d_x = _gmm(d_g, wb[:, 0], sizes, transpose_rhs=True) \
        + _gmm(d_u, wb[:, 1], sizes, transpose_rhs=True)
    return d_x, (_tgmm(x, d_g, sizes), _tgmm(x, d_u, sizes),
                 _tgmm(d_out, a, sizes))


@telemetry.scope("lm.moe.experts")
def _relu2_forward(x, wb, sizes):
    u = _gmm(x, wb[:, 0], sizes)
    a = jnp.square(jax.nn.relu(u)).astype(x.dtype)
    return u, a, _gmm(a, wb[:, 1], sizes, transpose_rhs=True)


@telemetry.scope("lm.moe.experts")
def _relu2_backward(x, wb, sizes, u, a, rw, d_rows):
    """``d_u = 2 relu(u) d_a``, written out."""
    d_out = (rw * d_rows).astype(x.dtype)
    d_a = _gmm(d_out, wb[:, 1], sizes)
    d_u = (2.0 * jax.nn.relu(u) * d_a).astype(x.dtype)
    return _gmm(d_u, wb[:, 0], sizes, transpose_rhs=True), (
        _tgmm(x, d_u, sizes), _tgmm(d_out, a, sizes))


# an activation's expert forward (its last output the expert's rows)
# and backward, the forward's other outputs handed to the backward
_EXPERTS = {"silu": (_experts_forward, _experts_backward),
            "relu2": (_relu2_forward, _relu2_backward)}


@telemetry.scope("lm.moe.experts")
def _held_rows(valid, rows, weight=None):
    """``rows`` (times their ``weight``) where an expert held here owns
    them, zero elsewhere: on the chip the select is fused with the
    product that made the rows."""
    return jnp.where(valid, rows if weight is None else weight * rows, 0.0)


def _cast_held(w, dtype):
    """The held experts' weights in the products' dtype, once a pass."""
    @telemetry.scope("lm.moe.accumulate")
    def run(w):
        return w.astype(dtype)
    return run(w)


@telemetry.scope("lm.moe.accumulate")
def _accumulate(d_w, d_rw, new_w, out, d_rows, r0):
    """A block's gradients added to what the blocks before it left: the
    weight accumulators, one a part (read and written whole, every
    block), and the block's rows of the row weights' gradient."""
    return (tuple(acc + new for acc, new in zip(d_w, new_w)),
            lax.dynamic_update_slice(d_rw, jnp.sum(out * d_rows, -1),
                                     (r0,)))


@telemetry.scope("lm.moe.accumulate")
def _stack_gradients(d_w):
    return jnp.stack(d_w, axis=1)


def _chunks(plan_, chunk_rows):
    return (plan_.offsets[-1] + chunk_rows - 1) // chunk_rows


@partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def routed_experts(h, w, row_w, plan_, chunk_rows, dtype=jnp.bfloat16,
                   activation="silu"):
    """What the experts held here add to every token: ``y`` [T, D]
    float32 with ``y[t] = sum over t's assignments held here of
    weight * expert(h[t])``, and the number of rows that went through
    (the assignments held here, every one of them).

    ``h`` [T, D] float32, ``w`` [held, parts, D, F] float32 (the
    parts of ``activation``, :data:`PARTS`), ``row_w`` [R]
    float32 the weight of each sorted row (``top_s.reshape(-1)[plan_.
    row_src]``); the products take operands of ``dtype``. Differentiable
    in ``h``, ``w`` and ``row_w``; the backward pass recomputes each
    block from ``h``. It is written out because jax refuses reverse mode
    through a loop whose trip count is traced (``ragged_dot`` itself
    differentiates); a static count would be ``T k / chunk_rows`` blocks
    (24 at the cell's sizes) where the mean routing fills 3. (``dtype``
    float32 on a v5e, libtpu 0.0.34: a block of 8,192 float32 rows came
    out of ``ragged_dot`` wrong — 0.95 of the layer's value — where
    2,048 agree with the plain form to 2e-7, and bfloat16 blocks of
    8,192 to rounding; PERF.md §7, PR 34.)

    Every op of the two loops carries a program scope: gathers and
    scatter-adds ``lm.moe.permute``, the products with the valid-row
    selects around them ``lm.moe.experts``, the cast of the held weights
    and the gradients' accumulators ``lm.moe.accumulate``."""
    return _routed_forward(h, w, row_w, plan_, chunk_rows, dtype,
                           activation)


def _routed_forward(h, w, row_w, plan_, chunk_rows, dtype, activation):
    wb = _cast_held(w, dtype)
    forward = _EXPERTS[activation][0]

    def body(c, carry):
        y, done = carry
        tok, rw, valid, sizes = _block(c, chunk_rows, plan_, row_w)
        x = _gather_rows(h, tok).astype(dtype)
        out = forward(x, wb, sizes)[-1]
        return (_scatter_rows(y, tok, _held_rows(valid, out, rw)),
                done + jnp.sum(sizes))

    return lax.fori_loop(0, _chunks(plan_, chunk_rows), body,
                         (jnp.zeros(h.shape, jnp.float32),
                          jnp.zeros((), jnp.int32)))


def _routed_fwd(h, w, row_w, plan_, chunk_rows, dtype, activation):
    return (_routed_forward(h, w, row_w, plan_, chunk_rows, dtype,
                            activation), (h, w, row_w, plan_))


def _routed_bwd(chunk_rows, dtype, activation, saved, cotangent):
    h, w, row_w, plan_ = saved
    d_y, _ = cotangent
    wb = _cast_held(w, dtype)
    forward, backward = _EXPERTS[activation]

    def body(c, carry):
        d_h, d_w, d_rw = carry
        tok, rw, valid, sizes = _block(c, chunk_rows, plan_, row_w)
        x = _gather_rows(h, tok).astype(dtype)
        *kept, out = forward(x, wb, sizes)
        d_rows = _held_rows(valid, _gather_rows(d_y, tok))
        d_x, d_wc = backward(x, wb, sizes, *kept, rw, d_rows)
        d_w, d_rw = _accumulate(d_w, d_rw, d_wc, _held_rows(valid, out),
                                d_rows, c * chunk_rows)
        return _scatter_rows(d_h, tok, _held_rows(valid, d_x)), d_w, d_rw

    one = jnp.zeros(w.shape[:1] + w.shape[2:], jnp.float32)
    d_h, d_w, d_rw = lax.fori_loop(
        0, _chunks(plan_, chunk_rows), body,
        (jnp.zeros(h.shape, jnp.float32), (one,) * w.shape[1],
         jnp.zeros(row_w.shape, jnp.float32)))
    return d_h, _stack_gradients(d_w), d_rw, None


routed_experts.defvjp(_routed_fwd, _routed_bwd)


def expert_load_max_over_mean(counts, *, first: int, held: int):
    """Largest load of an expert held here over their mean load."""
    mine = lax.dynamic_slice(counts, (first,), (held,)).astype(jnp.float32)
    return jnp.max(mine) / jnp.maximum(jnp.mean(mine), 1.0)
