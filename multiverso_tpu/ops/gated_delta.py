"""Gated delta-rule linear attention over packed documents.

A head keeps a state ``S`` [d_k, d_v] instead of keys and values
(Gated DeltaNet, arXiv:2412.06464; the layer of the public
``flash-linear-attention`` ``GatedDeltaNet``): per token ``t``

    S_t = a_t S_{t-1} + b_t k_t (v_t - (a_t S_{t-1})^T k_t)^T
    o_t = S_t^T q_t

with a decay ``a_t = exp(g_t)`` in (0, 1] and a write strength ``b_t``
in (0, 2) a head, ``q`` and ``k`` of unit length. ``S`` is 0 at a
document's first token: the state restarts at every document boundary,
the counterpart of attention's document mask.

:func:`project` makes ``q | k | v`` (before the convolution), the output
gate, ``g`` and ``b`` from the residual in one product;
:func:`short_conv` is the causal depthwise filter over ``q | k | v``
(its taps never reach into an earlier document) and the ``silu``;
:func:`recur` is the recurrence; :func:`gate_out` the gated norm over
each head's output and the projection back.

:func:`recur` is the CHUNKED form: inside a chunk of ``C`` tokens the
recurrence is solved at once — with ``G`` the running sum of ``g`` in
the chunk, ``A[i, j] = b_i (k_i . k_j) exp(G_i - G_j)`` below the
diagonal and ``T = (I + A)^-1``, the writes of a chunk that starts from
state ``S`` are ``T (b v) - T (b exp(G) k) S`` — and only the state
crosses from chunk to chunk, ``S / C`` steps in order where the
token-by-token form has ``S``. A pair (i, j) of different documents
counts as decayed to nothing, and so does the entering state for every
token after a document start, which is all a restart is. It is plain
blocked XLA: the chunk-local terms are batched products over every
chunk at once, the state a ``lax.scan`` over the chunks, and the
backward pass is the scan's own — it keeps a chunk's [C, C] terms and
one state a chunk, so its memory is linear in the sequence.

Products take operands of ``GatedDeltaShape.dtype`` (bfloat16) and
accumulate in float32; the gates, the decay and its running sums, the
unit norms, the triangular solve and the state between chunks are
float32. (The decay is the exponential of up to 16 times a softplus of
``W_a x``: a rounding of the RESIDUAL of a few tenths of a percent, as
the layers below leave it, moves a token's decay by percents. Making
the two narrow projections float32 products changed no reading against
the float32 reference and cost 2 % of the step, PERF.md PR 32.)
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from multiverso_tpu import telemetry

L2_EPS = 1e-6       # under the root of q's and k's unit norm
_GONE = -1e30       # a log decay that leaves nothing


class GatedDeltaShape(NamedTuple):
    heads: int          # linear_num_key_heads (= value heads)
    dk: int             # linear_key_head_dim
    dv: int             # linear_value_head_dim
    taps: int           # linear_conv_kernel_dim
    neg_eigval: bool    # linear_allow_neg_eigval: b in (0, 2), not (0, 1)
    eps: float          # rms_norm_eps
    chunk: int = 64     # tokens solved at once
    dtype: str = "bfloat16"     # of the matrix products' operands

    @property
    def conv_width(self) -> int:
        """Channels the convolution filters: ``q | k | v``."""
        return self.heads * (2 * self.dk + self.dv)

    @property
    def in_width(self) -> int:
        """Columns of the input projection: ``q | k | v | gate | a | b``."""
        return self.conv_width + self.heads * (self.dv + 2)


def _dot(a, b, dtype):
    return jnp.dot(a.astype(dtype), b.astype(dtype),
                   preferred_element_type=jnp.float32)


def starts(doc):
    """Where a document starts in ``doc`` [B, S], bool."""
    return jnp.concatenate(
        [jnp.ones_like(doc[:, :1], bool), doc[:, 1:] != doc[:, :-1]], 1)


def segments(doc):
    """``doc`` [B, S] as ids that change at every document start and
    never come back: the starts up to a token, int32."""
    return jnp.cumsum(starts(doc).astype(jnp.int32), axis=1)


def _running_sum(g, start):
    """Sum of ``g`` along the last axis from the last ``start`` (or the
    axis' start) to each position. A sum that restarts is made of its
    own document's terms alone, so that no rounding of it depends on
    another document's values."""
    def combine(left, right):
        (restarted, total), (restarts, more) = left, right
        return (restarted | restarts,
                jnp.where(restarts, more, total + more))
    return lax.associative_scan(
        combine, (jnp.broadcast_to(start, g.shape), g), axis=g.ndim - 1)[1]


def project(x, w_in, a_log, dt_bias, shape: GatedDeltaShape):
    """From the residual ``x`` [B, S, D]: ``qkv`` [B, S, H (2 dk + dv)]
    before its convolution, the output gate's input [B, S, H dv], the
    log decay ``g`` and the write strength ``b`` [B, S, H], float32."""
    @telemetry.scope("lm.gdn.project")
    def run(x, w_in, a_log, dt_bias):
        H, conv = shape.heads, shape.conv_width
        gate_end = conv + H * shape.dv
        z = _dot(x, w_in, shape.dtype)
        beta = jax.nn.sigmoid(z[..., gate_end + H:])
        if shape.neg_eigval:
            beta = 2.0 * beta
        g = -jnp.exp(a_log) * jax.nn.softplus(
            z[..., gate_end:gate_end + H] + dt_bias)
        return z[..., :conv], z[..., conv:gate_end], g, beta
    return run(x, w_in, a_log, dt_bias)


def causal_taps(x, taps, doc):
    """Each channel of ``x`` [B, S, W] through its own causal filter
    ``taps`` [K, W] (tap ``K - 1`` weighs the token itself, tap ``j`` the
    token ``K - 1 - j`` back; no bias), a tap that would reach into an
    earlier document — or before the sequence — reading 0."""
    K = taps.shape[0]
    seg = segments(doc)
    y = x * taps[K - 1]
    for back in range(1, K):
        shifted = jnp.pad(x, ((0, 0), (back, 0), (0, 0)))[:, :-back]
        same = jnp.pad(seg, ((0, 0), (back, 0)))[:, :-back] == seg
        y = y + jnp.where(same[..., None], shifted, 0.0) * taps[K - 1 - back]
    return y


@telemetry.scope("lm.gdn.conv")
def short_conv(qkv, taps, doc):
    """``q | k | v`` [B, S, W] through :func:`causal_taps`, then
    ``silu``."""
    return jax.nn.silu(causal_taps(qkv, taps, doc))


def _unit(x):
    return x * lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)


def recur(qkv, g, beta, doc, shape: GatedDeltaShape):
    """The gated delta rule on the convolved ``qkv`` [B, S, H (2 dk +
    dv)] with log decay ``g`` and write strength ``beta`` [B, S, H],
    chunked; the state restarts where ``doc`` [B, S] changes. Returns
    ``o`` [B, S, H, dv] float32."""
    @telemetry.scope("lm.gdn.recur")
    def run(qkv, g, beta, doc):
        H, dk, dv, dtype = shape.heads, shape.dk, shape.dv, shape.dtype
        B, S, _ = qkv.shape
        C = min(shape.chunk, S)
        if S % C:
            raise ValueError(f"sequence {S} is no multiple of the "
                             f"recurrence's chunk {C}")
        N = S // C
        q = _unit(qkv[..., :H * dk].reshape(B, S, H, dk)) * dk ** -0.5
        k = _unit(qkv[..., H * dk:2 * H * dk].reshape(B, S, H, dk))
        v = qkv[..., 2 * H * dk:].reshape(B, S, H, dv)

        def chunks(a):      # [B, S, H, ...] -> [N, B, H, C, ...]
            a = a.reshape(B, N, C, H, *a.shape[3:])
            return jnp.moveaxis(jnp.moveaxis(a, 3, 1), 2, 0)

        q, k, v, g, beta = map(chunks, (q, k, v, g, beta))
        in_chunks = lambda a: jnp.moveaxis(a.reshape(B, N, C), 1, 0)
        seg = in_chunks(segments(doc))                      # [N, B, C]
        # the segment the entering state belongs to: the last token's of
        # the chunk before (none before the first chunk)
        entering = jnp.concatenate(
            [jnp.zeros_like(seg[:1, :, -1]), seg[:-1, :, -1]], 0)
        carried = (seg == entering[..., None])[:, :, None]  # [N, B, 1, C]
        same = (seg[..., :, None] == seg[..., None, :])[:, :, None]
        at = jnp.arange(C)
        upto = at[:, None] >= at[None]                      # j <= i
        # the log decay from the chunk's or the document's start
        G = _running_sum(g, in_chunks(starts(doc))[:, :, None])
        decay = jnp.exp(jnp.where(same & upto,
                                  G[..., :, None] - G[..., None, :], _GONE))
        from_state = jnp.where(carried, jnp.exp(G), 0.0)
        to_end = decay[..., -1, :]      # exp(G_C - G_j) inside the last
        # token's document, else 0
        kb, qb = k.astype(dtype), q.astype(dtype)
        pairs = lambda a, b: jnp.einsum("...id,...jd->...ij", a, b,
                                        preferred_element_type=jnp.float32)
        A = jnp.where(at[:, None] > at[None],
                      beta[..., None] * pairs(kb, kb) * decay, 0.0)
        # (I + A) [W | U] = [beta exp(G) k | beta v]
        rhs = jnp.concatenate(
            [(beta * from_state)[..., None] * k, beta[..., None] * v], -1)
        solved = jax.scipy.linalg.solve_triangular(
            A + jnp.eye(C, dtype=A.dtype), rhs, lower=True,
            unit_diagonal=True)
        w, u = solved[..., :dk].astype(dtype), solved[..., dk:]
        attn = (pairs(qb, kb) * decay).astype(dtype)
        q_in = (q * from_state[..., None]).astype(dtype)
        k_out = (k * to_end[..., None]).astype(dtype)
        keep = from_state[..., -1]                          # [N, B, H]

        def chunk(state, xs):
            w, u, attn, q_in, k_out, keep = xs
            held = state.astype(dtype)
            new = u - jnp.einsum("bhck,bhkv->bhcv", w, held,
                                 preferred_element_type=jnp.float32)
            newb = new.astype(dtype)
            o = jnp.einsum("bhck,bhkv->bhcv", q_in, held,
                           preferred_element_type=jnp.float32) \
                + jnp.einsum("bhij,bhjv->bhiv", attn, newb,
                             preferred_element_type=jnp.float32)
            state = keep[..., None, None] * state + jnp.einsum(
                "bhck,bhcv->bhkv", k_out, newb,
                preferred_element_type=jnp.float32)
            return state, o

        _, o = lax.scan(chunk, jnp.zeros((B, H, dk, dv), jnp.float32),
                        (w, u, attn, q_in, k_out, keep))
        # [N, B, H, C, dv] -> [B, S, H, dv]
        return jnp.moveaxis(jnp.moveaxis(o, 0, 2), 1, 3).reshape(
            B, S, H, dv)
    return run(qkv, g, beta, doc)


def gate_out(o, gate, norm_w, w_out, shape: GatedDeltaShape):
    """``w_out`` [D, H dv] (out x in) on the gated norm of ``o``
    [B, S, H, dv]: an RMS norm over each head's ``dv`` with the one
    weight ``norm_w`` [dv], times ``silu`` of the gate's input."""
    @telemetry.scope("lm.gdn.gate_out")
    def run(o, gate, norm_w, w_out):
        B, S, H, dv = o.shape
        y = o * lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + shape.eps) \
            * norm_w
        y = y.reshape(B, S, H * dv) * jax.nn.silu(gate)
        return lax.dot_general(
            y.astype(shape.dtype), w_out.astype(shape.dtype),
            (((2,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    return run(o, gate, norm_w, w_out)


def doc_starts(doc):
    """Documents that start in ``doc`` [B, S] (padding, id 0, is none):
    the restarts of one layer's state."""
    return jnp.sum(starts(doc) & (doc > 0)).astype(jnp.int32)
