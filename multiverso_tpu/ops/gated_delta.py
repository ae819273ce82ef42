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
token after a document start, which is all a restart is.

What a chunk needs of itself — unit norms, the decay and its masks, the
pairs, the float32 triangular solve — is plain XLA over every chunk at
once (:func:`chunk_terms`), and so are its gradients. What crosses from
chunk to chunk (:func:`across_chunks`) is two Pallas kernels,
differentiated by hand (``jax.custom_vjp``): Mosaic compiles them on a
TPU, Pallas's interpreter runs the same kernels anywhere else. A grid
step is one (block of heads, chunk), the chunks innermost and in order;
the state [heads a block, d_k, d_v] float32 lives in VMEM from a head
block's first chunk to its last and never reaches HBM between two
products. The forward kernel also writes the state ENTERING each chunk
when a backward pass will follow; the backward kernel walks the chunks
in reverse with the state's cotangent in VMEM and rebuilds what the
forward pass held (``held``, ``new``) from that saved state with the
same casts — so its memory is one state a chunk, linear in the
sequence. Head dims of 96 and 192 are no multiple of 128 lanes: a block
spans a whole minor dimension, and the padding to whole lanes is the
kernels' inside VMEM.

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

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

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


# -- the state from chunk to chunk: two Pallas kernels -------------------------

VMEM_LIMIT = 64 * 2 ** 20   # of a v5e core's 128 MiB; the default is 16
# what a grid step's blocks may hold, each of them twice (the pipeline's
# two buffers): sets the heads a step. (At 30 heads of 96 x 192 and
# chunks of 64 that is 15; alone on a v5e a forward pass took 0.71 /
# 0.71 / 0.70 ms and forward + backward 2.29 / 2.26 / 2.25 ms with 10 /
# 15 / 30 heads a step: PERF.md PR 35.)
_STEP_BYTES = 24 * 2 ** 20
_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_TN = (((0,), (0,)), ((), ()))      # a.T @ b


def _mm(a, b, dims=(((1,), (0,)), ((), ()))):
    return lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _each_head(heads: int):
    """Runs the decorated body for each head of a grid step (they are
    independent), in a loop that is traced once: unrolled, fifteen
    heads' products ran 5 % faster and cost every process 5 s of
    lowering before the compile cache is asked (PERF.md PR 35)."""
    def run(body):
        lax.fori_loop(0, heads, lambda h, _: body(h), None)
    return run


def _forward_kernel(w_ref, u_ref, attn_ref, q_ref, k_ref, keep_ref, o_ref,
                    *rest):
    """One (block of heads, chunk) step: the state [heads, d_k, d_v]
    float32 stays in the scratch from a head block's first chunk to its
    last. ``rest``: the entering states' output, when a backward pass
    will follow, and the scratch."""
    *entering_ref, state = rest
    dtype = w_ref.dtype

    @pl.when(pl.program_id(1) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    @_each_head(state.shape[0])
    def _(h):
        s = state[h]
        if entering_ref:
            entering_ref[0][h] = s
        held = s.astype(dtype)
        newb = (u_ref[h] - _mm(w_ref[h], held)).astype(dtype)
        o_ref[h] = _mm(q_ref[h], held) + _mm(attn_ref[h], newb)
        state[h] = keep_ref[h] * s + _mm(k_ref[h], newb, _TN)


def _backward_kernel(w_ref, u_ref, attn_ref, q_ref, k_ref, keep_ref,
                     entering_ref, do_ref, dw_ref, du_ref, dattn_ref,
                     dq_ref, dk_ref, dkeep_ref, d_state):
    """The same step with the chunks in reverse: ``d_state`` holds the
    cotangent of the state LEAVING the chunk on entry and of the state
    entering it on exit. ``held`` and ``new`` are rebuilt from the saved
    entering state with the forward pass's casts; a cotangent is cast to
    the operands' dtype before a product, as XLA's transpose of a
    product with float32 accumulation rounds it."""
    dtype = w_ref.dtype

    @pl.when(pl.program_id(1) == 0)
    def _():
        d_state[...] = jnp.zeros_like(d_state)

    @_each_head(d_state.shape[0])
    def _(h):
        s, d_out = entering_ref[h], d_state[h]
        held, w, q_in = s.astype(dtype), w_ref[h], q_ref[h]
        newb = (u_ref[h] - _mm(w, held)).astype(dtype)
        do, d_outb = do_ref[h].astype(dtype), d_out.astype(dtype)
        d_new = _mm(attn_ref[h], do, _TN) + _mm(k_ref[h], d_outb)
        d_newb = d_new.astype(dtype)
        du_ref[h] = d_new
        dw_ref[h] = (-_mm(d_newb, held, _NT)).astype(dw_ref.dtype)
        dattn_ref[h] = _mm(do, newb, _NT).astype(dattn_ref.dtype)
        dq_ref[h] = _mm(do, held, _NT).astype(dq_ref.dtype)
        dk_ref[h] = _mm(newb, d_outb, _NT).astype(dk_ref.dtype)
        dkeep_ref[h] = jnp.sum(jnp.sum(s * d_out, 1, keepdims=True), 0,
                               keepdims=True)
        d_state[h] = keep_ref[h] * d_out + _mm(q_in, do, _TN) \
            - _mm(w, d_newb, _TN)


def _head_block(heads: int, C: int, dk: int, dv: int) -> int:
    """Heads a grid step: the most that divide ``heads`` and whose
    blocks of the backward pass (the larger set: every operand, the
    entering state, ``do`` and five cotangents, float32 counted for
    all) fit ``_STEP_BYTES``. One head a step is too fine: its data are
    a fraction of a microsecond of HBM time, under a grid step's own
    cost."""
    lanes = lambda d: -(-d // 128) * 128
    a_head = 4 * (C * (6 * lanes(dk) + 3 * lanes(dv) + 2 * lanes(C))
                  + dk * lanes(dv))
    most = max(1, _STEP_BYTES // (2 * a_head))
    return max(n for n in range(1, heads + 1)
               if heads % n == 0 and n <= most)


def _call(kernel, interpret, operands, outs, backward):
    """``kernel`` over the grid (blocks of heads, chunks), chunks
    innermost and in order (``backward``: last to first). Every operand
    and output is [N, heads, rows, cols] and a block all of one chunk's
    rows and cols for a block of heads: a block that spans a whole minor
    dimension needs no multiple of 128 lanes in HBM."""
    w, u = operands[:2]
    N, heads, C, dk = w.shape
    dv = u.shape[3]
    hb = _head_block(heads, C, dk, dv)
    at = (lambda i, n: (N - 1 - n, i, 0, 0)) if backward else (
        lambda i, n: (n, i, 0, 0))
    spec = lambda a: pl.BlockSpec((None, hb) + a.shape[2:], at)
    return pl.pallas_call(
        kernel, grid=(heads // hb, N), in_specs=[spec(a) for a in operands],
        out_specs=[spec(a) for a in outs], out_shape=outs,
        scratch_shapes=[pltpu.VMEM((hb, dk, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret)(*operands)


def _forward(w, u, attn, q_in, k_out, keep, interpret, residuals: bool):
    """``o`` [N, heads, C, d_v] float32 and, with ``residuals``, the
    state entering every chunk [N, heads, d_k, d_v] float32."""
    N, heads, _, dk = w.shape
    outs = [jax.ShapeDtypeStruct(u.shape, jnp.float32)]
    if residuals:
        outs.append(jax.ShapeDtypeStruct((N, heads, dk, u.shape[3]),
                                         jnp.float32))
    out = _call(_forward_kernel, interpret, (w, u, attn, q_in, k_out, keep),
                outs, False)
    return out if residuals else (out[0], None)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def across_chunks(w, u, attn, q_in, k_out, keep, interpret):
    """What crosses from chunk to chunk, for operands [N, heads, C, ·]
    (``keep`` [N, heads, 1, 1]) in chunk order: from a state ``S``
    [d_k, d_v] that starts at 0, a chunk writes ``new = u - w S``, puts
    out ``o = q_in S + attn new`` and leaves ``keep S + k_out^T new``.
    Products take ``w``'s dtype and accumulate in float32; the state is
    float32 and is rounded only as a product's operand."""
    return _forward(w, u, attn, q_in, k_out, keep, interpret, False)[0]


def _across_chunks_fwd(w, u, attn, q_in, k_out, keep, interpret):
    o, entering = _forward(w, u, attn, q_in, k_out, keep, interpret, True)
    return o, (w, u, attn, q_in, k_out, keep, entering)


def _across_chunks_bwd(interpret, saved, do):
    like = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
    return tuple(_call(_backward_kernel, interpret, saved + (do,),
                       [like(a) for a in saved[:6]], True))


across_chunks.defvjp(_across_chunks_fwd, _across_chunks_bwd)


def chunk_terms(qkv, g, beta, doc, shape: GatedDeltaShape):
    """What :func:`recur` computes for every chunk at once, in plain
    XLA, before anything crosses a chunk: :func:`across_chunks`'s six
    operands ``w``, ``u``, ``attn``, ``q_in``, ``k_out`` [N, B H, C, ·]
    and ``keep`` [N, B H, 1, 1] (the heads of every sequence side by
    side, ``N`` chunks of ``C`` tokens)."""
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
    keep = from_state[..., -1, None, None]              # [N, B, H, 1, 1]
    return tuple(a.reshape(N, B * H, *a.shape[3:])
                 for a in (w, u, attn, q_in, k_out, keep))


def from_chunks(o, B: int):
    """:func:`across_chunks`'s output [N, B H, C, dv] as [B, S, H, dv]."""
    N, heads, C, dv = o.shape
    o = o.reshape(N, B, heads // B, C, dv)
    return jnp.moveaxis(jnp.moveaxis(o, 0, 2), 1, 3).reshape(
        B, N * C, heads // B, dv)


def recur(qkv, g, beta, doc, shape: GatedDeltaShape,
          interpret: Optional[bool] = None):
    """The gated delta rule on the convolved ``qkv`` [B, S, H (2 dk +
    dv)] with log decay ``g`` and write strength ``beta`` [B, S, H],
    chunked; the state restarts where ``doc`` [B, S] changes. Returns
    ``o`` [B, S, H, dv] float32. ``interpret``: run the kernels in
    Pallas's interpreter; left out, every backend but a TPU does."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    @telemetry.scope("lm.gdn.recur")
    def run(qkv, g, beta, doc):
        return from_chunks(across_chunks(
            *chunk_terms(qkv, g, beta, doc, shape), bool(interpret)),
            qkv.shape[0])
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
