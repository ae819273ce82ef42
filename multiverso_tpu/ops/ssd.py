"""Mamba-2's state-space mixer over packed documents (SSD, the
state-space duality of arXiv:2405.21060; the ``M`` layer of the
Nemotron-H family, ``model_type`` ``nemotron_h``).

From the block's normed input ``u`` [B, S, D], one product makes
``z | x | B | C | dt`` (:func:`project`: ``x`` is ``H`` heads of ``P``,
``B`` and ``C`` are ``G`` groups of ``N``, head ``h`` reads group
``h // (H / G)``). ``x | B | C`` go through a causal depthwise filter of
``taps`` taps with a bias, then ``silu`` (:func:`conv`: a tap that would
reach into an earlier document reads 0, the rule of
:func:`multiverso_tpu.ops.gated_delta.causal_taps`). With
``dt_t = softplus(dt_raw_t + dt_bias)`` and ``A = -exp(A_log)``, a
scalar a head, each head keeps a state ``h`` [N, P]:

    h_t = exp(dt_t A) h_{t-1} + B_t (dt_t x_t)^T
    y_t = h_t^T C_t + D x_t

``h`` is 0 before a document's first token: the state restarts at every
document start. :func:`gate_out` puts out ``W_out (rmsnorm_g(y * silu(z))
* w)``, an RMS norm over each of ``G`` groups of ``H P / G`` channels.

:func:`scan` is the CHUNKED form: with ``a_t = dt_t A`` and ``L_i`` its
running sum inside a chunk of ``C`` tokens (restarting at a document
start), ``L[i, j] = exp(L_i - L_j)`` for ``j <= i`` of one document and
0 otherwise, a chunk that starts from state ``S`` puts out

    y_i = sum_j (C_i . B_j) L[i, j] dt_j x_j + exp(L_i) C_i^T S

and leaves ``exp(L_C) S + sum_j exp(L_C - L_j) B_j (dt_j x_j)^T``; the
terms of a token whose document started inside the chunk take no part
of ``S``, which is all a restart is. Unlike the delta rule
(:mod:`multiverso_tpu.ops.gated_delta`) a chunk needs no triangular
solve, and all of it is one pair of Pallas kernels, differentiated by
hand (``jax.custom_vjp``). A grid step is one (sequence, group of heads
that read one ``B`` and one ``C``, chunk), the chunks innermost and in
order. It reads ``x`` [B, S, H P], ``B | C`` [B, S, 2 G N] and the
running sums and steps [B, H, S] where they lie and forms in VMEM
``C B^T`` once for the group, each head's decay and masked product, the
state's read ``exp(L_i) (C S)`` and write ``B^T (exp(L_C - L_j) u)``
with ``u = dt x``; the group's state [N, heads P] stays float32 in VMEM
from its first chunk to its last, and ``y`` is written once, [B, S,
H P]. The forward kernel also writes the state ENTERING each chunk when
a backward pass will follow; the backward kernel walks the chunks in
reverse with the state's cotangent in VMEM, forms ``C B^T``, the decay
and ``u`` again, and puts out ``du``, the running sums' cotangent and
``dB``, ``dC`` summed over the group's heads. The running sums, ``dt``
and the chain through ``u`` are XLA's. Mosaic compiles the kernels on a
TPU, Pallas's interpreter runs the same kernels anywhere else.

Products take operands of ``SSDShape.dtype`` (bfloat16) and accumulate
in float32; ``dt``, ``A``, the decay and its running sums, the gated
norm and the state between chunks are float32.
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
from multiverso_tpu.ops import gated_delta as gdn

class SSDShape(NamedTuple):
    heads: int          # mamba_num_heads
    head_dim: int       # mamba_head_dim (P)
    state: int          # ssm_state_size (N)
    groups: int         # n_groups: heads a group share B and C
    taps: int           # conv_kernel
    eps: float          # of the gated norm
    chunk: int = 128    # chunk_size: tokens solved at once
    dtype: str = "bfloat16"     # of the matrix products' operands

    @property
    def inner(self) -> int:
        """Channels of ``x`` (and ``z``, and ``y``): ``H P``."""
        return self.heads * self.head_dim

    @property
    def conv_width(self) -> int:
        """Channels the convolution filters: ``x | B | C``."""
        return self.inner + 2 * self.groups * self.state

    @property
    def in_width(self) -> int:
        """Columns of the input projection: ``z | x | B | C | dt``."""
        return self.inner + self.conv_width + self.heads


def project(u, w_in, dt_bias, shape: SSDShape):
    """From the normed input ``u`` [B, S, D]: ``z`` [B, S, H P],
    ``x | B | C`` [B, S, conv_width] before the convolution, and
    ``dt = softplus(dt_raw + dt_bias)`` [B, S, H], float32."""
    @telemetry.scope("lm.ssm.project")
    def run(u, w_in, dt_bias):
        inner, conv = shape.inner, shape.conv_width
        p = gdn._dot(u, w_in, shape.dtype)
        return (p[..., :inner], p[..., inner:inner + conv],
                jax.nn.softplus(p[..., inner + conv:] + dt_bias))
    return run(u, w_in, dt_bias)


@telemetry.scope("lm.ssm.conv")
def conv(xbc, taps_bias, doc):
    """``x | B | C`` [B, S, W] through :func:`causal_taps` with the
    filter ``taps_bias[:-1]`` [taps, W], plus the bias (its last row),
    then ``silu``."""
    return jax.nn.silu(gdn.causal_taps(xbc, taps_bias[:-1], doc)
                       + taps_bias[-1])


# -- the scan: one Pallas kernel pair over (sequence, group, chunk) -----------

def heads_per_step(shape: SSDShape) -> int:
    """Heads a grid step of the scan holds: one group's, the heads that
    read one ``B`` and one ``C``."""
    return shape.heads // shape.groups


class _Chunk(NamedTuple):
    """What a grid step forms once for its group and chunk: ``B``,
    ``C`` [C, N] as products' operands, ``C B^T`` [C, C] float32, the
    mask ``j <= i`` of one document, whether each token takes the
    entering state and shares the last token's document [C, 1], the
    running sums of the log decay ``L`` as rows [heads, C] and columns
    [C, heads], and the steps ``dt`` as columns."""
    b: jax.Array
    c: jax.Array
    cb: jax.Array
    mask: jax.Array
    carried: jax.Array
    to_last: jax.Array
    l_rows: jax.Array
    l_cols: jax.Array
    dt_cols: jax.Array


def _chunk(b_ref, c_ref, l_ref, dt_ref, doc_ref, dtype) -> _Chunk:
    b, c = b_ref[...].astype(dtype), c_ref[...].astype(dtype)
    doc = doc_ref[...]              # [2, C]: segment ids, carried
    cols = doc.T
    seg = cols[:, :1]
    C = seg.shape[0]
    i = lax.broadcasted_iota(jnp.int32, (C, C), 0)
    j = lax.broadcasted_iota(jnp.int32, (C, C), 1)
    l_rows = l_ref[...]
    return _Chunk(b, c, gdn._mm(c, b, gdn._NT),
                  (seg == doc[:1]) & (j <= i), cols[:, 1:] > 0,
                  seg == seg[C - 1:], l_rows, l_rows.T, dt_ref[...].T)


def _head(k: int, ch: _Chunk, x_ref, P: int):
    """Head ``k``'s chunk: ``u = dt x`` float32 [C, P], the decay
    ``exp(L_i - L_j)`` [C, C] (0 across a document start and above the
    diagonal), and [C, 1] columns ``from_state`` (``exp(L_i)`` where the
    entering state reaches token ``i``, else 0) and ``to_end``
    (``exp(L_C - L_j)`` inside the last token's document, else 0);
    ``keep`` [1, 1] is the last ``from_state``."""
    li, lj = ch.l_cols[:, k:k + 1], ch.l_rows[k:k + 1]
    C = li.shape[0]
    u = ch.dt_cols[:, k:k + 1] * x_ref[:, k * P:(k + 1) * P]
    decay = jnp.exp(jnp.where(ch.mask, li - lj, gdn._GONE))
    from_state = jnp.exp(jnp.where(ch.carried, li, gdn._GONE))
    to_end = jnp.exp(jnp.where(ch.to_last, lj[:, C - 1:] - li, gdn._GONE))
    # a sum, not a slice: Mosaic broadcasts no slice [1, 1] in both axes
    last = lax.broadcasted_iota(jnp.int32, (C, 1), 0) == C - 1
    keep = jnp.sum(jnp.where(last, from_state, 0.0), 0, keepdims=True)
    return u, decay, from_state, to_end, keep


def _forward_kernel(x_ref, b_ref, c_ref, l_ref, dt_ref, doc_ref, y_ref,
                    *rest):
    """One (sequence, group, chunk) step: the group's state
    [N, heads P] float32 stays in the scratch from its first chunk to
    its last. ``rest``: the entering states' output, when a backward
    pass will follow, then the scratch for the state and for the
    write's operand ``to_end u`` [C, heads P]."""
    *entering_ref, state, write = rest
    dtype = write.dtype
    P = x_ref.shape[1] // l_ref.shape[0]

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    ch = _chunk(b_ref, c_ref, l_ref, dt_ref, doc_ref, dtype)
    s = state[...]
    if entering_ref:
        entering_ref[0][...] = s
    read = gdn._mm(ch.c, s.astype(dtype))          # C S [C, heads P]
    for k in range(l_ref.shape[0]):
        at = slice(k * P, (k + 1) * P)
        u, decay, from_state, to_end, keep = _head(k, ch, x_ref, P)
        attn = (ch.cb * decay).astype(dtype)
        y_ref[:, at] = (gdn._mm(attn, u.astype(dtype))
                        + from_state * read[:, at])
        write[:, at] = (to_end * u).astype(dtype)
        state[:, at] = keep * s[:, at]
    state[...] += gdn._mm(ch.b, write[...], gdn._TN)


def _backward_kernel(x_ref, b_ref, c_ref, l_ref, dt_ref, doc_ref,
                     entering_ref, dy_ref, du_ref, dl_ref, db_ref, dc_ref,
                     d_state, write, read_dy, cols, rows):
    """The same step with the chunks in reverse: ``d_state`` holds the
    cotangent of the state LEAVING the chunk on entry and of the state
    entering it on exit. ``C B^T``, the decay and ``u`` are formed again
    from the operands. A cotangent is cast to the operands' dtype before
    a product, and a product's operand's cotangent is rounded to it, as
    XLA's transpose of a product with float32 accumulation does. The
    log decay's cotangent is gathered as columns and rows [heads, C]
    and leaves as rows."""
    dtype = write.dtype
    P = x_ref.shape[1] // l_ref.shape[0]
    rounded = lambda a: a.astype(dtype).astype(jnp.float32)

    @pl.when(pl.program_id(2) == 0)
    def _():
        d_state[...] = jnp.zeros_like(d_state)

    ch = _chunk(b_ref, c_ref, l_ref, dt_ref, doc_ref, dtype)
    s = entering_ref[...]
    held = s.astype(dtype)
    d_out = d_state[...]
    d_outb = d_out.astype(dtype)
    read = gdn._mm(ch.c, held)                     # C S [C, heads P]
    d_write = rounded(gdn._mm(ch.b, d_outb))       # [C, heads P]
    C = read.shape[0]
    last = lax.broadcasted_iota(jnp.int32, (C, 1), 0) == C - 1
    d_cb = jnp.zeros_like(ch.cb)
    for k in range(l_ref.shape[0]):
        at = slice(k * P, (k + 1) * P)
        u, decay, from_state, to_end, keep = _head(k, ch, x_ref, P)
        dy = dy_ref[:, at]
        dyb = dy.astype(dtype)
        attn = (ch.cb * decay).astype(dtype)
        d_attn = rounded(gdn._mm(dyb, u.astype(dtype), gdn._NT))
        d_cb = d_cb + d_attn * decay
        d_exp = d_attn * ch.cb * decay      # of the decay's exponent
        du_ref[:, at] = (rounded(gdn._mm(attn, dyb, gdn._TN))
                         + to_end * d_write[:, at])
        write[:, at] = (to_end * u).astype(dtype)
        read_dy[:, at] = (from_state * dy).astype(dtype)
        # the exponents' cotangents of from_state, to_end and keep
        d_from = from_state * jnp.sum(dy * read[:, at], 1, keepdims=True)
        d_to = to_end * jnp.sum(d_write[:, at] * u, 1, keepdims=True)
        d_keep = keep * jnp.sum(s[:, at] * d_out[:, at], keepdims=True)
        cols[:, k:k + 1] = (jnp.sum(d_exp, 1, keepdims=True) + d_from
                            - d_to + jnp.where(
                                last, jnp.sum(d_to, keepdims=True)
                                + d_keep, 0.0))
        rows[k:k + 1] = -jnp.sum(d_exp, 0, keepdims=True)
        d_state[:, at] = keep * d_out[:, at]
    dl_ref[...] = rows[...] + cols[...].T
    read_dy, write, d_cb = read_dy[...], write[...], d_cb.astype(dtype)
    d_state[...] += gdn._mm(ch.c, read_dy, gdn._TN)
    dc_ref[...] = gdn._mm(d_cb, ch.b) + gdn._mm(read_dy, held, gdn._NT)
    db_ref[...] = (gdn._mm(d_cb, ch.c, gdn._TN)
                   + gdn._mm(write, d_outb, gdn._NT))


def _call(kernel, shape: SSDShape, interpret, operands, ins, outs,
          scratch, backward):
    """``kernel`` over the grid (sequence, group, chunk), chunks
    innermost and in order (``backward``: last to first); a block is one
    chunk of one group. ``ins`` names each operand's block and ``outs``
    pairs each output with its block's name: ``x`` the group's heads'
    columns of [B, S, H P]; ``b`` / ``c`` the group's ``N`` columns of
    ``B`` / ``C`` in ``bc`` [B, S, 2 G N] (``b`` also of a cotangent
    [B, S, G N]); ``rows`` the group's rows of [B, H, S]; ``docs`` of
    [B, 2, S]; ``states`` the group's columns of [B, Nc, N, H P]."""
    Bs, S, _ = operands[0].shape
    G, N = shape.groups, shape.state
    hb = heads_per_step(shape)
    C = min(shape.chunk, S)
    Nc = S // C
    t = (lambda n: Nc - 1 - n) if backward else (lambda n: n)
    specs = {
        "x": pl.BlockSpec((None, C, hb * shape.head_dim),
                          lambda b, g, n: (b, t(n), g)),
        "b": pl.BlockSpec((None, C, N), lambda b, g, n: (b, t(n), g)),
        "c": pl.BlockSpec((None, C, N), lambda b, g, n: (b, t(n), G + g)),
        "rows": pl.BlockSpec((None, hb, C), lambda b, g, n: (b, g, t(n))),
        "docs": pl.BlockSpec((None, 2, C), lambda b, g, n: (b, 0, t(n))),
        "states": pl.BlockSpec((None, None, N, hb * shape.head_dim),
                               lambda b, g, n: (b, t(n), 0, g))}
    return pl.pallas_call(
        kernel, grid=(Bs, G, Nc), in_specs=[specs[k] for k in ins],
        out_specs=[specs[k] for _, k in outs],
        out_shape=[o for o, _ in outs], scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=gdn.VMEM_LIMIT),
        interpret=interpret)(*operands)


_OPERANDS = ("x", "b", "c", "rows", "rows", "docs")


def _scratch(shape: SSDShape, C: int, *shapes):
    hp = heads_per_step(shape) * shape.head_dim
    return [pltpu.VMEM((shape.state, hp), jnp.float32),
            pltpu.VMEM((C, hp), jnp.dtype(shape.dtype))] + [
        pltpu.VMEM(s, d) for s, d in shapes]


def _forward(x, bc, l, dt, docs, shape: SSDShape, interpret,
             residuals: bool):
    """``y`` [B, S, H P] float32 and, with ``residuals``, the state
    entering every chunk [B, Nc, N, H P] float32."""
    Bs, S, HP = x.shape
    C = min(shape.chunk, S)
    outs = [(jax.ShapeDtypeStruct((Bs, S, HP), jnp.float32), "x")]
    if residuals:
        outs.append((jax.ShapeDtypeStruct((Bs, S // C, shape.state, HP),
                                          jnp.float32), "states"))
    out = _call(_forward_kernel, shape, interpret, (x, bc, bc, l, dt, docs),
                _OPERANDS, outs, _scratch(shape, C), False)
    return out if residuals else (out[0], None)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _chunked(x, bc, l, dt, docs, shape: SSDShape, interpret):
    """The chunked recurrence on ``x`` [B, S, H P], ``bc`` [B, S, 2 G N],
    the running sums of the log decay ``l`` and the steps ``dt`` as rows
    [B, H, S] float32, and the documents [B, 2, S] int32 (each token's
    segment id, and 1 where the state entering its chunk reaches it).
    Returns ``y`` [B, S, H P] float32."""
    return _forward(x, bc, l, dt, docs, shape, interpret, False)[0]


def _chunked_fwd(x, bc, l, dt, docs, shape, interpret):
    y, entering = _forward(x, bc, l, dt, docs, shape, interpret, True)
    return y, (x, bc, l, dt, docs, entering)


def _chunked_bwd(shape, interpret, saved, dy):
    x, bc, l, dt, docs, entering = saved
    Bs, S, HP = x.shape
    H, P, C = shape.heads, shape.head_dim, min(shape.chunk, S)
    hb = heads_per_step(shape)
    like = lambda a: jax.ShapeDtypeStruct(a.shape, jnp.float32)
    half = jax.ShapeDtypeStruct((Bs, S, shape.groups * shape.state),
                                jnp.float32)
    du, dl, db, dc = _call(
        _backward_kernel, shape, interpret,
        (x, bc, bc, l, dt, docs, entering, dy),
        _OPERANDS + ("states", "x"),
        [(like(x), "x"), (like(l), "rows"), (half, "b"), (half, "b")],
        _scratch(shape, C, ((C, hb * P), jnp.dtype(shape.dtype)),
                 ((C, hb), jnp.float32), ((hb, C), jnp.float32)), True)
    # the chain through u = dt x, in XLA
    du = du.reshape(Bs, S, H, P)
    dx = (du * jnp.swapaxes(dt, 1, 2)[..., None]).reshape(Bs, S, HP)
    d_dt = jnp.swapaxes(jnp.sum(du * x.reshape(Bs, S, H, P), -1), 1, 2)
    return (dx.astype(x.dtype), jnp.concatenate([db, dc], -1).astype(
        bc.dtype), dl, d_dt, None)


_chunked.defvjp(_chunked_fwd, _chunked_bwd)


def scan(x, bc, dt, a_log, doc, shape: SSDShape,
         interpret: Optional[bool] = None):
    """The state-space recurrence on the convolved ``x`` [B, S, H P] and
    ``B | C`` [B, S, 2 G N], with step ``dt`` [B, S, H] and decay rate
    ``-exp(a_log)`` [H], chunked; the state restarts where ``doc``
    [B, S] changes. Returns ``y`` [B, S, H, P] float32, before ``D x``.
    ``interpret``: run the kernels in Pallas's interpreter; left out,
    every backend but a TPU does."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    @telemetry.scope("lm.ssm.scan")
    def run(x, bc, dt, a_log, doc):
        Bs, S, _ = x.shape
        C = min(shape.chunk, S)
        if S % C:
            raise ValueError(f"sequence {S} is no multiple of the scan's "
                             f"chunk {C}")
        in_chunks = lambda a: a.reshape(*a.shape[:-1], S // C, C)
        dt = jnp.swapaxes(dt, 1, 2)                     # [B, H, S]
        # the log decay from the chunk's or the document's start
        l = gdn._running_sum(in_chunks(dt * -jnp.exp(a_log)[:, None]),
                             in_chunks(gdn.starts(doc))[:, None])
        seg = in_chunks(gdn.segments(doc))              # [B, Nc, C]
        # the segment the entering state belongs to: the last token's of
        # the chunk before (none before the first chunk)
        entering = jnp.pad(seg[:, :-1, -1:], ((0, 0), (1, 0), (0, 0)))
        docs = jnp.stack([seg, (seg == entering).astype(jnp.int32)], 1)
        y = _chunked(x, bc, l.reshape(dt.shape), dt,
                     docs.reshape(Bs, 2, S), shape, bool(interpret))
        return y.reshape(Bs, S, shape.heads, shape.head_dim)
    return run(x, bc, dt, a_log, doc)


def gate_out(y, x, d_skip, z, norm_w, w_out, shape: SSDShape):
    """``w_out`` [D, H P] (out x in) on the gated norm of ``y + D x``
    (``y``, ``x`` [B, S, H, P], ``D`` = ``d_skip`` [H]): times ``silu(z)``
    first, then an RMS norm over each of the ``G`` groups of channels,
    times ``norm_w`` [H P]."""
    @telemetry.scope("lm.ssm.gate_out")
    def run(y, x, d_skip, z, norm_w, w_out):
        B, S, H, P = y.shape
        g = (y + d_skip[:, None] * x).reshape(B, S, H * P) * jax.nn.silu(z)
        g = g.reshape(B, S, shape.groups, -1)
        g = g * lax.rsqrt(jnp.mean(g * g, -1, keepdims=True) + shape.eps)
        g = g.reshape(B, S, H * P) * norm_w
        return lax.dot_general(
            g.astype(shape.dtype), w_out.astype(shape.dtype),
            (((2,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    return run(y, x, d_skip, z, norm_w, w_out)


def mix(u, doc, w_in, taps_bias, decay, norm_w, w_out, shape: SSDShape,
        interpret: Optional[bool] = None):
    """The whole mixer's addition to the residual from the normed input
    ``u`` [B, S, D]: ``decay`` [3, H] is ``A_log``, ``dt_bias``, ``D``."""
    z, xbc, dt = project(u, w_in, decay[1], shape)
    xbc = conv(xbc, taps_bias, doc)
    B, S, _ = u.shape
    x = xbc[..., :shape.inner]
    y = scan(x, xbc[..., shape.inner:], dt, decay[0], doc, shape,
             interpret)
    return gate_out(y, x.reshape(B, S, shape.heads, shape.head_dim),
                    decay[2], z, norm_w, w_out, shape)
