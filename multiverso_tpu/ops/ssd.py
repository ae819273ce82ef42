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
solve: its own term is one masked product of plain XLA over every
chunk at once (:func:`chunk_terms`). What crosses from chunk to chunk
(:func:`across_chunks`) is two Pallas kernels, differentiated by hand
(``jax.custom_vjp``), the delta rule's scheme without its write
correction: a grid step is one (block of heads, chunk), the chunks
innermost and in order, the state [heads a block, N, P] float32 in VMEM
from a head block's first chunk to its last; the forward kernel also
writes the state ENTERING each chunk when a backward pass will follow,
and the backward kernel walks the chunks in reverse with the state's
cotangent in VMEM. Mosaic compiles them on a TPU, Pallas's interpreter
runs the same kernels anywhere else.

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


# -- the state from chunk to chunk: two Pallas kernels -------------------------

# what a grid step's blocks may hold, each of them twice (the pipeline's
# two buffers): sets the heads a step
_STEP_BYTES = 24 * 2 ** 20


def _forward_kernel(q_ref, k_ref, u_ref, keep_ref, o_ref, *rest):
    """One (block of heads, chunk) step: the state [heads, N, P] float32
    stays in the scratch from a head block's first chunk to its last.
    ``rest``: the entering states' output, when a backward pass will
    follow, and the scratch."""
    *entering_ref, state = rest
    dtype = q_ref.dtype

    @pl.when(pl.program_id(1) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    @gdn._each_head(state.shape[0])
    def _(h):
        s = state[h]
        if entering_ref:
            entering_ref[0][h] = s
        o_ref[h] = gdn._mm(q_ref[h], s.astype(dtype))
        state[h] = keep_ref[h] * s + gdn._mm(k_ref[h], u_ref[h], gdn._TN)


def _backward_kernel(q_ref, k_ref, u_ref, keep_ref, entering_ref, do_ref,
                     dq_ref, dk_ref, du_ref, dkeep_ref, d_state):
    """The same step with the chunks in reverse: ``d_state`` holds the
    cotangent of the state LEAVING the chunk on entry and of the state
    entering it on exit. A cotangent is cast to the operands' dtype
    before a product, as XLA's transpose of a product with float32
    accumulation rounds it."""
    dtype = q_ref.dtype

    @pl.when(pl.program_id(1) == 0)
    def _():
        d_state[...] = jnp.zeros_like(d_state)

    @gdn._each_head(d_state.shape[0])
    def _(h):
        s, d_out = entering_ref[h], d_state[h]
        held, d_outb = s.astype(dtype), d_out.astype(dtype)
        do = do_ref[h].astype(dtype)
        dq_ref[h] = gdn._mm(do, held, gdn._NT).astype(dq_ref.dtype)
        dk_ref[h] = gdn._mm(u_ref[h], d_outb, gdn._NT).astype(dk_ref.dtype)
        du_ref[h] = gdn._mm(k_ref[h], d_outb).astype(du_ref.dtype)
        dkeep_ref[h] = jnp.sum(jnp.sum(s * d_out, 1, keepdims=True), 0,
                               keepdims=True)
        d_state[h] = keep_ref[h] * d_out + gdn._mm(q_ref[h], do, gdn._TN)


def _head_block(heads: int, C: int, N: int, P: int) -> int:
    """Heads a grid step: the most that divide ``heads`` and whose
    blocks of the backward pass (the larger set: the operands, the
    entering state, ``do`` and four cotangents, float32 counted for
    all) fit ``_STEP_BYTES``."""
    lanes = lambda d: -(-d // 128) * 128
    a_head = 4 * (C * (4 * lanes(N) + 3 * lanes(P)) + N * lanes(P))
    most = max(1, _STEP_BYTES // (2 * a_head))
    return max(n for n in range(1, heads + 1)
               if heads % n == 0 and n <= most)


def _call(kernel, interpret, operands, outs, backward):
    """``kernel`` over the grid (blocks of heads, chunks), chunks
    innermost and in order (``backward``: last to first). Every operand
    and output is [Nc, heads, rows, cols] and a block all of one chunk's
    rows and cols for a block of heads."""
    q, _, u = operands[:3]
    Nc, heads, C, N = q.shape
    P = u.shape[3]
    hb = _head_block(heads, C, N, P)
    at = (lambda i, n: (Nc - 1 - n, i, 0, 0)) if backward else (
        lambda i, n: (n, i, 0, 0))
    spec = lambda a: pl.BlockSpec((None, hb) + a.shape[2:], at)
    return pl.pallas_call(
        kernel, grid=(heads // hb, Nc), in_specs=[spec(a) for a in operands],
        out_specs=[spec(a) for a in outs], out_shape=outs,
        scratch_shapes=[pltpu.VMEM((hb, N, P), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=gdn.VMEM_LIMIT),
        interpret=interpret)(*operands)


def _forward(q_in, k_out, u, keep, interpret, residuals: bool):
    """``o`` [Nc, heads, C, P] float32 and, with ``residuals``, the
    state entering every chunk [Nc, heads, N, P] float32."""
    Nc, heads, _, N = q_in.shape
    outs = [jax.ShapeDtypeStruct(u.shape, jnp.float32)]
    if residuals:
        outs.append(jax.ShapeDtypeStruct((Nc, heads, N, u.shape[3]),
                                         jnp.float32))
    out = _call(_forward_kernel, interpret, (q_in, k_out, u, keep), outs,
                False)
    return out if residuals else (out[0], None)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def across_chunks(q_in, k_out, u, keep, interpret):
    """What crosses from chunk to chunk, for operands [Nc, heads, C, ·]
    (``keep`` [Nc, heads, 1, 1]) in chunk order: from a state ``S``
    [N, P] that starts at 0, a chunk puts out ``o = q_in S`` and leaves
    ``keep S + k_out^T u``. Products take ``q_in``'s dtype and
    accumulate in float32; the state is float32 and is rounded only as a
    product's operand."""
    return _forward(q_in, k_out, u, keep, interpret, False)[0]


def _across_chunks_fwd(q_in, k_out, u, keep, interpret):
    o, entering = _forward(q_in, k_out, u, keep, interpret, True)
    return o, (q_in, k_out, u, keep, entering)


def _across_chunks_bwd(interpret, saved, do):
    like = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
    return tuple(_call(_backward_kernel, interpret, saved + (do,),
                       [like(a) for a in saved[:4]], True))


across_chunks.defvjp(_across_chunks_fwd, _across_chunks_bwd)


def chunk_terms(x, bc, dt, a_log, doc, shape: SSDShape):
    """What :func:`scan` computes for every chunk at once, in plain XLA,
    before anything crosses a chunk: each chunk's own term ``y_local``
    [Nc, B, H, C, P] float32, and :func:`across_chunks`'s four operands
    ``q_in``, ``k_out`` [Nc, B H, C, N], ``u`` [Nc, B H, C, P] and
    ``keep`` [Nc, B H, 1, 1]. ``x`` [B, S, H P], ``bc`` [B, S, 2 G N]
    (``B | C``), ``dt`` [B, S, H] float32."""
    H, P, N, G = shape.heads, shape.head_dim, shape.state, shape.groups
    dtype = shape.dtype
    Bs, S, _ = x.shape
    C = min(shape.chunk, S)
    if S % C:
        raise ValueError(f"sequence {S} is no multiple of the scan's "
                         f"chunk {C}")
    Nc = S // C

    def chunks(a):      # [B, S, n, ...] -> [Nc, B, n, C, ...]
        a = a.reshape(Bs, Nc, C, *a.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(a, 3, 1), 2, 0)

    xs = chunks(x.reshape(Bs, S, H, P))
    b = chunks(bc[..., :G * N].reshape(Bs, S, G, N))
    c = chunks(bc[..., G * N:].reshape(Bs, S, G, N))
    dt_c = chunks(dt)                                   # [Nc, B, H, C]
    in_chunks = lambda a: jnp.moveaxis(a.reshape(Bs, Nc, C), 1, 0)
    seg = in_chunks(gdn.segments(doc))                  # [Nc, B, C]
    # the segment the entering state belongs to: the last token's of
    # the chunk before (none before the first chunk)
    entering = jnp.concatenate(
        [jnp.zeros_like(seg[:1, :, -1]), seg[:-1, :, -1]], 0)
    carried = (seg == entering[..., None])[:, :, None]  # [Nc, B, 1, C]
    same = (seg[..., :, None] == seg[..., None, :])[:, :, None]
    at = jnp.arange(C)
    upto = at[:, None] >= at[None]                      # j <= i
    # the log decay from the chunk's or the document's start
    a = dt_c * -jnp.exp(a_log)[:, None]
    Lsum = gdn._running_sum(a, in_chunks(gdn.starts(doc))[:, :, None])
    decay = jnp.exp(jnp.where(same & upto,
                              Lsum[..., :, None] - Lsum[..., None, :],
                              gdn._GONE))
    from_state = jnp.where(carried, jnp.exp(Lsum), 0.0)
    to_end = decay[..., -1, :]      # exp(L_C - L_j) inside the last
    # token's document, else 0
    per_head = lambda a: jnp.repeat(a, H // G, axis=2)
    cb = jnp.einsum("...in,...jn->...ij", c.astype(dtype), b.astype(dtype),
                    preferred_element_type=jnp.float32)  # [Nc, B, G, C, C]
    u = (dt_c[..., None] * xs).astype(dtype)
    attn = (per_head(cb) * decay).astype(dtype)
    y_local = jnp.einsum("...ij,...jp->...ip", attn, u,
                         preferred_element_type=jnp.float32)
    q_in = (per_head(c) * from_state[..., None]).astype(dtype)
    k_out = (per_head(b) * to_end[..., None]).astype(dtype)
    keep = from_state[..., -1, None, None]              # [Nc, B, H, 1, 1]
    flat = lambda a: a.reshape(Nc, Bs * H, *a.shape[3:])
    return y_local, tuple(map(flat, (q_in, k_out, u, keep)))


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
        y_local, operands = chunk_terms(x, bc, dt, a_log, doc, shape)
        Nc, Bs, H, C, P = y_local.shape
        y = y_local + across_chunks(*operands, bool(interpret)).reshape(
            Nc, Bs, H, C, P)
        return jnp.moveaxis(jnp.moveaxis(y, 0, 2), 1, 3).reshape(
            Bs, Nc * C, H, P)
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
