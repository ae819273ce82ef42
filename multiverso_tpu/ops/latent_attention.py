"""Multi-head latent attention over packed documents.

Queries per head; ONE compressed key-value latent a token
(``kv_lora_rank`` wide) plus one rotary key shared by all heads,
up-projected per head into the keys' non-rotary part and the values
(DeepSeek-V2, arXiv:2405.04434 §2.1). :func:`project` makes queries,
keys and values from the residual; :func:`attend` is the softmax
attention, causal and inside a document only; :func:`output` projects
back.

:func:`attend` is one family of Pallas kernels with a backward pass
written by hand (``jax.custom_vjp``): Mosaic compiles them on a TPU,
Pallas's interpreter runs the same kernels anywhere else. A grid step
is one (sequence, head, query block, key block) and its score tile
``[block, block]`` float32 (keys down, queries across, so that what is
kept a query — maximum, sum, log-sum-exp — is a row and a reduction
over the keys runs down the sublanes). The forward kernel keeps the
tile, the query block's running maximum and sum and its weighted values
in VMEM (an online softmax) and writes the output ``[B, S, H * v]``
and, when a backward pass will follow, one float32 log-sum-exp a
(sequence, head, query): no score ever reaches HBM. The backward pass is
ONE kernel, a key block over its query blocks: it recomputes each score
tile in VMEM from the operands and the log-sum-exp, accumulates the key
block's two gradients over the query blocks and keeps the queries'
gradient of the head's whole sequence in VMEM until the head is done;
so a layer's attention computes its scores three times (forward,
recomputed forward, backward) and stores them never. A (query block,
key block) pair in which no query may see a key — every pair above the
diagonal, and every pair whose blocks' ``doc`` ids do not overlap — is
neither fetched nor computed (:func:`block_plan`, scalar-prefetched).
The operands are read where the projections left them (a head is a
128-lane column block of ``[B, S, H * d]``, the rotary key one block
for every head); head dims that are no multiple of 128 lanes (the
rotary 64) are zero-padded first, which changes no score.

The same kernels run plain multi-head attention — per-head keys of one
depth, no rotary part (:func:`attend_heads`, with :func:`project_heads`
and :func:`output_heads` around it): the variant is static, the rotary
operands, their products, gradients and scratch are simply not there.
With fewer key-value heads than query heads (grouped-query attention,
:func:`project_grouped` before it: QK-norm a head, then a rotary
embedding over the whole head) query head ``h`` fetches the key and
value blocks of head ``h // group``; the backward kernel writes the
keys' and values' gradients one a QUERY head in float32 and the group's
are summed outside it — a key block's accumulators live through one
head's query blocks, and the queries' gradient of a head's whole
sequence is held across the head's key blocks, so a sum over the group
inside the kernel would need both held at once for every head of the
group; the sum outside costs one pass over ``group`` x the keys.

Matrix products take operands of ``LatentShape.dtype`` (bfloat16) and
accumulate in float32; norms, the rotary embedding and the softmax are
float32; the probabilities are cast to the operands' dtype before the
weighted values, the scores' gradient before its products.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from multiverso_tpu import telemetry


class LatentShape(NamedTuple):
    heads: int
    nope: int           # qk_nope_head_dim
    rope: int           # qk_rope_head_dim
    v: int              # v_head_dim
    rank: int           # kv_lora_rank
    eps: float          # rms_norm_eps
    dtype: str = "bfloat16"     # of the matrix products' operands


def yarn_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


class Rotary(NamedTuple):
    """Frequencies of the rotary dims, the factor on cos and sin, and
    the scale of the attention scores, from ``rope_theta`` and
    ``rope_scaling`` (YaRN, arXiv:2309.00071, or none)."""
    inv_freq: np.ndarray
    magnitude: float
    score_scale: float

    @classmethod
    def from_config(cls, *, rope_dim: int, qk_dim: int, theta: float,
                    scaling: Optional[dict]) -> "Rotary":
        plain = 1.0 / theta ** (np.arange(0, rope_dim, 2,
                                          dtype=np.float64) / rope_dim)
        if not scaling:
            return cls(plain.astype(np.float32), 1.0, qk_dim ** -0.5)
        if scaling.get("type", "yarn") != "yarn":
            raise NotImplementedError(
                f"rope_scaling type {scaling['type']!r}: only yarn")
        factor = scaling["factor"]
        orig = scaling["original_max_position_embeddings"]

        def correction(rotations):
            return rope_dim * math.log(orig / (rotations * 2 * math.pi)) \
                / (2 * math.log(theta))

        low = max(math.floor(correction(scaling["beta_fast"])), 0)
        high = min(math.ceil(correction(scaling["beta_slow"])),
                   rope_dim - 1)
        if low == high:
            high += 0.001
        ramp = np.clip((np.arange(rope_dim // 2, dtype=np.float64) - low)
                       / (high - low), 0.0, 1.0)
        inv_freq = plain / factor * ramp + plain * (1.0 - ramp)
        all_dim = yarn_mscale(factor, scaling.get("mscale_all_dim", 0))
        return cls(inv_freq.astype(np.float32),
                   yarn_mscale(factor, scaling.get("mscale", 1)) / all_dim,
                   qk_dim ** -0.5 * (all_dim * all_dim if
                                     scaling.get("mscale_all_dim") else 1))


def rms_norm(x, weight, eps):
    x = x.astype(jnp.float32)
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * weight


def _dot(a, b, dtype):
    return jnp.dot(a.astype(dtype), b.astype(dtype),
                   preferred_element_type=jnp.float32)


def _rotate(x, cos, sin):
    """Half-split rotary: dim ``i`` pairs with ``i + d/2``."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def project(x, pos, norm_w, w_q, w_kv_a, kv_norm_w, w_kv_b,
            shape: LatentShape, rotary: Rotary):
    """From the residual ``x`` [B, S, D] float32 and the positions
    inside the documents: ``q_nope`` [B, S, H, nope], ``q_pe``
    [B, S, H, rope], ``k_nope`` [B, S, H, nope], ``k_pe`` [B, S, rope]
    (one for all heads), ``v`` [B, S, H, v], all ``shape.dtype``."""
    @telemetry.scope("lm.mla.project")
    def run(x, pos, norm_w, w_q, w_kv_a, kv_norm_w, w_kv_b):
        B, S, _ = x.shape
        H, nope, rope, vd, rank, eps, dtype = shape
        h = rms_norm(x, norm_w, eps)
        q = _dot(h, w_q, dtype).reshape(B, S, H, nope + rope)
        kv_a = _dot(h, w_kv_a, dtype)
        c = rms_norm(kv_a[..., :rank], kv_norm_w, eps)
        kv = _dot(c, w_kv_b, dtype).reshape(B, S, H, nope + vd)
        ang = pos[..., None].astype(jnp.float32) \
            * jnp.asarray(rotary.inv_freq)
        cos = jnp.cos(ang) * rotary.magnitude
        sin = jnp.sin(ang) * rotary.magnitude
        q_pe = _rotate(q[..., nope:], cos[:, :, None], sin[:, :, None])
        k_pe = _rotate(kv_a[..., rank:], cos, sin)
        bf = lambda a: a.astype(dtype)
        return (bf(q[..., :nope]), bf(q_pe), bf(kv[..., :nope]), bf(k_pe),
                bf(kv[..., nope:]))
    return run(x, pos, norm_w, w_q, w_kv_a, kv_norm_w, w_kv_b)


# -- attend: one Pallas kernel family, differentiated by hand -----------------

LANES = 128
VMEM_LIMIT = 64 * 2 ** 20   # of a v5e core's 128 MiB; the default is 16
_MASKED = -1e30
_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_TN = (((0,), (0,)), ((), ()))      # a.T @ b


class _Static(NamedTuple):
    scale: float
    # the query block, ``attend``'s ``block``, and the key block too: a
    # square score tile (at 512 queries on a v5e a key block of 1,024
    # ran as fast and one of 256 a fifth slower, PERF.md PR 29)
    block: int
    interpret: bool
    # of the variant without rotary operands, which has no shared key
    # to count its heads by
    heads: int = 0
    # query heads that share a key-value head (that variant's alone)
    group: int = 1


def block_plan(doc, block: int):
    """Which (sequence, query block, key block) pairs the kernels
    compute, [B, S / block, S / block] int32 (1 or 0), from the blocks'
    ranges of ``doc`` ids alone. A pair is skipped — neither fetched nor
    computed — when no query of it may see a key of it: the key block
    lies above the diagonal, or the two blocks' id ranges are disjoint.
    The block on the diagonal is never skipped: a token sees itself.
    (Telling the pairs that need no mask, or the document mask alone,
    apart from the rest bought nothing on a v5e, PERF.md PR 29.)"""
    B, S = doc.shape
    n = S // block
    blocks = doc.reshape(B, n, block)
    lo, hi = blocks.min(-1), blocks.max(-1)
    overlap = (lo[:, None, :] <= hi[:, :, None]) \
        & (hi[:, None, :] >= lo[:, :, None])
    return (jnp.tril(jnp.ones((n, n), bool)) & overlap).astype(jnp.int32)


def key_blocks(doc, block: int):
    """``[block pairs under the diagonal, block pairs the kernels
    compute]`` of one call of :func:`attend` on ``doc`` [B, S] (a head's;
    every head and every pass runs the same pairs), int32 [2]."""
    B, S = doc.shape
    block = min(block, S)
    n = S // block
    return jnp.stack([jnp.asarray(B * n * (n + 1) // 2),
                      jnp.sum(block_plan(doc, block))]).astype(jnp.int32)


def _fetch_plan(plan):
    """For the kernels' index maps, along the last axis of ``plan``: the
    block to hold at each grid step — the step's own where it is
    computed, else the next one that is (so its fetch overlaps the steps
    skipped before it), else the last one that was (nothing is fetched
    after it)."""
    n = plan.shape[-1]
    at = jnp.arange(n, dtype=jnp.int32)
    nxt = lax.cummin(jnp.where(plan > 0, at, n), axis=plan.ndim - 1,
                     reverse=True)
    prev = lax.cummax(jnp.where(plan > 0, at, -1), axis=plan.ndim - 1)
    return jnp.where(nxt < n, nxt, prev).astype(jnp.int32)


def _scores(a_nope, a_pe, b_nope, b_pe, scale):
    """``a @ b.T`` over the non-rotary and the rotary depth (where there
    is one), float32, scaled: [rows of a, rows of b]."""
    s = lax.dot_general(a_nope, b_nope, _NT,
                        preferred_element_type=jnp.float32)
    if a_pe is not None:
        s = s + lax.dot_general(a_pe, b_pe, _NT,
                                preferred_element_type=jnp.float32)
    return s * scale


def _read(ref):
    return None if ref is None else ref[...]




def _mask(s, q0, k0, q_doc, k_doc):
    """The score tile ``s`` [keys, queries] with ``_MASKED`` where the
    query may not see the key: it comes before it, or their ``doc`` ids
    (a row of queries', a column of keys') differ."""
    ahead = lax.broadcasted_iota(jnp.int32, s.shape, 1) \
        - lax.broadcasted_iota(jnp.int32, s.shape, 0)
    return jnp.where((ahead >= k0 - q0) & (q_doc == k_doc), s, _MASKED)


def _computed(plan_ref, outer, inner, n):
    """Whether the plan has this grid step's pair computed."""
    return plan_ref[(pl.program_id(0) * n + outer) * n + inner] > 0


def _forward_kernel(hold_ref, plan_ref, qn_ref, qp_ref, kn_ref, kp_ref,
                    v_ref, qd_ref, kd_ref, o_ref, *rest, scale, block, n):
    """One (sequence, head, query block, key block) step of the online
    softmax. The tile is [keys, queries], so the
    query block's running maximum ``m`` and sum ``l`` are rows and a
    reduction over the keys runs down the sublanes; the weighted values
    accumulate transposed, [v, queries]. Tile, ``m``, ``l`` and the
    accumulator never leave VMEM."""
    del hold_ref
    *lse_ref, m_ref, l_ref, acc_ref = rest
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(_computed(plan_ref, i, j, n))
    def _():
        s = _mask(_scores(kn_ref[...], _read(kp_ref), qn_ref[...],
                          _read(qp_ref), scale),
                  i * block, j * block, qd_ref[...], kd_ref[...])
        m = m_ref[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=0, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=0, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + lax.dot_general(
            v_ref[...], p.astype(v_ref.dtype), _TN,
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == n - 1)
    def _():
        o_ref[...] = (acc_ref[...] / l_ref[...]).T.astype(o_ref.dtype)
        if lse_ref:
            lse_ref[0][...] = m_ref[...] + jnp.log(l_ref[...])


def _forward_kernel_plain(hold_ref, plan_ref, qn_ref, kn_ref, *rest,
                          **static):
    """The forward kernel of the variant whose calls carry no rotary
    operand: ``None`` in the place of the two rotary refs."""
    _forward_kernel(hold_ref, plan_ref, qn_ref, None, kn_ref, None, *rest,
                    **static)


def _backward_kernel(hold_ref, plan_ref, qn_ref, qp_ref, kn_ref, kp_ref,
                     v_ref, qd_ref, kd_ref, do_ref, lse_ref, di_ref,
                     dqn_ref, dqp_ref, dkn_ref, dkp_ref, dv_ref,
                     dqn_acc, dqp_acc, dkn_acc, dkp_acc, dv_acc, *, scale,
                     block, n):
    """One (sequence, head, key block, query block) step of the backward
    pass. The tile is [keys, queries]: the probabilities are recomputed from the scores and the
    forward pass's log-sum-exp (a row, like ``di``), the keys' and
    values' gradients of the key block accumulate over its query blocks,
    and the queries' gradient of the whole sequence stays in VMEM until
    the head's last step."""
    del hold_ref
    j, i = pl.program_id(2), pl.program_id(3)

    rotary = qp_ref is not None

    @pl.when((j == 0) & (i == 0))
    def _():
        dqn_acc[...] = jnp.zeros_like(dqn_acc)
        if rotary:
            dqp_acc[...] = jnp.zeros_like(dqp_acc)

    @pl.when(i == 0)
    def _():
        dkn_acc[...] = jnp.zeros_like(dkn_acc)
        if rotary:
            dkp_acc[...] = jnp.zeros_like(dkp_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(_computed(plan_ref, j, i, n))
    def _():
        s = _mask(_scores(kn_ref[...], _read(kp_ref), qn_ref[...],
                          _read(qp_ref), scale),
                  i * block, j * block, qd_ref[...], kd_ref[...])
        p = jnp.exp(s - lse_ref[...])
        do = do_ref[...]
        dv_acc[...] += jnp.dot(p.astype(do.dtype), do,
                               preferred_element_type=jnp.float32)
        dprob = lax.dot_general(v_ref[...], do, _NT,
                                preferred_element_type=jnp.float32)
        ds = p * (dprob - di_ref[...])
        ds_t = ds.T.astype(kn_ref.dtype)
        ds = ds.astype(qn_ref.dtype)
        dkn_acc[...] += jnp.dot(ds, qn_ref[...],
                                preferred_element_type=jnp.float32)
        if rotary:
            dkp_acc[...] += jnp.dot(ds, qp_ref[...],
                                    preferred_element_type=jnp.float32)
        rows = pl.ds(pl.multiple_of(i * block, block), block)
        dqn_acc[rows, :] += jnp.dot(ds_t, kn_ref[...],
                                    preferred_element_type=jnp.float32)
        if rotary:
            dqp_acc[rows, :] += jnp.dot(ds_t, kp_ref[...],
                                        preferred_element_type=jnp.float32)

    @pl.when(i == n - 1)
    def _():
        dkn_ref[...] = (dkn_acc[...] * scale).astype(dkn_ref.dtype)
        if rotary:
            dkp_ref[...] = dkp_acc[...] * scale
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)

    @pl.when((j == n - 1) & (i == n - 1))
    def _():
        dqn_ref[...] = (dqn_acc[...] * scale).astype(dqn_ref.dtype)
        if rotary:
            dqp_ref[...] = (dqp_acc[...] * scale).astype(dqp_ref.dtype)


def _backward_kernel_plain(hold_ref, plan_ref, qn_ref, kn_ref, v_ref, qd_ref,
                           kd_ref, do_ref, lse_ref, di_ref, dqn_ref,
                           dkn_ref, dv_ref, dqn_acc, dkn_acc, dv_acc,
                           **static):
    """The backward kernel without rotary operands, their two gradients
    and their scratch."""
    _backward_kernel(hold_ref, plan_ref, qn_ref, None, kn_ref, None, v_ref,
                     qd_ref, kd_ref, do_ref, lse_ref, di_ref, dqn_ref, None,
                     dkn_ref, None, dv_ref, dqn_acc, None, dkn_acc, None,
                     dv_acc, **static)


def _grid(static: _Static, B, H, S, Dn, Dr, Dv, doc, queries_inner):
    """Grid, the two scalar-prefetched plans and the block specs the
    kernels share. The operands stay as the projections left them,
    [B, S, H * d]: a head's block is the column block ``h``; ``k_pe``
    [B, S, Dr] is every head's (``Dr`` 0: no rotary operands). The tile is [keys, queries], so the queries'
    ``doc`` ids and statistics are rows ([B, 1, S], [B, H, 1, S]) and
    the keys' ids a column ([B, S, 1]). The grid is (sequence, head,
    outer block, inner block): the outer block is held, the inner one
    follows the fetch plan — key blocks under a query block, or, with
    ``queries_inner``, query blocks over a key block."""
    block, n, group = static.block, S // static.block, static.group
    plan = block_plan(doc, block)
    if queries_inner:
        plan = plan.transpose(0, 2, 1)

    def fetched(b, outer, inner, hold):
        return hold[(b * n + outer) * n + inner]

    if queries_inner:
        q_at = lambda b, h, j, i, hold, plan: fetched(b, j, i, hold)
        k_at = lambda b, h, j, i, hold, plan: j
    else:
        q_at = lambda b, h, i, j, hold, plan: i
        k_at = lambda b, h, i, j, hold, plan: fetched(b, i, j, hold)

    def rows(at, d, head=True, shared=False):
        """[B, S, H * d]: the rows of block ``at`` of head ``h`` (of the
        one head there is; ``shared``: of the head ``h``'s group
        shares)."""
        if shared and group > 1:
            return pl.BlockSpec((None, block, d), lambda b, h, *a: (
                b, at(b, h, *a), h // group))
        return pl.BlockSpec((None, block, d), lambda b, h, *a: (
            b, at(b, h, *a), h if head else 0))

    per_q = functools.partial(rows, q_at)
    per_k = functools.partial(rows, k_at)
    held_k = functools.partial(rows, k_at, shared=True)
    q_doc = pl.BlockSpec((None, 1, block),
                         lambda b, h, *a: (b, 0, q_at(b, h, *a)))
    q_stat = pl.BlockSpec((None, None, 1, block),
                          lambda b, h, *a: (b, h, 0, q_at(b, h, *a)))
    operands = [per_q(Dn), per_q(Dr), held_k(Dn), per_k(Dr, head=False),
                held_k(Dv), q_doc, per_k(1, head=False)]
    if not Dr:
        del operands[3], operands[1]
    return ((B, H, n, n), (_fetch_plan(plan).reshape(-1), plan.reshape(-1)),
            operands, per_q, per_k, q_stat)


def _pallas(kernel, static: _Static, grid, plans, in_specs, out_specs,
            out_shape, scratch, S, inner_only: bool = True):
    """The call; ``inner_only``: the innermost grid axis alone carries
    state from step to step (else the two block axes do)."""
    return functools.partial(pl.pallas_call(
        functools.partial(kernel, scale=static.scale, block=static.block,
                          n=S // static.block),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=grid, in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=scratch),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel",
                                 "parallel" if inner_only else "arbitrary",
                                 "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=static.interpret), *plans)


def _sizes(qn, qp, kp, v, static: _Static):
    if kp is None:
        (B, S, _), H, Dr = v.shape, static.heads, 0
    else:
        B, S, Dr = kp.shape
        H = qp.shape[2] // Dr
    return B, H, S, qn.shape[2] // H, Dr, v.shape[2] * static.group // H


def _forward(qn, qp, kn, kp, v, doc, static: _Static, residuals: bool):
    """``o`` [B, S, H * Dv] and, with ``residuals``, the log-sum-exp of
    every (sequence, head, query) [B, H, 1, S] float32."""
    B, H, S, Dn, Dr, Dv = _sizes(qn, qp, kp, v, static)
    block = static.block
    grid, plans, operands, per_q, _, q_stat = _grid(
        static, B, H, S, Dn, Dr, Dv, doc, False)
    kernel = _forward_kernel if Dr else _forward_kernel_plain
    rotary = (qp, kp) if Dr else ()
    out_specs = [per_q(Dv)]
    out_shape = [jax.ShapeDtypeStruct((B, S, H * Dv), v.dtype)]
    if residuals:
        out_specs.append(q_stat)
        out_shape.append(jax.ShapeDtypeStruct((B, H, 1, S), jnp.float32))
    out = _pallas(kernel, static, grid, plans, operands, out_specs,
                  out_shape, [pltpu.VMEM((1, block), jnp.float32),
                              pltpu.VMEM((1, block), jnp.float32),
                              pltpu.VMEM((Dv, block), jnp.float32)], S)(
        qn, *rotary[:1], kn, *rotary[1:], v, doc[:, None, :],
        doc[:, :, None])
    return out if residuals else (out[0], None)


def _backward(static: _Static, saved, do):
    """The five operands' gradients from what the forward pass kept (its
    operands, ``o`` and the log-sum-exp): one kernel that recomputes the
    score tiles in VMEM."""
    qn, qp, kn, kp, v, doc, o, lse = saved
    B, H, S, Dn, Dr, Dv = _sizes(qn, qp, kp, v, static)
    block = static.block
    # rowsum(do * o), what the softmax's gradient subtracts: [B, H, 1, S]
    di = jnp.sum((do.astype(jnp.float32) * o.astype(jnp.float32))
                 .reshape(B, S, H, Dv), -1).transpose(0, 2, 1)[:, :, None]
    f32 = lambda *shape: pltpu.VMEM(shape, jnp.float32)
    like = lambda a, dtype=None: jax.ShapeDtypeStruct(a.shape,
                                                      dtype or a.dtype)
    # a head's whole sequence of query gradients: one block, held
    whole = lambda d: pl.BlockSpec((None, S, d), lambda b, h, *a: (b, 0, h))
    grid, plans, operands, per_q, per_k, q_stat = _grid(
        static, B, H, S, Dn, Dr, Dv, doc, True)
    if not Dr:
        group = static.group
        # grouped: a key block's two gradients one a QUERY head, float32
        per_head = (lambda a: like(a)) if group == 1 else (
            lambda a: jax.ShapeDtypeStruct(
                a.shape[:2] + (a.shape[2] * group,), jnp.float32))
        dqn, dkn, dv = _pallas(
            _backward_kernel_plain, static, grid, plans,
            operands + [per_q(Dv), q_stat, q_stat],
            [whole(Dn), per_k(Dn), per_k(Dv)],
            [like(qn), per_head(kn), per_head(v)],
            [f32(S, Dn), f32(block, Dn), f32(block, Dv)],
            S, inner_only=False)(
                qn, kn, v, doc[:, None, :], doc[:, :, None], do, lse, di)
        if group > 1:       # a key-value head's is its query heads' sum
            dkn, dv = (d.reshape(B, S, H // group, group, -1).sum(3)
                       .reshape(a.shape).astype(a.dtype)
                       for d, a in ((dkn, kn), (dv, v)))
        return dqn, None, dkn, None, dv, None
    dqn, dqp, dkn, dkp, dv = _pallas(
        _backward_kernel, static, grid, plans,
        operands + [per_q(Dv), q_stat, q_stat],
        [whole(Dn), whole(Dr), per_k(Dn), per_k(Dr), per_k(Dv)],
        [like(qn), like(qp), like(kn), like(qp, jnp.float32), like(v)],
        [f32(S, Dn), f32(S, Dr), f32(block, Dn), f32(block, Dr),
         f32(block, Dv)],
        S, inner_only=False)(
            qn, qp, kn, kp, v, doc[:, None, :], doc[:, :, None], do, lse, di)
    # the rotary key is every head's: its gradient is the heads' sum
    dkp = dkp.reshape(B, S, H, Dr).sum(2).astype(kp.dtype)
    return dqn, dqp, dkn, dkp, dv, None


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _attention(qn, qp, kn, kp, v, doc, static):
    return _forward(qn, qp, kn, kp, v, doc, static, False)[0]


def _attention_fwd(qn, qp, kn, kp, v, doc, static):
    o, lse = _forward(qn, qp, kn, kp, v, doc, static, True)
    return o, (qn, qp, kn, kp, v, doc, o, lse)


_attention.defvjp(_attention_fwd, _backward)


def _lanes(x):
    """``x`` with its last dimension zero-padded to whole lanes (no
    score, value or gradient changes; nothing happens at 128)."""
    pad = -x.shape[-1] % LANES
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)]) if pad else x


def attend(q_nope, q_pe, k_nope, k_pe, v, doc, *, scale: float,
           block: int, interpret: Optional[bool] = None):
    """Softmax attention, causal and inside a document: a token attends
    to the earlier tokens (and itself) whose ``doc`` id equals its own.
    ``block`` queries at a time. Returns [B, S, H * v] in the operands'
    dtype. ``interpret``: run the kernels in Pallas's interpreter; left
    out, every backend but a TPU does."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    @telemetry.scope("lm.mla.attend")
    def run(q_nope, q_pe, k_nope, k_pe, v, doc):
        B, S, H, vd = v.shape
        flat = lambda a: _lanes(a).reshape(B, S, -1)
        o = _attention(flat(q_nope), flat(q_pe), flat(k_nope), _lanes(k_pe),
                       flat(v), doc,
                       _Static(float(scale), _block(block, S),
                               bool(interpret)))
        return o.reshape(B, S, H, -1)[..., :vd].reshape(B, S, H * vd)
    return run(q_nope, q_pe, k_nope, k_pe, v, doc)


def _block(block: int, S: int) -> int:
    size = min(block, S)
    if S % size:
        raise ValueError(f"sequence {S} is no multiple of the "
                         f"attention block {size}")
    return size


def _project_out(o, w_o):
    """``o`` [B, S, H * v] times ``w_o`` [D, H * v] transposed."""
    return lax.dot_general(o, w_o.astype(o.dtype), (((2,), (1,)), ((), ())),
                           preferred_element_type=jnp.float32)


output = telemetry.scope("lm.mla.project")(_project_out)


# -- plain multi-head attention: per-head keys, no rotary part ----------------

def project_heads(x, w_q, w_k, w_v, q_norm_w, k_norm_w, heads: int,
                  eps: float, dtype):
    """From the residual ``x`` [B, S, D] float32: ``q``, ``k``, ``v``
    [B, S, H, d] in ``dtype``; ``q`` and ``k`` through an RMS norm over
    all heads' dims (QK-norm) first. No positions: nothing is rotated."""
    @telemetry.scope("lm.attn.project")
    def run(x, w_q, w_k, w_v, q_norm_w, k_norm_w):
        B, S, _ = x.shape
        heads_of = lambda a: a.astype(dtype).reshape(B, S, heads, -1)
        return (heads_of(rms_norm(_dot(x, w_q, dtype), q_norm_w, eps)),
                heads_of(rms_norm(_dot(x, w_k, dtype), k_norm_w, eps)),
                heads_of(_dot(x, w_v, dtype)))
    return run(x, w_q, w_k, w_v, q_norm_w, k_norm_w)


def project_grouped(x, pos, w_q, w_k, w_v, q_norm_w, k_norm_w, heads: int,
                    kv_heads: int, eps: float, theta: float, dtype):
    """From the block's normed input ``x`` [B, S, D] float32 and the
    positions inside the documents: ``q`` [B, S, H, d], ``k``, ``v``
    [B, S, G, d] in ``dtype``. ``q`` and ``k`` go through an RMS norm
    over ONE head's ``d`` dims (one weight vector of ``d`` for every
    head), then a rotary embedding over all ``d`` dims, half-split pairs
    ``(i, i + d / 2)``, frequencies ``theta ** (-2 i / d)``."""
    @telemetry.scope("lm.attn.project")
    def run(x, pos, w_q, w_k, w_v, q_norm_w, k_norm_w):
        B, S, _ = x.shape
        d = w_q.shape[1] // heads
        ang = pos[..., None].astype(jnp.float32) * jnp.asarray(
            Rotary.from_config(rope_dim=d, qk_dim=d, theta=float(theta),
                               scaling=None).inv_freq)
        cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]

        def normed(w, norm_w, n):
            y = rms_norm(_dot(x, w, dtype).reshape(B, S, n, d), norm_w, eps)
            return _rotate(y, cos, sin).astype(dtype)

        return (normed(w_q, q_norm_w, heads), normed(w_k, k_norm_w, kv_heads),
                _dot(x, w_v, dtype).astype(dtype).reshape(B, S, kv_heads, d))
    return run(x, pos, w_q, w_k, w_v, q_norm_w, k_norm_w)


def attend_heads(q, k, v, doc, *, scale: float, block: int,
                 interpret: Optional[bool] = None):
    """:func:`attend` for keys of one depth a head and no rotary part:
    ``q`` [B, S, H, d], ``k`` [B, S, G, d], ``v`` [B, S, G, v] with
    ``G`` = ``H``, or a divisor of it: query head ``h`` reads key-value
    head ``h // (H / G)``; the same kernels without the rotary operands.
    Returns [B, S, H * v]."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    @telemetry.scope("lm.attn.attend")
    def run(q, k, v, doc):
        (B, S, H, _), vd = q.shape, v.shape[-1]
        if H % v.shape[2]:
            raise ValueError(f"{v.shape[2]} key-value heads do not divide "
                             f"{H} query heads")
        flat = lambda a: _lanes(a).reshape(B, S, -1)
        o = _attention(flat(q), None, flat(k), None, flat(v), doc,
                       _Static(float(scale), _block(block, S),
                               bool(interpret), H, H // v.shape[2]))
        return o.reshape(B, S, H, -1)[..., :vd].reshape(B, S, H * vd)
    return run(q, k, v, doc)


output_heads = telemetry.scope("lm.attn.project")(_project_out)
