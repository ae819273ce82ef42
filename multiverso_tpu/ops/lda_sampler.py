"""Fused collapsed-Gibbs posterior+sampler Pallas kernels for LDA: the
doc-blocked sampler of apps/lightlda.py (``sampler="tiled"``), resident
(:func:`gibbs_sample_docblock`) and streamed
(:func:`gibbs_sample_docblock_build`).

Why a kernel: the plain-XLA posterior+sample pipeline materializes
several [B, K]-sized HBM intermediates beyond the count-row gathers
(float posterior, CDF, one-hots, layout copies). These kernels keep
everything after the word-row gather in VMEM: per block of TB tokens of
WHOLE documents they read (or build) the block's doc-topic counts, form
the collapsed posterior over the [C, 128] topic tile, draw by two-level
inverse-CDF (chunk totals via a triangular matmul — cumsum has no Pallas
TPU lowering — then within-chunk lanes), move the block's doc counts and
accumulate the topic-summary delta across the sequential grid. Their
share of a sweep and of their roofline: PERF.md §5
(``lda_sampler_roofline``).

Semantics (the same approximation stack as the reference's own
distributed sampler — AD-LDA, see apps/lightlda.py):

- own-token removal is in-register (iota==z compare-subtract) on the
  numerator counts; the summary denominator keeps the own count (a +1 in
  a ~T/K-sized denominator),
- other tokens in the batch are batch-stale (counts snapshotted at the
  gather).

Counts must be tile-aligned: [*, C, 128] with K = C*128, so one logical
row is one (8,128) int32 tile (4 KB payload per random row access).

Reference: LightLDA's `LightDocSampler` role (SURVEY.md §3.6) — the O(1)
MH machinery is replaced by an exact O(K) vectorized posterior (module
docstring of apps/lightlda.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128

# A [512, C, 128] block of int32 count rows at K=1024 is 2 MiB; two
# double-buffered operands of them plus the f32 posterior temporaries
# need ~17 MiB — just past Mosaic's 16 MiB default scoped-VMEM limit
# (the bf16/int16 production operands fit under it). v5e has 128 MiB of
# VMEM per core; 32 MiB covers every dtype the samplers accept at the
# block sizes block_tokens produces.
_COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=32 * 2 ** 20)


def _lane_iotas(tb: int, c: int):
    kc = jax.lax.broadcasted_iota(jnp.int32, (tb, c, LANES), 1)
    kl = jax.lax.broadcasted_iota(jnp.int32, (tb, c, LANES), 2)
    return kc, kc * LANES + kl


def _posterior(A, W, sinv, soh_f, alpha: float, beta: float):
    """Collapsed posterior over the [C, 128] topic tile with in-register
    own-token removal. A/W already f32 (int counts < 2^24: exact).
    1/S is precomputed outside (kills a [TB,C,128] divide on the VPU)."""
    return jnp.maximum((A - soh_f + alpha) * (W - soh_f + beta),
                       0.0) * sinv[None]


def _two_level_draw(probs, kc, u1, u2, c: int):
    """Two-level inverse-CDF draw: chunk totals then within-chunk lanes.
    cumsum has no Pallas TPU lowering -- triangular matmuls (tiny on the
    MXU) instead. Returns z [TB, 1] int32. Per-token values stay
    [TB, 1] columns throughout: Mosaic broadcasts a column into the
    token's [C, 128] tile, but refuses to re-lay a [TB, C] mask out as
    [TB, C, 1]."""
    cs = probs.sum(-1)                             # [TB, C]
    ci = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    cj = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    tric = (ci <= cj).astype(jnp.float32)          # [C, C]
    ccdf = jnp.dot(cs, tric, preferred_element_type=jnp.float32)
    t1 = u1 * ccdf[:, -1:]
    sel_c = jnp.minimum(
        (ccdf < t1).astype(jnp.int32).sum(1, keepdims=True), c - 1)
    sub = jnp.where(kc == sel_c[:, :, None], probs, 0.0).sum(1)
    li = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0)
    lj = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 1)
    tril = (li <= lj).astype(jnp.float32)
    scdf = jnp.dot(sub, tril, preferred_element_type=jnp.float32)
    t2 = u2 * scdf[:, -1:]
    lane = jnp.minimum(
        (scdf < t2).astype(jnp.int32).sum(1, keepdims=True), LANES - 1)
    return sel_c * LANES + lane


def _docblock_kernel(ndk_ref, W_ref, sinv_ref, zi_ref, drel_ref, msk_ref,
                     u1_ref, u2_ref, ndk_out_ref, znew_ref, nkd_ref, *,
                     alpha: float, beta: float, tb: int, c: int,
                     maxd: int):
    """One grid block = TB tokens of WHOLE documents owning an exclusive
    [MAXD, C, 128] slice of the blocked doc-topic counts: A rows
    materialize by a one-hot matmul against the VMEM-resident block and
    the block's count moves apply in VMEM (E^T @ one-hot diff), so the
    doc side never touches XLA gather/scatter at all."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        nkd_ref[:] = jnp.zeros_like(nkd_ref)

    k = c * LANES
    ndk = ndk_ref[0].reshape(maxd, k).astype(jnp.float32)
    W = W_ref[:].astype(jnp.float32)               # [TB, C, 128]
    zi = zi_ref[:]                                 # [TB, 1]
    drel = drel_ref[:]                             # [TB, 1]
    one = msk_ref[:]                               # [TB, 1]
    rows = jax.lax.broadcasted_iota(jnp.int32, (tb, maxd), 1)
    E = (rows == drel).astype(jnp.float32)         # [TB, MAXD]
    A = jnp.dot(E, ndk, preferred_element_type=jnp.float32)
    A3 = A.reshape(tb, c, LANES)
    kc, kk = _lane_iotas(tb, c)
    self_oh = ((kk == zi[:, :, None]) & (one[:, :, None] > 0))
    sohf = self_oh.astype(jnp.float32)
    probs = _posterior(A3, W, sinv_ref[:], sohf, alpha, beta)
    znew = jnp.where(one > 0,
                     _two_level_draw(probs, kc, u1_ref[:], u2_ref[:], c),
                     zi)
    znew_ref[:] = znew
    new_oh = ((kk == znew[:, :, None]) & (one[:, :, None] > 0))
    ohdiff = new_oh.astype(jnp.float32) - sohf     # [TB, C, 128]
    nkd_ref[:] += ohdiff.sum(0).astype(jnp.int32)
    delta = jnp.dot(E.T, ohdiff.reshape(tb, k),
                    preferred_element_type=jnp.float32)
    ndk_out_ref[0] = (ndk + delta).astype(ndk_out_ref.dtype).reshape(
        maxd, c, LANES)


def _docblock_build_kernel(W_ref, sinv_ref, zi_ref, drel_ref, msk_ref,
                           u1_ref, u2_ref, znew_ref, nkd_ref, *,
                           alpha: float, beta: float, tb: int, c: int,
                           maxd: int):
    """Count-building variant for the OUT-OF-CORE mode: the block's doc
    counts are not read from HBM but BUILT in VMEM from (zi, drel) by one
    MXU matmul (E_masked^T @ onehot(zi)) — valid because whole docs live
    in one block and each block is visited exactly once per sweep, so
    counts(z) IS the block's doc-count state. No ndk input, no ndk
    output: z is the only streamed sampler state."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        nkd_ref[:] = jnp.zeros_like(nkd_ref)

    k = c * LANES
    W = W_ref[:].astype(jnp.float32)               # [TB, C, 128]
    zi = zi_ref[:]                                 # [TB, 1]
    drel = drel_ref[:]                             # [TB, 1]
    one = msk_ref[:]                               # [TB, 1]
    kc, kk = _lane_iotas(tb, c)
    self_oh = ((kk == zi[:, :, None]) & (one[:, :, None] > 0))
    sohf = self_oh.astype(jnp.float32)
    rows = jax.lax.broadcasted_iota(jnp.int32, (tb, maxd), 1)
    Em = ((rows == drel) & (one > 0)).astype(jnp.float32)  # [TB, MAXD]
    ndk = jnp.dot(Em.T, sohf.reshape(tb, k),
                  preferred_element_type=jnp.float32)      # [MAXD, K]
    A = jnp.dot(Em, ndk, preferred_element_type=jnp.float32)
    A3 = A.reshape(tb, c, LANES)
    probs = _posterior(A3, W, sinv_ref[:], sohf, alpha, beta)
    znew = jnp.where(one > 0,
                     _two_level_draw(probs, kc, u1_ref[:], u2_ref[:], c),
                     zi)
    znew_ref[:] = znew
    new_oh = ((kk == znew[:, :, None]) & (one[:, :, None] > 0))
    nkd_ref[:] += (new_oh.astype(jnp.int32)
                   - self_oh.astype(jnp.int32)).sum(0)


@functools.partial(jax.jit, static_argnames=("alpha", "beta", "tb",
                                             "maxd", "interpret"))
def gibbs_sample_docblock_build(W3: jax.Array, sinv: jax.Array,
                                zi: jax.Array, drel: jax.Array,
                                msk: jax.Array, u1: jax.Array,
                                u2: jax.Array, *, alpha: float,
                                beta: float, tb: int, maxd: int,
                                interpret: bool = False):
    """Doc-blocked sampler that BUILDS each block's doc counts in VMEM
    instead of reading/writing a blocked count array (see
    :func:`_docblock_build_kernel`). Same draw semantics as
    :func:`gibbs_sample_docblock` — bit-identical znew for real tokens.

    Returns (znew [NB*TB], nk_delta [C, 128]).
    """
    b, c, lanes = W3.shape
    if lanes != LANES:
        raise ValueError(f"last dim must be {LANES}, got {lanes}")
    if b % tb:
        raise ValueError(f"token count {b} not divisible by tb {tb}")
    nb = b // tb
    kern = functools.partial(_docblock_build_kernel, alpha=float(alpha),
                             beta=float(beta), tb=tb, c=c, maxd=maxd)
    tok_spec = pl.BlockSpec((tb, 1), lambda i: (i, 0),
                            memory_space=pltpu.VMEM)
    grid_spec = pl.GridSpec(
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((tb, c, LANES), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((c, LANES), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            tok_spec, tok_spec, tok_spec, tok_spec, tok_spec,
        ],
        out_specs=[
            tok_spec,
            pl.BlockSpec((c, LANES), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
    )
    znew2, nkd = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, 1), jnp.int32),
                   jax.ShapeDtypeStruct((c, LANES), jnp.int32)],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(W3, sinv, zi[:, None], drel[:, None], msk[:, None],
      u1[:, None], u2[:, None])
    return znew2[:, 0], nkd


@functools.partial(jax.jit, static_argnames=("alpha", "beta", "tb",
                                             "interpret"))
def gibbs_sample_docblock(ndk_blk: jax.Array, W3: jax.Array,
                          sinv: jax.Array, zi: jax.Array,
                          drel: jax.Array, msk: jax.Array, u1: jax.Array,
                          u2: jax.Array, *, alpha: float, beta: float,
                          tb: int, interpret: bool = False):
    """Doc-blocked fused sampler + doc-count update.

    Args:
      ndk_blk: [NB, MAXD, C, 128] int16/int32 — blocked doc-topic counts;
        block b EXCLUSIVELY owns its MAXD rows (whole docs per block).
      W3:   [NB*TB, C, 128] — gathered (stale) word-count rows.
      sinv: [C, 128] f32 — 1 / (summary + V*beta).
      zi, drel, msk, u1, u2: [NB*TB] — current topics, doc row within
        block, token mask, uniforms.
      tb: tokens per block (static; NB*TB must equal len(zi)).

    Returns (ndk_blk', znew [NB*TB], nk_delta [C, 128]); ndk_blk is
    donated/aliased in place.
    """
    nb, maxd, c, lanes = ndk_blk.shape
    if lanes != LANES:
        raise ValueError(f"last dim must be {LANES}, got {lanes}")
    b = zi.shape[0]
    if b != nb * tb:
        raise ValueError(f"token count {b} != blocks {nb} * tb {tb}")
    kern = functools.partial(_docblock_kernel, alpha=float(alpha),
                             beta=float(beta), tb=tb, c=c, maxd=maxd)
    tok_spec = pl.BlockSpec((tb, 1), lambda i: (i, 0),
                            memory_space=pltpu.VMEM)
    grid_spec = pl.GridSpec(
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((1, maxd, c, LANES), lambda i: (i, 0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((tb, c, LANES), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((c, LANES), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            tok_spec, tok_spec, tok_spec, tok_spec, tok_spec,
        ],
        out_specs=[
            pl.BlockSpec((1, maxd, c, LANES), lambda i: (i, 0, 0, 0),
                         memory_space=pltpu.VMEM),
            tok_spec,
            pl.BlockSpec((c, LANES), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
    )
    ndk_out, znew2, nkd = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(ndk_blk.shape, ndk_blk.dtype),
                   jax.ShapeDtypeStruct((b, 1), jnp.int32),
                   jax.ShapeDtypeStruct((c, LANES), jnp.int32)],
        input_output_aliases={0: 0},
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(ndk_blk, W3, sinv, zi[:, None], drel[:, None], msk[:, None],
      u1[:, None], u2[:, None])
    return ndk_out, znew2[:, 0], nkd
