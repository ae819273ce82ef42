"""Ring attention + Ulysses-style all-to-all sequence parallelism.

Long-context attention where the sequence axis is sharded over mesh
devices (SURVEY.md §6.7's "idiomatic TPU path: shard_map + ppermute
ring over the sequence axis"):

- :func:`ring_attention` — blockwise ring attention: every device holds
  its Q/K/V sequence block; K/V blocks rotate around the ring
  (``lax.ppermute`` over ICI) while each device streams them through an
  online-softmax accumulator (flash-attention style max/sum carries, so
  the full [S, S] score matrix never exists anywhere). Communication
  per step is one K/V block; compute overlaps the next permute under
  XLA's latency-hiding scheduler.
- :func:`ulysses_attention` — the all-to-all alternative: reshard
  [S/p, H] -> [S, H/p] with ``lax.all_to_all``, run plain full-sequence
  attention per head group, reshard back. Cheaper at moderate S with
  enough heads; ring wins when S is the long axis.

Both take GLOBAL arrays ``[batch, seq, heads, dim]`` with the sequence
axis sharded over the given mesh axis, run under ``shard_map``, and
return the same global layout — drop-in for a dense attention call.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from multiverso_tpu import core

NEG_INF = -1e30


def _block_attn(q, k, v, *, scale, causal, q_off, k_off):
    """Scores of one (q-block, k-block) pair + streaming-softmax stats.

    q/k/v [B, s, H, D] -> (o [B, s, H, D] unnormalized, m [B, s, H] row
    max, l [B, s, H] row expsum). q_off/k_off are the blocks' global
    sequence offsets (traced scalars) for causal masking.
    """
    s = jnp.einsum("bqhd,bkhd->bqhk", q, k) * scale     # [B, sq, H, sk]
    if causal:
        qi = q_off + jnp.arange(q.shape[1])[:, None, None]
        ki = k_off + jnp.arange(k.shape[1])[None, None, :]
        s = jnp.where(qi >= ki, s, NEG_INF)
    m = s.max(axis=-1)                                  # [B, sq, H]
    p = jnp.exp(s - m[..., None])
    # fully masked rows: exp(NEG_INF - NEG_INF) = 1 -> zero them
    p = jnp.where(m[..., None] <= NEG_INF / 2, 0.0, p)
    l = p.sum(axis=-1)
    o = jnp.einsum("bqhk,bkhd->bqhd", p, v)
    return o, m, l


def _merge(o1, m1, l1, o2, m2, l2):
    """Combine two streaming-softmax partials (associative)."""
    m = jnp.maximum(m1, m2)
    a1 = jnp.exp(jnp.maximum(m1 - m, NEG_INF))
    a2 = jnp.exp(jnp.maximum(m2 - m, NEG_INF))
    o = o1 * a1[..., None] + o2 * a2[..., None]
    l = l1 * a1 + l2 * a2
    return o, m, l


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                   mesh: Optional[Mesh] = None,
                   axis: str = core.DATA_AXIS,
                   causal: bool = False) -> jax.Array:
    """Sequence-parallel attention over a device ring.

    Args:
      q, k, v: [batch, seq, heads, dim]; ``seq`` must divide evenly over
        the mesh ``axis``.
      mesh: defaults to the runtime mesh.
      axis: mesh axis carrying the sequence shards (the ring).
      causal: standard causal masking in GLOBAL sequence positions.

    Returns [batch, seq, heads, dim], sharded like q.
    """
    mesh = mesh if mesh is not None else core.mesh()
    n = mesh.shape[axis]
    if q.shape[1] % n:
        raise ValueError(f"seq {q.shape[1]} not divisible by mesh axis "
                         f"{axis} size {n}")
    scale = 1.0 / np.sqrt(q.shape[-1])
    s_blk = q.shape[1] // n

    def local(q, k, v):
        # q/k/v [B, s_blk, H, D] — this device's sequence block
        me = lax.axis_index(axis)
        q_off = me * s_blk

        # carry: rotating k/v block and the streaming accumulator
        # (o, m, l) per q row
        def attend(i, kb, vb, acc):
            owner = (me + i) % n         # whose block we hold at step i
            o, m, l = _block_attn(q, kb, vb, scale=scale, causal=causal,
                                  q_off=q_off, k_off=owner * s_blk)
            return _merge(*acc, o, m, l)

        def body(i, carry):
            kb, vb, *acc = carry
            acc = attend(i, kb, vb, acc)
            # pass our current block to the left neighbor (ring shift)
            perm = [(j, (j - 1) % n) for j in range(n)]
            kb = lax.ppermute(kb, axis, perm)
            vb = lax.ppermute(vb, axis, perm)
            return (kb, vb, *acc)

        B, s, H, D = q.shape
        init = (k, v,
                jnp.zeros((B, s, H, D), jnp.float32),
                jnp.full((B, s, H), NEG_INF, jnp.float32),
                jnp.zeros((B, s, H), jnp.float32))
        # n-1 rotated steps; the last block attends WITHOUT the final
        # rotation (its result would be discarded — dead ICI traffic)
        kb, vb, *acc = lax.fori_loop(0, n - 1, body, init)
        o, m, l = attend(n - 1, kb, vb, acc)
        return (o / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)

    spec = P(None, axis, None, None)
    from jax import shard_map
    from multiverso_tpu.telemetry.profiling import cached_profiled_jit
    # keyed on everything `local` closes over (+ mesh for shard_map):
    # same ring program → same profiled wrapper → one compile, one
    # profile.* series (see cached_profiled_jit)
    fn = cached_profiled_jit(
        ("ring_attention", mesh, axis, causal, n, s_blk, scale),
        "parallel.ring_attention",
        lambda: shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                          out_specs=spec, check_vma=False))
    return fn(q, k, v)


def ulysses_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      mesh: Optional[Mesh] = None,
                      axis: str = core.DATA_AXIS,
                      causal: bool = False) -> jax.Array:
    """All-to-all sequence parallelism (DeepSpeed-Ulysses shape): trade
    the sequence shard for a head shard, attend over the FULL sequence
    per local head group, trade back. ``heads`` must divide over the
    mesh axis."""
    mesh = mesh if mesh is not None else core.mesh()
    n = mesh.shape[axis]
    if q.shape[1] % n or q.shape[2] % n:
        raise ValueError(f"seq {q.shape[1]} and heads {q.shape[2]} must "
                         f"divide mesh axis {axis} size {n}")
    scale = 1.0 / np.sqrt(q.shape[-1])

    def local(q, k, v):
        # [B, s_blk, H, D] -> all_to_all -> [B, S, H/n, D]
        def fwd(x):
            return lax.all_to_all(x, axis, split_axis=2, concat_axis=1,
                                  tiled=True)

        def bwd(x):
            return lax.all_to_all(x, axis, split_axis=1, concat_axis=2,
                                  tiled=True)

        qf, kf, vf = fwd(q), fwd(k), fwd(v)
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, kf) * scale
        if causal:
            qi = jnp.arange(s.shape[2])[:, None]
            ki = jnp.arange(s.shape[3])[None, :]
            s = jnp.where(qi >= ki, s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", p, vf).astype(q.dtype)
        return bwd(o)

    spec = P(None, axis, None, None)
    from jax import shard_map
    from multiverso_tpu.telemetry.profiling import cached_profiled_jit
    fn = cached_profiled_jit(
        ("ulysses_attention", mesh, axis, causal, n, scale),
        "parallel.ulysses_attention",
        lambda: shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                          out_specs=spec, check_vma=False))
    return fn(q, k, v)
