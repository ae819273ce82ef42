"""Fused device-side tensor summaries: the numerics-audit kernels.

One dispatch per audited tensor computes a PACKED stats vector —

    f32[6] = (sum_sq, abs_max, nan_count, inf_count, zero_count, count)

— so the training-health layer (`telemetry/health.py`) reads ONE tiny
replicated buffer per audited op instead of five, and the hot path pays
one async XLA dispatch (the D2H readback happens on the health poller's
worker thread, never here). Host-side :func:`unpack` derives the
operator-facing stats: ``l2`` (sqrt of the finite sum of squares),
``absmax`` (over finite values), ``nan_count`` / ``inf_count``,
``zero_frac``.

Engine shapes, mirroring the table-kernel engine's flat/sharded split:

- **flat** (single-device or GSPMD meshes): one jitted reduction with a
  replicated output sharding — XLA inserts whatever collectives the
  operand's sharding needs.
- **sharded** (multi-shard model axis, operands laid out
  ``P("model", ...)`` like table storage / lane-sliced KV batches): the
  reduction runs per-shard under ``shard_map`` and combines with
  ``psum`` (sums/counts) + ``pmax`` (abs-max), so a sharded table's
  stats never materialize the operand on one device.

Counts ride the f32 vector (one buffer, one transfer); beyond ~2^24
elements the zero/total counts lose exact integer precision — fine for
the ratios and the ``> 0`` predicates health rules evaluate, and the
NaN/Inf counts of a HEALTHY tensor are exactly 0.

Both paths are trace-safe: :func:`stats_vector` can be called inside a
fused superstep body, and the jitted wrappers dispatch from host code.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map

#: order of the packed stats vector's lanes
PACKED_FIELDS = ("sum_sq", "abs_max", "nan_count", "inf_count",
                 "zero_count", "count")
#: operator-facing stat names :func:`unpack` derives
STAT_NAMES = ("l2", "absmax", "nan_count", "inf_count", "zero_frac")


def stats_vector(x: jax.Array) -> jax.Array:
    """In-trace packed summary of one tensor → ``f32[6]`` (see module
    docstring for the lane order). Non-finite values are EXCLUDED from
    the sum-of-squares and abs-max (a single Inf would otherwise
    saturate both and mask the drift signal the EWMA windows track) and
    counted in their own lanes instead."""
    xf = x.astype(jnp.float32)
    finite = jnp.isfinite(xf)
    clean = jnp.where(finite, xf, 0.0)
    return jnp.stack([
        jnp.sum(clean * clean),
        jnp.max(jnp.abs(clean)) if x.size else jnp.float32(0.0),
        jnp.sum(jnp.isnan(xf)).astype(jnp.float32),
        jnp.sum(jnp.isinf(xf)).astype(jnp.float32),
        jnp.sum(xf == 0).astype(jnp.float32),
        jnp.float32(x.size),
    ])


# jitted summary fns, keyed (mesh, axis, ndim, sharded) — ndim matters
# only to the sharded variant's in_specs; the flat fn is rank-generic
# but keyed the same way for one cache
_CACHE: Dict[Tuple, object] = {}


def _flat_summary(mesh: Mesh):
    key = (mesh, None, 0, False)
    fn = _CACHE.get(key)
    if fn is None:
        replicated = NamedSharding(mesh, P())
        fn = jax.jit(stats_vector, out_shardings=replicated)
        _CACHE[key] = fn
    return fn


def _sharded_summary(mesh: Mesh, axis: str, ndim: int):
    """Per-shard reduction under shard_map, combined with psum/pmax —
    the sharded-mesh engine (operand sharded ``P(axis, None, ...)``)."""
    key = (mesh, axis, ndim, True)
    fn = _CACHE.get(key)
    if fn is None:
        def body(xs):
            v = stats_vector(xs)
            sums = jax.lax.psum(v, axis)
            amax = jax.lax.pmax(v[1], axis)
            # count/zero/nan/inf/sumsq add across shards; abs_max maxes
            return sums.at[1].set(amax)

        mapped = shard_map(
            body, mesh=mesh,
            in_specs=P(axis, *([None] * (ndim - 1))),
            out_specs=P(), check_vma=False)
        fn = jax.jit(mapped,
                     out_shardings=NamedSharding(mesh, P()))
        _CACHE[key] = fn
    return fn


def _is_model_sharded(x, mesh: Mesh, axis: str) -> bool:
    """True when ``x`` is a device array committed to a multi-shard
    ``P(axis, ...)`` layout on ``mesh`` — the operands the sharded
    engine is built for (table storage, lane-sliced KV batches)."""
    if mesh.shape.get(axis, 1) <= 1:
        return False
    sharding = getattr(x, "sharding", None)
    spec = getattr(sharding, "spec", None)
    if spec is None or len(spec) == 0:
        return False
    lead = spec[0]
    if isinstance(lead, tuple):
        return axis in lead
    return lead == axis


def summarize(x, *, mesh: Optional[Mesh] = None,
              axis: str = "model") -> jax.Array:
    """Dispatch one packed-stats reduction over ``x`` (device f32[6]
    future — async, nothing blocks here). Model-axis-sharded operands
    route through the shard_map+psum engine; everything else through
    the flat GSPMD jit."""
    if mesh is None:
        from multiverso_tpu import core
        mesh = core.mesh()
    if _is_model_sharded(x, mesh, axis):
        return _sharded_summary(mesh, axis, np.ndim(x))(x)
    return _flat_summary(mesh)(x)


def unpack(vec) -> Dict[str, float]:
    """Packed ``f32[6]`` (host or device) → the operator-facing stats
    dict (``l2``, ``absmax``, ``nan_count``, ``inf_count``,
    ``zero_frac`` + the raw ``count``). Blocks on D2H when handed a
    device future — call it on a worker thread."""
    v = np.asarray(vec, dtype=np.float64)
    if v.shape != (len(PACKED_FIELDS),):
        raise ValueError(f"packed stats vector has shape {v.shape}, "
                         f"want ({len(PACKED_FIELDS)},)")
    count = float(v[5])
    return {
        "l2": float(np.sqrt(max(v[0], 0.0))),
        "absmax": float(v[1]),
        "nan_count": float(v[2]),
        "inf_count": float(v[3]),
        "zero_frac": float(v[4] / count) if count else 0.0,
        "count": count,
    }


def numpy_reference(x: np.ndarray) -> Dict[str, float]:
    """Pure-numpy oracle for the parity tests: what :func:`summarize` +
    :func:`unpack` must produce for ``x``."""
    xf = np.asarray(x, dtype=np.float32)
    finite = np.isfinite(xf)
    clean = np.where(finite, xf, 0.0).astype(np.float64)
    count = float(xf.size)
    return {
        "l2": float(np.sqrt(np.sum(np.square(clean), dtype=np.float64))),
        "absmax": float(np.max(np.abs(clean)) if xf.size else 0.0),
        "nan_count": float(np.isnan(xf).sum()),
        "inf_count": float(np.isinf(xf).sum()),
        "zero_frac": float((xf == 0).sum() / count) if count else 0.0,
        "count": count,
    }


def reset_cache() -> None:
    """Drop the jitted-summary cache (tests that rebuild meshes)."""
    _CACHE.clear()
