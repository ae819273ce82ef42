"""Pallas TPU kernel engine for the server-side table hot paths, plus
the engine selection layer (``MVTPU_KERNELS``).

The XLA table paths materialize full bucket rows via ``jnp.take``, pay
a batch-wide stable ``argsort`` per KV dispatch, and round-trip whole
rows through HBM on the COO path. The kernels here keep the touched
rows in VMEM:

- **KV probe+update** (:func:`build_kv_probe_update`): a probe kernel
  (slot claim per lane + overflow count) and a commit kernel (updater
  apply + masked scatter). The batch is host-sorted by bucket
  (``KVTable.prepare_add``), so each bucket's lanes are CONSECUTIVE
  steps of the sequential TPU grid and the bucket's slot rows stay
  resident in VMEM across them; the per-bucket empty-slot rank is a
  run-local claimed mask — an in-kernel per-bucket scan replacing the
  XLA path's global ``argsort``. The overflow count between the two
  kernels preserves the all-or-nothing contract: ANY overflow voids the
  whole batch on device, bit-identical to the XLA path.
- **KV lookup** (:func:`build_kv_lookup`): gather bucket rows by
  scalar-prefetch index map, match + pick in VMEM.
- **Row gather / row scatter-add / COO scatter-add**
  (:func:`build_row_gather`, :func:`build_row_scatter_add`,
  :func:`build_coo_scatter_add`): matrix/sparse-table row paths. Scatter
  batches are host-sorted by row, so each touched block is fetched once,
  segment-summed in VMEM across its run of grid steps, and written back
  to HBM exactly once (duplicate-safe without XLA's sorted-scatter
  machinery).

Correctness-critical grid semantics the scatter kernels rely on
(documented Pallas behavior on TPU): consecutive grid steps whose index
maps return the SAME block index keep the block resident (no
flush/refetch between them), and with ``input_output_aliases`` the
unvisited rows of the aliased output keep their input content. Input
blocks always read PRE-batch data (each block's input is fetched once,
at its run start, before any flush of that block).

Selection layer (:func:`select_kernel`): every kernel registers as an
(xla, pallas) pair behind ``MVTPU_KERNELS``, decided ONCE per table
from the platform of the table's mesh:

- ``auto`` (default): Pallas on an accelerator mesh, XLA on a CPU mesh
  (counted in ``kernels.fallbacks{reason=cpu}``).
- ``pallas``: force Pallas; on a CPU mesh the kernels run under
  ``interpret=True`` — how tier-1 exercises them.
- ``xla``: force the XLA implementations.

There is no fallback at run time: a kernel that fails to lower, compile
or run on its platform raises to the caller. The only build-time
decision besides the mode is :class:`UnsupportedShardingLayout` (the
per-shard lane slicer cannot express the table's layout), counted as
``reason=sharded_unsupported_layout``. ``tests/test_table_kernels.py``
cross-lowers every kernel ``auto`` can select for ``"tpu"`` on the CPU
rig.

Sharded tables (mesh.size > 1) run the SAME kernels per shard inside
``shard_map``: a bare ``pallas_call`` has no SPMD partitioning rule, so
each model-axis shard runs its own VMEM-resident grid over only its
local buckets/rows. Host prep sorts by shard-then-bucket/row and hands
the engine per-shard lane slices (``tables/hashing.shard_lane_slices``
— dense, contiguous, pow2-padded lane rows with non-local lanes as
masked padding), so there are NO cross-shard collectives inside any
kernel. A table that registers no sharded Pallas form keeps XLA
(``reason=sharded``). Selections are observable: ``kernels.selected``
gauges, the ``kernels.fallbacks`` counter, and the per-engine
``profile.calls{fn=...}`` / ``profile.calls{fn=....pallas}`` dispatch
counts (every engine stays under ``profiled_jit``).

Functional forms (:func:`gather_rows`, :func:`row_scatter_add`,
:func:`coo_scatter_add`) are traceable inside an outer jit — fused
supersteps pick up the same kernels by calling them from their bodies
(re-exported by ``tables/superstep.py``). Under a
:func:`kernel_mesh_scope` (installed by ``FusedSuperstep`` around its
dispatch) they shard too — masked-lane ``shard_map`` wrappers rather
than lane slices, because per-shard lane counts are dynamic inside a
trace.

This module imports NO table classes (it sits below the table layer);
shared hashing helpers live in ``tables/hashing.py``.
"""

from __future__ import annotations

import contextlib
import contextvars
import functools
import os
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from multiverso_tpu import core
from multiverso_tpu.telemetry import metrics as _metrics
from multiverso_tpu.telemetry import trace as _trace
from multiverso_tpu.updaters import AddOption
from multiverso_tpu.utils import log

LANES = 128

_MODES = ("auto", "xla", "pallas")
_WARNED: set = set()


class UnsupportedShardingLayout(Exception):
    """A sharded Pallas build met a layout the per-shard lane slicer
    can't express (e.g. a leading dim not divisible by the model-axis
    shard count). ``select_kernel`` counts it as
    ``reason=sharded_unsupported_layout`` and keeps XLA."""


def kernel_mode() -> str:
    """The engine knob, re-read per selection (tests flip it):
    ``MVTPU_KERNELS=auto|xla|pallas`` (default ``auto``)."""
    mode = os.environ.get("MVTPU_KERNELS", "auto").strip().lower() or "auto"
    if mode not in _MODES:
        if ("mode", mode) not in _WARNED:
            _WARNED.add(("mode", mode))
            log.warn("ignoring unknown MVTPU_KERNELS=%r (valid: %s); "
                     "using 'auto'", mode, "|".join(_MODES))
        mode = "auto"
    return mode


def interpret_mode(mesh: Any = None) -> bool:
    """Pallas interpreter mode: on for a CPU mesh (tests), off on a
    real accelerator. Keyed on the MESH's platform (``core.platform``),
    the same test ``select_kernel`` and LightLDA apply."""
    return core.platform(mesh) == "cpu"


def _mesh_axes(mesh: Any) -> tuple:
    """((axis, size), ...) of a mesh — the log and latch key
    ingredient."""
    return tuple(dict(mesh.shape).items()) if mesh is not None else ()


def _note_fallback(name: str, reason: str,
                   exc: Optional[BaseException] = None,
                   mesh: Any = None) -> None:
    """Count (always) + log (once per (kernel, reason, mesh shape)) a
    selection that kept XLA where Pallas was asked for or implied. The
    counter is never latched."""
    _metrics.registry().counter("kernels.fallbacks", kernel=name,
                                reason=reason).inc()
    axes = _mesh_axes(mesh)
    key = ("fallback", name, reason, axes)
    if key not in _WARNED:
        _WARNED.add(key)
        mesh_s = ",".join(f"{a}={s}" for a, s in axes) or "unmeshed"
        log.warn("kernel engine: %s falling back to XLA (reason=%s, "
                 "mesh=%s%s); further %s fallbacks counted in "
                 "kernels.fallbacks without this log line", name, reason,
                 mesh_s, f": {exc!r}" if exc is not None else "", reason)


class KernelEngine:
    """One selected kernel. Holders treat it exactly like the jitted
    callable they held before; ``.engine`` ("xla"|"pallas") is the
    selection evidence tests and the micro-bench read. The selection is
    final: a failing engine raises, it is never swapped at run time."""

    def __init__(self, name: str, fn: Callable, engine: str = "xla",
                 layout: str = "flat") -> None:
        self.name = name
        self._fn = fn
        self.engine = engine
        #: operand layout the engine expects: "flat" (whole-batch
        #: arrays) or "sharded" (per-shard (shards, L, ...) lane slices
        #: from tables/hashing.shard_lane_slices)
        self.layout = layout
        # the /statusz kernel table: ONE live engine per kernel
        _metrics.registry().gauge(
            "kernels.selected", kernel=name, engine=engine,
            layout=layout).set(1)

    def __call__(self, *args: Any, **kwargs: Any) -> Any:
        with _trace.span(f"kernel.{self.name}", engine=self.engine,
                         layout=self.layout):
            return self._fn(*args, **kwargs)

    # AOT passthrough, matching _ProfiledJit's debugging surface
    def lower(self, *args: Any, **kwargs: Any):
        return self._fn.lower(*args, **kwargs)


def select_kernel(name: str, *, xla: Callable,
                  pallas: Optional[Callable[[], Callable]] = None,
                  pallas_sharded: Optional[Callable[[], Callable]] = None,
                  mesh: Any = None) -> KernelEngine:
    """Register one hot-path kernel behind the engine knob.

    ``xla`` is the already-built (profiled_jit) XLA implementation;
    ``pallas`` is a zero-arg FACTORY for the flat Pallas
    implementation, built only when selected (tables on the default CPU
    path pay nothing). On a sharded ``mesh`` (size > 1) selection goes
    to ``pallas_sharded`` instead — the shard_map-wrapped per-shard
    engine whose operands are the lane slices of
    ``tables/hashing.shard_lane_slices``. A sharded mesh with no
    ``pallas_sharded`` keeps XLA (``reason=sharded``); a
    ``pallas_sharded`` build that raises
    :class:`UnsupportedShardingLayout` keeps XLA as
    ``reason=sharded_unsupported_layout``. Any other build failure
    raises.
    """
    mode = kernel_mode()
    sharded = mesh is not None and getattr(mesh, "size", 1) > 1
    if mode == "xla" or (pallas is None and pallas_sharded is None):
        return KernelEngine(name, xla)
    if mode == "auto" and core.platform(mesh) == "cpu":
        _note_fallback(name, "cpu", mesh=mesh)
        return KernelEngine(name, xla)
    if not sharded:
        return KernelEngine(name, pallas(), "pallas")
    if pallas_sharded is None:
        _note_fallback(name, "sharded", mesh=mesh)
        return KernelEngine(name, xla)
    try:
        built = pallas_sharded()
    except UnsupportedShardingLayout as e:
        _note_fallback(name, "sharded_unsupported_layout", e, mesh=mesh)
        return KernelEngine(name, xla)
    return KernelEngine(name, built, "pallas", layout="sharded")


# -- block geometry shared by every kernel ---------------------------------
#
# Mosaic accepts a block only when its last two dims are multiples of
# (8, 128) or span the array. A flat ``(R, C)`` table can therefore not
# be addressed one ``(1, C)`` row at a time: the table-side block is the
# ALIGNED 8-ROW GROUP holding the row and the kernel picks the row inside
# it (a dynamic sublane index). Tiled ``(R, C/128, 128)`` rows and
# ``(B, S, ·)`` bucket rows already span their last two dims. Per-lane
# batch operands ride as ``(n, 1, C)`` with ``(1, 1, C)`` blocks; per-lane
# SCALARS (ids, columns, values, key words, write gates) ride SMEM as
# scalar-prefetch operands, which is also what lets the kernels branch
# on them.

SUBLANES = 8
# Lanes per pallas_call. Per-lane scalars live in SMEM (1 MiB on v5e;
# at most five int32 arrays per kernel here → 320 KiB); longer batches
# run as consecutive calls over static chunks (sorted runs that straddle
# a chunk edge re-read what the previous call wrote — same result).
LANE_CAP = 16384


def _lane_chunks(n: int):
    return [(s, min(LANE_CAP, n - s)) for s in range(0, n, LANE_CAP)]


def _table_block(tiles: int, num_cols: int) -> pl.BlockSpec:
    """Table-side block holding row ``ids[i]`` (``ids`` = the FIRST
    scalar-prefetch operand): the row itself in tiled storage, its
    aligned 8-row group in flat ``(R, C)`` storage."""
    if tiles:
        return pl.BlockSpec((1, tiles, LANES),
                            lambda i, ids, *_: (ids[i], 0, 0),
                            memory_space=pltpu.VMEM)
    return pl.BlockSpec((SUBLANES, num_cols),
                        lambda i, ids, *_: (ids[i] // SUBLANES, 0),
                        memory_space=pltpu.VMEM)


def _row_at(rid, tiles: int):
    """Index of row ``rid`` inside its resident :func:`_table_block`."""
    if tiles:
        return ...
    return (pl.ds(rid % SUBLANES, 1), slice(None))


def _new_block(ids_ref, i, tiles: int):
    """True on the first grid step of a run of lanes sharing one
    :func:`_table_block` (ids sorted → one run per block)."""
    cur, prev = ids_ref[i], ids_ref[jnp.maximum(i - 1, 0)]
    if not tiles:
        cur, prev = cur // SUBLANES, prev // SUBLANES
    return jnp.logical_or(i == 0, cur != prev)


def _lane_shape(n: int, tiles: int, num_cols: int) -> tuple:
    return (n, tiles, LANES) if tiles else (n, 1, num_cols)


def _lane_block(tiles: int, num_cols: int) -> pl.BlockSpec:
    """Batch-side block: lane ``i``'s row of a :func:`_lane_shape`
    operand — ``ref[0]`` has the shape of ``table[_row_at(...)]``."""
    return pl.BlockSpec(_lane_shape(1, tiles, num_cols),
                        lambda i, *_: (i, 0, 0), memory_space=pltpu.VMEM)


def _lane_at(tiles: int):
    return ... if tiles else 0


# -- KV lookup -------------------------------------------------------------


def _key_words(query):
    """(n, 2) uint32 keys → the two (n,) SMEM word planes."""
    return query[:, 0], query[:, 1]


def _key_row(hi, lo, slots: int):
    """The scalar key (hi, lo) broadcast to a (1, S, 2) bucket row."""
    word = jax.lax.broadcasted_iota(jnp.int32, (1, slots, 2), 2)
    return jnp.where(word == 0, hi, lo)


def _slot_iota(shape):
    return jax.lax.broadcasted_iota(jnp.int32, shape, 1)


def _both_words(eq):
    """(1, S, 2) per-word equality → (1, S, 1) whole-key equality."""
    return eq.astype(jnp.int32).sum(-1, keepdims=True) == 2


def _match_slot(row, hi, lo, slots: int):
    """Scalar slot of bucket ``row`` (1, S, 2) holding key (hi, lo), or
    -1. Everything downstream rebuilds its one-hot from this scalar, so
    no mask ever changes layout between the keys' (S on sublanes) and
    the scalar values' (S on lanes) blocks."""
    eq = _both_words(row == _key_row(hi, lo, slots))
    return jnp.max(jnp.where(eq, _slot_iota((1, slots, 1)), -1))


def _kv_block(slots: int, vdim: int) -> pl.BlockSpec:
    """Values/state block of bucket ``bkt[i]``: ``(1, S, D)`` for vector
    values, the aligned 8-bucket group of ``(B, S)`` scalar values."""
    if vdim:
        return pl.BlockSpec((1, slots, vdim),
                            lambda i, bkt, *_: (bkt[i], 0, 0),
                            memory_space=pltpu.VMEM)
    return _table_block(0, slots)


def _keys_block(slots: int) -> pl.BlockSpec:
    return pl.BlockSpec((1, slots, 2), lambda i, bkt, *_: (bkt[i], 0, 0),
                        memory_space=pltpu.VMEM)


_SMEM_OUT = pl.BlockSpec(memory_space=pltpu.SMEM)


def _kv_lookup_kernel(bkt, qhi, qlo, keys_ref, vals_ref, picked_ref,
                      found_ref, *, slots: int, vdim: int):
    """One lane: match the query against its bucket's slot rows (VMEM)
    and pick the matched value. Same pick formula as the XLA path
    (where-sum over the matching slot), so NaN payloads round-trip
    identically."""
    i = pl.program_id(0)
    slot = _match_slot(keys_ref[...], qhi[i], qlo[i], slots)
    found_ref[i] = (slot >= 0).astype(jnp.int32)
    if vdim:
        vals = vals_ref[...]                              # (1, S, D)
        picked_ref[0] = jnp.where(_slot_iota(vals.shape) == slot,
                                  vals, 0).sum(axis=1)
    else:
        vals = vals_ref[_row_at(bkt[i], 0)]               # (1, S)
        picked_ref[i] = jnp.sum(jnp.where(_slot_iota(vals.shape) == slot,
                                          vals, 0))


def build_kv_lookup(*, slots: int, value_dim: int, default_value: float,
                    interpret: bool) -> Callable:
    """(keys_arr, values_arr, query, buckets) -> (picked, found) —
    signature-compatible with ``KVTable``'s XLA ``lookup``."""
    vdim = int(value_dim)
    kern = functools.partial(_kv_lookup_kernel, slots=slots, vdim=vdim)

    def lookup_chunk(keys_arr, values_arr, qhi, qlo, buckets):
        b = buckets.shape[0]
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b,),
            in_specs=[_keys_block(slots), _kv_block(slots, vdim)],
            out_specs=[_lane_block(0, vdim) if vdim else _SMEM_OUT,
                       _SMEM_OUT],
        )
        return pl.pallas_call(
            kern,
            grid_spec=grid_spec,
            out_shape=[
                jax.ShapeDtypeStruct((b, 1, vdim) if vdim else (b,),
                                     values_arr.dtype),
                jax.ShapeDtypeStruct((b,), jnp.int32)],
            interpret=interpret,
        )(buckets, qhi, qlo, keys_arr, values_arr)

    def lookup(keys_arr, values_arr, query, buckets):
        b = buckets.shape[0]
        qhi, qlo = _key_words(query)
        outs = [lookup_chunk(keys_arr, values_arr, qhi[s:s + m],
                             qlo[s:s + m], buckets[s:s + m])
                for s, m in _lane_chunks(b)]
        picked = jnp.concatenate([o[0] for o in outs])
        found_b = jnp.concatenate([o[1] for o in outs]) != 0
        if vdim:
            picked = picked.reshape(b, vdim)
            fill = found_b[:, None]
        else:
            fill = found_b
        picked = jnp.where(fill, picked,
                           jnp.asarray(default_value, picked.dtype))
        return picked, found_b

    return lookup


# -- KV probe + updater apply + scatter ------------------------------------
#
# Two kernels with one scalar between them: PROBE claims a slot per lane
# and counts overflows; COMMIT applies the updater into the claimed
# slots, gated on the batch-wide overflow count (ANY overflow voids the
# WHOLE batch — the table must stay untouched for the raise). On a
# sharded mesh each shard runs both over its own lanes and the count is
# a jnp.sum across shards between the two shard_maps: the one global
# interaction the KV contract needs, outside any kernel.

_EMPTY_WORD = 0xFFFFFFFF


def _kv_probe_kernel(bkt, qhi, qlo, valid, carry, keys_ref, claimed_in,
                     slot_ref, nover_ref, claimed_ref, *, slots: int):
    """One grid step per bucket-sorted lane: the matching slot, else the
    first empty slot of the ORIGINAL row no earlier lane of this bucket
    run has claimed — the run-local scan that replaces the XLA path's
    global argsort rank (k-th new key of a bucket takes its k-th empty
    slot on both; both miss past the last). ``slot == slots`` encodes a
    dropped lane. ``carry``/``claimed_in`` continue a run across a
    chunk edge (:data:`LANE_CAP`)."""
    i = pl.program_id(0)
    prev = jnp.where(i == 0, carry[0], bkt[jnp.maximum(i - 1, 0)])

    @pl.when(i == 0)
    def _():
        nover_ref[0] = jnp.int32(0)
        claimed_ref[...] = claimed_in[...]

    @pl.when(bkt[i] != prev)
    def _():
        claimed_ref[...] = jnp.zeros_like(claimed_ref)

    row = keys_ref[...]                               # (1, S, 2) uint32
    lanes = _slot_iota((1, slots, 1))
    mslot = _match_slot(row, qhi[i], qlo[i], slots)
    free = _both_words(row == jnp.uint32(_EMPTY_WORD)) \
        & (claimed_ref[...] == 0)
    eslot = jnp.min(jnp.where(free, lanes, slots))    # == slots: full
    live = valid[i] > 0
    matched = mslot >= 0
    new = jnp.logical_and(live, jnp.logical_not(matched))
    placed = eslot < slots
    slot_ref[i] = jnp.where(live, jnp.where(matched, mslot, eslot), slots)

    @pl.when(jnp.logical_and(new, placed))
    def _():
        claimed_ref[...] = jnp.where(lanes == eslot, 1, claimed_ref[...])

    nover_ref[0] = nover_ref[0] + jnp.logical_and(
        new, jnp.logical_not(placed)).astype(jnp.int32)


def _kv_probe(keys_arr, buckets, qhi, qlo, valid, *, slots: int,
              interpret: bool):
    """(slot per lane (b,), overflow count) for one bucket-sorted lane
    range over ``keys_arr`` — read-only."""
    kern = functools.partial(_kv_probe_kernel, slots=slots)
    claimed_spec = pl.BlockSpec((1, slots, 1), lambda i, *_: (0, 0, 0),
                                memory_space=pltpu.VMEM)
    claimed = jnp.zeros((1, slots, 1), jnp.int32)
    carry = jnp.full((1,), -1, jnp.int32)
    slot_chunks, n_over = [], jnp.int32(0)
    for s, m in _lane_chunks(buckets.shape[0]):
        bkt = buckets[s:s + m]
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(m,),
            in_specs=[_keys_block(slots), claimed_spec],
            out_specs=[_SMEM_OUT, _SMEM_OUT, claimed_spec],
        )
        slot, nover, claimed = pl.pallas_call(
            kern, grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct((m,), jnp.int32),
                       jax.ShapeDtypeStruct((1,), jnp.int32),
                       jax.ShapeDtypeStruct((1, slots, 1), jnp.int32)],
            interpret=interpret,
        )(bkt, qhi[s:s + m], qlo[s:s + m], valid[s:s + m], carry,
          keys_arr, claimed)
        carry = bkt[-1:]
        slot_chunks.append(slot)
        n_over = n_over + nover[0]
    return jnp.concatenate(slot_chunks), n_over


def _kv_commit_kernel(*refs, slots: int, vdim: int, nstate: int,
                      updater: Any, state_treedef: Any):
    """Masked one-hot updater apply into the claimed slots of the
    resident (aliased) bucket blocks; ``gate != 0`` turns the whole
    batch into a no-op that writes every visited bucket back
    bit-identically. Old values read the PRE-batch inputs (dup keys per
    batch are rejected upstream, so each slot is written at most
    once)."""
    bkt, qhi, qlo, slot_ref, gate = refs[:5]
    keys_in, vals_in = refs[5], refs[6]
    state_in = refs[7:7 + nstate]
    d_ref, opt_ref = refs[7 + nstate], refs[8 + nstate]
    keys_out, vals_out = refs[9 + nstate], refs[10 + nstate]
    state_out = refs[11 + nstate:11 + 2 * nstate]

    i = pl.program_id(0)

    # run starts copy input→output so masked slot writes merge into the
    # original rows (the aliased buffer keeps unvisited rows); keys and
    # vector values are blocked per bucket, scalar values per 8-bucket
    # group — each copies on ITS block's run start
    @pl.when(_new_block(bkt, i, 1))
    def _():
        keys_out[...] = keys_in[...]
        if vdim:
            vals_out[...] = vals_in[...]
            for si, so in zip(state_in, state_out):
                so[...] = si[...]

    if not vdim:
        @pl.when(_new_block(bkt, i, 0))
        def _():
            vals_out[...] = vals_in[...]
            for si, so in zip(state_in, state_out):
                so[...] = si[...]

    slot = jnp.where(gate[0] == 0, slot_ref[i], slots)
    keys_out[...] = jnp.where(
        _slot_iota((1, slots, 2)) == slot,
        _key_row(qhi[i], qlo[i], slots), keys_out[...])

    at = ... if vdim else _row_at(bkt[i], 0)
    oh = _slot_iota((1, slots, vdim) if vdim else (1, slots)) == slot

    def pick(ref):
        old = jnp.where(oh, ref[at], 0)
        return old.sum(axis=1) if vdim else old.sum(axis=1,
                                                    keepdims=True)

    opt = jax.tree.unflatten(_OPTION_TREE,
                             [opt_ref[k:k + 1, :] for k in range(5)])
    upd, new_state = updater.apply(
        pick(vals_in),
        jax.tree.unflatten(state_treedef, [pick(s) for s in state_in]),
        d_ref[0], opt)

    def put(ref, new):
        new = new[:, None, :] if vdim else new
        ref[at] = jnp.where(oh, new.astype(ref.dtype), ref[at])

    put(vals_out, upd)
    for so, ns in zip(state_out, jax.tree.leaves(new_state)):
        put(so, ns)


_OPTION_TREE = jax.tree.structure(AddOption())


def _option_column(option: AddOption):
    """The AddOption as an (8, 1) f32 VMEM operand: row k is leaf k as
    a (1, 1) vector, so updaters do vector math on it (Mosaic's scalar
    core has no pow/sqrt)."""
    col = jnp.stack([jnp.asarray(leaf, jnp.float32)
                     for leaf in jax.tree.leaves(option)])
    return jnp.zeros((8, 1), jnp.float32).at[:5, 0].set(col)


def _kv_commit(keys_arr, values_arr, state_leaves, buckets, qhi, qlo,
               slot, gate, deltas, opt, *, slots: int, vdim: int,
               updater: Any, state_treedef: Any, interpret: bool):
    """Apply one bucket-sorted lane range into (keys, values, *state),
    in place. ``deltas`` (b, max(vdim, 1))."""
    nstate = len(state_leaves)
    kern = functools.partial(
        _kv_commit_kernel, slots=slots, vdim=vdim, nstate=nstate,
        updater=updater, state_treedef=state_treedef)
    width = deltas.shape[1]
    vspec = _kv_block(slots, vdim)
    table_specs = [_keys_block(slots), vspec] + [vspec] * nstate
    # operands 5.. (keys, values, state) alias their outputs in place —
    # one HBM buffer, unvisited rows untouched
    aliases = {5 + j: j for j in range(2 + nstate)}
    outs = [keys_arr, values_arr, *state_leaves]
    for s, m in _lane_chunks(buckets.shape[0]):
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(m,),
            in_specs=table_specs + [
                _lane_block(0, width),
                pl.BlockSpec((8, 1), lambda i, *_: (0, 0),
                             memory_space=pltpu.VMEM)],
            out_specs=table_specs,
        )
        outs = pl.pallas_call(
            kern, grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct(o.shape, o.dtype)
                       for o in outs],
            input_output_aliases=aliases,
            interpret=interpret,
        )(buckets[s:s + m], qhi[s:s + m], qlo[s:s + m], slot[s:s + m],
          gate, *outs, deltas[s:s + m].reshape(m, 1, width), opt)
    return outs


def build_kv_probe_update(*, slots: int, value_dim: int, updater: Any,
                          state_template: Any,
                          interpret: bool) -> Callable:
    """(keys, values, state, buckets, query, deltas, valid, option) ->
    (keys, values, state, n_over) — signature-compatible with
    ``KVTable``'s XLA ``probe_update``. Requires the batch host-sorted
    by bucket (``prepare_add`` guarantees it)."""
    vdim = int(value_dim)
    treedef = jax.tree.structure(state_template)

    def probe_update(keys_arr, values_arr, state, buckets, query,
                     deltas, valid, option):
        b = buckets.shape[0]
        qhi, qlo = _key_words(query)
        slot, n_over = _kv_probe(keys_arr, buckets, qhi, qlo,
                                 valid.astype(jnp.int32), slots=slots,
                                 interpret=interpret)
        outs = _kv_commit(
            keys_arr, values_arr, jax.tree.leaves(state), buckets, qhi,
            qlo, slot, n_over.reshape(1), deltas.reshape(b, max(vdim, 1)),
            _option_column(option), slots=slots, vdim=vdim,
            updater=updater, state_treedef=treedef, interpret=interpret)
        return (outs[0], outs[1],
                jax.tree.unflatten(treedef, outs[2:]), n_over)

    return probe_update


# -- matrix / sparse row paths ---------------------------------------------


def _gather_kernel(ids_ref, p_ref, o_ref, *, tiles: int):
    i = pl.program_id(0)
    o_ref[_lane_at(tiles)] = p_ref[_row_at(ids_ref[i], tiles)]


def build_row_gather(*, num_cols: int, tiles: int,
                     interpret: bool) -> Callable:
    """(param, ids) -> rows [n, num_cols] — the ``jnp.take`` row gather
    as a scalar-prefetch-indexed VMEM copy."""
    kern = functools.partial(_gather_kernel, tiles=tiles)

    def gather(param, ids):
        n = ids.shape[0]
        rows = []
        for s, m in _lane_chunks(n):
            grid_spec = pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(m,),
                in_specs=[_table_block(tiles, num_cols)],
                out_specs=_lane_block(tiles, num_cols),
            )
            rows.append(pl.pallas_call(
                kern,
                grid_spec=grid_spec,
                out_shape=jax.ShapeDtypeStruct(
                    _lane_shape(m, tiles, num_cols), param.dtype),
                interpret=interpret,
            )(ids[s:s + m], param))
        return jnp.concatenate(rows).reshape(n, num_cols)

    return gather


def _row_scatter_kernel(*refs, tiles: int, masked: bool):
    ids_ref = refs[0]
    p_ref, d_ref, o_ref = refs[1 + masked:]
    i = pl.program_id(0)

    @pl.when(_new_block(ids_ref, i, tiles))
    def _():
        o_ref[...] = p_ref[...]

    row = _row_at(ids_ref[i], tiles)

    def add():
        o_ref[row] = o_ref[row] + d_ref[_lane_at(tiles)].astype(
            o_ref.dtype)

    if masked:
        pl.when(refs[1][i] > 0)(add)
    else:
        add()


def build_row_scatter_add(*, num_cols: int, tiles: int, interpret: bool,
                          masked: bool = False) -> Callable:
    """(param, ids, deltas[, valid]) -> param — duplicate-safe row
    scatter-add. Requires ``ids`` sorted (host prep); each touched
    block is fetched once, its lanes segment-summed in the resident
    VMEM block, and written back to HBM once. ``masked`` adds a
    per-lane write gate: invalid lanes still walk the grid (their block
    copies through bit-exact), so foreign/padding lanes can ride a
    shard's dense lane range — the shard_map builder and the in-trace
    functional form both wrap the masked kernel."""
    kern = functools.partial(_row_scatter_kernel, tiles=tiles,
                             masked=masked)
    table = _table_block(tiles, num_cols)

    def scatter_add(param, ids, deltas, *valid):
        n = ids.shape[0]
        deltas = deltas.reshape(_lane_shape(n, tiles, num_cols))
        gates = [v.astype(jnp.int32) for v in valid]
        for s, m in _lane_chunks(n):
            grid_spec = pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1 + masked,
                grid=(m,),
                in_specs=[table, _lane_block(tiles, num_cols)],
                out_specs=table,
            )
            param = pl.pallas_call(
                kern,
                grid_spec=grid_spec,
                out_shape=jax.ShapeDtypeStruct(param.shape, param.dtype),
                input_output_aliases={1 + masked: 0},
                interpret=interpret,
            )(ids[s:s + m], *(g[s:s + m] for g in gates), param,
              deltas[s:s + m])
        return param

    return scatter_add


def _coo_kernel(*refs, tiles: int, num_cols: int, masked: bool):
    rows_ref, cols_ref, vals_ref = refs[:3]
    p_ref, o_ref = refs[3 + masked:]
    i = pl.program_id(0)

    @pl.when(_new_block(rows_ref, i, tiles))
    def _():
        o_ref[...] = p_ref[...]

    if tiles:
        kc = jax.lax.broadcasted_iota(jnp.int32, (1, tiles, LANES), 1)
        kl = jax.lax.broadcasted_iota(jnp.int32, (1, tiles, LANES), 2)
        col = kc * LANES + kl
    else:
        col = jax.lax.broadcasted_iota(jnp.int32, (1, num_cols), 1)
    row = _row_at(rows_ref[i], tiles)

    def add():
        o_ref[row] = o_ref[row] + jnp.where(col == cols_ref[i],
                                            vals_ref[i], 0)

    if masked:
        pl.when(refs[3][i] > 0)(add)
    else:
        add()


def build_coo_scatter_add(*, num_cols: int, tiles: int, interpret: bool,
                          masked: bool = False) -> Callable:
    """(param, rows, cols, vals[, valid]) -> param — the COO sparse Add.
    Requires ``rows`` sorted (host prep): one VMEM-resident run per
    touched block, one HBM write per touched block. ``masked`` as in
    :func:`build_row_scatter_add`."""
    kern = functools.partial(_coo_kernel, tiles=tiles, num_cols=num_cols,
                             masked=masked)
    table = _table_block(tiles, num_cols)

    def coo(param, rows, cols, vals, *valid):
        lanes = [rows, cols, vals.astype(param.dtype)] \
            + [v.astype(jnp.int32) for v in valid]
        for s, m in _lane_chunks(rows.shape[0]):
            grid_spec = pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3 + masked,
                grid=(m,),
                in_specs=[table],
                out_specs=table,
            )
            param = pl.pallas_call(
                kern,
                grid_spec=grid_spec,
                out_shape=jax.ShapeDtypeStruct(param.shape, param.dtype),
                input_output_aliases={3 + masked: 0},
                interpret=interpret,
            )(*(a[s:s + m] for a in lanes), param)
        return param

    return coo


# -- sharded engines: per-shard grids under shard_map ----------------------
#
# Each model-axis shard runs the SAME per-lane kernels over ONLY its
# local rows/buckets. Operands arrive as the (shards, L, ...) lane
# slices of tables/hashing.shard_lane_slices — shard s's grid walks row
# s, a dense bucket/row-sorted lane range whose non-local tail is
# masked padding — so no kernel ever communicates across shards.


def _shards_of(mesh: Any, axis: str, lead: int, what: str) -> int:
    shards = int(dict(mesh.shape)[axis])
    if lead % shards:
        raise UnsupportedShardingLayout(
            f"{what}={lead} not divisible by {shards} "
            f"{axis!r}-axis shards")
    return shards


def build_kv_probe_update_sharded(*, slots: int, value_dim: int,
                                  updater: Any, state_template: Any,
                                  interpret: bool, mesh: Any, axis: str,
                                  num_buckets: int) -> Callable:
    """(keys, values, state, buckets, query, deltas, valid, option) ->
    (keys, values, state, n_over) with the LANE-SLICED operand layout:
    ``buckets`` (shards, L) LOCAL bucket ids sorted per shard,
    ``query`` (shards, L, 2), ``deltas`` (shards, L[, D]), ``valid``
    (shards, L) — ``KVTable.prepare_add`` emits them through
    ``shard_lane_slices``."""
    shards = _shards_of(mesh, axis, num_buckets, "num_buckets")
    vdim = int(value_dim)
    treedef = jax.tree.structure(state_template)
    nstate = len(jax.tree.leaves(state_template))
    kspec = P(axis, None, None)
    vspec = P(axis, None, None) if vdim else P(axis, None)
    lanes2 = P(axis, None)
    lanes3 = P(axis, None, None)

    def probe_body(keys_blk, bkt_blk, q_blk, v_blk):
        qhi, qlo = _key_words(q_blk[0])
        slot, nover = _kv_probe(keys_blk, bkt_blk[0], qhi, qlo, v_blk[0],
                                slots=slots, interpret=interpret)
        return slot[None], nover.reshape(1)

    def commit_body(keys_blk, vals_blk, *rest):
        state_blks = rest[:nstate]
        bkt_blk, q_blk, d_blk, slot_blk, gate, opt = rest[nstate:]
        qhi, qlo = _key_words(q_blk[0])
        return tuple(_kv_commit(
            keys_blk, vals_blk, list(state_blks), bkt_blk[0], qhi, qlo,
            slot_blk[0], gate, d_blk[0], opt, slots=slots, vdim=vdim,
            updater=updater, state_treedef=treedef, interpret=interpret))

    def probe_update(keys_arr, values_arr, state, buckets, query,
                     deltas, valid, option):
        L = buckets.shape[1]
        slot, nover = shard_map(
            probe_body, mesh=mesh,
            in_specs=(kspec, lanes2, lanes3, lanes2),
            out_specs=(lanes2, P(axis)), check_vma=False,
        )(keys_arr, buckets, query, valid.astype(jnp.int32))
        # the ONE global interaction: the all-or-nothing overflow gate
        n_over = jnp.sum(nover).astype(jnp.int32)
        outs = shard_map(
            commit_body, mesh=mesh,
            in_specs=(kspec, vspec) + (vspec,) * nstate
            + (lanes2, lanes3, lanes3, lanes2, P(None), P(None, None)),
            out_specs=(kspec, vspec) + (vspec,) * nstate,
            check_vma=False,
        )(keys_arr, values_arr, *jax.tree.leaves(state), buckets, query,
          deltas.reshape(shards, L, max(vdim, 1)), slot,
          n_over.reshape(1), _option_column(option))
        return (outs[0], outs[1],
                jax.tree.unflatten(treedef, list(outs[2:])), n_over)

    return probe_update


def build_kv_lookup_sharded(*, slots: int, value_dim: int,
                            default_value: float, interpret: bool,
                            mesh: Any, axis: str,
                            num_buckets: int) -> Callable:
    """(keys, values, query, buckets, inv) -> (picked, found) with the
    lane-sliced layout: ``query`` (shards, L, 2) / ``buckets``
    (shards, L) local ids, plus ``inv`` — flat ``shard*L + pos``
    indices unpermuting the per-shard lane rows back to caller order
    (``KVTable.get_jax`` builds all three). Wraps the flat lookup
    kernel per shard."""
    _shards_of(mesh, axis, num_buckets, "num_buckets")
    vdim = int(value_dim)
    inner = build_kv_lookup(slots=slots, value_dim=value_dim,
                            default_value=default_value,
                            interpret=interpret)
    kspec = P(axis, None, None)
    vspec = P(axis, None, None) if vdim else P(axis, None)
    lanes2 = P(axis, None)
    lanes3 = P(axis, None, None)

    def body(keys_blk, vals_blk, q_blk, bkt_blk):
        picked, found = inner(keys_blk, vals_blk, q_blk[0], bkt_blk[0])
        return picked[None], found[None]

    sm = shard_map(body, mesh=mesh,
                   in_specs=(kspec, vspec, lanes3, lanes2),
                   out_specs=(lanes3 if vdim else lanes2, lanes2),
                   check_vma=False)

    def lookup(keys_arr, values_arr, query, buckets, inv):
        picked, found = sm(keys_arr, values_arr, query, buckets)
        flat = picked.reshape(-1, vdim) if vdim else picked.reshape(-1)
        return (jnp.take(flat, inv, axis=0),
                jnp.take(found.reshape(-1), inv, axis=0))

    return lookup


def build_row_gather_sharded(*, num_cols: int, tiles: int,
                             interpret: bool, mesh: Any, axis: str,
                             lead: int) -> Callable:
    """(param, ids, inv) -> rows [len(inv), num_cols]: per-shard local
    gathers of the lane-sliced ``ids`` (shards, L) of LOCAL row ids,
    unpermuted by the flat ``inv`` map."""
    _shards_of(mesh, axis, lead, "lead")
    inner = build_row_gather(num_cols=num_cols, tiles=tiles,
                             interpret=interpret)
    pspec = P(axis, None, None) if tiles else P(axis, None)

    def body(p_blk, ids_blk):
        return inner(p_blk, ids_blk[0])[None]

    sm = shard_map(body, mesh=mesh, in_specs=(pspec, P(axis, None)),
                   out_specs=P(axis, None, None), check_vma=False)

    def gather(param, ids, inv):
        rows = sm(param, ids)
        return jnp.take(rows.reshape(-1, num_cols), inv, axis=0)

    return gather


def build_row_scatter_add_sharded(*, num_cols: int, tiles: int,
                                  interpret: bool, mesh: Any, axis: str,
                                  lead: int) -> Callable:
    """(param, ids, deltas, valid) -> param with lane-sliced operands
    (shards, L[, C]) of LOCAL row ids: each shard scatter-adds only its
    valid lanes into its local row block."""
    _shards_of(mesh, axis, lead, "lead")
    inner = build_row_scatter_add(num_cols=num_cols, tiles=tiles,
                                  interpret=interpret, masked=True)
    pspec = P(axis, None, None) if tiles else P(axis, None)

    def body(p_blk, ids_blk, d_blk, v_blk):
        return inner(p_blk, ids_blk[0], d_blk[0], v_blk[0])

    return shard_map(body, mesh=mesh,
                     in_specs=(pspec, P(axis, None), P(axis, None, None),
                               P(axis, None)),
                     out_specs=pspec, check_vma=False)


def build_coo_scatter_add_sharded(*, num_cols: int, tiles: int,
                                  interpret: bool, mesh: Any, axis: str,
                                  lead: int) -> Callable:
    """(param, rows, cols, vals, valid) -> param with lane-sliced
    operands (shards, L) of LOCAL row ids."""
    _shards_of(mesh, axis, lead, "lead")
    inner = build_coo_scatter_add(num_cols=num_cols, tiles=tiles,
                                  interpret=interpret, masked=True)
    pspec = P(axis, None, None) if tiles else P(axis, None)
    lanes2 = P(axis, None)

    def body(p_blk, r_blk, c_blk, v_blk, m_blk):
        return inner(p_blk, r_blk[0], c_blk[0], v_blk[0], m_blk[0])

    return shard_map(body, mesh=mesh,
                     in_specs=(pspec, lanes2, lanes2, lanes2, lanes2),
                     out_specs=pspec, check_vma=False)


# -- functional forms for superstep bodies ---------------------------------
#
# Traceable inside an outer jit (a bare pallas_call is a first-class
# primitive): fused supersteps use the SAME gather/scatter engine by
# calling these from their bodies. Engine choice is made at trace time
# from MVTPU_KERNELS + the mesh's platform. Scatter inputs are sorted
# in-trace (a batch-sized argsort — still far smaller than the XLA
# scatter's full sorted-segment machinery over table rows).
#
# Under a kernel_mesh_scope (FusedSuperstep installs one around its
# dispatch) the forms shard: masked-lane shard_map wrappers rather than
# host lane slices, because per-shard lane counts are dynamic inside a
# trace. Foreign lanes map to the shard's LAST local row, masked off by
# the write gate of the masked kernels; gathers psum masked partial
# rows across the model axis (the one collective, outside the kernel).


_KERNEL_MESH: contextvars.ContextVar = contextvars.ContextVar(
    "mvtpu_kernel_mesh", default=None)


@contextlib.contextmanager
def kernel_mesh_scope(mesh: Any, axis: str):
    """Tell the functional forms which mesh/model-axis the enclosing
    dispatch shards tables over. ``FusedSuperstep`` wraps its jitted
    dispatch in this scope; a body tracing :func:`gather_rows` /
    :func:`row_scatter_add` / :func:`coo_scatter_add` inside it gets
    the sharded wrappers (on single-device meshes only the platform is
    read from it)."""
    token = _KERNEL_MESH.set((mesh, axis))
    try:
        yield
    finally:
        _KERNEL_MESH.reset(token)


def _functional_engine():
    """(use pallas?, interpret?, sharded scope or None) for a
    functional form traced now: the scope's mesh when a superstep
    installed one, else the runtime mesh."""
    scope = _KERNEL_MESH.get()
    mesh = scope[0] if scope is not None else None
    cpu = core.platform(mesh) == "cpu"
    mode = kernel_mode()
    use = mode == "pallas" or (mode == "auto" and not cpu)
    if scope is not None and getattr(mesh, "size", 1) <= 1:
        scope = None
    return use, cpu, scope


def _layout(param) -> tuple:
    """(num_cols, tiles) from a flat (R, C) or tiled (R, C/128, 128)
    param array."""
    if param.ndim == 3:
        return param.shape[1] * param.shape[2], param.shape[1]
    return param.shape[1], 0


@functools.lru_cache(maxsize=64)
def _cached(builder: Callable, num_cols: int, tiles: int,
            interpret: bool, **kw: Any) -> Callable:
    return builder(num_cols=num_cols, tiles=tiles, interpret=interpret,
                   **kw)


def _sharded_gather_rows(param, ids, interpret, mesh, axis):
    """In-trace sharded gather: each shard gathers its local hits
    (foreign lanes read row 0, masked to zero) and the masked partial
    rows psum across the model axis — outside any kernel."""
    num_cols, tiles = _layout(param)
    shards = int(dict(mesh.shape)[axis])
    if param.shape[0] % shards:
        _note_fallback("fn.gather_rows", "sharded_unsupported_layout",
                       mesh=mesh)
        rows = jnp.take(param, ids, axis=0)
        return rows.reshape(ids.shape[0], num_cols)
    rps = param.shape[0] // shards
    inner = _cached(build_row_gather, num_cols, tiles, interpret)
    pspec = P(axis, None, None) if tiles else P(axis, None)

    def body(p_blk, ids_blk):
        s = jax.lax.axis_index(axis)
        lo = s * rps
        mine = (ids_blk >= lo) & (ids_blk < lo + rps)
        lids = jnp.where(mine, ids_blk - lo, 0).astype(jnp.int32)
        rows = inner(p_blk, lids)
        return jax.lax.psum(jnp.where(mine[:, None], rows, 0), axis)

    sm = shard_map(body, mesh=mesh, in_specs=(pspec, P(None)),
                   out_specs=P(None, None), check_vma=False)
    return sm(param, ids.astype(jnp.int32))


def _sharded_row_scatter_add(param, ids, deltas, interpret, mesh, axis):
    """In-trace sharded scatter-add: sorted lanes, foreign lanes mapped
    to the shard's LAST local row and masked off by the write gate (a
    no-op run only re-copies the pre-batch block, so a later real run of
    that block stays correct)."""
    num_cols, tiles = _layout(param)
    shards = int(dict(mesh.shape)[axis])
    if param.shape[0] % shards:
        _note_fallback("fn.row_scatter_add",
                       "sharded_unsupported_layout", mesh=mesh)
        d = deltas.reshape((ids.shape[0],) + param.shape[1:])
        return param.at[ids].add(d.astype(param.dtype))
    rps = param.shape[0] // shards
    inner = _cached(build_row_scatter_add, num_cols, tiles, interpret,
                    masked=True)
    pspec = P(axis, None, None) if tiles else P(axis, None)
    order = jnp.argsort(ids, stable=True)
    sids = jnp.take(ids, order).astype(jnp.int32)
    sdel = jnp.take(deltas.reshape(ids.shape[0], num_cols), order,
                    axis=0)

    def body(p_blk, ids_blk, d_blk):
        s = jax.lax.axis_index(axis)
        lo = s * rps
        mine = (ids_blk >= lo) & (ids_blk < lo + rps)
        lids = jnp.where(mine, ids_blk - lo, rps - 1).astype(jnp.int32)
        return inner(p_blk, lids, d_blk, mine)

    sm = shard_map(body, mesh=mesh,
                   in_specs=(pspec, P(None), P(None, None)),
                   out_specs=pspec, check_vma=False)
    return sm(param, sids, sdel)


def _sharded_coo_scatter_add(param, rows, cols, vals, interpret, mesh,
                             axis):
    """In-trace sharded COO scatter-add — same foreign-lane mapping as
    :func:`_sharded_row_scatter_add`."""
    num_cols, tiles = _layout(param)
    shards = int(dict(mesh.shape)[axis])
    if param.shape[0] % shards:
        _note_fallback("fn.coo_scatter_add",
                       "sharded_unsupported_layout", mesh=mesh)
        if tiles:
            return param.at[rows, cols // LANES, cols % LANES].add(
                vals.astype(param.dtype))
        return param.at[rows, cols].add(vals.astype(param.dtype))
    rps = param.shape[0] // shards
    inner = _cached(build_coo_scatter_add, num_cols, tiles, interpret,
                    masked=True)
    pspec = P(axis, None, None) if tiles else P(axis, None)
    order = jnp.argsort(rows, stable=True)
    srows = jnp.take(rows, order).astype(jnp.int32)
    scols = jnp.take(cols, order).astype(jnp.int32)
    svals = jnp.take(vals, order)

    def body(p_blk, r_blk, c_blk, v_blk):
        s = jax.lax.axis_index(axis)
        lo = s * rps
        mine = (r_blk >= lo) & (r_blk < lo + rps)
        lrows = jnp.where(mine, r_blk - lo, rps - 1).astype(jnp.int32)
        return inner(p_blk, lrows, c_blk, v_blk, mine)

    sm = shard_map(body, mesh=mesh,
                   in_specs=(pspec, P(None), P(None), P(None)),
                   out_specs=pspec, check_vma=False)
    return sm(param, srows, scols, svals)


def gather_rows(param, ids):
    """Row gather ``param[ids]`` → ``[n, num_cols]`` through the
    selected engine (superstep-body form)."""
    num_cols, tiles = _layout(param)
    use, interpret, scope = _functional_engine()
    if not use:
        rows = jnp.take(param, ids, axis=0)
        return rows.reshape(ids.shape[0], num_cols)
    if scope is not None:
        return _sharded_gather_rows(param, ids, interpret, *scope)
    fn = _cached(build_row_gather, num_cols, tiles, interpret)
    return fn(param, ids.astype(jnp.int32))


def row_scatter_add(param, ids, deltas):
    """Duplicate-safe ``param.at[ids].add(deltas)`` through the selected
    engine (superstep-body form; sorts in-trace)."""
    num_cols, tiles = _layout(param)
    use, interpret, scope = _functional_engine()
    if not use:
        d = deltas.reshape((ids.shape[0],) + param.shape[1:])
        return param.at[ids].add(d.astype(param.dtype))
    if scope is not None:
        return _sharded_row_scatter_add(param, ids, deltas, interpret,
                                        *scope)
    order = jnp.argsort(ids, stable=True)
    fn = _cached(build_row_scatter_add, num_cols, tiles, interpret)
    return fn(param, jnp.take(ids, order).astype(jnp.int32),
              jnp.take(deltas.reshape(ids.shape[0], num_cols), order,
                       axis=0))


def coo_scatter_add(param, rows, cols, vals):
    """COO ``param[rows[i], cols[i]] += vals[i]`` through the selected
    engine (superstep-body form; sorts in-trace)."""
    num_cols, tiles = _layout(param)
    use, interpret, scope = _functional_engine()
    if not use:
        if tiles:
            return param.at[rows, cols // LANES, cols % LANES].add(
                vals.astype(param.dtype))
        return param.at[rows, cols].add(vals.astype(param.dtype))
    if scope is not None:
        return _sharded_coo_scatter_add(param, rows, cols, vals,
                                        interpret, *scope)
    order = jnp.argsort(rows, stable=True)
    fn = _cached(build_coo_scatter_add, num_cols, tiles, interpret)
    return fn(param, jnp.take(rows, order).astype(jnp.int32),
              jnp.take(cols, order).astype(jnp.int32),
              jnp.take(vals, order))


__all__ = [
    "KernelEngine", "UnsupportedShardingLayout",
    "build_coo_scatter_add", "build_coo_scatter_add_sharded",
    "build_kv_lookup", "build_kv_lookup_sharded",
    "build_kv_probe_update", "build_kv_probe_update_sharded",
    "build_row_gather", "build_row_gather_sharded",
    "build_row_scatter_add", "build_row_scatter_add_sharded",
    "coo_scatter_add", "gather_rows", "interpret_mode",
    "kernel_mesh_scope", "kernel_mode", "row_scatter_add",
    "select_kernel",
]
