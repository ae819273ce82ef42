"""KVTable: fixed-capacity hashed key→value table.

Reference: `include/multiverso/table/kv_table.h` (upstream layout;
SURVEY.md §3.3, confidence [M]) — a hash-map ``key→T`` table for
unbounded/sparse feature spaces (logistic regression with hashed
features), keys partitioned across servers by hash.

TPU design (SURVEY.md §3.9 / §8 hard-part #4): XLA wants static shapes,
so the open hash becomes a **bucketed cuckoo-free hash in fixed int32
arrays**: ``num_buckets × slots_per_bucket`` slots, each bucket probed
fully vectorized (no data-dependent while loops on the device). The
bucket axis is sharded over the mesh model axis — hash→bucket IS the
reference's hash→server partition.

- ``get(keys)``: one jitted gather+compare; missing keys return
  ``default_value`` and a found-mask.
- ``add(keys, deltas)``: slot assignment is a DEVICE-SIDE vectorized
  probe fused into the update program: a key takes its matching slot if
  present, else the first empty lane of its bucket — same-bucket new
  keys tie-break by batch order (a sort-free run-rank over the sorted
  bucket ids). Assignment is a pure function of (table state, batch), so
  under the SPMD collective contract (every process issues the same
  adds) multi-host processes stay in lockstep with NO host-side mirror.
  Bucket overflow drops the batch atomically on device and raises at
  the next table op (deferred — async adds stay fire-and-forget).

Values may be scalar (``value_dim=0``) or fixed-dim vectors.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from functools import partial
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from multiverso_tpu import core
from multiverso_tpu.ft.chaos import chaos_corrupt
from multiverso_tpu.ops import table_kernels as tk
from multiverso_tpu.tables.base import (Handle, Table, _register,
                                        loadz_stream, pack_state,
                                        savez_stream, unpack_state)
# hashing helpers live in tables/hashing.py (shared with the kernel
# engine); re-imported here so historical `from kv_table import ...`
# call sites keep working
from multiverso_tpu.tables.hashing import (EMPTY_KEY, _bucket, _hash_u64,
                                           _join_keys, _split_keys,
                                           shard_lane_slices)
from multiverso_tpu.telemetry import health as _health
from multiverso_tpu.telemetry import metrics as telemetry
from multiverso_tpu.telemetry import trace as tracing
from multiverso_tpu.telemetry.profiling import profiled_jit
from multiverso_tpu.updaters import (AddOption, get_updater,
                                     resolve_default_option)
from multiverso_tpu.utils import configure, log


@dataclasses.dataclass
class KVTableOption:
    capacity: int
    value_dim: int = 0
    dtype: Any = "float32"
    slots_per_bucket: int = 8
    updater: Optional[str] = None
    name: str = "kv_table"
    shard_update: bool = False   # data-axis updater-state sharding


@dataclasses.dataclass
class PreparedKVAdd:
    """One Add batch with host prep done and operands staged on device
    (H2D already issued): the unit the async staging pipeline hands
    between its prepare thread and the dispatching thread."""
    buckets: Any        # device int32 [b]   (b = pow2 bucket of n);
    #                     sharded layout: int32 [shards, L] LOCAL ids
    query: Any          # device uint32 [b, 2]   (sharded: [shards, L, 2])
    deltas: Any         # device [b(, D)]        (sharded: [shards, L(, D)])
    valid: Any          # device bool [b]        (sharded: [shards, L])
    option: AddOption   # device-leaved (resolved at prepare time)
    elems: int
    nbytes: int
    #: operand layout this batch was prepped for — must match the
    #: engine's ``KernelEngine.layout`` ("flat" | "sharded")
    layout: str = "flat"
    #: host copy of the batch's GLOBAL bucket ids (sorted, no padding)
    #: — kept alongside the deferred overflow flag so a later raise can
    #: name the overflowing buckets, not just count keys
    host_buckets: Any = None


class KVTable:
    """Fixed-capacity hashed table. Not a dense-array Table subclass —
    storage is (keys, values, state) triple — but implements the same
    get/add/store/load contract and registers a table id."""

    #: subclasses that break the kernel engine's operand contract (the
    #: tiered store re-sorts lanes at dispatch) keep the plain XLA
    #: closures and skip the Pallas factories entirely
    ALLOW_PALLAS = True

    def __init__(self, capacity: int, value_dim: int = 0,
                 dtype: Any = "float32", *, slots_per_bucket: int = 8,
                 updater: Optional[str] = None,
                 mesh: Optional[Mesh] = None, name: str = "kv_table",
                 default_value: float = 0.0,
                 default_option: Optional[AddOption] = None,
                 shard_update: bool = False) -> None:
        if capacity <= 0:
            raise ValueError("capacity must be positive")
        self.name = name
        self.mesh = mesh if mesh is not None else core.mesh()
        self.value_dim = value_dim
        self.dtype = jnp.dtype(dtype)
        self.slots = slots_per_bucket
        self.default_value = default_value
        updater_name = updater if updater is not None \
            else configure.get_flag("updater_type")
        self.updater = get_updater(updater_name)
        self.default_option = resolve_default_option(updater_name,
                                                     default_option)
        self._option_lock = threading.Lock()
        self.generation = 0
        # client-pipeline hooks (see tables/base.py) — shared by
        # unbound-method assignment below, like _record_op
        self._view_refs: list = []
        self._coalescer_refs: list = []

        shards = self.mesh.shape[core.MODEL_AXIS]
        dp = dict(self.mesh.shape).get(core.DATA_AXIS, 1)
        # arXiv:2004.13336 for the KV updater state: the state leaves
        # (adagrad/adam accumulators) refine over the data axis too, so
        # optimizer memory per device shrinks by dp — same contract as
        # Table.shard_update for the dense tables (base.py)
        self.shard_update = bool(shard_update) and dp > 1
        bucket_mult = shards * dp if self.shard_update else shards
        buckets = -(-capacity // self.slots)
        self.num_buckets = -(-buckets // bucket_mult) * bucket_mult
        self.capacity = self.num_buckets * self.slots
        self._shards = shards
        # bucket→shard ownership is contiguous equal blocks (shard s
        # owns [s*bps, (s+1)*bps)), so a sort by bucket IS a sort by
        # shard-then-bucket — the invariant the sharded lane slicer and
        # the per-shard Pallas grids both stand on
        self._buckets_per_shard = self.num_buckets // shards

        kv_shape = (self.num_buckets, self.slots)
        val_shape = kv_shape + ((value_dim,) if value_dim else ())
        self._key_sharding = NamedSharding(
            self.mesh, P(core.MODEL_AXIS, None, None))
        self._val_sharding = NamedSharding(
            self.mesh, P(core.MODEL_AXIS, *([None] * (len(val_shape) - 1))))
        self._state_sharding = NamedSharding(
            self.mesh, P((core.MODEL_AXIS, core.DATA_AXIS),
                         *([None] * (len(val_shape) - 1)))) \
            if self.shard_update else self._val_sharding
        # 64-bit keys are stored as two uint32 planes (hi, lo): with
        # jax_enable_x64 off, uint64 device arrays silently canonicalize to
        # uint32, aliasing keys that share low 32 bits.
        self.keys = jax.device_put(
            np.full(kv_shape + (2,), 0xFFFFFFFF, dtype=np.uint32),
            self._key_sharding)
        self.values = jax.device_put(
            np.full(val_shape, default_value, dtype=self.dtype),
            self._val_sharding)
        self.state = jax.tree.map(
            lambda s: jax.device_put(s, self._state_sharding),
            self.updater.init_state(self.values))
        self._pending_over: list = []  # deferred overflow flags (device
        # scalars, one per in-flight add; drained non-blocking in add,
        # blocking at every other table op)
        self._build_jits()
        # checkpoint-export copier, built lazily on the first export
        self._export_copy = None
        # read-replica copier (keys+values only), lazy like _export_copy
        self._kv_snapshot_copy = None
        self.table_id = _register(self)  # type: ignore[arg-type]
        lbl = f"{self.table_id}:{self.name}"
        self._h_get = telemetry.histogram(
            "table.get.seconds", telemetry.LATENCY_BUCKETS, table=lbl)
        self._h_add = telemetry.histogram(
            "table.add.seconds", telemetry.LATENCY_BUCKETS, table=lbl)
        log.debug("kv table %r: %d buckets x %d slots (capacity %d)",
                  name, self.num_buckets, self.slots, self.capacity)

    def _build_jits(self) -> None:
        replicated = NamedSharding(self.mesh, P(None))

        def lookup(keys_arr, values_arr, query, buckets):
            # keys_arr: (B, S, 2) uint32; query: (n, 2) uint32
            slots = jnp.take(keys_arr, buckets, axis=0)        # (n, S, 2)
            vals = jnp.take(values_arr, buckets, axis=0)       # (n, S[, D])
            match = (slots == query[:, None, :]).all(axis=-1)  # (n, S)
            found = match.any(axis=1)
            m = match if vals.ndim == 2 else match[..., None]
            picked = jnp.sum(jnp.where(m, vals, 0), axis=1)
            fill = found if vals.ndim == 2 else found[:, None]
            picked = jnp.where(fill, picked,
                               jnp.asarray(self.default_value, vals.dtype))
            return picked, found

        n_slots = self.slots
        scalar_sh = NamedSharding(self.mesh, P())
        state_sh = jax.tree.map(lambda _: self._state_sharding, self.state)
        # the Pallas engines slice state like values (model axis only);
        # data-axis-refined state (shard_update) and subclasses that
        # re-sort lanes at dispatch (tiered) keep the XLA closures
        allow_pallas = self.ALLOW_PALLAS and not self.shard_update \
            and self.dtype.itemsize == 4    # (8, 128) 32-bit blocks

        def probe_update(keys_arr, values_arr, state, buckets, query,
                         deltas, valid, option):
            """Fused slot probe + updater + scatter. The probe is the
            reference's hash-bucket insertion vectorized: match lane if
            the key is present, else the (rank+1)-th empty lane where
            rank = this key's position among the batch's NEW keys of the
            same bucket (deterministic batch-order tie-break, computed
            by a run-rank over the sorted bucket ids — no host state).
            Unplaced keys (bucket overflow) get an out-of-range slot and
            their scatters DROP; the count comes back for the host to
            raise on.

            ``valid`` masks PADDING lanes: batch lengths are bucketed to
            powers of two (prepare_add), so variable-size adds reuse a
            bounded set of compiled signatures instead of retracing per
            length. Padded lanes carry the EMPTY sentinel as query (can
            only ever match empty slots — a reserved key), are excluded
            from ranks and the overflow count, and are forced to the
            out-of-range slot so every one of their scatters drops."""
            rows = jnp.take(keys_arr, buckets, axis=0)       # (n, S, 2)
            match = (rows == query[:, None, :]).all(-1)      # (n, S)
            matched = match.any(axis=1)
            mlane = jnp.argmax(match, axis=1)
            empty = (rows == jnp.uint32(0xFFFFFFFF)).all(-1)
            new = ~matched & valid
            # rank among same-bucket new keys, in batch order
            perm = jnp.argsort(buckets, stable=True)
            b_s = jnp.take(buckets, perm)
            new_s = jnp.take(new, perm).astype(jnp.int32)
            csx = jnp.cumsum(new_s) - new_s                  # exclusive
            bound = jnp.concatenate(
                [jnp.ones(1, bool), b_s[1:] != b_s[:-1]])
            base = jax.lax.cummax(jnp.where(bound, csx, -1))
            rank_s = csx - base
            rank = jnp.zeros_like(rank_s).at[perm].set(rank_s)
            # (rank+1)-th empty lane of the bucket
            ecs = jnp.cumsum(empty.astype(jnp.int32), axis=1)
            hit = empty & (ecs == (rank + 1)[:, None])
            placed_new = hit.any(axis=1)
            elane = jnp.argmax(hit, axis=1)
            ok = matched | placed_new
            n_over = jnp.sum(~ok & valid)
            slot = jnp.where(matched, mlane, elane)
            # all-or-nothing: ANY overflow voids the whole batch (the
            # raise must leave the table untouched) — out-of-range slots
            # make every scatter drop; padding lanes always drop
            slot = jnp.where(ok & valid & (n_over == 0), slot, n_slots)
            keys_arr = keys_arr.at[buckets, slot].set(query)
            safe = jnp.minimum(slot, n_slots - 1)
            old = values_arr[buckets, safe]
            old_state = jax.tree.map(lambda s: s[buckets, safe], state)
            upd, new_state = self.updater.apply(old, old_state, deltas,
                                                option)
            values_arr = values_arr.at[buckets, slot].set(
                upd.astype(values_arr.dtype))
            state = jax.tree.map(
                lambda s, ns: s.at[buckets, slot].set(ns.astype(s.dtype)),
                state, new_state)
            return keys_arr, values_arr, state, n_over

        @partial(jax.jit, out_shardings=scalar_sh)
        def count_live(keys_arr):
            return jnp.sum(~(keys_arr == jnp.uint32(0xFFFFFFFF))
                           .all(-1))

        # profiled: profile.calls{fn=kv.lookup/kv.apply.<name>} are the
        # Get/Add dispatch counts the client pipeline's coalescing and
        # caching claims are asserted against. All paths register
        # behind the kernel engine (MVTPU_KERNELS): the Pallas engine
        # (same signatures, bit-equal results —
        # tests/test_table_kernels.py) keeps each bucket's slot rows in
        # VMEM and replaces the batch-wide argsort with the in-kernel
        # per-bucket scan; on a multi-device mesh the sharded forms run
        # the same per-shard grids under shard_map. The Pallas engine's
        # dispatches land on profile.calls{fn=....pallas}.
        kw = dict(slots=self.slots, value_dim=self.value_dim,
                  interpret=tk.interpret_mode(self.mesh))
        sharded_kw = dict(mesh=self.mesh, axis=core.MODEL_AXIS,
                          num_buckets=self.num_buckets)

        def engines(op, build, build_sharded, jit_kw, **build_kw):
            if not allow_pallas:
                return {}
            name = f"kv.{op}.{self.name}.pallas"
            return dict(
                pallas=lambda: profiled_jit(
                    build(**kw, **build_kw), name=name, **jit_kw),
                pallas_sharded=lambda: profiled_jit(
                    build_sharded(**kw, **build_kw, **sharded_kw),
                    name=name, **jit_kw))

        lookup_kw = dict(out_shardings=(replicated, replicated))
        self._lookup = tk.select_kernel(
            f"kv.lookup.{self.name}",
            xla=profiled_jit(lookup, name=f"kv.lookup.{self.name}",
                             **lookup_kw),
            mesh=self.mesh, **engines(
                "lookup", tk.build_kv_lookup, tk.build_kv_lookup_sharded,
                lookup_kw, default_value=self.default_value))
        apply_kw = dict(
            donate_argnums=(0, 1, 2),
            out_shardings=(self._key_sharding, self._val_sharding,
                           state_sh, scalar_sh))
        self._probe_update = tk.select_kernel(
            f"kv.apply.{self.name}",
            xla=profiled_jit(probe_update, name=f"kv.apply.{self.name}",
                             **apply_kw),
            mesh=self.mesh, **engines(
                "apply", tk.build_kv_probe_update,
                tk.build_kv_probe_update_sharded, apply_kw,
                updater=self.updater, state_template=self.state))
        self._count_live = count_live

    def _buckets_of(self, keys: np.ndarray) -> np.ndarray:
        return (_hash_u64(keys) % np.uint64(self.num_buckets)).astype(
            np.int32)

    def _check_keys(self, keys: np.ndarray) -> np.ndarray:
        keys = np.asarray(keys, dtype=np.uint64)
        if keys.ndim != 1 or len(keys) == 0:
            raise ValueError("keys must be a non-empty 1-D array")
        if (keys == EMPTY_KEY).any():
            raise ValueError(f"key {EMPTY_KEY} is the reserved empty "
                             "sentinel")
        return keys

    def _raise_overflow(self, n_over: int, bucket_ids=None) -> None:
        where = ""
        if bucket_ids:
            shown = ", ".join(str(b) for b in bucket_ids[:16])
            more = "" if len(bucket_ids) <= 16 \
                else f" (+{len(bucket_ids) - 16} more)"
            where = f"; bucket id(s) at capacity for the batch: " \
                    f"[{shown}]{more}"
        raise RuntimeError(
            f"kv table {self.name!r}: {n_over} keys overflowed their "
            f"buckets in a previous add (configured capacity "
            f"{self.capacity} keys = {self.capacity // self.slots} "
            f"buckets x {self.slots} slots{where}; the batch was "
            "dropped "
            "atomically); raise capacity or slots_per_bucket. NOTE: "
            "the dropped add still advanced the table generation and "
            "option step (its buffers were swapped; overflow is only "
            "known after device execution) — re-issue the dropped "
            "batch after resizing")

    def _overflowing_buckets(self, host_buckets) -> list:
        """Cold path behind an overflow raise: name the buckets that
        could not take the dropped batch. A bucket is flagged when the
        batch's key demand plus its CURRENT fill exceeds ``slots`` —
        an upper bound (keys already present match in place and need
        no new slot), but the dropped batch left fill untouched, so
        the true overflowing bucket is always in the list."""
        if host_buckets is None or len(host_buckets) == 0:
            return []
        ub, cnt = np.unique(np.asarray(host_buckets, np.int64),
                            return_counts=True)
        rows = np.asarray(jnp.take(
            self.keys, jnp.asarray(ub, jnp.int32), axis=0))
        fill = (~(rows == np.uint32(0xFFFFFFFF)).all(-1)).sum(-1)
        return [int(b) for b in ub[(fill + cnt) > self.slots]]

    @staticmethod
    def _over_entry(entry):
        """``_pending_over`` entries are ``(flag, host_buckets)`` pairs;
        a bare flag (the pre-tiering contract, still poked in by tests
        and tools) reads as a pair with no bucket context."""
        return entry if isinstance(entry, tuple) else (entry, None)

    def _drain_overflow(self, entries) -> None:
        n_over = 0
        bucket_ids: set = set()
        for entry in entries:
            flag, host_buckets = self._over_entry(entry)
            n = int(np.asarray(flag))
            if n:
                n_over += n
                bucket_ids.update(self._overflowing_buckets(host_buckets))
        if n_over:
            self._raise_overflow(n_over, sorted(bucket_ids))

    def _check_overflow(self) -> None:
        """Raise any pending overflow from previous async adds —
        BLOCKING (drains every in-flight flag). Called by every table
        op except ``add``: their own D2H results already serialize
        behind the in-flight updates, so the extra readback costs
        nothing; the overflowed batches were dropped atomically on
        device, so the table is consistent."""
        pending, self._pending_over = self._pending_over, []
        self._drain_overflow(pending)

    def _poll_overflow(self) -> None:
        """Non-blocking drain for the ``add`` hot path: only flags whose
        device scalar is already computed are inspected, so back-to-back
        ``add(sync=False)`` calls keep pipelining (a blocking readback
        here would cap the async queue at depth 1 — the exact
        serialization the deferral exists to avoid). A flag with no
        ``is_ready`` attribute stays DEFERRED (treated as still in
        flight): readiness is unknowable without a blocking
        ``np.asarray`` readback, and every non-add table op drains it
        through :meth:`_check_overflow` anyway."""
        still, ready = [], []
        for entry in self._pending_over:
            is_ready = getattr(self._over_entry(entry)[0], "is_ready",
                               None)
            (ready if is_ready is not None and is_ready()
             else still).append(entry)
        self._pending_over = still
        self._drain_overflow(ready)

    # -- API ---------------------------------------------------------------

    # per-table op accounting + client-pipeline hooks, shared with the
    # dense Table hierarchy (KVTable is contract-compatible, not a
    # subclass)
    _record_op = Table._record_op
    _attach_view = Table._attach_view
    _attach_coalescer = Table._attach_coalescer
    _notify_views = Table._notify_views
    flush_coalesced = Table.flush_coalesced

    def get_jax(self, keys) -> Tuple[jax.Array, jax.Array]:
        """Device-resident batched lookup → (values, found_mask) as
        device arrays (futures — dispatch is async; nothing blocks until
        the caller reads them back).

        Query lengths are bucketed to powers of two like adds (padded
        lanes carry the EMPTY sentinel and are sliced off), so variable
        query sizes share compiled signatures."""
        self._check_overflow()
        keys = self._check_keys(keys)
        return self._get_with_buckets(keys, self._buckets_of(keys))

    def _get_with_buckets(self, keys: np.ndarray,
                          lane_buckets: np.ndarray):
        """Dispatch half of a Get for pre-hashed per-lane bucket ids in
        DEVICE geometry — the seam the tiered store drives after
        translating logical buckets to resident device slots
        (``storage/tiered_kv.py``); :meth:`get_jax` is the identity
        translation."""
        n = len(keys)
        t0 = time.monotonic()
        with tracing.span("table.get",
                          table=f"{self.table_id}:{self.name}", n=n,
                          engine=self._lookup.engine):
            elems = n * max(self.value_dim, 1)
            self._record_op("get", elems, elems * self.dtype.itemsize)
            if self._lookup.layout == "sharded":
                out = self._get_jax_sharded(keys, lane_buckets, n)
                self._h_get.observe(time.monotonic() - t0)
                return out
            b = _bucket(n)
            query = np.full((b, 2), 0xFFFFFFFF, np.uint32)
            query[:n] = _split_keys(keys)
            buckets = np.zeros(b, np.int32)
            buckets[:n] = lane_buckets
            vals, found = self._lookup(
                self.keys, self.values,
                core.place(query, mesh=self.mesh),
                core.place(buckets, mesh=self.mesh))
            if b != n:  # padding lanes (sentinel query) sliced away
                vals, found = vals[:n], found[:n]
        self._h_get.observe(time.monotonic() - t0)
        return vals, found

    def _get_jax_sharded(self, keys: np.ndarray,
                         lane_buckets: np.ndarray, n: int):
        """Lane-sliced Get prep for the sharded engine: sort lanes by
        owning shard, hand each shard its dense row of local bucket ids
        + queries, and an ``inv`` map (flat ``shard*L + pos`` indices,
        pow2-padded) that unpermutes the per-shard results back to
        caller order."""
        bps = self._buckets_per_shard
        shard_ids = lane_buckets // bps
        order = np.argsort(shard_ids, kind="stable")
        sshard = shard_ids[order]
        local = (lane_buckets[order] - sshard * bps).astype(np.int32)
        (sl_local, sl_query), _valid, pos = shard_lane_slices(
            sshard, self._shards, [local, _split_keys(keys[order])],
            [np.int32(bps - 1), np.uint32(0xFFFFFFFF)])
        lanes = sl_local.shape[1]
        inv = np.zeros(_bucket(n), np.int32)
        inv[order] = (sshard * lanes + pos).astype(np.int32)
        mput = lambda a: core.place(
            a, P(core.MODEL_AXIS, *([None] * (a.ndim - 1))),
            mesh=self.mesh)
        vals, found = self._lookup(
            self.keys, self.values, mput(sl_query), mput(sl_local),
            core.place(inv, mesh=self.mesh))
        if len(inv) != n:
            vals, found = vals[:n], found[:n]
        return vals, found

    def get(self, keys) -> Tuple[np.ndarray, np.ndarray]:
        """Batched lookup → (values, found_mask). Missing keys yield
        ``default_value`` (the reference's KV semantics: absent = initial
        value). Blocks on the device→host readback; use
        :meth:`get_async` / :meth:`get_jax` to keep the hot loop
        non-blocking."""
        vals, found = self.get_jax(keys)
        return np.asarray(vals), np.asarray(found)

    def get_async(self, keys) -> Handle:
        """Non-blocking Get: a handle wrapping the DEVICE (values,
        found) pair; ``wait()`` returns the device arrays once computed
        (the true-async variant of the reference's ``GetAsync``)."""
        return Handle(self.get_jax(keys))

    def prepare_add(self, keys, deltas,
                    option: Optional[AddOption] = None) -> "PreparedKVAdd":
        """Host-side half of an Add: validate, hash, split, and STAGE the
        batch onto the device (H2D), without touching table state.

        Safe to run on a worker thread while the device applies a
        previous batch — the double-buffered upload seam
        (:class:`multiverso_tpu.client.KVStagingWriter` drives it). The
        AddOption (lr/step) is resolved HERE, at prepare time.

        The batch is PADDED to a power-of-two length (masked lanes carry
        the EMPTY sentinel and drop on device), so variable-size adds
        share a bounded set of compiled signatures — without it every
        distinct length recompiles the fused probe program.

        Lanes are stable-SORTED by bucket: the Pallas probe engine needs
        same-bucket lanes on consecutive grid steps (its per-bucket scan
        replaces the XLA path's global argsort), and the XLA path is
        lane-order-insensitive (its rank tie-break is batch order, which
        a stable sort preserves within each bucket) — so the final table
        state is identical either way."""
        keys, deltas, lane_buckets, opt = self._prep_host_add(
            keys, deltas, option)
        return self._pack_prepared(keys, deltas, lane_buckets, opt)

    def _prep_host_add(self, keys, deltas,
                       option: Optional[AddOption] = None):
        """Placement-independent host half of :meth:`prepare_add`:
        validate, hash, stable-sort by bucket, resolve the AddOption.
        Returns host arrays sorted by THIS table's bucket ids — device
        geometry here; LOGICAL geometry in the tiered subclass, which
        defers packing until its dispatch half has faulted the buckets
        in and can translate them to device slots."""
        keys = self._check_keys(keys)
        uniq = np.unique(keys)
        if len(uniq) != len(keys):
            raise ValueError("duplicate keys in one add; pre-aggregate")
        deltas = np.asarray(deltas)
        n = len(keys)
        want = (n, self.value_dim) if self.value_dim else (n,)
        if deltas.shape != want:
            raise ValueError(f"deltas shape {deltas.shape} != {want}")
        deltas = chaos_corrupt("table.add", deltas)
        lane_buckets = self._buckets_of(keys)
        order = np.argsort(lane_buckets, kind="stable")
        opt = (option or self.default_option).as_jax(self.mesh)
        return keys[order], deltas[order], lane_buckets[order], opt

    def _pack_prepared(self, keys: np.ndarray, deltas: np.ndarray,
                       lane_buckets: np.ndarray,
                       opt: AddOption) -> "PreparedKVAdd":
        """Pack bucket-sorted host lanes into the selected engine's
        operand layout and STAGE them on device (H2D). ``lane_buckets``
        must be DEVICE-geometry bucket ids, sorted ascending with
        per-bucket batch order preserved (what :meth:`_prep_host_add`
        returns for a non-tiered table)."""
        n = len(keys)
        if self._probe_update.layout == "sharded":
            # bucket ownership is contiguous equal blocks, so the sort
            # above already grouped lanes by owning shard (in shard
            # order) with each shard's lanes bucket-sorted — exactly
            # what shard_lane_slices and the per-shard grids need
            bps = self._buckets_per_shard
            shard_ids = lane_buckets // bps
            local = (lane_buckets - shard_ids * bps).astype(np.int32)
            (sl_local, sl_query, sl_deltas), valid, _pos = \
                shard_lane_slices(
                    shard_ids, self._shards,
                    [local, _split_keys(keys), deltas],
                    [np.int32(bps - 1), np.uint32(0xFFFFFFFF), 0])
            mput = lambda a: core.place(
                a, P(core.MODEL_AXIS, *([None] * (a.ndim - 1))),
                mesh=self.mesh)
            return PreparedKVAdd(
                buckets=mput(sl_local), query=mput(sl_query),
                deltas=mput(sl_deltas), valid=mput(valid), option=opt,
                elems=int(deltas.size),
                nbytes=int(deltas.size) * self.dtype.itemsize,
                layout="sharded", host_buckets=lane_buckets)
        b = _bucket(n)
        query = np.full((b, 2), 0xFFFFFFFF, np.uint32)
        query[:n] = _split_keys(keys)
        # padding lanes park on the LAST bucket so the sorted-by-bucket
        # invariant holds across them (they never write — valid=False)
        buckets = np.full(b, self.num_buckets - 1, np.int32)
        buckets[:n] = lane_buckets
        pdeltas = np.zeros((b,) + deltas.shape[1:], deltas.dtype)
        pdeltas[:n] = deltas
        valid = np.zeros(b, bool)
        valid[:n] = True
        put = lambda a: core.place(a, mesh=self.mesh)
        return PreparedKVAdd(buckets=put(buckets), query=put(query),
                             deltas=put(pdeltas), valid=put(valid),
                             option=opt, elems=int(deltas.size),
                             nbytes=int(deltas.size) * self.dtype.itemsize,
                             host_buckets=lane_buckets)

    def add_prepared(self, prepared: "PreparedKVAdd",
                     sync: bool = False) -> Handle:
        """Device half of an Add: dispatch one staged batch through the
        fused probe+updater program. Must run on the thread that owns
        the table (it swaps the live buffers)."""
        self._poll_overflow()
        t0 = time.monotonic()
        with tracing.span("table.add",
                          table=f"{self.table_id}:{self.name}",
                          engine=self._probe_update.engine, sync=sync):
            self._record_op("add", prepared.elems, prepared.nbytes)
            _health.observe_update(self, prepared.deltas)
            self.keys, self.values, self.state, n_over = \
                self._probe_update(
                    self.keys, self.values, self.state,
                    prepared.buckets, prepared.query, prepared.deltas,
                    prepared.valid, prepared.option)
            self._pending_over.append((n_over, prepared.host_buckets))
            _health.observe_param(self, self.values)
            with self._option_lock:
                self.default_option.step += 1
                self.generation += 1
                gen = self.generation
            self._notify_views()
            handle = Handle(table=self, generation=gen)
            if sync:
                handle.wait()
                self._check_overflow()
        self._h_add.observe(time.monotonic() - t0)
        return handle

    def add(self, keys, deltas, option: Optional[AddOption] = None,
            sync: bool = False) -> Handle:
        """Batched upsert-through-updater.

        Duplicate keys within one batch must be pre-aggregated (the
        client-side Aggregator role) — they raise otherwise.
        :class:`multiverso_tpu.client.CoalescingBuffer` does that
        pre-aggregation (and batches K adds into one dispatch).

        On bucket overflow the batch is dropped atomically ON DEVICE and
        the error surfaces at a later table op; the returned Handle and
        the option step still advance (overflow is unknowable at
        dispatch time without serializing the async queue).
        """
        self._poll_overflow()
        return self.add_prepared(self.prepare_add(keys, deltas, option),
                                 sync=sync)

    def wait(self) -> None:
        jax.block_until_ready(self._live_buffers())
        self._check_overflow()

    def _live_buffers(self):
        return (self.keys, self.values, self.state)

    def _live_value(self):
        return self.values

    def __len__(self) -> int:
        """Number of live keys (device count — there is no host mirror)."""
        self._check_overflow()
        return int(np.asarray(self._count_live(self.keys)))

    def snapshot_kv_async(self):
        """Light async copy of (keys, values) for read replicas: jitted
        device copies that survive the next add's donation, returned as
        futures for an off-thread ``np.asarray``. Unlike
        :meth:`export_checkpoint_async` this does NOT flush coalescers
        or drain overflow flags — it is a dispatch-thread hot-path call
        and must never block or raise for unrelated pending adds."""
        if self._kv_snapshot_copy is None:
            self._kv_snapshot_copy = jax.jit(
                lambda k, v: (jnp.copy(k), jnp.copy(v)),
                out_shardings=(self._key_sharding, self._val_sharding))
        return self._kv_snapshot_copy(self.keys, self.values)

    # -- checkpoint --------------------------------------------------------

    KV_MAGIC = "multiverso_tpu.kvtable.v1"

    def export_checkpoint_async(self):
        """Checkpoint export split like ``Table.export_checkpoint_async``:
        dispatch half here (flush, overflow check, jitted copies of the
        keys/values/state triple — the copies survive the next add's
        donation), blocking half in the returned ``finish()``."""
        # checkpoint contract: every issued delta lands, including ones
        # parked in attached coalescing buffers
        self.flush_coalesced()
        self._check_overflow()
        if self._export_copy is None:
            state_sh = jax.tree.map(lambda _: self._state_sharding,
                                    self.state)
            self._export_copy = jax.jit(
                lambda k, v, s: (jnp.copy(k), jnp.copy(v),
                                 jax.tree.map(jnp.copy, s)),
                out_shardings=(self._key_sharding, self._val_sharding,
                               state_sh))
        keys_fut, vals_fut, state_fut = self._export_copy(
            self.keys, self.values, self.state)
        manifest = {"magic": self.KV_MAGIC, "name": self.name,
                    "capacity": self.capacity, "value_dim": self.value_dim,
                    "slots": self.slots, "num_buckets": self.num_buckets,
                    "dtype": self.dtype.name, "updater": self.updater.name,
                    "step": self.default_option.step}

        def finish():
            host_keys = np.asarray(keys_fut)
            # lanes fill contiguously (no deletion), so fill = live count
            fill = (~(host_keys == 0xFFFFFFFF).all(-1)).sum(-1)
            payload = {"keys": host_keys,
                       "values": np.asarray(vals_fut),
                       "bucket_fill": fill.astype(np.int32)}
            manifest["n_state_leaves"] = pack_state(state_fut, payload)
            self._record_op("store", payload["values"].size,
                            sum(a.nbytes for a in payload.values()))
            return manifest, payload
        return finish

    def store(self, uri: str) -> None:
        # every rank writes (per-process targets need their own copy);
        # shared-path safety comes from the stream layer's atomic rename
        # — same rationale as tables/base.py store
        manifest, payload = self.export_checkpoint_async()()
        savez_stream(uri, manifest, payload)

    def load(self, uri: str) -> None:
        # buffered deltas refer to the PRE-load state — flush them into
        # it before the restore replaces the triple
        self.flush_coalesced()
        # load is a table op: a pending overflow surfaces HERE, before
        # the restore replaces the state it refers to (a post-load raise
        # about pre-load state would be spurious)
        self._check_overflow()
        manifest, data = loadz_stream(uri, self.KV_MAGIC)
        for field in ("value_dim", "dtype"):
            mine = getattr(self, field) if field != "dtype" \
                else self.dtype.name
            theirs = manifest[field]
            if theirs != mine:
                raise ValueError(
                    f"kv table {field} mismatch: checkpoint {theirs!r} != "
                    f"table {mine!r}")
        if manifest["updater"] != self.updater.name:
            raise ValueError(
                f"checkpoint updater {manifest['updater']!r} != "
                f"{self.updater.name!r}")
        new_buckets = self.num_buckets
        if manifest["num_buckets"] != self.num_buckets \
                or manifest["slots"] != self.slots:
            # mesh-portable restore: num_buckets is padded to the mesh
            # model-axis size at construction, so a checkpoint written on
            # mp=2 has a different geometry than an mp=1/4 table.  Dense
            # tables repad (base.py); here the live triples are rehashed
            # into the current geometry instead.
            new_buckets, host_keys, host_vals, host_state = \
                self._rehash_checkpoint(manifest, data)
            state_src = {f"state_{i}": leaf
                         for i, leaf in enumerate(host_state)}
        else:
            host_keys = data["keys"]
            host_vals = data["values"]
            state_src = data
        keys_dev = jax.device_put(host_keys, self._key_sharding)
        vals_dev = jax.device_put(host_vals.astype(self.dtype),
                                  self._val_sharding)
        state_dev = unpack_state(
            state_src, manifest["n_state_leaves"], self.state,
            lambda leaf, tmpl: jax.device_put(leaf.astype(tmpl.dtype),
                                              self._state_sharding))
        # commit only after every new array placed: an exception above
        # (missing state leaf, placement failure) must leave the live
        # table consistent — geometry fields changing ahead of the
        # arrays would make get()/add() silently address wrong slots
        self._record_op("load", data["values"].size,
                        data["keys"].nbytes + data["values"].nbytes)
        self.keys, self.values, self.state = keys_dev, vals_dev, state_dev
        if new_buckets != self.num_buckets:
            log.warn(
                "kv table %r: rehash from %dx%d into %dx%d overflowed a "
                "bucket; geometry auto-grown to %dx%d (capacity %d -> "
                "%d) so the restore succeeds",
                self.name, manifest["num_buckets"], manifest["slots"],
                self.num_buckets, self.slots, new_buckets, self.slots,
                self.capacity, new_buckets * self.slots)
            self.num_buckets = new_buckets
            self.capacity = new_buckets * self.slots
        # slot assignment is device-derived: nothing host-side to rebuild
        self.default_option.step = int(manifest.get("step", 0))
        # load replaces live state: outstanding add-handles read superseded
        with self._option_lock:
            self.generation += 1
        self._notify_views()

    def _rehash_checkpoint(self, manifest, data):
        """Re-insert a checkpoint's live (key, value, state) triples into
        THIS table's (num_buckets, slots) geometry.

        Host-side: a checkpoint restore is not a hot path, and the insert
        needs data-dependent bucket occupancy that a fixed-shape device
        program handles worse than numpy.  Lane order within a bucket is
        the checkpoint's bucket-major traversal order — deterministic,
        and lookup/probe semantics don't depend on lane order.

        If a bucket of the requested geometry would overflow (restores
        into a smaller mesh/geometry concentrate keys), the bucket count
        DOUBLES until every key fits — restores succeed with a larger
        table instead of failing (runtime probes stay one-bucket; a
        spill-to-second-choice design would tax every get/add instead of
        this cold path).  Doubling preserves the model-axis shard
        divisibility established at construction.  Returns the chosen
        bucket count WITHOUT mutating the table — load() commits the
        geometry only after the new arrays are safely placed on device,
        so a failure mid-restore can't leave geometry fields ahead of
        the arrays."""
        ck_keys = data["keys"]                        # [B0, S0, 2] u32
        live = ~(ck_keys == np.uint32(0xFFFFFFFF)).all(-1)
        bb, ss = np.nonzero(live)
        k2 = ck_keys[bb, ss]                          # [n, 2]
        hashes = _hash_u64(_join_keys(k2))
        n = len(hashes)
        nb = self.num_buckets
        # occupancy-only check per doubling — via unique, O(n) memory
        # regardless of nb (a bincount(minlength=nb) would allocate
        # gigabytes before the pathological-collision guard could
        # fire); the full lane assignment runs once, for the geometry
        # that fits
        while n and np.unique(hashes % np.uint64(nb),
                              return_counts=True)[1].max() > self.slots:
            if nb >= 2 ** 30:
                raise ValueError(
                    f"kv table {self.name!r}: rehash from "
                    f"{manifest['num_buckets']}x{manifest['slots']} "
                    f"cannot fit every bucket even at {nb} buckets of "
                    f"{self.slots} slot(s). At small slots_per_bucket "
                    "the bucket count needed for n keys grows like the "
                    "birthday bound (~n^2 at 1 slot) — construct the "
                    "restoring table with slots_per_bucket >= 4 "
                    "instead of relying on geometry growth")
            nb *= 2
        buckets = (hashes % np.uint64(nb)).astype(np.int32)
        order = np.argsort(buckets, kind="stable")
        sb = buckets[order]
        # lane = rank within each bucket run of the sorted order
        pos = np.arange(n)
        run_start = np.concatenate([[True], sb[1:] != sb[:-1]]) \
            if n else np.zeros(0, bool)
        lane = pos - np.maximum.accumulate(np.where(run_start, pos, 0))
        kv_shape = (nb, self.slots)
        new_keys = np.full(kv_shape + (2,), 0xFFFFFFFF, np.uint32)
        new_keys[sb, lane] = k2[order]

        def remap(arr, fill):
            out_shape = kv_shape + arr.shape[2:]
            out = np.full(out_shape, fill, arr.dtype)
            out[sb, lane] = arr[bb, ss][order]
            return out

        new_vals = remap(data["values"], self.default_value)
        new_state = [remap(data[f"state_{i}"], 0)
                     for i in range(manifest["n_state_leaves"])]
        return nb, new_keys, new_vals, new_state
