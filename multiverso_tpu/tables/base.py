"""Table base: the Worker/Server table contract collapsed onto sharded
``jax.Array`` storage.

Reference mapping (upstream layout `include/multiverso/table_interface.h`,
`src/table.cpp`, `src/table_factory.cpp` — SURVEY.md §3.3/§3.9):

- ``WorkerTable::Get/Add/GetAsync/AddAsync/Wait`` → :meth:`Table.get`,
  :meth:`Table.add`, ``*_async`` variants returning :class:`Handle`,
  :meth:`Table.wait`. There is no Partition/ProcessReply machinery: the
  "partition across servers" is the array's ``NamedSharding``, and the
  request/reply round-trip is an XLA gather/scatter inside one compiled
  program.
- ``ServerTable::ProcessAdd`` (through the Updater) → a jitted
  ``(param, state, delta, option) -> (param, state)`` step with donated
  buffers, state sharded like params.
- ``ServerTable::Store/Load(Stream*)`` → :meth:`Table.store` /
  :meth:`Table.load` through the URI stream layer.
- ``TableFactory`` / ``MV_CreateTable(option)`` → :func:`create_table`
  dispatching on the option dataclass; tables registered process-wide
  with integer ids like the reference's table ids.

Sharding convention: tables shard their leading dimension over the mesh
``"model"`` axis (the analog of row-blocks across server shards). Sizes
that don't divide the shard count are zero-padded internally; the logical
size is preserved at the API boundary.
"""

from __future__ import annotations

import io
import json
import threading
import time
import weakref
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from multiverso_tpu import core
from multiverso_tpu.ft.chaos import chaos_corrupt, chaos_point
from multiverso_tpu.io import open_stream
from multiverso_tpu.telemetry import health as _health
from multiverso_tpu.telemetry import metrics as telemetry
from multiverso_tpu.telemetry import trace as tracing
from multiverso_tpu.telemetry.profiling import profiled_jit
from multiverso_tpu.updaters import (AddOption, Updater, get_updater,
                                     resolve_default_option)
from multiverso_tpu.utils import configure, log

CHECKPOINT_MAGIC = "multiverso_tpu.table.v1"


def _payload_crc32(arr: np.ndarray) -> int:
    """CRC32 over an array's raw bytes (C order) — the per-array
    checksum ``savez_stream`` stamps and ``loadz_stream`` verifies."""
    import zlib
    return int(zlib.crc32(np.ascontiguousarray(arr).tobytes()))


def savez_stream(uri: str, manifest: Dict[str, Any],
                 payload: Dict[str, np.ndarray]) -> None:
    """Write an npz (manifest json + arrays) through the stream layer.

    The manifest is stamped with a per-array CRC32 (verified at load:
    a torn or bit-rotted checkpoint fails LOUDLY instead of silently
    corrupting a resumed run), and the stream write is guarded by the
    env-configured IO :class:`~multiverso_tpu.ft.retry.RetryPolicy`
    (transient faults — including chaos-injected ones — are retried
    with jittered backoff and ``retry.*`` telemetry)."""
    from multiverso_tpu.ft.retry import io_retry_policy
    manifest = dict(manifest)
    manifest["crc32"] = {k: _payload_crc32(v) for k, v in payload.items()}
    buf = io.BytesIO()
    np.savez(buf, manifest=json.dumps(manifest), **payload)
    data = buf.getvalue()

    def write() -> None:
        with open_stream(uri, "wb") as stream:
            stream.write(data)
    io_retry_policy("io.store").call(write)


def loadz_stream(uri: str, magic: str):
    """Read an npz through the stream layer; validate its manifest magic
    and (when present) the per-array CRC32 checksums.
    Returns (manifest dict, npz data)."""
    from multiverso_tpu.ft.retry import io_retry_policy

    def read() -> bytes:
        with open_stream(uri, "rb") as stream:
            return stream.read()
    data = np.load(io.BytesIO(io_retry_policy("io.load").call(read)),
                   allow_pickle=False)
    try:
        manifest = json.loads(str(data["manifest"]))
    except Exception:
        raise ValueError(f"{uri!r} is not a multiverso_tpu checkpoint "
                         "(no manifest)") from None
    if manifest.get("magic") != magic:
        raise ValueError(f"{uri!r}: checkpoint magic "
                         f"{manifest.get('magic')!r} != expected {magic!r}")
    # checksum verification: pre-CRC checkpoints (no "crc32" key) load
    # unverified for back-compat; anything stamped must match
    for key, want in (manifest.get("crc32") or {}).items():
        if key not in data:
            raise ValueError(
                f"{uri!r}: checkpoint is torn — manifest lists payload "
                f"{key!r} but the archive lacks it")
        got = _payload_crc32(data[key])
        if got != int(want):
            raise ValueError(
                f"{uri!r}: payload {key!r} checksum mismatch "
                f"(crc32 {got:#010x} != manifest {int(want):#010x}) — "
                "the checkpoint is torn or bit-rotted; use an older "
                "complete generation")
    return manifest, data


def pack_state(state: Any, payload: Dict[str, np.ndarray]) -> int:
    """Add updater-state leaves to a checkpoint payload as state_{i}.
    Returns the leaf count (for the manifest)."""
    leaves = jax.tree.leaves(state)
    for i, leaf in enumerate(leaves):
        payload[f"state_{i}"] = np.asarray(leaf)
    return len(leaves)


def unpack_state(data, n_leaves: int, template_state: Any, convert) -> Any:
    """Rebuild an updater-state pytree from checkpoint leaves.
    ``convert(leaf_np, template_leaf)`` places one leaf on device."""
    leaves = [data[f"state_{i}"] for i in range(n_leaves)]
    _, treedef = jax.tree.flatten(template_state)
    tmpl = jax.tree.leaves(template_state)
    return jax.tree.unflatten(
        treedef, [convert(l, t) for l, t in zip(leaves, tmpl)])


class Handle:
    """Async completion handle (the reference's Waiter, SURVEY.md §3.7):
    wraps dispatched device values; ``wait()`` blocks until they land.

    Contract (explicit, generation-based — no exception sniffing):

    - A **get-handle** wraps a stable snapshot buffer (never donated);
      ``wait()`` blocks on it and returns exactly that snapshot.
    - An **add-handle** records the table and the *generation* its update
      produced. Updates apply in program order, so by the time the
      table's current buffers are ready, every generation ≤ the current
      one has been applied. ``wait()`` on an add-handle therefore blocks
      on the table's live buffers and returns the CURRENT param value —
      which is the handle's own result only while the handle is the
      latest update; a superseded handle returns the newer state (use
      :meth:`superseded` to distinguish). The original buffer is never
      touched after donation.
    """

    def __init__(self, values: Any = None, *, table: "Table" = None,
                 generation: Optional[int] = None) -> None:
        if (values is None) == (table is None):
            raise ValueError("Handle wraps either snapshot values or a "
                             "(table, generation) pair")
        self._values = values
        self._table = table
        self._generation = generation

    @property
    def generation(self) -> Optional[int]:
        """The table generation this add-handle's update produced
        (None for get-handles)."""
        return self._generation

    def superseded(self) -> bool:
        """True when a later update has been applied to the table since
        this handle was issued: ``wait()`` will return the newer state."""
        return (self._table is not None
                and self._table.generation > self._generation)

    def done(self) -> bool:
        """Non-blocking completion check.

        WARNING (add-handles): reports readiness of the table's CURRENT
        buffers, consistent with :meth:`wait`'s generation contract — so
        ``done()`` is NOT monotonic: it can flip back to False when a
        LATER add is dispatched after this handle's update already
        landed. Poll ``done() or superseded()`` to ask "has *my* update
        been applied"."""
        values = self._values if self._table is None \
            else self._table._live_buffers()
        return all(getattr(v, "is_ready", lambda: True)()
                   for v in jax.tree.leaves(values))

    def wait(self) -> Any:
        if self._table is None:
            jax.block_until_ready(self._values)
            return self._values
        # program order: the current buffers being ready implies this
        # handle's generation has been applied
        jax.block_until_ready(self._table._live_buffers())
        return self._table._live_value()

    # the reference's GetAsync returns data through the waiting buffer;
    # here the handle carries the result.
    def result(self) -> Any:
        return self.wait()


class Table:
    """Base class owning one sharded param array (+ updater state)."""

    def __init__(self, name: str, shape: Tuple[int, ...], dtype: Any,
                 *, updater: Optional[str] = None,
                 mesh: Optional[Mesh] = None,
                 init_value: Any = 0,
                 default_option: Optional[AddOption] = None,
                 shard_update: bool = False) -> None:
        self.name = name
        self.mesh = mesh if mesh is not None else core.mesh()
        self.logical_shape = tuple(shape)
        self.dtype = jnp.dtype(dtype)
        updater_name = updater if updater is not None \
            else configure.get_flag("updater_type")
        self.updater: Updater = get_updater(updater_name)
        self.default_option = resolve_default_option(updater_name,
                                                     default_option)
        self._option_lock = threading.Lock()
        # monotonically increasing update counter backing the Handle
        # generation contract (bumped on every applied update/load)
        self.generation = 0
        # client-pipeline hooks (weakrefs — a dropped CachedView or
        # CoalescingBuffer must not be pinned by its table):
        # views are woken on every generation bump so their background
        # refresh starts at the update, not at the next read; coalescers
        # are flushed by ops that must observe every buffered delta
        # (supersteps, store/load)
        self._view_refs: List[weakref.ref] = []
        self._coalescer_refs: List[weakref.ref] = []

        # weight-update sharding (cross-replica sharding of the weight
        # update, arXiv:2004.13336 — the ZeRO-2-on-TPU classic): shard
        # updater STATE (and so the state-update compute) over the data
        # axis too, instead of every data replica holding and updating
        # identical state. Costs ~one data-axis all-gather per add when
        # the param update needs the state; buys state memory and
        # update FLOPs divided by dp. Opt-in: best for whole-table adds
        # (the DP gradient push); row-streamed adds pay the gather per
        # call.
        dp = dict(self.mesh.shape).get(core.DATA_AXIS, 1)
        self.shard_update = bool(shard_update) and dp > 1

        # pad leading dim to a multiple of the model-axis size — and of
        # the model*data product under shard_update (subclasses override
        # _pad_lead to reserve scratch rows); dense checkpoints repad
        # across differing padded shapes, so the flag stays portable
        shards = self.mesh.shape[core.MODEL_AXIS]
        lead = self.logical_shape[0] if self.logical_shape else 1
        lead_mult = shards * dp if self.shard_update else shards
        padded_lead = self._pad_lead(lead, lead_mult)
        self.padded_shape = (padded_lead,) + self._pad_trailing(
            self.logical_shape[1:])
        # physical layout of the param array; subclasses may re-tile it
        # (storage_shape != padded_shape) while keeping the 2-D logical
        # contract — checkpoints always serialize the PADDED shape
        self.storage_shape = self.padded_shape
        self.spec = P(core.MODEL_AXIS, *([None] * (len(shape) - 1)))
        self.sharding = NamedSharding(self.mesh, self.spec)
        state_spec = P((core.MODEL_AXIS, core.DATA_AXIS),
                       *([None] * (len(shape) - 1))) \
            if self.shard_update else self.spec
        self.state_sharding = NamedSharding(self.mesh, state_spec)

        if callable(init_value):
            # made where it will live, in the padded shape: gigabytes are
            # neither built twice on the host nor, where they are zeros
            # (``core.sharded_zeros``), sent at all
            self.param = init_value(self.padded_shape, self.dtype,
                                    self.sharding)
        else:
            init = np.full(self.padded_shape, init_value, dtype=self.dtype) \
                if np.isscalar(init_value) \
                else self._pad(np.asarray(init_value))
            self.param = jax.device_put(init, self.sharding)
        # state leaves are zeros_like(param) shaped -> param sharding,
        # refined over the data axis under shard_update
        self.state = jax.tree.map(
            lambda s: jax.device_put(s, self.state_sharding),
            self.updater.init_state(self.param))
        state_sh = jax.tree.map(lambda _: self.state_sharding, self.state)
        # profiled_jit, not bare jax.jit: profile.calls{fn=table.apply.*}
        # is THE dispatch count of the Add path — the client pipeline's
        # coalescing contract ("K buffered adds -> 1 apply dispatch") is
        # asserted against it in tests and the micro-bench
        self._apply = profiled_jit(
            self.updater.apply, name=f"table.apply.{name}",
            donate_argnums=(0, 1),
            out_shardings=(self.sharding, state_sh))

        # whole-table snapshot: logical region, REPLICATED output (the
        # all-gather is the reference's whole-table Get; a replicated
        # result is also host-readable on every process of a multi-host
        # run, where a model-sharded array is not fully addressable)
        replicated = NamedSharding(
            self.mesh, P(*([None] * len(self.padded_shape))))
        slices = tuple(slice(0, l) for l in self.logical_shape)

        def snapshot(param):
            # jnp.copy guarantees a fresh buffer even when the slice is
            # the whole array and shardings coincide — the snapshot must
            # survive the next add's donation of the live buffer
            return jnp.copy(param[slices])

        # profiled: profile.calls{fn=table.snapshot.*} counts whole-table
        # Get dispatches — the number a CachedView exists to shrink
        self._snapshot = profiled_jit(snapshot,
                                      name=f"table.snapshot.{name}",
                                      out_shardings=replicated)
        # checkpoint-export copier, built lazily on the first export
        # (tables that never checkpoint pay nothing)
        self._export_copy = None
        self.table_id = _register(self)
        lbl = f"{self.table_id}:{self.name}"
        # tail-latency histograms over the dispatch paths (the SLO
        # monitor's table.{get,add}.p99 targets)
        self._h_get = telemetry.histogram(
            "table.get.seconds", telemetry.LATENCY_BUCKETS, table=lbl)
        self._h_add = telemetry.histogram(
            "table.add.seconds", telemetry.LATENCY_BUCKETS, table=lbl)
        log.debug("table %r id=%d shape=%s padded=%s updater=%s", name,
                  self.table_id, self.logical_shape, self.padded_shape,
                  self.updater.name)

    # -- helpers -----------------------------------------------------------

    def _record_op(self, op: str, elems: int, nbytes: int) -> None:
        """Per-table op accounting: ``table.<op>.{ops,elems,bytes}``
        keyed by table id (the telemetry spine's hot-path
        instrumentation — counts what the Get/Add/Store/Load contract
        actually moved). Shared by KVTable (not a subclass) via
        unbound-method assignment — only needs table_id + name."""
        lbl = f"{self.table_id}:{self.name}"
        telemetry.counter(f"table.{op}.ops", table=lbl).inc()
        telemetry.counter(f"table.{op}.elems", table=lbl).inc(int(elems))
        telemetry.counter(f"table.{op}.bytes", table=lbl).inc(int(nbytes))

    def _pad_lead(self, lead: int, shards: int) -> int:
        return -(-lead // shards) * shards

    def _pad_trailing(self, trailing: Tuple[int, ...]) -> Tuple[int, ...]:
        """The padded shape's further dimensions; a subclass may widen
        them (MatrixTable ``tile_aligned``). Get, Add, Store and Load
        pad and slice every dimension between the logical and the padded
        shape, so a checkpoint loads across tables padded differently."""
        return trailing

    def _pad(self, arr: np.ndarray) -> np.ndarray:
        if arr.shape == self.padded_shape:
            return arr.astype(self.dtype, copy=False)
        if arr.shape != self.logical_shape:
            raise ValueError(f"table {self.name!r}: value shape {arr.shape} "
                             f"!= table shape {self.logical_shape}")
        pad = [(0, p - l) for p, l in zip(self.padded_shape, arr.shape)]
        return np.pad(arr.astype(self.dtype, copy=False), pad)

    def _resolve_option(self, option: Optional[AddOption]) -> AddOption:
        opt = option if option is not None else self.default_option
        return opt.as_jax(self.mesh)

    def _bump_step(self) -> int:
        """Advance step + generation; returns the new generation. Handles
        must be minted from the RETURNED value — reading self.generation
        afterwards races with concurrent adds (a handle could carry a
        later add's generation and never read as superseded)."""
        with self._option_lock:
            self.default_option.step += 1
            self.generation += 1
            gen = self.generation
        self._notify_views()
        return gen

    # -- client-pipeline hooks (multiverso_tpu.client) ---------------------

    def _attach_view(self, view: Any) -> None:
        """Register a CachedView for update notification (weakref)."""
        self._view_refs.append(weakref.ref(view))

    def _attach_coalescer(self, buf: Any) -> None:
        """Register a CoalescingBuffer so flush-demanding table ops
        (supersteps, store/load) can force its buffered deltas out."""
        self._coalescer_refs.append(weakref.ref(buf))

    def _notify_views(self) -> None:
        """Wake attached CachedViews: the generation advanced, so their
        background refresh should start NOW rather than at the next
        read. Must stay cheap — it runs on every applied update."""
        refs = self._view_refs
        if not refs:
            return
        live = []
        for r in refs:
            v = r()
            if v is not None:
                v._on_table_update()
                live.append(r)
        self._view_refs[:] = live

    def flush_coalesced(self) -> None:
        """Flush every attached CoalescingBuffer's pending deltas into
        the table. Called by ops whose contract requires observing all
        prior adds (fused supersteps before they read/donate ``param``,
        store/load around checkpoints); plain ``get`` does NOT call this
        — a buffered delta is invisible until its flush, the bounded-
        staleness semantics coalescing opts into."""
        refs = self._coalescer_refs
        if not refs:
            return
        live = []
        for r in refs:
            b = r()
            if b is not None:
                b.flush()
                live.append(r)
        self._coalescer_refs[:] = live

    # -- the Get/Add contract ---------------------------------------------

    def raw(self) -> jax.Array:
        """The padded device array — a LIVE view of table storage: the next
        ``add`` donates this buffer to XLA, invalidating the reference.
        Use :meth:`get_jax` for a stable snapshot."""
        return self.param

    def put_raw(self, padded: jax.Array) -> None:
        """Replace table storage with a device value of the STORAGE shape
        (placed to the table's sharding). The supported way for apps to
        install computed initial state (e.g. LDA's count build); advances
        the generation so outstanding add-handles read as superseded.
        Updater state is untouched."""
        if tuple(padded.shape) != self.storage_shape:
            raise ValueError(
                f"table {self.name!r}: put_raw shape {tuple(padded.shape)} "
                f"!= storage shape {self.storage_shape}")
        if padded.dtype != self.dtype:
            raise ValueError(
                f"table {self.name!r}: put_raw dtype {padded.dtype} != "
                f"table dtype {self.dtype}")
        self.param = jax.device_put(padded, self.sharding)
        with self._option_lock:
            self.generation += 1
        self._notify_views()

    def get_jax(self) -> jax.Array:
        """Device-resident logical value (slices off padding), replicated.

        Returns a fresh buffer: ``add`` donates the param buffer, so a
        zero-copy view would be invalidated by the next update.
        """
        chaos_point("table.get")
        t0 = time.monotonic()
        with tracing.span("table.get",
                          table=f"{self.table_id}:{self.name}"):
            elems = int(np.prod(self.logical_shape)) \
                if self.logical_shape else 1
            self._record_op("get", elems, elems * self.dtype.itemsize)
            _health.observe_param(self)
            out = self._snapshot(self.param)
        self._h_get.observe(time.monotonic() - t0)
        return out

    def get(self) -> np.ndarray:
        """Whole-table fetch to host (``WorkerTable::Get``)."""
        return np.asarray(self.get_jax())

    def get_async(self) -> Handle:
        """Non-blocking whole-table Get: the returned handle wraps the
        DEVICE snapshot (a future — dispatch is async), so nothing
        round-trips to host unless the caller converts the waited value
        (``np.asarray(h.wait())``)."""
        return Handle(self.get_jax())

    def add(self, delta: Any, option: Optional[AddOption] = None,
            sync: bool = False) -> Handle:
        """``WorkerTable::Add``: fold a delta through the updater.

        Dispatch is asynchronous (XLA async dispatch); ``sync=True`` blocks
        until the update has been applied, matching the reference's
        blocking Add.
        """
        chaos_point("table.add")
        delta = chaos_corrupt("table.add", delta)
        t0 = time.monotonic()
        with tracing.span("table.add",
                          table=f"{self.table_id}:{self.name}",
                          sync=sync):
            if isinstance(delta, jax.Array):
                if delta.shape == self.logical_shape \
                        and self.logical_shape != self.padded_shape:
                    pad = [(0, p - l) for p, l in zip(self.padded_shape,
                                                      delta.shape)]
                    delta = jnp.pad(delta, pad)
                elif delta.shape != self.padded_shape:
                    if delta.shape != self.logical_shape:
                        raise ValueError(
                            f"table {self.name!r}: delta shape "
                            f"{delta.shape} != table shape "
                            f"{self.logical_shape}")
            else:
                delta = self._pad(np.asarray(delta))
            if self.storage_shape != self.padded_shape:
                # re-tiled storage layouts (SparseMatrixTable
                # tiled=True): same elements, tile-aligned shape
                delta = delta.reshape(self.storage_shape)
            elems = int(np.prod(self.logical_shape)) \
                if self.logical_shape else 1
            self._record_op("add", elems, elems * self.dtype.itemsize)
            _health.observe_update(self, delta)
            opt = self._resolve_option(option)
            self.param, self.state = self._apply(self.param, self.state,
                                                 delta, opt)
            _health.observe_param(self)
            handle = Handle(table=self, generation=self._bump_step())
            if sync:
                handle.wait()
        self._h_add.observe(time.monotonic() - t0)
        return handle

    add_async = add

    def wait(self) -> None:
        """Block until all outstanding updates on this table are applied."""
        jax.block_until_ready(self._live_buffers())

    def _live_buffers(self) -> Any:
        """The buffers an add-handle's wait() blocks on (KVTable adds its
        key store)."""
        return (self.param, self.state)

    def _live_value(self) -> Any:
        """What an add-handle's wait() returns: the current param array."""
        return self.param

    # -- checkpoint (ServerTable::Store/Load) ------------------------------

    def _manifest(self) -> Dict[str, Any]:
        return {
            "magic": CHECKPOINT_MAGIC,
            "kind": type(self).__name__,
            "name": self.name,
            "logical_shape": list(self.logical_shape),
            "padded_shape": list(self.padded_shape),
            "dtype": self.dtype.name,
            "updater": self.updater.name,
            "step": self.default_option.step,
        }

    def _install_param(self, host_padded: np.ndarray) -> None:
        """Place a host array of the padded shape into table storage."""
        self.param = jax.device_put(
            host_padded.reshape(self.storage_shape), self.sharding)

    def export_checkpoint_async(self):
        """The checkpoint export, split along the thread-safety line
        (the :class:`~multiverso_tpu.ft.checkpoint.RunCheckpointManager`
        overlap contract, same split as ``client/cache.py``):

        - the DISPATCH half runs here, on the caller's (table dispatch)
          thread: flush attached coalescers, then launch one jitted
          copy of param + state into fresh buffers — the copies survive
          the next add's donation, and under ``shard_update`` the state
          gathers to the model-only sharding (per-process addressable),
        - the returned ``finish()`` closure is the BLOCKING half, safe
          on a worker thread: D2H waits, payload assembly, accounting.

        ``finish()`` returns ``(manifest, payload)`` ready for
        :func:`savez_stream`.
        """
        # a checkpoint must contain every delta the worker has issued,
        # including ones still parked in attached coalescing buffers
        self.flush_coalesced()
        manifest = self._manifest()
        if self._export_copy is None:
            state_sh = jax.tree.map(lambda _: self.sharding, self.state)
            self._export_copy = jax.jit(
                lambda p, s: (jnp.copy(p),
                              jax.tree.map(jnp.copy, s)),
                out_shardings=(self.sharding, state_sh))
        param_fut, state_fut = self._export_copy(self.param, self.state)

        def finish():
            payload = {"param": np.asarray(param_fut)
                       .reshape(self.padded_shape)}
            manifest["n_state_leaves"] = pack_state(state_fut, payload)
            self._record_op("store", payload["param"].size,
                            sum(a.nbytes for a in payload.values()))
            return manifest, payload
        return finish

    def store(self, uri: str) -> None:
        """Serialize param + updater state through the stream layer.

        Multi-process: COLLECTIVE — every rank runs the export fetch (a
        device collective) and every rank writes, so per-process targets
        (mem://, per-host local disks) each get a copy; on a shared
        filesystem the identical payloads land via the stream layer's
        atomic rename, so same-path writers never interleave."""
        manifest, payload = self.export_checkpoint_async()()
        savez_stream(uri, manifest, payload)

    def load(self, uri: str) -> None:
        # buffered deltas refer to the PRE-load state — flush them into
        # it before the restore replaces param/state (dropping them
        # silently, or applying them onto restored state, would both be
        # wrong orders)
        self.flush_coalesced()
        manifest, data = loadz_stream(uri, CHECKPOINT_MAGIC)
        if tuple(manifest["logical_shape"]) != self.logical_shape:
            raise ValueError(
                f"checkpoint shape {manifest['logical_shape']} != table "
                f"shape {list(self.logical_shape)}")
        if manifest["updater"] != self.updater.name:
            raise ValueError(
                f"checkpoint updater {manifest['updater']!r} != table "
                f"updater {self.updater.name!r}")
        def repad(arr: np.ndarray, want_shape, want_dtype):
            # slice to the logical region, then pad to the current padded
            # shape — the checkpoint may come from a different shard count
            if arr.shape != want_shape:
                arr = arr[tuple(slice(0, l) for l in self.logical_shape)]
                pad = [(0, p - l) for p, l in zip(want_shape, arr.shape)]
                arr = np.pad(arr, pad)
            return arr.astype(want_dtype)

        n_leaves = int(manifest["n_state_leaves"])
        self._record_op("load", data["param"].size,
                        data["param"].nbytes + sum(
                            data[f"state_{i}"].nbytes
                            for i in range(n_leaves)))
        self._install_param(repad(data["param"], self.padded_shape,
                                  self.dtype))
        self.state = unpack_state(
            data, n_leaves, self.state,
            lambda leaf, tmpl: jax.device_put(
                repad(leaf, tmpl.shape, tmpl.dtype), self.state_sharding))
        self.default_option.step = int(manifest.get("step", 0))
        # load replaces live state: outstanding add-handles must read as
        # superseded (generation contract: bumped on every applied
        # update/load)
        with self._option_lock:
            self.generation += 1
        self._notify_views()


# -- process-wide table registry (TableFactory / table ids) ---------------

_TABLES: List[Table] = []
_REG_LOCK = threading.Lock()


def _register(table: Table) -> int:
    with _REG_LOCK:
        _TABLES.append(table)
        return len(_TABLES) - 1


def get_table(table_id: int) -> Table:
    with _REG_LOCK:
        return _TABLES[table_id]


def num_tables() -> int:
    with _REG_LOCK:
        return len(_TABLES)


def reset_tables() -> None:
    """Drop all registered tables (tests / shutdown)."""
    with _REG_LOCK:
        _TABLES.clear()
