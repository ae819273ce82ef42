"""MatrixTable: 2-D dense row-major table with row-subset Get/Add.

Reference: `include/multiverso/table/matrix_table.h` (upstream layout;
SURVEY.md §3.3) — row-sharded across servers; Get/Add of the whole matrix
or an arbitrary row-id list; word2vec's embedding store
(``MatrixWorkerTable<T>::Get(row_ids, ...)``, ``Add(row_ids, deltas)``).

TPU design:

- storage is one row-sharded array (``P("model", None)``); the reference's
  row→server partition map is the sharding.
- ``get_rows(ids)`` is a jitted gather (XLA inserts the collectives); the
  six-thread-hop request/reply path of the reference (SURVEY.md §4.2)
  becomes one compiled op.
- ``add_rows(ids, deltas)`` for the ``default`` updater is a jitted
  duplicate-safe scatter-add; for stateful updaters it is
  gather→updater→masked scatter, touching only the addressed rows (the
  reference applies the updater only to rows present in the Add).
- row-count-dependent shapes are bucketed to powers of two and padded, so
  the jit cache stays small; padded lanes scatter into a reserved scratch
  row that lives beyond the logical row range.
- ``tile_aligned=True`` holds the table in whole (8, 128) tiles: each
  shard's rows padded to a multiple of 8, the columns to a multiple of
  128, the padding zero. The device then stores it row by row (a
  [N, 300] float32 array is held column-major, and every program that
  gathers its rows first copies it whole), and a kernel may address its
  8-row groups by DMA (``ops/distinct_rows.py``). The logical shape, and
  so Get, Add and a checkpoint's portability, stay [num_rows, num_cols].
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from multiverso_tpu import core
from multiverso_tpu.ft.chaos import chaos_corrupt
from multiverso_tpu.ops import table_kernels as tk
from multiverso_tpu.tables.base import Handle, Table
# _bucket lives in tables/hashing.py now (shared with the kernel
# engine); re-imported here for historical import sites
from multiverso_tpu.tables.hashing import _bucket, shard_lane_slices
from multiverso_tpu.telemetry import health as _health
from multiverso_tpu.telemetry.profiling import profiled_jit
from multiverso_tpu.updaters import AddOption


@dataclasses.dataclass
class MatrixTableOption:
    num_rows: int
    num_cols: int
    dtype: Any = "float32"
    init_value: Any = 0
    updater: Optional[str] = None
    name: str = "matrix_table"
    shard_update: bool = False   # data-axis weight-update sharding


class MatrixTable(Table):
    def __init__(self, num_rows: int, num_cols: int, dtype: Any = "float32",
                 *, init_value: Any = 0, updater: Optional[str] = None,
                 mesh: Optional[Mesh] = None, name: str = "matrix_table",
                 default_option: Optional[AddOption] = None,
                 shard_update: bool = False,
                 tile_aligned: bool = False) -> None:
        if num_rows <= 0 or num_cols <= 0:
            raise ValueError(f"MatrixTable dims must be positive, got "
                             f"{num_rows}x{num_cols}")
        self._tile_aligned = tile_aligned   # read by the padding hooks
        super().__init__(name, (num_rows, num_cols), dtype, updater=updater,
                         mesh=mesh, init_value=init_value,
                         default_option=default_option,
                         shard_update=shard_update)
        # scratch row: guaranteed > logical rows (base padding reserves it)
        self._scratch_row = self.padded_shape[0] - 1
        assert self._scratch_row >= self.logical_shape[0], \
            "scratch row must live in the padded area"
        # row→shard ownership is contiguous equal blocks over the model
        # axis (base padding makes the lead divisible), so a sort by
        # row id IS a sort by shard-then-row — the sharded lane
        # slicer's precondition
        self._shards = self.mesh.shape[core.MODEL_AXIS]
        self._rows_per_shard = self.padded_shape[0] // self._shards
        self._build_jits()

    # base class hook: reserve at least one padding row for scatter scratch
    def _pad_lead(self, lead: int, shards: int) -> int:
        if self._tile_aligned:
            shards *= tk.SUBLANES
        return -(-(lead + 1) // shards) * shards

    def _pad_trailing(self, trailing):
        if not self._tile_aligned:
            return trailing
        return (-(-trailing[0] // tk.LANES) * tk.LANES,)

    @property
    def num_rows(self) -> int:
        return self.logical_shape[0]

    @property
    def num_cols(self) -> int:
        return self.logical_shape[1]

    # -- jitted kernels ----------------------------------------------------

    def _build_jits(self) -> None:
        replicated = NamedSharding(self.mesh, P(None, None))

        def gather_rows(param, ids):
            return jnp.take(param, ids, axis=0)

        def scatter_add(param, ids, deltas):
            return param.at[ids].add(deltas.astype(param.dtype))

        state_sh = jax.tree.map(lambda _: self.state_sharding, self.state)

        def gather_apply_scatter(param, state, ids, deltas, mask, option):
            rows = jnp.take(param, ids, axis=0)
            st_rows = jax.tree.map(lambda s: jnp.take(s, ids, axis=0), state)
            new_rows, new_st = self.updater.apply(rows, st_rows, deltas,
                                                  option)
            m = mask[:, None]
            new_rows = jnp.where(m, new_rows, rows)
            param = param.at[ids].set(new_rows.astype(param.dtype))
            state = jax.tree.map(
                lambda s, ns, olds: s.at[ids].set(
                    jnp.where(m, ns, olds).astype(s.dtype)),
                state, new_st, st_rows)
            return param, state

        # profiled: profile.calls{fn=table.{gather,scatter_add,
        # apply_rows}.<name>} count the row-path dispatches the client
        # pipeline's row coalescing / caching are measured against.
        # Gather and scatter-add register behind the kernel engine
        # (MVTPU_KERNELS; per-shard shard_map grids on multi-device
        # meshes); apply_rows (stateful row updates) stays XLA-only.
        self._gather_rows = tk.select_kernel(
            f"table.gather.{self.name}",
            xla=profiled_jit(
                gather_rows, name=f"table.gather.{self.name}",
                out_shardings=replicated),
            mesh=self.mesh, **self._pallas_rows(
                "gather", tk.build_row_gather,
                tk.build_row_gather_sharded, 0,
                out_shardings=replicated))
        self._scatter_add = tk.select_kernel(
            f"table.scatter_add.{self.name}",
            xla=profiled_jit(
                scatter_add, name=f"table.scatter_add.{self.name}",
                donate_argnums=(0,)),
            mesh=self.mesh, **self._pallas_rows(
                "scatter_add", tk.build_row_scatter_add,
                tk.build_row_scatter_add_sharded, 0,
                donate_argnums=(0,)))
        self._gather_apply_scatter = profiled_jit(
            gather_apply_scatter, name=f"table.apply_rows.{self.name}",
            donate_argnums=(0, 1),
            out_shardings=(self.sharding, state_sh))

    def _pallas_rows(self, op: str, build, build_sharded, tiles: int,
                     **jit_kw) -> dict:
        """The ``pallas``/``pallas_sharded`` factories of one row kernel
        for :func:`tk.select_kernel` — none for a dtype the kernels'
        (8, 128) 32-bit blocks cannot hold."""
        if self.dtype.itemsize != 4:
            return {}
        name = f"table.{op}.{self.name}.pallas"
        kw = dict(num_cols=self.padded_shape[1], tiles=tiles,
                  interpret=tk.interpret_mode(self.mesh))
        return dict(
            pallas=lambda: profiled_jit(build(**kw), name=name, **jit_kw),
            pallas_sharded=lambda: profiled_jit(
                build_sharded(**kw, mesh=self.mesh, axis=core.MODEL_AXIS,
                              lead=self.padded_shape[0]),
                name=name, **jit_kw))

    def _pad_ids(self, ids: np.ndarray,
                 deltas: Optional[np.ndarray] = None, *,
                 sort: bool = False):
        # scatter paths stable-sort by row id: the Pallas scatter engine
        # segment-sums each touched row's run in VMEM (requires sorted
        # ids), XLA's duplicate-combining scatter is order-insensitive,
        # and the scratch-row padding (the max row id) keeps the array
        # sorted. Gathers must NOT sort — output order is request order.
        if sort and len(ids) > 1:
            order = np.argsort(ids, kind="stable")
            ids = ids[order]
            if deltas is not None:
                deltas = deltas[order]
        n = len(ids)
        b = _bucket(n)
        out_ids = np.full(b, self._scratch_row, dtype=np.int32)
        out_ids[:n] = ids
        mask = np.zeros(b, dtype=bool)
        mask[:n] = True
        if deltas is None:
            return out_ids, mask, n
        out_d = np.zeros((b, deltas.shape[1]), dtype=deltas.dtype)
        out_d[:n] = deltas
        return out_ids, mask, n, out_d

    def _pad_ids_sharded(self, ids: np.ndarray,
                         deltas: Optional[np.ndarray] = None, *,
                         sort: bool = False):
        """Lane-slice prep for the sharded engines: group lanes by
        owning shard (scatters sort by GLOBAL row id, which implies it
        and keeps each shard's lanes row-sorted for the run-scan
        kernels) and slice into per-shard rows of LOCAL ids via
        ``shard_lane_slices``. Padding lanes carry the shard's max
        local id (keeps in-shard sortedness; their writes are masked).
        Returns ``(local_ids, valid, inv, n[, deltas])`` with the
        lane-sliced (shards, L, ...) layout; ``inv`` is the pow2-padded
        flat ``shard*L + pos`` map gathers unpermute through."""
        rps = self._rows_per_shard
        if len(ids) > 1:
            key = ids if sort else ids // rps
            order = np.argsort(key, kind="stable")
            ids = ids[order]
            if deltas is not None:
                deltas = deltas[order]
        else:
            order = np.arange(len(ids))
        shard_ids = ids // rps
        local = (ids - shard_ids * rps).astype(np.int32)
        arrays, pads = [local], [np.int32(rps - 1)]
        if deltas is not None:
            arrays.append(deltas)
            pads.append(0)
        sliced, valid, pos = shard_lane_slices(shard_ids, self._shards,
                                               arrays, pads)
        n = len(ids)
        lanes = sliced[0].shape[1]
        inv = np.zeros(_bucket(n), np.int32)
        inv[order] = (shard_ids * lanes + pos).astype(np.int32)
        if deltas is None:
            return sliced[0], valid, inv, n
        return sliced[0], valid, inv, n, sliced[1]

    # -- row API -----------------------------------------------------------

    def _gather_dispatch(self, ids: np.ndarray):
        """One gather dispatch in whichever operand layout the selected
        engine wants; returns the device rows future (first n real,
        without a tile-aligned table's padding columns)."""
        if self._gather_rows.layout == "sharded":
            sl_ids, _valid, inv, n = self._pad_ids_sharded(ids)
            rows = self._gather_rows(self.param, sl_ids, inv)
        else:
            padded, _, n = self._pad_ids(ids)
            rows = self._gather_rows(self.param, padded)
        return rows[:n, :self.num_cols]

    def get_rows(self, row_ids) -> np.ndarray:
        """Fetch a list of rows (``MatrixWorkerTable::Get(row_ids, ...)``)."""
        ids = np.asarray(row_ids, dtype=np.int32)
        self._check_ids(ids)
        n = len(ids)
        self._record_op("get", n * self.num_cols,
                        n * self.num_cols * self.dtype.itemsize)
        return np.asarray(self._gather_dispatch(ids))

    def get_rows_async(self, row_ids) -> Handle:
        ids = np.asarray(row_ids, dtype=np.int32)
        self._check_ids(ids)
        n = len(ids)
        self._record_op("get", n * self.num_cols,
                        n * self.num_cols * self.dtype.itemsize)
        return Handle(self._gather_dispatch(ids))

    def add_rows(self, row_ids, deltas, option: Optional[AddOption] = None,
                 sync: bool = False) -> Handle:
        """Apply deltas to a row subset (``MatrixWorkerTable::Add(rows)``).

        With the ``default`` updater duplicate row ids accumulate (true
        scatter-add). Stateful updaters (adagrad/momentum/adam) require
        unique row ids per call — pre-aggregate duplicates first (the
        reference's client-side Aggregator role).
        """
        ids = np.asarray(row_ids, dtype=np.int32)
        self._check_ids(ids)
        deltas = np.asarray(deltas)
        if deltas.shape != (len(ids), self.num_cols):
            raise ValueError(f"deltas shape {deltas.shape} != "
                             f"({len(ids)}, {self.num_cols})")
        deltas = chaos_corrupt("table.add", deltas)
        self._record_op("add", deltas.size,
                        deltas.size * self.dtype.itemsize)
        _health.observe_update(self, deltas)
        if self.padded_shape[1] != self.num_cols:     # tile_aligned
            deltas = np.pad(deltas, ((0, 0), (
                0, self.padded_shape[1] - self.num_cols)))
        if self.updater.name in ("default", "sgd"):
            if self.updater.name == "sgd":
                # stateless: scatter-add of -lr*delta, duplicate-safe
                lr = float(option.learning_rate if option is not None
                           else self.default_option.learning_rate)
                deltas = -lr * deltas
            if self._scatter_add.layout == "sharded":
                sl_ids, valid, _inv, _n, sl_d = self._pad_ids_sharded(
                    ids, deltas, sort=True)
                self.param = self._scatter_add(self.param, sl_ids, sl_d,
                                               valid)
            else:
                padded, _, _, pd = self._pad_ids(ids, deltas, sort=True)
                self.param = self._scatter_add(self.param, padded, pd)
        else:
            if len(np.unique(ids)) != len(ids):
                raise ValueError(
                    f"add_rows with stateful updater "
                    f"{self.updater.name!r} requires unique row ids; "
                    "pre-aggregate duplicates (Aggregator role)")
            opt = self._resolve_option(option)
            padded, mask, _, pd = self._pad_ids(ids, deltas)
            self.param, self.state = self._gather_apply_scatter(
                self.param, self.state, padded, pd, mask, opt)
        handle = Handle(table=self, generation=self._bump_step())
        if sync:
            handle.wait()
        return handle

    def _check_ids(self, ids: np.ndarray) -> None:
        if len(ids) == 0:
            raise ValueError("empty row id list")
        if ids.min() < 0 or ids.max() >= self.num_rows:
            raise ValueError(f"row ids out of range [0, {self.num_rows}): "
                             f"min={ids.min()} max={ids.max()}")
