"""SparseMatrixTable: matrix table with COO sparse Add and sparse-row Get.

Reference: `include/multiverso/table/sparse_matrix_table.h` (upstream
layout; SURVEY.md §3.3) — a matrix table variant where Add carries
(row, col, value) sparse deltas and Get returns only requested rows;
LightLDA's word-topic count store.

TPU design (SURVEY.md §3.9): storage stays DENSE and row-sharded (TPU HBM
is fine with dense counts; vocab×topics fits comfortably), and the sparse
COO Add becomes a jitted duplicate-safe ``.at[rows, cols].add(values)``
scatter — XLA lowers this to a sorted segment scatter on TPU. COO batch
lengths are bucketed to powers of two; padded lanes scatter zeros into a
reserved scratch row.

Tiled storage (``tiled=True``, requires ``num_cols % 128 == 0``): the
physical array is ``[rows, C, 128]`` with ``C = num_cols/128``, so ONE
LOGICAL ROW IS EXACTLY ONE (8,128) int32 TPU TILE — a random row gather
reads a 4 KB payload instead of the 32 KB tile-span the 2-D layout
incurs (8 consecutive rows share each tile). This is the layout the LDA
Gibbs superstep's gathers/scatters want (benchmarks/experiments/
lda_tile_probe.py); the PUBLIC API stays 2-D — row/COO/checkpoint
operations reshape at the jit boundary, and checkpoints serialize the
layout-agnostic padded 2-D shape either way.

Sparse adds are supported for the stateless updaters (``default`` — the
LightLDA count case — and ``sgd``). Stateful updaters would need
per-element state touched only at COO positions; the reference never uses
them with sparse tables either.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from multiverso_tpu import core
from multiverso_tpu.ft.chaos import chaos_corrupt
from multiverso_tpu.ops import table_kernels as tk
from multiverso_tpu.tables.base import Handle
from multiverso_tpu.tables.hashing import _bucket, shard_lane_slices
from multiverso_tpu.tables.matrix_table import MatrixTable
from multiverso_tpu.telemetry import health as _health
from multiverso_tpu.telemetry.profiling import profiled_jit
from multiverso_tpu.updaters import AddOption

LANES = 128


@dataclasses.dataclass
class SparseMatrixTableOption:
    num_rows: int
    num_cols: int
    dtype: Any = "float32"
    init_value: Any = 0
    updater: Optional[str] = None
    name: str = "sparse_matrix_table"
    tiled: bool = False


class SparseMatrixTable(MatrixTable):
    def __init__(self, num_rows: int, num_cols: int,
                 dtype: Any = "float32", *, init_value: Any = 0,
                 updater: Optional[str] = None, mesh=None,
                 name: str = "sparse_matrix_table",
                 default_option: Optional[AddOption] = None,
                 tiled: bool = False) -> None:
        if tiled and num_cols % LANES:
            raise ValueError(f"tiled storage needs num_cols % {LANES} == 0,"
                             f" got {num_cols}")
        self.tiled = tiled
        self.tiles = num_cols // LANES if tiled else 0
        super().__init__(num_rows, num_cols, dtype, init_value=init_value,
                         updater=updater, mesh=mesh, name=name,
                         default_option=default_option)
        if self.updater.name not in ("default", "sgd"):
            raise ValueError(
                f"SparseMatrixTable supports stateless updaters "
                f"(default, sgd), got {self.updater.name!r}")
        if tiled:
            self._retile_storage()
        self._build_sparse_jits()

    # -- tiled layout ------------------------------------------------------

    def _retile_storage(self) -> None:
        """Swap the 2-D param for the [rows, C, 128] tile-aligned layout
        (state is the empty pytree — stateless updaters enforced)."""
        c = self.tiles
        self.storage_shape = (self.padded_shape[0], c, LANES)
        self.spec = P(core.MODEL_AXIS, None, None)
        self.sharding = NamedSharding(self.mesh, self.spec)
        host = np.asarray(self.param).reshape(self.storage_shape)
        self.param = jax.device_put(host, self.sharding)

        replicated = NamedSharding(self.mesh, P(None, None))
        n_rows, n_cols = self.logical_shape

        def snapshot(param):
            p2 = param.reshape(self.padded_shape)
            return jnp.copy(p2[:n_rows, :n_cols])

        # profiled like the base kernels (tiled layouts replace them)
        self._snapshot = profiled_jit(
            snapshot, name=f"table.snapshot.{self.name}",
            out_shardings=replicated)

        def gather_rows(param, ids):
            rows = jnp.take(param, ids, axis=0)      # [n, C, 128]
            return rows.reshape(ids.shape[0], n_cols)

        def scatter_add(param, ids, deltas):
            d3 = deltas.reshape(ids.shape[0], c, LANES)
            return param.at[ids].add(d3.astype(param.dtype))

        # tiled layouts re-register behind the kernel engine with
        # tiles=c (one logical row = one (8,128) tile — the layout the
        # Pallas row kernels want)
        self._gather_rows = tk.select_kernel(
            f"table.gather.{self.name}",
            xla=profiled_jit(
                gather_rows, name=f"table.gather.{self.name}",
                out_shardings=replicated),
            mesh=self.mesh, **self._pallas_rows(
                "gather", tk.build_row_gather,
                tk.build_row_gather_sharded, c,
                out_shardings=replicated))
        self._scatter_add = tk.select_kernel(
            f"table.scatter_add.{self.name}",
            xla=profiled_jit(
                scatter_add, name=f"table.scatter_add.{self.name}",
                donate_argnums=(0,)),
            mesh=self.mesh, **self._pallas_rows(
                "scatter_add", tk.build_row_scatter_add,
                tk.build_row_scatter_add_sharded, c,
                donate_argnums=(0,)))
        # _gather_apply_scatter is unreachable: stateless updaters only

    # -- jitted sparse kernels --------------------------------------------

    def _build_sparse_jits(self) -> None:
        if self.tiled:
            def coo_scatter_add(param, rows, cols, vals):
                return param.at[rows, cols // LANES, cols % LANES].add(
                    vals.astype(param.dtype))
        else:
            def coo_scatter_add(param, rows, cols, vals):
                return param.at[rows, cols].add(vals.astype(param.dtype))

        # profiled: the COO Add dispatch count (client coalescing of
        # sparse adds is asserted against profile.calls on this name).
        # Registered behind the kernel engine: the Pallas COO kernel
        # segment-sums each touched row's entries in VMEM and writes the
        # row back to HBM once (requires add_sparse's row sort).
        self._coo_scatter_add = tk.select_kernel(
            f"table.coo_scatter_add.{self.name}",
            xla=profiled_jit(
                coo_scatter_add,
                name=f"table.coo_scatter_add.{self.name}",
                donate_argnums=(0,)),
            mesh=self.mesh, **self._pallas_rows(
                "coo_scatter_add", tk.build_coo_scatter_add,
                tk.build_coo_scatter_add_sharded, self.tiles,
                donate_argnums=(0,)))

        replicated = NamedSharding(self.mesh, P(None))
        n_cols = self.num_cols

        @partial(jax.jit, out_shardings=replicated)
        def row_nnz(param, ids):
            rows = jnp.take(param, ids, axis=0).reshape(ids.shape[0],
                                                        n_cols)
            return (rows != 0).sum(axis=1).astype(jnp.int32)

        self._row_nnz = row_nnz
        # per-k jitted top-k extractors (k is a trace constant; cache keeps
        # the jit-churn bounded the same way _bucket bounds id lengths)
        self._topk_jits: Dict[int, Any] = {}

    def _topk_fn(self, k: int):
        fn = self._topk_jits.get(k)
        if fn is None:
            replicated = NamedSharding(self.mesh, P(None, None))
            n_cols = self.num_cols

            @partial(jax.jit, out_shardings=(replicated, replicated))
            def topk(param, ids):
                rows = jnp.take(param, ids, axis=0).reshape(ids.shape[0],
                                                            n_cols)
                mag = jnp.abs(rows.astype(jnp.float32))
                _, cols = lax.top_k(mag, k)
                vals = jnp.take_along_axis(rows, cols, axis=1)
                return cols.astype(jnp.int32), vals

            fn = self._topk_jits[k] = topk
        return fn

    # (whole-table dense add comes from Table.add — the base class
    # reshapes normalized deltas to storage_shape for tiled layouts)

    # -- COO sparse Add ----------------------------------------------------

    def add_sparse(self, rows, cols, values,
                   option: Optional[AddOption] = None,
                   sync: bool = False) -> Handle:
        """COO sparse Add: ``param[rows[i], cols[i]] += values[i]``.

        Duplicate (row, col) pairs accumulate. With the ``sgd`` updater the
        values are treated as gradients: ``param -= lr * values``.
        """
        rows = np.asarray(rows, dtype=np.int32)
        cols = np.asarray(cols, dtype=np.int32)
        values = np.asarray(values)
        if not (rows.shape == cols.shape == values.shape) or rows.ndim != 1:
            raise ValueError(
                f"COO arrays must be same-length 1-D, got rows={rows.shape} "
                f"cols={cols.shape} values={values.shape}")
        if len(rows) == 0:
            raise ValueError("empty COO add")
        self._check_ids(rows)
        if cols.min() < 0 or cols.max() >= self.num_cols:
            raise ValueError(f"col ids out of range [0, {self.num_cols})")

        n = len(rows)
        values = chaos_corrupt("table.add", values)
        self._record_op("add", n, n * self.dtype.itemsize)
        _health.observe_update(self, values)
        # stable row sort: the Pallas COO engine segment-sums each row's
        # run in VMEM (requires sorted rows; same-(row,col) duplicates
        # keep their input order, so float accumulation order matches
        # the XLA scatter on the same sorted batch), and the scratch-row
        # padding (the max row id) keeps the array sorted
        order = np.argsort(rows, kind="stable")
        rows, cols, values = rows[order], cols[order], values[order]
        if self.updater.name == "sgd":
            lr = float(option.learning_rate if option is not None
                       else self.default_option.learning_rate)
            values = -lr * values
        if self._coo_scatter_add.layout == "sharded":
            # row ownership is contiguous equal blocks, so the row sort
            # above IS a shard sort; padding lanes take each shard's max
            # local row (keeps the in-shard run scan sorted) and are
            # masked out of the write-back
            rps = self._rows_per_shard
            shard_ids = rows // rps
            local = (rows - shard_ids * rps).astype(np.int32)
            (sl_rows, sl_cols, sl_vals), valid, _pos = shard_lane_slices(
                shard_ids, self._shards, [local, cols, values],
                [np.int32(rps - 1), np.int32(0), 0])
            self.param = self._coo_scatter_add(
                self.param, sl_rows, sl_cols, sl_vals, valid)
        else:
            b = _bucket(n)
            prows = np.full(b, self._scratch_row, dtype=np.int32)
            pcols = np.zeros(b, dtype=np.int32)
            pvals = np.zeros(b, dtype=values.dtype)
            prows[:n], pcols[:n], pvals[:n] = rows, cols, values
            self.param = self._coo_scatter_add(self.param, prows, pcols,
                                               pvals)
        handle = Handle(table=self, generation=self._bump_step())
        if sync:
            handle.wait()
        return handle

    # -- sparse Get --------------------------------------------------------

    def get_rows_sparse(self, row_ids) -> Tuple[np.ndarray, np.ndarray,
                                                np.ndarray]:
        """Sparse Get: only the NONZERO entries of the requested rows
        reach the host (the reference's SparseMatrixWorkerTable Get
        returns only nonzero/requested entries — SURVEY.md §3.3).

        Returns CSR-style ``(indptr [n+1], cols [nnz], vals [nnz])``:
        row ``i`` of the request holds entries
        ``cols[indptr[i]:indptr[i+1]]`` (ascending col order).

        Exact, not top-k-truncated: a device-side nnz reduction sizes the
        extraction, so the device→host transfer is O(max_nnz·n), not
        O(num_cols·n) — the TPU analog of the reference's sparse wire
        format (its point was not shipping the dense row).
        """
        ids = np.asarray(row_ids, dtype=np.int32)
        self._check_ids(ids)
        padded, _, n = self._pad_ids(ids)
        nnz = np.asarray(self._row_nnz(self.param, padded))[:n]
        k = min(_bucket(max(int(nnz.max(initial=0)), 1)), self.num_cols)
        cols, vals = self._topk_fn(k)(self.param, padded)
        cols = np.asarray(cols)[:n]
        vals = np.asarray(vals)[:n]
        indptr = np.zeros(n + 1, np.int64)
        np.cumsum(nnz, out=indptr[1:])
        # one vectorized pass over all requested rows (a per-row Python
        # loop crawls on full-model dumps): np.nonzero walks row-major,
        # then a single lexsort orders each row's entries by column
        ri, ci = np.nonzero(vals != 0)
        ecols = cols[ri, ci]
        order = np.lexsort((ecols, ri))
        self._record_op("get", len(ecols),
                        len(ecols) * self.dtype.itemsize)
        return indptr, ecols[order], vals[ri, ci][order]
