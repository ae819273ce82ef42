"""Add update rows into a table whose lanes may name the same row many
times, without the serial scatter: ``table[ids[l]] += updates[l]``.

Why a kernel: XLA lowers ``table.at[ids].add(updates)`` to a loop that
has to assume any two lanes may hit one row, so it keeps one
read-modify-write in flight — 100 ns a 1.2 KB row on a v5e where the
same rows gather in 11, whatever flags it is given (PERF.md §6, PR 33).
Here the lanes are sorted by row id first (plain XLA: a stable sort that
carries the lane number), so a row's duplicates are neighbours, and the
table is read, added to and written once a DISTINCT 8-row group (an
(8, 128) tile row: the smallest piece of a tiled table a DMA may
address), a block's groups all in flight together. Distinct groups are
what make that safe.

Exactness: bit for bit what the scatter gives. A row's duplicates are
added to it one by one in lane order, as XLA's scatter adds them (its
loop runs in lane order on the chip: 288 steps of the word2vec cell's
lanes came out equal to the last bit). This matters: word2vec's hot
rows amplify a last-digit difference past 1e-4 of the loss within three
calls of 512 steps, so a pre-summed run, exact in real numbers, fails
the benchmark's comparison.

Mosaic on a TPU, interpreted elsewhere (``interpret=True``: tier-1).
"""

from __future__ import annotations

import functools
from typing import Callable, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

GROUP = 8           # rows of a table a DMA moves: one sublane tile
LANES = 128
# sorted lanes a grid step adds; it holds as many 8-row groups in VMEM
BLOCK = 256
# lanes sorted and written by one kernel call; a step's further lanes
# follow in further calls, in lane order. XLA's sort of 24,576 lanes
# takes 13 s to COMPILE for a v5e, of 8,192 one second (and three int32
# a lane sit in the kernel's SMEM, 1 MiB)
MAX_LANES = 8192

# BLOCK + 2 tiles of a 384-wide table are 3.2 MB beside the pipelined
# updates; Mosaic's default of 16 MiB would hold rows of 1,536 columns,
# v5e has 128 MiB
_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("arbitrary",), vmem_limit_bytes=48 * 2 ** 20)


def aligned_shape(rows: int, cols: int) -> Tuple[int, int]:
    """The least table shape of whole (8, 128) tiles that holds
    [rows, cols] — what :func:`add_rows` takes. A table that is HELD in
    this shape is stored row by row on the device (a [N, 300] float32
    array is held column-major, and every program that gathers its rows
    first copies it whole); padding it inside a program instead costs
    that copy and one more."""
    return rows + -rows % GROUP, cols + -cols % LANES


def _kernel(reads_ref, writes_ref, dest_ref, plan_ref, upd_ref, _, table,
            buf, read_sem, write_sem, *, block: int):
    """One block of sorted lanes. A GROUP's lanes are neighbours: the
    block that holds the first of them reads the group into a tile of
    ``buf``, every lane adds its row to its tile in lane order
    (``dest_ref[l]`` = 8 x tile + row), the block that holds the last
    writes the tile back; between blocks a group that goes on rides the
    carry tile. ``reads_ref`` / ``writes_ref`` hold each block's groups to
    read / to write, in their order; ``plan_ref[n]`` = how many of each,
    whether the block's first group comes from the carry, whether its
    last goes on into it, and that last group's tile."""
    n = pl.program_id(0)
    carry = block

    def copies(groups, count, first, sem, into_table: bool):
        """Start ``count`` group copies between the table and the tiles
        from ``first`` on, then wait for all of them: what is in flight
        is a block's distinct groups, and nothing else moves meanwhile
        (reads left in flight under the adds measured 10 % slower)."""
        def copy(g, tile):
            src = table.at[pl.ds(pl.multiple_of(g * GROUP, GROUP), GROUP)]
            dst = buf.at[tile]
            if into_table:
                src, dst = dst, src
            return pltpu.make_async_copy(src, dst, sem)

        def start(j, c):
            copy(groups[n * block + j], first + j).start()
            return c

        def wait(_, c):         # every copy is one group
            copy(0, 0).wait()
            return c
        lax.fori_loop(0, count, start, 0)
        lax.fori_loop(0, count, wait, 0)

    # a carried first group holds tile 0 and is not read
    copies(reads_ref, plan_ref[n, 0], plan_ref[n, 2], read_sem, False)

    @pl.when(plan_ref[n, 2] > 0)
    def _():
        buf[0] = buf[carry]

    def add(i, c):
        # eight lanes a trip, one after another: a row's duplicates are
        # neighbours and each adds to what the one before it left.
        # (Loading the eight rows ahead of the eight stores, adding a
        # lane by whole-tile selects, and blocks of 128 or 512 lanes all
        # measured within 10 % of this on a v5e.)
        slab = upd_ref[pl.ds(pl.multiple_of(i * 8, 8), 8), :]
        for j in range(8):
            dest = dest_ref[n * block + i * 8 + j]
            tile, row = dest // GROUP, dest % GROUP
            buf[tile, pl.ds(row, 1), :] = (buf[tile, pl.ds(row, 1), :]
                                           + slab[j:j + 1, :])
        return c
    lax.fori_loop(0, block // 8, add, 0)

    @pl.when(plan_ref[n, 3] > 0)
    def _():
        buf[carry] = buf[plan_ref[n, 4]]

    # groups are written in the order of their tiles
    copies(writes_ref, plan_ref[n, 1], 0, write_sem, True)


def _listed(flags, values, block: int):
    """Each block's ``values`` where ``flags``, first and in their order."""
    lane = lax.broadcasted_iota(jnp.int32, flags.shape, 1)
    return lax.sort((jnp.where(flags, lane, block), values), dimension=1,
                    num_keys=1)[1].reshape(-1)


def _add_sorted(table, ids, updates, *, block: int, interpret: bool):
    """``ids`` ascending, none negative, ``len(ids)`` a multiple of
    ``block``; an id past the table's rows is skipped."""
    blocks = ids.shape[0] // block
    rows, width = table.shape
    valid = (ids < rows).reshape(blocks, block)
    group = ids // GROUP
    differs = group[1:] != group[:-1]
    one = jnp.ones((1,), bool)
    begins = jnp.concatenate([one, differs]).reshape(blocks, block)
    ends = jnp.concatenate([differs, one]).reshape(blocks, block)
    group = group.reshape(blocks, block)
    # a group's tile in its block: how many groups begin before it there,
    # a carried one (the block's first lane goes on with the block
    # before's last group) counted as the first
    carried = ~begins[:, :1] & valid[:, :1]
    goes_on = jnp.concatenate([carried[1:], jnp.zeros((1, 1), bool)])
    tile = jnp.cumsum(begins | (lax.broadcasted_iota(
        jnp.int32, begins.shape, 1) == 0), axis=1, dtype=jnp.int32) - 1
    dest = jnp.where(valid, tile * GROUP
                     + (ids % GROUP).reshape(blocks, block),
                     (block + 1) * GROUP)                    # a bin tile
    plan = jnp.concatenate([
        (begins & valid).sum(axis=1, keepdims=True, dtype=jnp.int32),
        (ends & valid).sum(axis=1, keepdims=True, dtype=jnp.int32),
        carried.astype(jnp.int32), goes_on.astype(jnp.int32),
        tile[:, -1:]], axis=1)
    return pl.pallas_call(
        functools.partial(_kernel, block=block),
        out_shape=jax.ShapeDtypeStruct(table.shape, table.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(blocks,),
            in_specs=[
                pl.BlockSpec((block, width), lambda n, *_: (n, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[
                pltpu.VMEM((block + 2, GROUP, width), table.dtype),
                pltpu.SemaphoreType.DMA(()),
                pltpu.SemaphoreType.DMA(()),
            ]),
        input_output_aliases={5: 0},
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(_listed(begins & valid, group, block),
      _listed(ends & valid, group, block), dest.reshape(-1), plan,
      updates, table)


def add_rows(table: jax.Array, ids: jax.Array,
             updates_of: Callable[[jax.Array], jax.Array], *,
             interpret: bool) -> Tuple[jax.Array, jax.Array]:
    """``table[ids[l]] += updates[l]`` for every lane l, duplicates
    summed, each distinct 8-row group read and written once. ``table``
    has an :func:`aligned_shape`; ``updates_of(lanes)`` builds the update rows
    [len(lanes), table.shape[1]] of the given lane numbers — the caller
    forms them in sorted order from what it holds (an outer product's
    factors, say), so that no unsorted [L, D] array is built and
    permuted. Ids are read as ``.at[].add`` reads them: a negative one
    counts from the table's end, one still outside the table is dropped.
    Returns the table and the number of rows written: the distinct rows
    of each ``MAX_LANES`` lanes."""
    rows, width = table.shape
    if rows % GROUP or width % LANES:
        raise ValueError(f"add_rows wants whole (8, 128) tiles, got a "
                         f"table of {table.shape}")
    distinct = jnp.int32(0)
    for lo in range(0, ids.shape[0], MAX_LANES):
        part = ids[lo:lo + MAX_LANES]
        lanes = part.shape[0]
        block = min(BLOCK, -(-lanes // 8) * 8)
        padded = -(-lanes // block) * block
        part = jnp.where(part < 0, part + rows, part)
        # pad lanes name no row and sort last
        part = jnp.concatenate([
            jnp.where((part >= 0) & (part < rows), part,
                      rows).astype(jnp.int32),
            jnp.full((padded - lanes,), rows, jnp.int32)])
        # stable: a row's duplicates stay in lane order, the order in
        # which XLA's scatter adds them
        part, order = lax.sort((part, lax.iota(jnp.int32, padded)),
                               num_keys=1, is_stable=True)
        updates = updates_of(lo + jnp.minimum(order, lanes - 1))
        distinct += jnp.sum((part < rows) & jnp.concatenate(
            [jnp.ones((1,), bool), part[1:] != part[:-1]]), dtype=jnp.int32)
        table = _add_sorted(table, part, updates.astype(table.dtype),
                            block=block, interpret=interpret)
    return table, distinct
