"""Fused superstep: the SUPPORTED way for an app to run a custom jitted
update over table storage in one compiled program.

Why this exists (SURVEY.md §3.3/§3.9 and the round-1 review): on TPU the
Get → local-train → Add round-trip of the reference (SURVEY.md §4.2/§4.3)
wants to be ONE fused XLA program per dispatch — gathers, model math, and
scatter-updates compiled together so nothing round-trips through HBM
staging or host. The first-round apps each hand-rolled that pattern
(private ``jax.jit`` + direct ``table.param`` assignment), which bypassed
the table contract: step counters did not advance and donation/sharding
handling was copy-pasted. :class:`FusedSuperstep` moves that machinery
into the table layer:

- reads each table's live ``param`` (and updater ``state``) as donated
  carry inputs,
- pins output shardings to each table's ``NamedSharding`` (and optional
  shardings for app-local carries),
- resolves each table's :class:`AddOption` (traced pytree — no retrace on
  lr/step changes) and passes it to the body,
- writes results back and advances each table's step/generation counters,
  so :class:`multiverso_tpu.tables.base.Handle` semantics hold for fused
  updates exactly as for plain ``add``.

Body contract::

    body(params, states, locals_, options, *inputs)
        -> (new_params, new_states, new_locals, aux)

where ``params``/``states``/``options`` are tuples aligned with the
``tables`` argument, ``locals_`` is the app-local carry tuple (e.g. LDA's
doc-topic counts and z-assignments), ``inputs`` are per-call operands
(minibatches, RNG keys, lr arrays), and ``aux`` is any non-donated output
pytree (losses/metrics) or ``None``. The body runs under ``jax.jit`` —
use ``lax.scan`` for multi-minibatch supersteps.

Tables with stateless updaters thread ``states`` through unchanged (their
state is the empty pytree). Bodies that apply updater math should call
``table.updater.apply(param, state, delta, option)`` — the same pure
function ``add`` uses, so the fused path and the plain path share
semantics.

Kernel engine: bodies that gather/scatter table rows should use the
re-exported :func:`gather_rows` / :func:`row_scatter_add` /
:func:`coo_scatter_add` (from ``ops/table_kernels.py``) instead of raw
``jnp.take`` / ``.at[].add`` — they are traceable inside the fused jit
and route through the same ``MVTPU_KERNELS``-selected Pallas/XLA engine
as the plain table Get/Add paths, so a fused superstep picks up the
kernel engine with no other change. On sharded meshes the dispatch runs
under :func:`kernel_mesh_scope`, so those functional kernels shard_map
their Pallas grids over the model axis (masked-lane form — lane counts
are dynamic inside a trace, so no host-side lane slicing here).
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Sequence, Tuple

import jax

from multiverso_tpu import core
from multiverso_tpu.ops import table_kernels as tk

# re-exported for superstep bodies (see module docstring): the
# engine-selected, trace-safe gather/scatter kernels
from multiverso_tpu.ops.table_kernels import (coo_scatter_add,
                                              gather_rows,
                                              row_scatter_add)
from multiverso_tpu.tables.base import Handle, Table
from multiverso_tpu.telemetry import health as _health
from multiverso_tpu.telemetry import trace as _trace
from multiverso_tpu.telemetry.profiling import profiled_jit
from multiverso_tpu.updaters import AddOption

__all__ = ["FusedSuperstep", "coo_scatter_add", "gather_rows",
           "make_superstep", "row_scatter_add"]


class FusedSuperstep:
    """A compiled fused update bound to one or more tables."""

    def __init__(self, tables: Sequence[Table],
                 body: Callable[..., Tuple[Any, Any, Any, Any]], *,
                 local_shardings: Any = None,
                 name: str = "superstep") -> None:
        if not tables:
            raise ValueError("FusedSuperstep needs at least one table")
        self.tables = tuple(tables)
        self.name = name
        self._last_generation: Optional[int] = None
        mesh0 = self.tables[0].mesh
        for t in self.tables[1:]:
            if t.mesh is not mesh0:
                raise ValueError(
                    f"superstep {name!r}: tables {self.tables[0].name!r} "
                    f"and {t.name!r} live on different meshes")

        param_sh = tuple(t.sharding for t in self.tables)
        state_sh = tuple(
            jax.tree.map(lambda _, t=t: t.state_sharding, t.state)
            for t in self.tables)

        # profiled_jit, not bare jax.jit: every app trains through a
        # superstep, so this is THE place the flight recorder learns
        # each program's lowering/compile wall time and HLO cost
        # (profile.* metrics keyed fn=superstep.<name>)
        def run(params, states, locals_, options, *inputs):
            return body(params, states, locals_, options, *inputs)

        self._run = profiled_jit(
            run, name=f"superstep.{name}", donate_argnums=(0, 1, 2),
            out_shardings=(param_sh, state_sh, local_shardings, None))

    def __call__(self, locals_: Any = (), *inputs: Any,
                 options: Optional[Sequence[Optional[AddOption]]] = None
                 ) -> Tuple[Any, Any]:
        """Dispatch one fused update.

        Returns ``(new_locals, aux)``; table params/states are written
        back in place and each table's step/generation advances. Dispatch
        is async (XLA) — use ``table.wait()`` or a returned value to
        fence.
        """
        if options is None:
            options = (None,) * len(self.tables)
        # client pipeline: buffered coalesced deltas must land BEFORE
        # the fused program reads (and donates) each table's storage —
        # applying them after would reorder updates across the superstep
        for t in self.tables:
            t.flush_coalesced()
        opts = tuple(t._resolve_option(o)
                     for t, o in zip(self.tables, options))
        params = tuple(t.param for t in self.tables)
        states = tuple(t.state for t in self.tables)
        # sharded meshes: the scope tells the in-trace functional kernels
        # which mesh/axis to shard_map their Pallas grids over (tracing
        # sees only abstract values — the mesh can't be inferred there)
        # the compiled call alone: the caller's span (lda.dispatch,
        # w2v.superstep) less this one is the table layer's bookkeeping
        with tk.kernel_mesh_scope(self.tables[0].mesh, core.MODEL_AXIS), \
                _trace.span("superstep.run"):
            new_params, new_states, new_locals, aux = self._run(
                params, states, locals_, opts, *inputs)
        for t, p, s in zip(self.tables, new_params, new_states):
            t.param = p
            t.state = s
            # a fused dispatch IS one Get -> train -> Add round-trip per
            # table (SURVEY §4.2/§4.3), so it lands in the same per-table
            # accounting the plain get()/add() paths record — apps that
            # only ever train through supersteps (all of them) still show
            # table.get/add bytes on every registry snapshot
            elems = 1
            for d in t.logical_shape:
                elems *= int(d)
            nbytes = elems * t.dtype.itemsize
            t._record_op("get", elems, nbytes)
            t._record_op("add", elems, nbytes)
            # fused updates never pass through add(), so the numerics
            # audit samples the written-back storage here (stride-gated
            # inside observe_param; a no-op when health is off)
            _health.observe_param(t, p)
            gen = t._bump_step()
            if t is self.tables[0]:
                # mint from the returned generation (racing with
                # concurrent adds through self.tables[0].generation could
                # hand this superstep a LATER update's generation)
                self._last_generation = gen
        return new_locals, aux

    def handle(self) -> Handle:
        """An add-handle for this superstep's latest dispatch on the
        first table (all tables in one superstep advance together)."""
        if self._last_generation is None:
            raise RuntimeError(f"superstep {self.name!r} has not been "
                               "dispatched yet")
        return Handle(table=self.tables[0],
                      generation=self._last_generation)


def make_superstep(tables: Sequence[Table], body: Callable, *,
                   local_shardings: Any = None,
                   name: str = "superstep") -> FusedSuperstep:
    """Build a :class:`FusedSuperstep` over ``tables`` (see module doc)."""
    return FusedSuperstep(tables, body, local_shardings=local_shardings,
                          name=name)
