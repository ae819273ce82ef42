"""Pipeline parallelism: GPipe-style microbatch schedule over a mesh
axis.

Beyond-parity module (SURVEY.md §3.8 lists PP as absent in the
reference): together with data parallelism (mesh data axis), model/tensor
sharding (model axis), and sequence parallelism (ring/Ulysses attention,
:mod:`multiverso_tpu.parallel.ring_attention`), this completes the
dp/tp/pp/sp set for the multi-chip story.

TPU-first design: the schedule is a single compiled program — a
`shard_map` over the pipeline axis in which every device runs the same
`lax.scan` over the S+M-1 schedule ticks, passing activations to its
right neighbor with one `ppermute` per tick (ICI neighbor traffic, the
mesh's cheapest collective). There is no host orchestration, no
per-stage dispatch, and reverse-mode AD works through the whole schedule
(scan + ppermute transpose), so `jax.grad` of a pipelined loss needs
nothing special — activation rematerialization composes via
`jax.checkpoint` on `stage_fn` if memory demands it.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from multiverso_tpu import core


def pipeline_apply(stage_params: Any, x: jax.Array,
                   stage_fn: Callable[[Any, jax.Array], jax.Array], *,
                   mesh: Optional[Mesh] = None,
                   axis: str = core.MODEL_AXIS,
                   microbatches: Optional[int] = None) -> jax.Array:
    """Apply S pipeline stages (one per device of ``axis``) to ``x``.

    Args:
      stage_params: pytree whose every leaf has leading axis S (the mesh
        ``axis`` size); stage s's slice lives on device s. The classic
        homogeneous-pipeline condition applies: ``stage_fn`` maps
        activations to activations of the SAME shape/dtype (embedding
        and head layers live outside the pipelined trunk).
      x: [B, ...] global batch; B must divide by ``microbatches``.
      stage_fn: ``(params_s, h) -> h``; traced once per device.
        CONSTRAINT: must be finite — in value and in gradient — on the
        INPUT distribution (``x_mb`` microbatches): bubble ticks run it
        on the current input microbatch as a safe dummy (double-where;
        the result is discarded, but a non-finite vjp would survive the
        output mask and poison ``jax.grad``). It need NOT be finite on
        zeros or stale activations — those never reach it.
      microbatches: schedule depth M (default: the axis size — the
        minimum that fills the pipeline; larger M lowers the bubble
        fraction (S-1)/(S-1+M) at constant memory per tick).

    Returns ``stage_{S-1}(... stage_0(x))`` for the full batch,
    replicated over ``axis``.

    The input is broadcast to every stage (simple and collective-free;
    for activation-dominated trunks the input microbatch is small
    relative to stage state). Schedule: at tick t, stage s computes
    microbatch ``t - s`` if it is in [0, M), then shifts its output one
    hop right; the last stage deposits finished microbatches into an
    output buffer that a final masked ``psum`` replicates.
    """
    mesh = mesh if mesh is not None else core.mesh()
    n = mesh.shape[axis]
    leaves = jax.tree.leaves(stage_params)
    for leaf in leaves:
        if leaf.shape[0] != n:
            raise ValueError(
                f"stage_params leading axis {leaf.shape[0]} != mesh "
                f"axis {axis!r} size {n}")
    m = microbatches if microbatches is not None else n
    if x.shape[0] % m:
        raise ValueError(f"batch {x.shape[0]} not divisible by "
                         f"{m} microbatches")
    x_mb = x.reshape((m, x.shape[0] // m) + x.shape[1:])

    def local(params, x_mb):
        params = jax.tree.map(lambda a: a[0], params)   # my stage slice
        me = lax.axis_index(axis)
        perm = [(j, (j + 1) % n) for j in range(n)]
        zero_act = jnp.zeros_like(x_mb[0])

        def tick(carry, t):
            act, out = carry
            mb_id = t - me
            valid = (mb_id >= 0) & (mb_id < m)
            # stage 0 pulls its microbatch from the input; later stages
            # consume the activation the previous tick shifted in
            inp = jnp.where(me == 0,
                            x_mb[jnp.clip(t, 0, m - 1)], act)
            # Double-where guard (the where-grad trap): during bubble
            # ticks ``inp`` is a zero/stale activation; if stage_fn is
            # non-finite there (log, rsqrt, division), its NaN/Inf
            # cotangent survives the output mask (0 * inf = nan inside
            # the vjp) and poisons jax.grad of the whole schedule. So
            # stage_fn only ever sees known-good data: bubble ticks feed
            # the current input microbatch (real data — stage_fn must be
            # finite, in value AND grad, on the input distribution; see
            # the docstring constraint), and the discarded result is
            # masked out below as before.
            safe_inp = jnp.where(valid, inp, x_mb[jnp.clip(t, 0, m - 1)])
            h = stage_fn(params, safe_inp)
            h = jnp.where(valid, h, inp)
            # the last stage deposits the finished microbatch
            out = lax.cond(
                valid & (me == n - 1),
                lambda o: lax.dynamic_update_index_in_dim(
                    o, h.astype(o.dtype), jnp.clip(mb_id, 0, m - 1), 0),
                lambda o: o, out)
            act = lax.ppermute(h, axis, perm)
            return (act, out), None

        out0 = jnp.zeros_like(x_mb)
        (act, out), _ = lax.scan(tick, (zero_act, out0),
                                 jnp.arange(n + m - 1))
        # only the last stage holds real outputs: masked psum replicates
        out = jnp.where(me == n - 1, out, jnp.zeros_like(out))
        out = lax.psum(out, axis)
        # flatten [M, B/M, ...] back to the caller's [B, ...]; derived
        # from the ARGUMENT, not the enclosing x — the compiled closure
        # is cached across calls and must not pin the first call's shape
        return out.reshape((out.shape[0] * out.shape[1],)
                           + out.shape[2:])

    param_specs = jax.tree.map(
        lambda leaf: P(*((axis,) + (None,) * (leaf.ndim - 1))),
        stage_params)
    x_spec = P(*((None,) * x_mb.ndim))
    from jax import shard_map

    def build():
        return shard_map(local, mesh=mesh,
                         in_specs=(param_specs, x_spec),
                         out_specs=P(*((None,) * x.ndim)),
                         check_vma=False)

    # cached profiled wrapper, not a bare eager shard_map call: `local`
    # is rebuilt per call, so without the key-cache every step would be
    # a fresh function to jax (retrace + recompile) and the flight
    # recorder could never attribute compile time to the schedule. The
    # key is exactly what the closure + specs capture; jit's own cache
    # handles shape changes under the same key.
    from multiverso_tpu.telemetry.profiling import cached_profiled_jit
    fn = cached_profiled_jit(
        ("pipeline_apply", stage_fn, mesh, axis, n, m,
         jax.tree.structure(stage_params),
         tuple(leaf.ndim for leaf in leaves), x.ndim),
        "parallel.pipeline_apply", build)
    return fn(stage_params, x_mb)


def sequential_oracle(stage_params: Any, x: jax.Array,
                      stage_fn: Callable[[Any, jax.Array], jax.Array]
                      ) -> jax.Array:
    """Single-device reference: apply the stages in order (tests)."""
    n = jax.tree.leaves(stage_params)[0].shape[0]
    h = x
    for s in range(n):
        params_s = jax.tree.map(lambda a, s=s: a[s], stage_params)
        h = stage_fn(params_s, h)
    return h
