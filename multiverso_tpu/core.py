"""Core runtime: init / shutdown / barrier / topology / the device mesh.

TPU-native replacement for the reference's process runtime (upstream layout
`src/multiverso.cpp`, `src/zoo.cpp`, `src/communicator.cpp`,
`src/controller.cpp`, `src/net/{mpi,zmq}_net.h` — SURVEY.md §3.1/§3.2/§4.1):

- ``MV_Init`` (flag parsing + MPI/ZMQ bootstrap + actor threads + register
  handshake + barrier) becomes :func:`init`: parse ``-name=value`` flags,
  optionally ``jax.distributed.initialize`` over DCN, and build one global
  :class:`jax.sharding.Mesh` over all devices.
- The Worker/Server actor roles dissolve: every chip is simultaneously a
  worker (compute) and a server (holds its parameter shard) — the
  "no CPU PS in the loop" north star (BASELINE.json).
- ``MV_Barrier`` (Control_Barrier round trip through the rank-0 Controller)
  becomes a device-level sync: all hosts dispatch one tiny all-reduce over
  every device and block on the result.
- Topology queries (``MV_Rank/Size/NumWorkers/NumServers/WorkerId/ServerId``)
  map onto JAX process/device topology: a "node" is a host process, a
  "worker" and a "server" are both "a chip".

The mesh convention: axes ``("data", "model")``. Tables shard their leading
dimension over ``"model"`` (the analog of partitioning rows across server
shards) and gradients are reduced over ``"data"`` (the analog of the
Add/Aggregator path). ``model_parallel=1`` (default) gives pure DP with
fully replicated tables, matching the reference's default deployment shape.
"""

from __future__ import annotations

import atexit
import os
import threading
import time
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from multiverso_tpu.telemetry import metrics as telemetry
from multiverso_tpu.utils import configure, log

DATA_AXIS = "data"
MODEL_AXIS = "model"

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def compile_cache_dir() -> str:
    """Where XLA's persistent compile cache lives: the directory
    ``JAX_COMPILATION_CACHE_DIR`` names when it is set (jax reads that
    itself — nothing is set in code), else ``<checkout>/.jax_cache``.
    The path is part of the cache key, so it is never a temp name."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or os.path.join(_REPO_ROOT, ".jax_cache")


def _place_compile_cache() -> None:
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", compile_cache_dir())
    # jax skips programs that compiled in under a second by default; a
    # warm start then depends on which side of a second each compile
    # happened to land. Cache every program.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


class _Runtime:
    """Process-global runtime state (the Zoo singleton's successor)."""

    def __init__(self) -> None:
        self.initialized = False
        self.mesh: Optional[Mesh] = None
        self.lock = threading.Lock()
        self.barrier_count = 0


_RT = _Runtime()


def _build_mesh(devices: Sequence[jax.Device], data_parallel: int,
                model_parallel: int) -> Mesh:
    n = len(devices)
    if model_parallel <= 0:
        raise ValueError("model_parallel must be >= 1")
    if data_parallel <= 0:
        data_parallel = n // model_parallel
    if data_parallel * model_parallel != n:
        raise ValueError(
            f"mesh {data_parallel}x{model_parallel} != {n} devices")
    dev_array = np.asarray(devices).reshape(data_parallel, model_parallel)
    return Mesh(dev_array, (DATA_AXIS, MODEL_AXIS))


def init(argv: Optional[Sequence[str]] = None, *,
         devices: Optional[Sequence[jax.Device]] = None,
         data_parallel: Optional[int] = None,
         model_parallel: Optional[int] = None) -> Mesh:
    """Initialise the runtime and build the global device mesh.

    ``argv`` may carry reference-style ``-name=value`` flags. ``devices``,
    ``data_parallel``, ``model_parallel`` override flags when given (used by
    tests to build virtual CPU meshes).

    Idempotent like ``MV_Init``: a second call with no arguments returns the
    existing mesh.
    """
    with _RT.lock:
        if argv:
            configure.parse_flags(argv)
        if _RT.initialized and not argv and devices is None \
                and data_parallel is None and model_parallel is None:
            assert _RT.mesh is not None
            return _RT.mesh

        log.set_level(configure.get_flag("log_level"))
        if configure.get_flag("log_file"):
            log.set_file(configure.get_flag("log_file"))
        _place_compile_cache()       # before the first compile

        coordinator = configure.get_flag("machine_file")
        if coordinator:
            # Multi-host bootstrap over DCN (the reference's MPI_Init /
            # ZMQ-machine_file moment). Must run before anything touches
            # the XLA backend; jax raises if the backend is already up,
            # and that is a real misconfiguration — fail fast, a silent
            # fallback to single-host topology would train wrong.
            # ``machine_file`` keeps the reference's flag shape: a FILE
            # listing one host per line (first = coordinator; the count
            # supplies -num_processes when unset). This host's rank comes
            # from -process_id (or the platform's auto-detection on cloud
            # TPU), NOT from the file — matching local addresses against
            # the list is unreliable in containers. A bare ``host`` /
            # ``host:port`` value is also accepted.
            if os.path.exists(coordinator):
                with open(coordinator) as f:
                    machines = [m for m in (ln.strip() for ln in f)
                                if m and not m.startswith("#")]
                if not machines:
                    raise ValueError(
                        f"machine_file {coordinator!r} lists no machines")
                coordinator = machines[0]
                if configure.get_flag("num_processes") == 0:
                    configure.set_flag("num_processes", len(machines))
            if ":" in coordinator:
                address = coordinator
            else:
                port = configure.get_flag("port") or 8476
                address = f"{coordinator}:{port}"
            nproc = configure.get_flag("num_processes")
            pid = configure.get_flag("process_id")
            kwargs = {}
            if nproc > 0:
                kwargs["num_processes"] = nproc
            if pid >= 0:
                kwargs["process_id"] = pid
            jax.distributed.initialize(coordinator_address=address,
                                       **kwargs)

        # fault injection rides runtime init: one env var turns any run
        # into a chaos run (tests / the chaos CI lane)
        from multiverso_tpu.ft.chaos import chaos_from_env
        chaos_from_env()

        # observability rides init the same way: MVTPU_STATUSZ_PORT
        # arms the live introspection server, MVTPU_SLO the tail-
        # latency monitor, MVTPU_HEALTH the training-health monitor
        # (all idempotent across re-inits)
        from multiverso_tpu.control.controller import maybe_controller
        from multiverso_tpu.telemetry.health import maybe_health_monitor
        from multiverso_tpu.telemetry.slo import maybe_slo_monitor
        from multiverso_tpu.telemetry.statusz import maybe_statusz
        maybe_statusz()
        maybe_slo_monitor()
        maybe_health_monitor()
        # MVTPU_AUTOTUNE closes the loop: the controller reads the
        # monitors' metrics and actuates the knob table
        maybe_controller()

        devs = list(devices) if devices is not None else jax.devices()
        dp = data_parallel if data_parallel is not None \
            else configure.get_flag("data_parallel")
        mp = model_parallel if model_parallel is not None \
            else configure.get_flag("model_parallel")
        _RT.mesh = _build_mesh(devs, dp, mp)
        _RT.initialized = True
        # topology on the record: one registry snapshot then identifies
        # the mesh shape a run's per-table byte counts came from
        telemetry.counter("core.init.ops").inc()
        telemetry.gauge("core.devices").set(len(devs))
        telemetry.gauge("core.data_parallel").set(
            _RT.mesh.shape[DATA_AXIS])
        telemetry.gauge("core.model_parallel").set(
            _RT.mesh.shape[MODEL_AXIS])
        telemetry.gauge("core.processes").set(jax.process_count())
        telemetry.gauge("core.process_index").set(jax.process_index())
        log.info("multiverso_tpu.init: %d devices, mesh data=%d model=%d, "
                 "process %d/%d", len(devs), _RT.mesh.shape[DATA_AXIS],
                 _RT.mesh.shape[MODEL_AXIS], jax.process_index(),
                 jax.process_count())
        return _RT.mesh


def is_initialized() -> bool:
    return _RT.initialized


def place(value, spec: P = P(), *, mesh: Optional[Mesh] = None) -> jax.Array:
    """Put a host value on the runtime mesh (replicated by default).

    Every device array an app creates MUST go through this (or an explicit
    ``NamedSharding`` ``device_put``): a bare ``jnp.asarray`` materialises
    on the process *default* device, which may be a different platform than
    the mesh — e.g. a TPU-default process building a CPU test mesh — and
    then either crashes the default backend or poisons a jit with
    mixed-platform operands.
    """
    m = mesh if mesh is not None else globals()["mesh"]()
    return jax.device_put(value, NamedSharding(m, spec))


def sharded_zeros(shape, dtype, sharding) -> jax.Array:
    """Zeros created DIRECTLY under a sharding — never on the default
    device and never materialised on host.

    A bare ``jnp.zeros(...)`` allocates on the process default backend
    before any ``device_put`` can move it (double allocation, and a crash
    when the default platform is broken — the same hazard ``place``
    documents); passing the sharding as ``device=`` makes jax allocate
    each shard on its target device only, with no per-call jit wrapper.
    """
    import jax.numpy as jnp
    return jnp.zeros(shape, dtype, device=sharding)


def prng_key(seed: int, *, mesh: Optional[Mesh] = None) -> jax.Array:
    """A PRNG key resident on the mesh, never on the default device.

    ``jax.random.PRNGKey(int)`` runs its seed-mixing ops eagerly on the
    default backend — which may be a different (even broken) platform than
    the mesh. Instead the key data is built on host and placed: for the
    default ``threefry2x32`` impl, ``PRNGKey(seed)`` is exactly the
    ``uint32[2]`` array ``[seed >> 32, seed & 0xffffffff]``, with negative
    seeds two's-complement wrapped — full 64-bit seed semantics preserved
    (verified against ``jax.random.PRNGKey`` in tests).
    """
    impl = jax.config.jax_default_prng_impl
    if impl != "threefry2x32":   # pragma: no cover - non-default impl
        return place(jax.random.PRNGKey(seed), mesh=mesh)
    # x64-off canonicalisation wraps the seed to int32 and the hi word of
    # threefry_seed's 32-by-32 logical shift is 0 — verified equal to
    # jax.random.PRNGKey for the int64 range in tests; beyond int64 raise
    # OverflowError exactly like jax's canonicalisation does (numpy 2.x
    # would silently give uint64/object dtype instead of raising)
    if not (-(2 ** 63) <= int(seed) < 2 ** 63):
        raise OverflowError(f"seed {seed} out of int64 range")
    wrapped = int(np.asarray(int(seed), dtype=np.int64).astype(np.int32))
    data = np.array([0, wrapped & 0xFFFFFFFF], dtype=np.uint32)
    return place(data, mesh=mesh)


def shutdown(finalize: bool = True) -> None:
    """``MV_ShutDown`` equivalent: drop the mesh; optionally report timing."""
    with _RT.lock:
        if not _RT.initialized:
            return
        _RT.initialized = False
        _RT.mesh = None
    from multiverso_tpu.control.controller import shutdown_controllers
    shutdown_controllers()
    if finalize:
        from multiverso_tpu.utils import dashboard
        log.debug("dashboard at shutdown:\n%s", dashboard.report())


def mesh() -> Mesh:
    if not _RT.initialized or _RT.mesh is None:
        init()
    assert _RT.mesh is not None
    return _RT.mesh


def platform(m: Optional[Mesh] = None) -> str:
    """Platform of the devices a mesh is built from (``"tpu"``,
    ``"cpu"``, ...) — what kernel selection and Pallas interpret mode
    key on. The process default backend can differ from it (a CPU test
    mesh in a process whose default backend is the chip)."""
    return (m if m is not None else mesh()).devices.flat[0].platform


def set_mesh(m: Mesh) -> None:
    """Install an externally-built mesh (tests, embedding in a larger app)."""
    with _RT.lock:
        _RT.mesh = m
        _RT.initialized = True


@jax.jit
def _barrier_sum(x):
    return x.sum()


def barrier(name: Optional[str] = None) -> None:
    """Global synchronisation point (``MV_Barrier``).

    Dispatches a tiny all-reduce over every device of the mesh and blocks
    until it completes; across hosts this is a true barrier because the
    collective cannot complete until every host has dispatched it.
    """
    m = mesh()
    # fault point: a 'latency' rule here models a straggler host; an
    # 'error' rule a lost peer (the failure mode SURVEY §6.3 records
    # the reference hangs on)
    from multiverso_tpu.ft.chaos import chaos_point
    chaos_point("core.barrier")
    _RT.barrier_count += 1
    t0 = time.perf_counter()
    ones = jax.device_put(
        np.zeros((len(m.devices.flat),), np.int32),
        NamedSharding(m, P((DATA_AXIS, MODEL_AXIS))))
    _barrier_sum(ones).block_until_ready()
    # barrier latency IS the straggler signal on a multi-host mesh: the
    # collective completes only when the slowest host dispatches it
    telemetry.counter("core.barrier.ops").inc()
    telemetry.histogram("core.barrier.seconds").observe(
        time.perf_counter() - t0)


# -- Topology queries (reference MV_* names, SURVEY.md §3.5) ---------------

def rank() -> int:
    """Host-process rank (reference: node rank)."""
    return jax.process_index()


def size() -> int:
    """Number of host processes (reference: node count)."""
    return jax.process_count()


def num_workers() -> int:
    """Reference: count of worker roles. Here every chip computes."""
    return len(mesh().devices.flat)


def num_servers() -> int:
    """Reference: count of server roles. Here every chip holds a shard."""
    return len(mesh().devices.flat)


def worker_id() -> int:
    """First local device's position in the mesh (per-host worker id)."""
    me = jax.process_index()
    for i, d in enumerate(mesh().devices.flat):
        if d.process_index == me:
            return i
    return -1


def server_id() -> int:
    return worker_id()


def is_worker() -> bool:
    return True


def is_server() -> bool:
    return True


def data_axis_size() -> int:
    return mesh().shape[DATA_AXIS]


def model_axis_size() -> int:
    return mesh().shape[MODEL_AXIS]


atexit.register(shutdown)
