"""Compile/runtime introspection: where did the wall-clock go BEFORE
the first step ran, and what does the compiled program cost?

A run stuck in ``jax.jit`` tracing, XLA compilation, or backend init
looks identical from outside to one stuck in a collective.
:func:`profiled_jit` splits that out:

- a drop-in ``jax.jit`` replacement that, on each NEW input signature,
  runs the explicit AOT pipeline (``lower()`` then ``compile()``),
  timing both phases into the metric registry and emitting trace spans
  (so a watchdog dump's trace tail shows "in compile" vs "in step"):

  - ``profile.lower.seconds{fn=...}`` / ``profile.compile.seconds{...}``
    histograms, and the last compile as the gauge
    ``profile.compile.last_s{fn=...}``,
  - ``profile.compiles{fn=...}`` counter (signature-cache misses —
    retrace storms show up as a climbing counter),
  - ``profile.calls{fn=...}`` counter (every dispatch through the
    wrapper, all paths — the denominator that proves dispatch-count
    claims like the client pipeline's delta coalescing),
  - ``profile.flops{fn=...}`` / ``profile.bytes_accessed{fn=...}``
    gauges from XLA cost analysis where the backend reports them,
  - ``profile.memory.*{fn=...}`` gauges from XLA memory analysis
    (argument/output/temp/generated-code bytes) where available,
  - ``profile.scope.ops{fn=...,scope=...}`` gauges: how many of the
    compiled module's instructions carry each program scope
    (:func:`multiverso_tpu.telemetry.trace.scope`) in their ``op_name``;
    :func:`op_scopes` returns the instruction-level map, which is what
    lets a device trace's ``jit_run/fusion.62`` be read as
    ``w2v.scatter_out``. A fusion whose own ``op_name`` names no scope
    takes its body's, and one the compiler made itself its operands'
    (:func:`infer_op_scopes`); the map's ``inferred`` says which names
    were found that way, and the gauge counts them with the rest.

  The compiled executable is cached per signature — avals AND input
  shardings, because an AOT executable accepts exactly the shardings
  it was compiled for — and called directly (jit's own cache never
  sees a second compile). Tracer inputs (the wrapper invoked inside an
  outer jit/grad trace) take the plain jitted path. A lowering, compile
  or execution error raises to the caller: there is no second attempt
  through another path.

- :func:`record_device_memory` — live-buffer count/bytes
  (``jax.live_arrays``) and per-device allocator stats
  (``Device.memory_stats``) as gauges; cheap enough to call at every
  tier boundary.

- :func:`profile_window` — an optional ``jax.profiler`` device capture
  gated by ``MVTPU_PROFILE_DIR``: set the env var and any region wrapped
  in this context writes a TensorBoard/Perfetto-loadable device trace;
  unset, the context is free.

jax is imported lazily (call time, never module import): the report CLI
and the jax-free parents of ``chip_smoke.py`` and the fleet launcher
import the telemetry package, and must not initialise a backend (one
process holds a chip).
"""

from __future__ import annotations

import contextlib
import os
import re
import sys
import time
from typing import (Any, Callable, Dict, Iterator, List, NamedTuple, Optional,
                    Tuple)

from multiverso_tpu.telemetry import metrics as _metrics
from multiverso_tpu.telemetry import trace as _trace


UNSCOPED = "unscoped"
# fn -> {"module": <HLO module name>, "scopes": {instruction: scope},
# "inferred": {instruction: scopes its body holds}}; process-wide like
# the registry, and outliving the wrappers: a benchmark reads it after
# the program's tables are freed
_OP_SCOPES: Dict[str, dict] = {}
_HLO_MODULE = re.compile(r"^HloModule\s+([^\s,]+)", re.M)
# a computation opens with ``[ENTRY ]%name (parameters) -> shape {``
_HLO_COMPUTATION = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+) \(.*\) -> .*\{$")
# one instruction per line: ``[ROOT ]%name = shape opcode(...), ...``
_HLO_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = (.*)$")
# the opcode is the first word in front of a parenthesis (a shape's
# ``T(8,128)`` follows a colon, a tuple shape opens the line)
_HLO_OPCODE = re.compile(r"\s([a-z][\w\-]*)\(")
_HLO_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_HLO_OPERAND = re.compile(r"%([\w.\-]+)")
_HLO_OP_NAME = re.compile(r'op_name="([^"]*)"')
# a program scope as :func:`trace.scope` writes it: the path segment
# ``jit(<app>.<phase>)`` of the op_name (jax's own — ``jit(run)``,
# ``jit(_take)``, ``while``, ``scatter-add`` — are never dotted words),
# also where differentiation wrapped it (``jvp(jit(lm.mla.attend))``:
# the forward ops of a phase that ``jax.grad`` traced)
_SCOPE_SEGMENT = re.compile(
    r"^(?:[a-z_]+\()*jit\(([a-z][a-z0-9_]*(?:\.[a-z][a-z0-9_]*)+)\)+$")
# a body's instructions that say nothing of the phase it belongs to
_GLUE = frozenset(("parameter", "constant", "bitcast", "tuple",
                   "get-tuple-element"))
# ... and those that decide it, where they agree
_PRODUCTS = frozenset(("convolution", "dot", "custom-call"))


class _Instruction(NamedTuple):
    name: str
    opcode: str
    scope: str          # its own op_name's, else UNSCOPED
    named: bool         # it carries an op_name at all
    calls: str          # a fusion's body, else ""
    operands: Tuple[str, ...]       # a fusion's, else ()


def _read_instruction(name: str, rest: str) -> _Instruction:
    op_name = _HLO_OP_NAME.search(rest)
    segments = op_name.group(1).split("/") if op_name else ()
    found = filter(None, map(_SCOPE_SEGMENT.match, reversed(segments)))
    scope = next((m.group(1) for m in found), UNSCOPED)
    opcode = _HLO_OPCODE.search(" " + rest)
    opcode = opcode.group(1) if opcode else ""
    calls, operands = "", ()
    if opcode == "fusion":
        found = _HLO_CALLS.search(rest)
        calls = found.group(1) if found else ""
        head = rest.split(", kind=", 1)[0]
        operands = tuple(_HLO_OPERAND.findall(
            head[head.index("fusion(") + 6:]))
    return _Instruction(name, opcode, scope, op_name is not None, calls,
                        operands)


def _read_computations(hlo_text: str) -> Dict[str, List[_Instruction]]:
    """The module's instructions by computation, in the text's order."""
    computations: Dict[str, List[_Instruction]] = {}
    current = computations.setdefault("", [])
    for line in hlo_text.splitlines():
        found = _HLO_INSTRUCTION.match(line)
        if found:
            current.append(_read_instruction(*found.groups()))
            continue
        found = _HLO_COMPUTATION.match(line)
        if found:
            current = computations.setdefault(found.group(1), [])
    return computations


def infer_op_scopes(hlo_text: str
                    ) -> Tuple[str, Dict[str, str], Dict[str, int]]:
    """``(module name, {instruction: scope}, {instruction: n})`` of one
    compiled module's text, by three rules in this order:

    1. an instruction whose own ``op_name`` names a program scope keeps
       it (the innermost one);
    2. a ``fusion`` left unscoped takes its body's: the scope of the
       body's products and kernels (``convolution``, ``dot``,
       ``custom-call``) where those that carry one agree, else the scope
       most of the body's scoped instructions carry (parameters,
       constants, bitcasts, tuples and their elements not counted; a
       fusion inside the body counts as what these rules make of it); a
       tie stays :data:`UNSCOPED`;
    3. a ``fusion`` the COMPILER made — no ``op_name`` of its own and a
       body that names nothing: what ``ragged_dot`` is expanded into, a
       masked convolution summed over the groups — takes the one scope
       its operands carry, where those that carry one agree.

    Everything else without a scope of its own is :data:`UNSCOPED`. The
    third value holds every instruction that rule 2 or 3 named, with the
    number of distinct scopes among its body's instructions: 0 = named
    by its operands, 1 = the body agrees, 2 or more = the fusion crosses
    a scope's edge and nobody splits its seconds."""
    found = _HLO_MODULE.search(hlo_text)
    module = found.group(1) if found else ""
    computations = _read_computations(hlo_text)
    bodies: Dict[str, Tuple[str, int]] = {}

    def body_scope(name: str) -> Tuple[str, int]:
        if name not in bodies:
            bodies[name] = (UNSCOPED, 0)       # a cycle names nothing
            votes: Dict[str, int] = {}
            products = set()
            for ins in computations.get(name, ()):
                scope = ins.scope
                if scope == UNSCOPED and ins.calls:
                    scope = body_scope(ins.calls)[0]
                if scope == UNSCOPED or ins.opcode in _GLUE:
                    continue
                votes[scope] = votes.get(scope, 0) + 1
                if ins.opcode in _PRODUCTS:
                    products.add(scope)
            most = sorted(votes.values())[-2:]
            if len(products) == 1:
                bodies[name] = (products.pop(), len(votes))
            elif votes and (len(most) == 1 or most[0] < most[1]):
                bodies[name] = (max(votes, key=votes.get), len(votes))
            else:
                bodies[name] = (UNSCOPED, len(votes))
        return bodies[name]

    scopes: Dict[str, str] = {}
    inferred: Dict[str, int] = {}
    for instructions in computations.values():
        for ins in instructions:
            scope = ins.scope
            if scope == UNSCOPED and ins.calls:
                scope, held = body_scope(ins.calls)
                if not held and not ins.named:
                    given = {scopes.get(o, UNSCOPED)
                             for o in ins.operands} - {UNSCOPED}
                    if len(given) == 1:
                        scope = given.pop()
                if scope != UNSCOPED:
                    inferred[ins.name] = held
            scopes[ins.name] = scope
    return module, scopes, inferred


def parse_op_scopes(hlo_text: str) -> Tuple[str, Dict[str, str]]:
    """``(module name, {instruction name: scope})`` of one compiled
    module's text: :func:`infer_op_scopes` without its account of which
    names were inferred. A fusion's name is its own scope where its
    ``op_name`` has one, else its body's, else — the compiler's own
    fusions — its operands'; :data:`UNSCOPED` where none names it."""
    return infer_op_scopes(hlo_text)[:2]


def _record_scopes(fn: str, compiled: Any) -> None:
    """Keep ``fn``'s instruction -> scope map (one text dump a compile).
    Two programs of one ``fn`` (two signatures) merge; an instruction
    name they scope differently is unscoped, and not inferred."""
    with _trace.span("profile.op_scopes"):
        module, scopes, inferred = infer_op_scopes(compiled.as_text())
    held = _OP_SCOPES.setdefault(
        fn, {"module": module, "scopes": {}, "inferred": {}})
    merge_op_scopes(held["scopes"], scopes, held["inferred"], inferred)
    if not _names_something(held["scopes"]):
        return
    counts: Dict[str, int] = {}
    for scope in held["scopes"].values():
        counts[scope] = counts.get(scope, 0) + 1
    reg = _metrics.registry()
    for scope, n in counts.items():
        reg.gauge("profile.scope.ops", fn=fn, scope=scope).set(n)


def _names_something(scopes: Dict[str, str]) -> bool:
    return any(scope != UNSCOPED for scope in scopes.values())


def merge_op_scopes(into: Dict[str, str], more: Dict[str, str],
                    into_inferred: Optional[Dict[str, int]] = None,
                    more_inferred: Optional[Dict[str, int]] = None
                    ) -> None:
    """Fold ``more`` into ``into``; a name the two scope differently
    becomes :data:`UNSCOPED` (two programs may share a module name, and
    with it their instructions' names) and leaves ``into_inferred``,
    which otherwise keeps the larger count of the two."""
    into_inferred = {} if into_inferred is None else into_inferred
    for name, scope in more.items():
        if into.setdefault(name, scope) != scope:
            into[name] = UNSCOPED
            into_inferred.pop(name, None)
    for name, n in (more_inferred or {}).items():
        if into[name] != UNSCOPED:
            into_inferred[name] = max(n, into_inferred.get(name, n))


def op_scopes() -> Dict[str, dict]:
    """``{fn: {"module": name, "scopes": {instruction: scope},
    "inferred": {instruction: n}}}`` for every program
    :func:`profiled_jit` compiled in this process whose compiled text
    names at least one program scope. ``module`` is the name a device
    trace files the ops under (``jit_run``); ``inferred`` holds the
    instructions whose scope is not their own ``op_name``'s
    (:func:`infer_op_scopes`: 0 = a compiler-made fusion named by its
    operands, 1 = a fusion named by a body that agrees, 2 or more = by
    a body that holds that many scopes)."""
    return {fn: {"module": held["module"],
                 "scopes": dict(held["scopes"]),
                 "inferred": dict(held["inferred"])}
            for fn, held in _OP_SCOPES.items()
            if _names_something(held["scopes"])}


def _leaf_sig(leaf: Any) -> Any:
    """A hashable signature for one argument leaf: aval (shape/dtype/
    weak_type — what jit keys on) plus the sharding of a device array."""
    from jax.api_util import shaped_abstractify
    return shaped_abstractify(leaf), getattr(leaf, "sharding", None)


class _ProfiledJit:
    """The wrapper :func:`profiled_jit` returns. Not a public type —
    hold it wherever a jitted callable was held before."""

    def __init__(self, fn: Callable, name: str, **jit_kw: Any) -> None:
        import jax

        self._fn = fn
        self.name = name
        self._jit = jax.jit(fn, **jit_kw)
        self._compiled: Dict[Tuple, Any] = {}
        # per-dispatch counter (cached object — the registry lookup is a
        # lock + dict probe, too hot for a per-call path): together with
        # profile.compiles this is the evidence the client pipeline's
        # coalescing claims rest on — N adds through a CoalescingBuffer
        # must move this by 1, not N
        self._calls = _metrics.registry().counter("profile.calls",
                                                  fn=name)

    def _sig(self, args, kwargs) -> Tuple:
        import jax

        leaves, treedef = jax.tree.flatten((args, kwargs))
        return (treedef, tuple(_leaf_sig(l) for l in leaves))

    def _compile(self, sig: Tuple, args, kwargs) -> Any:
        """AOT lower+compile for a new signature, timing both phases
        into the registry (and as trace spans)."""
        reg = _metrics.registry()
        with _trace.span("profile.lower", fn=self.name):
            t0 = time.perf_counter()
            lowered = self._jit.lower(*args, **kwargs)
            lower_s = time.perf_counter() - t0
        with _trace.span("profile.compile", fn=self.name):
            t0 = time.perf_counter()
            compiled = lowered.compile()
            compile_s = time.perf_counter() - t0
        reg.counter("profile.compiles", fn=self.name).inc()
        reg.histogram("profile.lower.seconds", fn=self.name) \
            .observe(lower_s)
        reg.histogram("profile.compile.seconds", fn=self.name) \
            .observe(compile_s)
        reg.gauge("profile.compile.last_s", fn=self.name).set(compile_s)
        self._record_cost(reg, compiled)
        _record_scopes(self.name, compiled)
        self._compiled[sig] = compiled
        return compiled

    def _record_cost(self, reg, compiled) -> None:
        """XLA cost/memory analysis of the compiled program."""
        cost = compiled.cost_analysis() or {}
        for key, metric in (("flops", "profile.flops"),
                            ("bytes accessed", "profile.bytes_accessed")):
            if cost.get(key):
                reg.gauge(metric, fn=self.name).set(float(cost[key]))
        ma = compiled.memory_analysis()
        for attr, key in (("argument_size_in_bytes", "args"),
                          ("output_size_in_bytes", "out"),
                          ("temp_size_in_bytes", "temp"),
                          ("generated_code_size_in_bytes", "code")):
            v = getattr(ma, attr, None)
            if v:
                reg.gauge(f"profile.memory.{key}_bytes",
                          fn=self.name).set(float(v))

    def __call__(self, *args: Any, **kwargs: Any) -> Any:
        import jax

        # counted on EVERY path (AOT, tracer): the counter means
        # "dispatches requested", not "AOT executions"
        self._calls.inc()
        if any(isinstance(l, jax.core.Tracer)
               for l in jax.tree.leaves((args, kwargs))):
            # inside an outer trace (grad/jit-of-jit): the plain path
            return self._jit(*args, **kwargs)
        sig = self._sig(args, kwargs)
        compiled = self._compiled.get(sig)
        if compiled is None:
            compiled = self._compile(sig, args, kwargs)
        return compiled(*args, **kwargs)

    # AOT introspection passthroughs, so holders of the wrapper keep
    # the jitted function's surface for debugging
    def lower(self, *args: Any, **kwargs: Any):
        return self._jit.lower(*args, **kwargs)


def profiled_jit(fn: Callable, *, name: Optional[str] = None,
                 **jit_kw: Any) -> Callable:
    """``jax.jit`` with a flight recorder (see module docstring).

    ``name`` labels every metric/span (default: the function's
    ``__name__``); remaining keywords pass through to ``jax.jit``
    (``donate_argnums``, ``out_shardings``, ``static_argnums``, ...).
    """
    return _ProfiledJit(fn, name or getattr(fn, "__name__", "jit"),
                        **jit_kw)


_CACHE: Dict[Any, Any] = {}
_CACHE_CAP = 64


def cached_profiled_jit(key: Any, name: str, build: Callable[[], Callable],
                        **jit_kw: Any) -> Callable:
    """Keyed cache of :func:`profiled_jit` wrappers for call-site-BUILT
    functions (the shard_map closures in ``parallel/`` are rebuilt on
    every call): the caller hashes whatever its closure captures into
    ``key``, and the same key returns the same wrapper — so XLA's
    compile cache and the ``profile.*`` metrics see ONE function per
    distinct program instead of a fresh one per call. ``build`` runs
    only on a miss. The cache is cleared (not LRU-evicted) past
    ``_CACHE_CAP`` keys — churny keys (e.g. lambdas rebuilt per call)
    must not pin arbitrary meshes/closures forever."""
    fn = _CACHE.get(key)
    if fn is None:
        if len(_CACHE) >= _CACHE_CAP:
            _CACHE.clear()
        fn = _CACHE[key] = profiled_jit(build(), name=name, **jit_kw)
    return fn


def record_device_memory(prefix: str = "device") -> dict:
    """Gauge the live-buffer population and per-device allocator stats;
    returns the recorded values (also useful in assertions). No-op dict
    when jax has no initialized backend."""
    reg = _metrics.registry()
    out: dict = {}
    try:
        import jax

        live = jax.live_arrays()
        out["live_buffers"] = len(live)
        out["live_bytes"] = int(sum(
            getattr(a, "nbytes", 0) or 0 for a in live))
        reg.gauge(f"{prefix}.live_buffers").set(out["live_buffers"])
        reg.gauge(f"{prefix}.live_bytes").set(out["live_bytes"])
        for d in jax.local_devices():
            stats = d.memory_stats()
            if not stats:
                continue          # CPU backends report nothing
            lbl = f"{d.platform}:{d.id}"
            for key in ("bytes_in_use", "peak_bytes_in_use",
                        "bytes_limit"):
                if key in stats:
                    reg.gauge(f"{prefix}.{key}", device=lbl) \
                        .set(float(stats[key]))
                    out[f"{lbl}.{key}"] = int(stats[key])
    except Exception:
        pass
    return out


@contextlib.contextmanager
def profile_window(name: str = "capture") -> Iterator[Optional[str]]:
    """Device-profiler capture window, gated by ``MVTPU_PROFILE_DIR``:
    when set, the wrapped region is captured with ``jax.profiler`` into
    ``$MVTPU_PROFILE_DIR/<name>`` (TensorBoard / Perfetto loadable) and
    the path is yielded; when unset, yields None and costs nothing.
    Windows must not nest (jax allows one active capture)."""
    base = os.environ.get("MVTPU_PROFILE_DIR")
    if not base:
        yield None
        return
    out = os.path.join(base, name)
    import jax

    try:
        jax.profiler.start_trace(out)
    except Exception as e:          # an already-active capture, etc.
        print(f"profile_window({name!r}): start_trace failed: {e!r}",
              file=sys.stderr)
        yield None
        return
    try:
        with _trace.span("profile.window", capture=name, dir=out):
            yield out
    finally:
        try:
            jax.profiler.stop_trace()
        except Exception:
            pass
