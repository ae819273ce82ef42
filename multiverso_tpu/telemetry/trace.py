"""Span tracing: one :func:`span` primitive with three outputs.

The host-side complement of the device profiler (PAPER/SURVEY §6.1's
"per-step wall-clock dashboard + ``jax.profiler.trace`` hooks"): a
:func:`span` context manager times a region and

1. enters a ``jax.profiler.TraceAnnotation`` when jax is already loaded,
   so the span is an event on the host plane of any profile being taken,
   on the PROFILE's clock, beside the device's ops (with no profile
   being taken the annotation is a flag test);
2. adds its duration to the registry histogram
   ``span.seconds{name=<name>}`` — always, so an untraced run still
   says where the host's time went;
3. appends one JSON record to the trace file when a sink is set,
   with its parent from a thread-local stack (ids are a
   process-monotonic counter — no randomness).

Span names are literals from a small vocabulary (``w2v.wait_data``,
``lda.dispatch``, ``table.get``): never built from keys, ids or sizes,
because each name is a registry series. :func:`scope` is the device-side
half of the vocabulary: a decorator for the phases INSIDE a traced
body, whose name lands in the compiled ops' metadata
(:func:`multiverso_tpu.telemetry.profiling.op_scopes` reads it back).

Record shapes (one JSON object per line):

- span:  ``{"kind": "span", "name", "id", "parent", "ts", "dur_s",
  "attrs"?, "req"?}`` (``parent`` is null for roots; ``ts`` is the
  epoch start; ``req`` is the request id when the span ran inside a
  :func:`request` scope)
- step:  ``{"kind": "step", "name", "step", "ts", ...metrics}`` — the
  per-superstep heartbeat apps emit via :func:`step_timeline`; a trace
  with step records is a per-step timeline even when nothing else is
  instrumented (the round-5 bench hang left zero such signal).

Request scoping (the serving-observability layer): :func:`request`
mints a ``request_id`` at a client entry point and stamps it — plus
parent links — onto every span nested under it, including spans on
OTHER threads via the :func:`link`/:func:`adopt` hand-off (the client
pipeline's D2H-wait and host-prep workers). One slow get then
reconstructs as one parent-linked tree in the JSONL and the
``--chrome-trace`` export.

Sink configuration: :func:`set_trace_file`, or ``MVTPU_TRACE_JSONL``
(a file path), or ``MVTPU_TRACE_DIR`` (a directory; the file becomes
``trace-<pid>.jsonl`` inside it — per-process files, safe multi-host).
``MVTPU_TRACE_MAX_MB`` size-caps the sink with a keep-1 rollover.
With no sink a span still nests, annotates and observes but builds no
record: it costs two ``perf_counter`` calls and one ``observe``.
"""

from __future__ import annotations

import contextlib
import functools
import itertools
import json
import os
import sys
import threading
import time
from typing import Dict, Iterator, List, Optional, TextIO, Tuple


def _sibling(name: str):
    """A stdlib-only module of this package WITHOUT a package import:
    ``client/transport.py`` loads this file by path in jax-free worker
    processes, where ``import multiverso_tpu`` (and with it jax) must
    not happen. Registered under its canonical name, so a later package
    import finds the same module (and the same registry)."""
    modname = f"multiverso_tpu.telemetry.{name}"
    mod = sys.modules.get(modname)
    if mod is not None:
        return mod
    import importlib.util
    if "multiverso_tpu" in sys.modules:
        return importlib.import_module(modname)
    spec = importlib.util.spec_from_file_location(modname, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[modname] = mod
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        sys.modules.pop(modname, None)
        raise
    return mod


_metrics = _sibling("metrics")

_IDS = itertools.count(1)
_REQS = itertools.count(1)
_TLS = threading.local()
_LOCK = threading.Lock()
_FILE: Optional[TextIO] = None
_PATH: Optional[str] = None

LinkToken = Tuple[Optional[str], Optional[int]]


def _stack() -> List[int]:
    st = getattr(_TLS, "stack", None)
    if st is None:
        st = _TLS.stack = []
    return st


def set_trace_file(path: Optional[str]) -> None:
    """Point the trace sink at ``path`` (append mode); None disables."""
    global _FILE, _PATH
    with _LOCK:
        if _FILE is not None:
            _FILE.close()
        if path:
            parent = os.path.dirname(os.path.abspath(path))
            os.makedirs(parent, exist_ok=True)
            # line-buffered + flush per record (_emit): a SIGKILL'd or
            # watchdog-terminated process keeps every span written up
            # to the kill point
            _FILE = open(path, "a", buffering=1)
        else:
            _FILE = None
        _PATH = path or None


def trace_path() -> Optional[str]:
    return _PATH


def active() -> bool:
    """True when a trace sink is configured. Hot paths that BUILD
    records retroactively (the server's post-dispatch span emission)
    check this first — with no sink, :func:`_emit` would discard the
    record anyway, and the dict assembly is the entire cost."""
    return _FILE is not None


def _emit(rec: dict) -> None:
    # identity stamps: host/pid pick the Perfetto process track (and
    # correlate with snapshots, log lines, and watchdog dumps); tid
    # separates concurrent host threads so span nesting stays true
    rec.setdefault("host", _metrics.host_index())
    rec.setdefault("pid", os.getpid())
    rec.setdefault("tid", threading.get_ident())
    global _FILE
    with _LOCK:
        if _FILE is not None:
            _FILE.write(json.dumps(rec) + "\n")
            _FILE.flush()
            limit = _metrics.sink_max_bytes()
            if limit and _PATH and _FILE.tell() >= limit:
                _FILE = _metrics.rotate_jsonl(_PATH, _FILE)


SPAN_SECONDS = "span.seconds"
# name -> (registry generation, histogram): a span holds the histogram
# object, so the hot path skips the registry's lock and label sort
_SPAN_HISTS: Dict[str, Tuple[int, "_metrics.Histogram"]] = {}


def _span_histogram(name: str) -> "_metrics.Histogram":
    reg = _metrics.registry()
    held = _SPAN_HISTS.get(name)
    if held is None or held[0] != reg.generation:
        held = _SPAN_HISTS[name] = (reg.generation, reg.histogram(
            SPAN_SECONDS, _metrics.LATENCY_BUCKETS, name=name))
    return held[1]


def _annotation(name: str):
    """``jax.profiler.TraceAnnotation(name)`` when jax is already loaded
    — the span is then an event on the host plane of a profile being
    taken. Never IMPORTS jax (the report CLI and pure-host tools must
    not pay, or fail, a backend init)."""
    jax = sys.modules.get("jax")
    if jax is None:
        return contextlib.nullcontext()
    return jax.profiler.TraceAnnotation(name)


def scope(name: str):
    """Decorator naming the device ops a function traces: for use on
    the phases INSIDE a jitted body (``@telemetry.scope("w2v.math")``).
    The names share the spans' vocabulary (``w2v.scatter_out``,
    ``lda.sample``) and come back from the compiled text through
    :func:`multiverso_tpu.telemetry.profiling.op_scopes`.

    The function becomes a nested ``jax.jit`` called ``name``: XLA
    inlines the call, so the compiled program is the one it was, with
    ``jit(<name>)`` in its ops' ``op_name``. Not ``jax.named_scope``:
    that lives in debug info only, which jax strips from the persistent
    compile cache's key — a cache filled by older source then hands
    back an executable without the names (seen on the chip, PERF.md
    PR 25). A function's name is part of the key."""
    import jax

    def wrap(fn):
        @functools.wraps(fn)
        def scoped(*args):
            return fn(*args)
        scoped.__name__ = scoped.__qualname__ = name
        return jax.jit(scoped)
    return wrap


def _span_record(name: str, sid: int, parent: Optional[int], ts: float,
                 dur_s: float, attrs: dict) -> dict:
    rec = {"kind": "span", "name": name, "id": sid,
           "parent": parent, "ts": ts, "dur_s": dur_s}
    rid = getattr(_TLS, "request", None)
    if rid is not None:
        rec["req"] = rid
    if parent is None:
        rparent = getattr(_TLS, "rparent", None)
        if rparent is not None:
            rec["rparent"] = rparent
    if attrs:
        rec["attrs"] = attrs
    return rec


@contextlib.contextmanager
def span(name: str, **attrs) -> Iterator[int]:
    """Time a region as a nestable span; yields the span id."""
    sid = next(_IDS)
    st = _stack()
    parent = st[-1] if st else None
    st.append(sid)
    hist = _span_histogram(name)
    t0 = time.perf_counter()
    try:
        with _annotation(name):
            yield sid
    finally:
        dur = time.perf_counter() - t0
        st.pop()
        hist.observe(dur)
        if _FILE is not None:
            _emit(_span_record(name, sid, parent, time.time() - dur,
                               dur, attrs))


def emit_span(name: str, ts: float, dur_s: float, **attrs) -> int:
    """Record an ALREADY-MEASURED interval as a span (retroactive
    emission — e.g. a queue wait only known at dequeue). Same record
    shape, parenting, and request stamping as :func:`span`; returns
    the span id."""
    sid = next(_IDS)
    st = _stack()
    parent = st[-1] if st else None
    _emit(_span_record(name, sid, parent, float(ts), float(dur_s), attrs))
    return sid


# -- request scoping -------------------------------------------------------

def new_request_id() -> str:
    """Mint a request id: ``r<host>-<pid>-<counter>`` — unique across a
    fleet, no randomness (the trace layer's id discipline)."""
    return f"r{_metrics.host_index()}-{os.getpid()}-{next(_REQS)}"


def current_request() -> Optional[str]:
    """The request id this thread is serving, or None."""
    return getattr(_TLS, "request", None)


@contextlib.contextmanager
def request(name: str, **attrs) -> Iterator[str]:
    """Open a request scope at a client entry point: mints a request
    id, opens a root span named ``name``, and stamps the id (``req``)
    onto that span and every span nested under it — on this thread, or
    on a worker thread that :func:`adopt`\\ s this scope's
    :func:`link` token. Yields the request id. Re-entrant: an entry
    point invoked while a request is already open joins the OUTER
    request (one user-visible operation = one tree)."""
    rid = getattr(_TLS, "request", None)
    fresh = rid is None
    if fresh:
        rid = new_request_id()
        _TLS.request = rid
    try:
        with span(name, **attrs):
            yield rid
    finally:
        if fresh:
            _TLS.request = None


def link() -> Optional[LinkToken]:
    """Capture ``(request_id, innermost span id)`` for hand-off to
    another thread (both halves may be None-padded); None when there is
    nothing to link — the no-tracing fast path."""
    st = _stack()
    rid = getattr(_TLS, "request", None)
    sid = st[-1] if st else None
    if rid is None and sid is None:
        return None
    return (rid, sid)


@contextlib.contextmanager
def adopt(token: Optional[LinkToken]) -> Iterator[None]:
    """Parent this thread's spans under a :func:`link` token minted on
    another thread — the cross-thread half of request scoping (D2H-wait
    workers, staging prep). Spans opened inside the block chain to the
    token's span and carry its request id."""
    if token is None:
        yield
        return
    rid, sid = token
    st = _stack()
    prev = getattr(_TLS, "request", None)
    if rid is not None:
        _TLS.request = rid
    if sid is not None:
        st.append(sid)
    try:
        yield
    finally:
        if sid is not None:
            st.pop()
        _TLS.request = prev


# -- cross-process propagation (the wire's trace context) ------------------
# Span ids are process-monotonic ints, so a parent link cannot cross a
# process boundary by id alone. The wire convention: the client ships
# ``{"req", "span", "host", "pid"}`` in the frame header
# (:func:`wire_context`), the server serves the request inside
# :func:`adopt_remote`, and every server-side ROOT span then carries an
# ``rparent`` field naming the foreign (host, pid, span) — enough for
# the chrome exporter to stitch one tree across N+1 processes.

def wire_context() -> dict:
    """Trace context to stamp into a wire frame header: the current
    request id (minted fresh when no request scope is open — the server
    side still gets a groupable tree), the innermost span id as the
    cross-process parent, and this process's (host, pid) identity."""
    rid = getattr(_TLS, "request", None)
    if rid is None:
        rid = new_request_id()
    ctx = {"req": rid, "host": _metrics.host_index(),
           "pid": os.getpid()}
    st = _stack()
    if st:
        ctx["span"] = st[-1]
    return ctx


@contextlib.contextmanager
def adopt_remote(ctx: Optional[dict]) -> Iterator[None]:
    """Serve a request under a foreign :func:`wire_context`: spans
    opened inside the block carry the originating request id, and root
    spans (no local parent) carry an ``rparent`` record naming the
    remote (host, pid, span) they chain under. Tolerant of missing or
    malformed contexts — an untraced frame serves exactly as before."""
    if not isinstance(ctx, dict) or not ctx.get("req"):
        yield
        return
    prev_req = getattr(_TLS, "request", None)
    prev_rp = getattr(_TLS, "rparent", None)
    _TLS.request = str(ctx["req"])
    rparent = {}
    for key in ("host", "pid", "span"):
        val = ctx.get(key)
        if isinstance(val, (int, str)):
            rparent[key] = val
    _TLS.rparent = rparent or None
    try:
        yield
    finally:
        _TLS.request = prev_req
        _TLS.rparent = prev_rp


def clock_record(peer: dict, offset_us: float, rtt_us: float) -> dict:
    """Record a per-connection clock-offset estimate: ``offset_us`` is
    the peer's wall clock minus ours (RTT-midpoint method), ``rtt_us``
    the ping round trip that produced it. The fleet report uses these
    to shift the peer's spans onto one honest timeline."""
    rec = {"kind": "clock", "ts": time.time(),
           "peer": {k: peer[k] for k in ("host", "pid") if k in peer},
           "offset_us": float(offset_us), "rtt_us": float(rtt_us)}
    _emit(rec)
    return rec


def step_timeline(name: str, step: int, **fields) -> dict:
    """Per-superstep heartbeat: one JSON record carrying the step number
    plus whatever throughput fields the app measured. Apps call this
    once per superstep dispatch — the trace file then always shows how
    far a run got and how fast it was moving when it stopped."""
    st = _stack()
    rec = {"kind": "step", "name": name, "step": int(step),
           "ts": time.time(), **fields}
    if st:
        rec["parent"] = st[-1]
    _emit(rec)
    return rec


def read_trace(path: str) -> List[dict]:
    """Load a trace JSONL file (skipping torn trailing lines — the
    writer may have been killed mid-record)."""
    records = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                records.append(json.loads(line))
            except ValueError:
                continue
    return records


_env = os.environ.get("MVTPU_TRACE_JSONL")
if not _env:
    _dir = os.environ.get("MVTPU_TRACE_DIR")
    if _dir:
        _env = os.path.join(_dir, f"trace-{os.getpid()}.jsonl")
if _env:
    set_trace_file(_env)
