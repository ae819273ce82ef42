"""The trainers name their own time: ``telemetry.span`` has three
outputs (profile annotation, ``span.seconds`` histogram, JSONL record),
the apps place spans where the work happens, the traced bodies carry
program scopes that ``profiling.op_scopes`` reads back from the compiled
text, and the names the benchmark's accepted readers lean on hold."""

import glob
import os
import re
import threading

import jax
import numpy as np
import pytest

from multiverso_tpu import telemetry
from multiverso_tpu.apps.lightlda import LDAConfig, LightLDA
from multiverso_tpu.apps.word_embedding import W2VConfig, WordEmbedding
from multiverso_tpu.data.corpus import Corpus
from multiverso_tpu.data.native import CorpusData
from multiverso_tpu.tables import base as table_base
from multiverso_tpu.telemetry import metrics, profiling, trace
from multiverso_tpu.utils.async_buffer import prefetch_iterator


@pytest.fixture(autouse=True)
def _clean():
    trace.set_trace_file(None)
    metrics.registry().reset()
    yield
    trace.set_trace_file(None)
    table_base.reset_tables()


def _series(name):
    return metrics.snapshot()["histograms"].get(
        f"span.seconds{{name={name}}}", {"count": 0, "sum": 0.0})


def _count(name):
    return _series(name)["count"]


def _w2v(mesh, name="w2v_spans"):
    V = 400
    ids = np.random.default_rng(0).integers(0, V, 20000).astype(np.int32)
    data = CorpusData(words=range(V),
                      counts=np.bincount(ids, minlength=V).astype(np.int64),
                      ids=ids, total_raw_tokens=len(ids))
    return WordEmbedding(
        Corpus(data, subsample=0.0),
        W2VConfig(embedding_dim=16, batch_size=64, steps_per_call=4,
                  ns_table_size=1 << 10, seed=1), mesh=mesh, name=name)


def _lda(mesh, name="lda_spans", **kw):
    rng = np.random.default_rng(5)
    lens = rng.integers(5, 60, 40)
    td = np.repeat(np.arange(40, dtype=np.int32), lens)
    tw = rng.integers(0, 200, len(td)).astype(np.int32)
    cfg = dict(num_topics=128, batch_tokens=512, steps_per_call=1, seed=3,
               sampler="tiled", doc_blocked=True, block_tokens=256,
               block_docs=8)
    cfg.update(kw)
    return LightLDA(tw, td, 200, LDAConfig(**cfg), mesh=mesh, name=name), \
        tw, td


@pytest.fixture()
def mesh1(devices):
    from multiverso_tpu import core
    m = core.init(devices=devices[:1], data_parallel=1, model_parallel=1)
    yield m
    core.shutdown()


# -- (a) the primitive -------------------------------------------------------

def test_span_without_a_sink_observes_and_writes_nothing(monkeypatch):
    def boom(*a, **k):
        raise AssertionError("not on the no-sink path")
    monkeypatch.setattr(trace, "_emit", boom)
    monkeypatch.setattr(jax, "named_scope", boom)
    monkeypatch.setattr(jax, "jit", boom)
    assert not trace.active()
    with telemetry.span("t.region", table="x") as sid:
        with telemetry.span("t.inner"):
            pass
    assert isinstance(sid, int)
    assert _count("t.region") == 1 and _count("t.inner") == 1
    h = _series("t.region")
    assert h["bounds"] == list(telemetry.LATENCY_BUCKETS)
    assert h["sum"] >= _series("t.inner")["sum"] > 0.0
    with telemetry.span("t.region"):
        pass
    assert _count("t.region") == 2


def test_span_with_a_sink_writes_the_record_too(tmp_path):
    path = str(tmp_path / "t.jsonl")
    trace.set_trace_file(path)
    with telemetry.span("t.outer", k=1) as outer:
        with telemetry.span("t.inner"):
            pass
    trace.set_trace_file(None)
    recs = {r["name"]: r for r in trace.read_trace(path)}
    assert recs["t.inner"]["parent"] == outer == recs["t.outer"]["id"]
    assert recs["t.outer"]["attrs"] == {"k": 1}
    assert recs["t.outer"]["ts"] <= recs["t.inner"]["ts"]
    assert recs["t.outer"]["dur_s"] == pytest.approx(
        _series("t.outer")["sum"])


def test_span_histogram_survives_a_registry_reset():
    with telemetry.span("t.reset"):
        pass
    metrics.registry().reset()
    assert _count("t.reset") == 0
    with telemetry.span("t.reset"):
        pass
    assert _count("t.reset") == 1


def test_a_label_may_be_called_name():
    metrics.histogram("h.x", bounds=(1.0,), name="a").observe(0.5)
    metrics.counter("c.x", name="a").inc()
    snap = metrics.snapshot()
    assert snap["histograms"]["h.x{name=a}"]["count"] == 1
    assert snap["counters"]["c.x{name=a}"] == 1
    assert 'h_x_count{name="a"} 1' in metrics.snapshot_to_prometheus(snap)


def test_named_prefetch_times_its_producer_thread():
    assert list(prefetch_iterator(range(5), depth=2)) == list(range(5))
    assert metrics.snapshot()["histograms"] == {}    # unnamed: nothing
    seen = []

    def gen():
        for i in range(5):
            seen.append(threading.get_ident())
            yield i
    assert list(prefetch_iterator(gen(), depth=1, name="t.pairs")) \
        == list(range(5))
    assert set(seen) != {threading.get_ident()}
    # one produce per item and one for the end of the stream
    assert _count("t.pairs.produce") == 6
    assert _count("t.pairs.backpressure") == 6


# -- (b) the spans reach a profile, on its clock -----------------------------

def _host_events(trace_dir):
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                out.setdefault(ev.name, []).append(
                    (ev.start_ns, ev.start_ns + ev.duration_ns))
    return out


def _inside(inner, outer):
    return all(any(a <= c and d <= b for a, b in outer) for c, d in inner)


def test_program_spans_are_host_events_of_a_profile(mesh1, tmp_path):
    w2v = _w2v(mesh1)
    lda, _, _ = _lda(mesh1)
    w2v.train(total_steps=4)         # compile outside the profile
    lda.sweep()
    jax.profiler.start_trace(str(tmp_path))
    try:
        w2v.train(total_steps=8)
        lda.sweep()
        lda.sweep()
        jax.block_until_ready(lda.word_topic.raw())
    finally:
        jax.profiler.stop_trace()
    ev = _host_events(str(tmp_path))
    for name in ("w2v.wait_data", "w2v.place", "w2v.superstep",
                 "w2v.pairs.produce", "w2v.fence", "lda.sweep",
                 "lda.to_stale", "lda.dispatch", "lda.rebuild",
                 "superstep.run"):
        assert ev.get(name), f"no host event {name!r} in the profile"
    assert len(ev["lda.sweep"]) == 2
    assert len(ev["lda.dispatch"]) == 2 * lda.calls_per_sweep
    assert len(ev["w2v.superstep"]) == 2
    for inner in ("lda.to_stale", "lda.dispatch", "lda.rebuild"):
        assert _inside(ev[inner], ev["lda.sweep"]), inner
    assert _inside(ev["superstep.run"],
                   ev["lda.dispatch"] + ev["w2v.superstep"])
    # the window's work never goes through the retired series
    assert not [k for k in metrics.snapshot()["histograms"]
                if k.startswith("app.step.seconds")]


# -- (c) lda.sweep is sweep()'s own -------------------------------------------

def test_lda_sweep_span_once_per_sweep(mesh1):
    lda, _, _ = _lda(mesh1)
    lda.sweep()
    assert _count("lda.sweep") == 1
    assert _count("lda.dispatch") == lda.calls_per_sweep
    assert _count("lda.to_stale") == _count("lda.rebuild") == 1
    lda.train(num_iterations=2)
    assert _count("lda.sweep") == 3
    assert _count("lda.setup.pack") == _count("lda.setup.counts") == 1
    assert _count("superstep.run") == 3 * lda.calls_per_sweep


def test_w2v_spans_one_per_phase_per_call(mesh1):
    w2v = _w2v(mesh1)
    assert _count("w2v.setup.init_tables") == 1
    assert _count("w2v.setup.vocab_tables") == 1
    w2v.train(total_steps=12)
    assert len(w2v.loss_history) == 3
    for name in ("w2v.wait_data", "w2v.place", "w2v.superstep",
                 "superstep.run"):
        assert _count(name) == 3, name
    assert _count("w2v.fence") == 1
    assert _count("w2v.pairs.produce") >= 12
    disp, run = _series("w2v.superstep"), _series("superstep.run")
    assert disp["sum"] >= run["sum"] > 0.0


# -- (d) the compiled text names the ops --------------------------------------

def test_op_scopes_name_the_supersteps_ops(mesh1):
    # another test's word2vec program of the same ``fn`` (this worker may
    # have compiled the hierarchical-softmax one) would merge with this
    # one's, and an instruction name the two scope differently reads
    # unscoped: read this test's own compile
    profiling._OP_SCOPES.pop("superstep.w2v_superstep", None)
    w2v = _w2v(mesh1)
    w2v.train(total_steps=4)
    held = profiling.op_scopes()["superstep.w2v_superstep"]
    assert held["module"] == "jit_run"
    by_scope = {}
    for instruction, scope in held["scopes"].items():
        by_scope.setdefault(scope, []).append(instruction)
    for scope in ("w2v.gather_in", "w2v.negatives", "w2v.gather_out",
                  "w2v.math", "w2v.scatter_out", "w2v.scatter_in"):
        assert by_scope.get(scope), scope
    lowered = w2v._fused._run.lower(
        (w2v.w_in.param, w2v.w_out.param),
        (w2v.w_in.state, w2v.w_out.state), (),
        tuple(t._resolve_option(None) for t in (w2v.w_in, w2v.w_out)),
        jax.ShapeDtypeStruct((4, 64, 2), np.int16), w2v._key,
        jax.ShapeDtypeStruct((4,), np.float32))
    # the names are in the IR proper (a function's name), not only in
    # debug info: the persistent compile cache keys on the former, so
    # a cached executable cannot come back without them
    assert "w2v.scatter_out" in lowered.as_text()
    text = lowered.compile().as_text()
    # on one device the scatter is the distinct-row writer: its sort of
    # the step's lanes is an op of the scope as the scatter was
    scatters = [ln for ln in text.splitlines()
                if " sort(" in ln and "w2v.scatter_out" in ln]
    assert scatters
    name = scatters[0].split(" = ")[0].replace("ROOT", "").strip(" %")
    assert held["scopes"][name] == "w2v.scatter_out"
    gauges = metrics.snapshot()["gauges"]
    assert gauges["profile.scope.ops{fn=superstep.w2v_superstep,"
                  "scope=w2v.scatter_out}"] >= 1


def test_a_program_without_scopes_maps_to_nothing():
    f = profiling.profiled_jit(lambda x: (x * 2.0).sum(),
                               name="t.unscoped")
    f(np.ones(8, np.float32))
    assert "t.unscoped" not in profiling.op_scopes()
    assert not [k for k in metrics.snapshot()["gauges"]
                if k.startswith("profile.scope.ops{fn=t.unscoped")]
    assert _count("profile.op_scopes") == 1


def test_parse_op_scopes_takes_the_innermost_program_scope():
    text = '''HloModule jit_run, is_scheduled=true

%fused_computation.1 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %neg.1 = f32[8]{0} negate(%p), metadata={op_name="jit(run)/while/body/jit(w2v.math)/neg"}
}

ENTRY %main.3 (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0), metadata={op_name="params[0]"}
  %fusion.62 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(run)/while/body/closed_call/jit(w2v.scatter_out)/scatter-add" source_file="x.py"}
  %copy.1 = f32[8]{0} copy(%fusion.62)
  ROOT %k.2 = f32[8]{0} custom-call(%copy.1), metadata={op_name="jit(run)/jit(lda.outer)/jit(lda.sample)/jit(k)/pallas_call"}
}
'''
    module, scopes = profiling.parse_op_scopes(text)
    assert module == "jit_run"
    assert scopes == {"p": "unscoped", "neg.1": "w2v.math",
                      "a": "unscoped", "fusion.62": "w2v.scatter_out",
                      "copy.1": "unscoped", "k.2": "lda.sample"}
    into = {"x": "a.b", "y": "a.b"}
    profiling.merge_op_scopes(into, {"y": "c.d", "z": "c.d"})
    assert into == {"x": "a.b", "y": "unscoped", "z": "c.d"}


def _op(name, scope=None, opcode="add", operands="%p", tail=""):
    """One line of compiled text: ``scope`` a program scope, ``""`` an
    ``op_name`` that names none, ``None`` no metadata at all."""
    meta = "" if scope is None else ', metadata={op_name="jit(run)/%s%s"}' \
        % (f"jit({scope})/" if scope else "", opcode)
    return f"  %{name} = f32[8]{{0}} {opcode}({operands}){tail}{meta}"


def _module(bodies, entry):
    """A module's text from its fused computations and its entry's
    instructions (``fusion`` lines call the bodies by name)."""
    text = ["HloModule jit_run, is_scheduled=true", ""]
    for name, lines in bodies.items():
        text += [f"%{name} (p: f32[8]) -> f32[8] {{",
                 "  %p = f32[8]{0} parameter(0)", *lines, "}", ""]
    text += ["ENTRY %main.9 (a: f32[8]) -> f32[8] {",
             '  %a = f32[8]{0} parameter(0), metadata={op_name="params[0]"}',
             *entry, "}", ""]
    return "\n".join(text)


def _fusion(name, body, scope=None, operands="%a"):
    return _op(name, scope, "fusion", operands,
               f", kind=kLoop, calls=%{body}")


_DOT = dict(opcode="dot", operands="%p, %p")
FUSION_CASES = {
    # case: (bodies, entry, scopes expected of the entry's fusions, inferred)
    "a body with one scope names its fusion":
        ({"b": [_op("m.1", "lm.x.y"), _op("n.1", "lm.x.y"), _op("o.1")]},
         [_fusion("fusion.1", "b", "")],
         {"fusion.1": "lm.x.y"}, {"fusion.1": 1}),
    "its own scope wins over the body's":
        ({"b": [_op("m.1", "lm.x.y")]},
         [_fusion("fusion.1", "b", "lm.own.z")],
         {"fusion.1": "lm.own.z"}, {}),
    "products decide over the count":
        ({"b": [_op("m.1", "lm.moe.permute"), _op("m.2", "lm.moe.permute"),
                _op("d.1", "lm.moe.experts", **_DOT),
                _op("c.1", None, "custom-call")]},
         [_fusion("fusion.1", "b")],
         {"fusion.1": "lm.moe.experts"}, {"fusion.1": 2}),
    "products that disagree leave it to the count":
        ({"b": [_op("d.1", "lm.a.b", **_DOT), _op("d.2", "lm.c.d", **_DOT),
                _op("m.1", "lm.c.d")]},
         [_fusion("fusion.1", "b")],
         {"fusion.1": "lm.c.d"}, {"fusion.1": 2}),
    "the count decides where there is no product":
        ({"b": [_op("m.1", "lm.a.b"), _op("m.2", "lm.c.d"),
                _op("m.3", "lm.c.d"),
                _op("g.1", "lm.a.b", "bitcast"),
                _op("g.2", "lm.a.b", "get-tuple-element")]},
         [_fusion("fusion.1", "b", "")],
         {"fusion.1": "lm.c.d"}, {"fusion.1": 2}),
    "a tie stays unscoped":
        ({"b": [_op("m.1", "lm.a.b"), _op("m.2", "lm.c.d")]},
         [_fusion("fusion.1", "b")],
         {"fusion.1": "unscoped"}, {}),
    "a body that names nothing stays unscoped":
        ({"b": [_op("m.1", ""), _op("m.2")]},
         [_op("x.1", "lm.a.b", operands="%a"),
          _fusion("fusion.1", "b", "", "%x.1")],
         {"fusion.1": "unscoped"}, {}),
    "a fusion inside the body counts as what it was made":
        ({"inner": [_op("m.1", "lm.a.b")],
          "b": [_fusion("fusion.7", "inner", operands="%p"), _op("m.2", "")]},
         [_fusion("fusion.1", "b")],
         {"fusion.1": "lm.a.b", "fusion.7": "lm.a.b"},
         {"fusion.1": 1, "fusion.7": 1}),
    "the compiler's own fusion takes its operands' scope":
        ({"b": [_op("conv.1", None, "convolution", "%p, %p"),
                _op("sel.1", None, "select")]},
         [_op("x.1", "lm.moe.experts", operands="%a"),
          _op("dep.1", None, "add-dependency", "%a"),
          _fusion("fusion.1", "b", None,
                  "%x.1, %dep.1, /*index=2*/%a"),
          _fusion("fusion.2", "b", None, "%fusion.1")],
         {"fusion.1": "lm.moe.experts", "fusion.2": "lm.moe.experts"},
         {"fusion.1": 0, "fusion.2": 0}),
    "operands that disagree name nothing":
        ({"b": [_op("conv.1", None, "convolution", "%p, %p")]},
         [_op("x.1", "lm.a.b", operands="%a"),
          _op("x.2", "lm.c.d", operands="%a"),
          _fusion("fusion.1", "b", None, "%x.1, %x.2")],
         {"fusion.1": "unscoped"}, {}),
}


@pytest.mark.parametrize("case", list(FUSION_CASES))
def test_a_fusion_without_a_scope_takes_its_bodys(case):
    bodies, entry, want, want_inferred = FUSION_CASES[case]
    text = _module(bodies, entry)
    module, scopes, inferred = profiling.infer_op_scopes(text)
    assert module == "jit_run"
    assert {name: scopes[name] for name in want} == want
    assert inferred == want_inferred
    assert (module, scopes) == profiling.parse_op_scopes(text)
    # glue and what nothing names read as they did
    assert scopes["p"] == scopes["a"] == "unscoped"


def test_two_programs_that_disagree_are_unscoped_and_not_inferred():
    body = {"b": [_op("m.1", "lm.a.b")]}
    _, one, one_inferred = profiling.infer_op_scopes(_module(
        body, [_fusion("fusion.1", "b"), _fusion("fusion.2", "b")]))
    _, two, two_inferred = profiling.infer_op_scopes(_module(
        {"b": [_op("m.1", "lm.c.d")], "c": [_op("m.3", "lm.a.b"),
                                            _op("m.4", "lm.e.f", **_DOT)]},
        [_fusion("fusion.1", "b"), _fusion("fusion.2", "c")]))
    assert one_inferred == {"fusion.1": 1, "fusion.2": 1}
    assert two_inferred == {"fusion.1": 1, "fusion.2": 2}
    profiling.merge_op_scopes(one, two, one_inferred, two_inferred)
    assert one["fusion.1"] == "unscoped" and one["m.1"] == "unscoped"
    assert one["fusion.2"] == "unscoped"
    assert one_inferred == {}
    # programs that agree keep the name, and the larger count
    _, three, three_inferred = profiling.infer_op_scopes(_module(
        body, [_fusion("fusion.1", "b")]))
    _, four, four_inferred = profiling.infer_op_scopes(_module(
        {"b": [_op("m.1", "lm.a.b"), _op("m.2", "lm.a.b"),
               _op("m.5", "lm.c.d")]}, [_fusion("fusion.1", "b")]))
    profiling.merge_op_scopes(three, four, three_inferred, four_inferred)
    assert three["fusion.1"] == "lm.a.b"
    assert three_inferred == {"fusion.1": 2}


def test_op_scopes_say_which_names_were_inferred():
    @telemetry.scope("t.inner.math")
    def inner(x):
        return jax.numpy.tanh(x) * 2.0

    f = profiling.profiled_jit(lambda x: inner(x).sum(), name="t.inferred")
    f(np.ones(8, np.float32))
    held = profiling.op_scopes()["t.inferred"]
    assert set(held) == {"module", "scopes", "inferred"}
    assert "t.inner.math" in held["scopes"].values()
    for name, n in held["inferred"].items():
        assert held["scopes"][name] != "unscoped" and n >= 0
    profiling._OP_SCOPES.pop("t.inferred", None)


# -- (e) the names the benchmark's accepted readers match ----------------------

def test_module_and_kernel_names_the_readers_lean_on(mesh1):
    lda, _, _ = _lda(mesh1)
    lda.sweep()
    held = profiling.op_scopes()["superstep.lda_docblock"]
    assert held["module"] == "jit_run"
    for scope in ("lda.gather_words", "lda.sample", "lda.doc_counts",
                  "lda.carry"):
        assert scope in held["scopes"].values(), scope
    rebuild = lda._rebuild.lower(lda._z, lda._tw_flat, lda._mask_flat)
    assert "module @jit_rebuild" in rebuild.as_text()
    stale = lda._to_stale.lower(lda.word_topic.raw())
    assert "module @jit_to_stale" in stale.as_text()
    # the sampler kernel sits in a jitted function of this name, which
    # names its custom call on the chip (gibbs_sample_docblock.N)
    from multiverso_tpu.ops import gibbs_sample_docblock
    nb, tb, c = 2, 256, 1
    sds = jax.ShapeDtypeStruct
    lowered = jax.jit(lambda *a: gibbs_sample_docblock(
        *a, alpha=0.1, beta=0.01, tb=tb, interpret=True)).lower(
        sds((nb, 8, c, 128), np.int16), sds((nb * tb, c, 128), np.float32),
        sds((c, 128), np.float32), *[sds((nb * tb,), np.int32)] * 3,
        *[sds((nb * tb,), np.float32)] * 2)
    assert "@gibbs_sample_docblock" in lowered.as_text()


# -- (f) the sweep's word-row gather is one plain local read -------------------

_COLLECTIVE = re.compile(
    r" (all-reduce|all-gather|all-to-all|collective-permute|reduce-scatter)"
    r"(-start|-done)?\(")


def _superstep_args(lda):
    """What ``sweep()`` hands ``superstep.lda_docblock`` for its first
    call (``lower`` donates nothing)."""
    wstale = lda._to_stale(lda.word_topic.raw())
    return ((lda.summary.param,), (lda.summary.state,),
            (lda._ndk, lda._z, lda._calls_dev),
            (lda.summary._resolve_option(None),),
            wstale, *lda._calls[0], lda._key)


def test_no_collective_under_gather_words_on_a_dp_mp_mesh(mesh8):
    from multiverso_tpu import core
    lda, _, _ = _lda(mesh8, batch_tokens=2048)     # a block a chip
    args = _superstep_args(lda)
    mirror = args[4]
    # the mirror is the chip's whole per-sweep cache; the table it
    # caches stays vocab-sliced
    assert mirror.dtype == jax.numpy.bfloat16
    assert mirror.sharding.is_fully_replicated
    assert lda.word_topic.raw().sharding.spec[0] == core.MODEL_AXIS
    lines = lda._fused._run.lower(*args).compile().as_text().splitlines()
    scoped = [ln for ln in lines if "jit(lda.gather_words)" in ln]
    assert [ln for ln in scoped if " gather(" in ln]
    assert not [ln for ln in scoped if _COLLECTIVE.search(ln)]
    # the pattern does see this mesh's collectives: the summary delta's
    # psum over data x model sits under lda.sample
    assert [ln for ln in lines if _COLLECTIVE.search(ln)
            and "jit(lda.sample)" in ln]


def _primitives_under(jaxpr, scope, inside=False):
    """Names of the primitives inside the nested jit called ``scope``."""
    out = []
    for eqn in jaxpr.eqns:
        here = inside or eqn.params.get("name") == scope
        if inside:
            out.append(eqn.primitive.name)
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    out += _primitives_under(sub, scope, here)
    return out


def test_gather_words_holds_no_fill_mask(mesh1):
    lda, _, _ = _lda(mesh1)
    jaxpr = lda._fused._run._jit.trace(*_superstep_args(lda)).jaxpr
    under = _primitives_under(jaxpr.jaxpr, "lda.gather_words")
    assert "gather" in under
    # jnp.take's default (fill) mode wraps the gather in an in-bounds
    # mask and a select over the whole [B, C, 128] result
    assert "select_n" not in under, under
    assert "psum" not in under and "axis_index" not in under


@pytest.mark.parametrize("shape", ["1x1", "4x2"])
def test_mirror_replications_counted_once_a_sweep(shape, request):
    if shape == "1x1":
        lda, _, _ = _lda(request.getfixturevalue("mesh1"))
    else:
        lda, _, _ = _lda(request.getfixturevalue("mesh8"),
                         batch_tokens=2048)
    snap = metrics.snapshot()
    assert snap["gauges"]["lda.mirror.bytes_per_chip"] == \
        2 * np.prod(lda.word_topic.storage_shape)
    lda.sweep()
    lda.train(num_iterations=2)
    counted = metrics.snapshot()["counters"].get(
        "lda.mirror.replications", 0)
    assert counted == (3 if shape == "4x2" else 0)
    assert _count("lda.to_stale") == 3


# -- the assignments accessor -------------------------------------------------

@pytest.mark.parametrize("shuffled", [False, True])
def test_assignments_docblock_in_corpus_order(mesh1, shuffled):
    lda, tw, td = _lda(mesh1)
    table_base.reset_tables()
    if shuffled:                    # a corpus that is not doc-contiguous
        order = np.random.default_rng(9).permutation(len(tw))
        tw, td = tw[order], td[order]
        lda = LightLDA(tw, td, 200, lda.config, mesh=mesh1,
                       name="lda_spans_shuffled")
    lda.sweep()
    z = lda.assignments()
    assert z.shape == (len(tw),) and z.dtype == np.int32
    # against the privates the benchmark's driver digs out today
    lanes = np.asarray(lda._mask_flat).astype(bool)
    packed_z = np.asarray(lda._z).reshape(-1)[lanes]
    packed_w = np.asarray(lda._tw_flat)[lanes]
    sort = np.argsort(td, kind="stable")
    assert np.array_equal(packed_w, tw[sort])
    assert np.array_equal(z[sort], packed_z)
    assert (lda._doc_order is None) == (not shuffled)
    # and against the tables: counts of (word, topic) over the corpus
    nwk = np.zeros((200, lda.K), np.int64)
    np.add.at(nwk, (tw, z), 1)
    assert np.array_equal(nwk, lda.word_topics()[:200, :lda.K])
    ndk = np.zeros((lda.num_docs, lda.K), np.int64)
    np.add.at(ndk, (td, z), 1)
    assert np.array_equal(ndk, lda.doc_topics())


@pytest.mark.parametrize("mode", ["gibbs", "dp_mp", "streamed"])
def test_assignments_other_layouts_match_the_tables(request, mode):
    kw = {"gibbs": dict(sampler="gibbs", doc_blocked=False, num_topics=8),
          "dp_mp": dict(batch_tokens=2048),  # 8 blocks a step, 4x2 chips
          "streamed": dict(stream_blocks=True)}[mode]
    mesh = request.getfixturevalue("mesh8" if mode == "dp_mp" else "mesh1")
    lda, tw, td = _lda(mesh, name=f"lda_spans_{mode}", **kw)
    lda.sweep()
    z = lda.assignments()
    assert z.shape == (len(tw),)
    nwk = np.zeros((200, lda.K), np.int64)
    np.add.at(nwk, (tw, z), 1)
    assert np.array_equal(nwk, lda.word_topics()[:200, :lda.K])
    ndk = np.zeros((lda.num_docs, lda.K), np.int64)
    np.add.at(ndk, (td, z), 1)
    assert np.array_equal(ndk, lda.doc_topics())
