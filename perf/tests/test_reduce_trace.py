"""The reduction on a small trace recorded on the chip (a v5e, PR 24):
five runs of one jitted matmul, three before and two after a 20 ms
sleep, under ``bench.*`` spans; and on two written traces (text protos):
two chips with collectives, and one chip under the program's own spans."""

import os

import pytest

from perf import reduce_trace as rt

FIXTURE = os.path.join(os.path.dirname(__file__), "data",
                       "fixture.xplane.pb")


@pytest.fixture(scope="module")
def reduced():
    return rt.reduce(rt.load(FIXTURE))


def test_busy_and_idle_share(reduced):
    # the window span is 24.704367 ms; the device clock runs ~1 ms
    # ahead of the host's, so the first of the five 90.2 us runs falls
    # before the window and three whole fusions are inside it
    assert reduced["window_s"] == pytest.approx(0.024704367)
    assert reduced["busy_s"] == pytest.approx(0.000270639, rel=1e-6)
    assert reduced["idle_share_pct"] == pytest.approx(
        100.0 * (1 - 0.000270639 / 0.024704367))


def test_op_time_by_name(reduced):
    assert reduced["device_ops"][0][0] == "jit_busy/fusion"
    assert rt.op_time(reduced, r"^jit_busy/fusion$") == pytest.approx(
        3 * 90.196e-6, rel=1e-3)
    assert rt.op_time(reduced, "no_such_kernel") == 0.0


def test_gap_goes_to_the_span_that_covers_it(reduced):
    name, seconds = reduced["idle_gaps"][0]
    assert name == "bench.fix.sleep"
    assert seconds == pytest.approx(0.0214, abs=0.001)
    assert set(reduced["spans"]) == {"bench.window", "bench.fix.compute",
                                     "bench.fix.sleep"}


def test_interval_arithmetic():
    merged = rt.union([(0, 4), (2, 6), (10, 12), (12, 12)])
    assert merged == [(0, 6), (10, 12)]
    assert rt.measure(merged) == 8
    assert rt.subtract([(0, 20)], merged) == [(6, 10), (12, 20)]
    assert rt.subtract(merged, [(1, 2), (5, 11)]) == [(0, 1), (2, 5),
                                                       (11, 12)]


def test_self_time_of_nested_events():
    # a while of 10 with two body ops of 3 inside, then a lone op of 2
    got = dict(rt._self_times([(0, 10, "while"), (1, 4, "a"),
                               (5, 8, "b"), (10, 12, "c")]))
    assert got == {"while": 4, "a": 3, "b": 3, "c": 2}


def test_op_name_from_hlo_text():
    assert rt.op_name("%fusion.3 = bf16[8]{0} fusion(bf16[8]{0} %p), "
                      "kind=kLoop") == "fusion.3"
    assert rt.op_name("gibbs_sample_docblock.2") == \
        "gibbs_sample_docblock.2"


def test_two_chips_collectives_and_nesting():
    """A written trace (text proto) of two chips over a 10 us window:
    chip 0 runs a ``while`` of 8 us holding a 3 us fusion, a 2 us
    all-reduce and a 1 us fusion; chip 1 a 4 us fusion and a 4 us
    all-reduce. Host spans: feed 0..1.5 us, sync 8.5..10 us."""
    r = rt.reduce(rt.load(os.path.join(os.path.dirname(FIXTURE),
                                       "two_chips.textproto")))
    assert r["window_s"] == pytest.approx(10e-6)
    assert [d["busy_s"] for d in r["devices"]] == [pytest.approx(8e-6)] * 2
    assert r["idle_share_pct"] == pytest.approx(20.0)
    # means over the chips; the while keeps only its own 2 us
    assert r["op_seconds"]["jit_step/fusion.7"] == pytest.approx(4e-6)
    assert r["op_seconds"]["jit_step/all-reduce.2"] == pytest.approx(3e-6)
    assert r["op_seconds"]["jit_step/while.1"] == pytest.approx(1e-6)
    # no compute op runs beside either all-reduce: 2 us and 4 us exposed
    assert r["collective_exposed_s"] == pytest.approx(3e-6)
    gaps = sorted(r["idle_gaps"])
    assert [g[0] for g in gaps] == ["bench.feed", "bench.feed",
                                    "bench.sync", "bench.sync"]


def test_collectives_by_both_names():
    for name in ("all-reduce.2", "all-gather.22", "psum.14",
                 "jit_run/psum.14", "reduce-scatter.1"):
        assert rt.COLLECTIVE.search(name), name
    for name in ("fusion.62", "gibbs_sample_docblock.1", "copy.24",
                 "multiply_reduce_fusion.4", "sort"):
        assert not rt.COLLECTIVE.search(name), name


SPANS = os.path.join(os.path.dirname(FIXTURE), "program_spans.textproto")
REGISTRY = ["lda.sweep", "lda.dispatch", "w2v.pairs.produce"]


@pytest.fixture(scope="module")
def spans_trace():
    """A written trace of one chip over a 20 us window (2..22 us). The
    device idles at 2..4, 10..11, 12..15 and 21..21.5 us. The window's
    thread holds ``bench.lda.sweep`` (2..21.2), ``bench.lda.sync``
    (21.2..22) and, inside the sweep, the program's ``lda.sweep``
    (2.1..21.1), ``lda.dispatch`` (2.2..4.5) and ``lda.fold_in``
    (12.1..14.9); another thread holds ``w2v.pairs.produce``
    (9.5..11.5)."""
    return rt.load(SPANS)


def test_gaps_go_to_the_programs_innermost_span(spans_trace):
    r = rt.reduce(spans_trace, span_names=REGISTRY)
    # 12..15: lda.fold_in is over it, but the registry holds no such
    # name; 2..4: nine tenths under lda.dispatch, all of it under the two
    # sweep spans around that; 10..11: the producer thread's span covers
    # it whole and claims nothing; 21..21.5: three fifths under the sync
    assert r["idle_gaps"] == [["lda.sweep", pytest.approx(3e-6)],
                              ["lda.dispatch", pytest.approx(2e-6)],
                              ["lda.sweep", pytest.approx(1e-6)],
                              ["bench.lda.sync", pytest.approx(5e-7)]]
    assert "w2v.pairs.produce" not in r["spans"]
    assert "lda.fold_in" not in r["spans"]
    named = rt.reduce(spans_trace, span_names=REGISTRY + ["lda.fold_in"])
    assert named["idle_gaps"][0] == ["lda.fold_in", pytest.approx(3e-6)]


def test_no_names_given_reads_the_harness_spans_alone(spans_trace):
    r = rt.reduce(spans_trace)
    assert [g[0] for g in r["idle_gaps"]] == ["bench.lda.sweep"] * 3 \
        + ["bench.lda.sync"]
    assert set(r["spans"]) == {"bench.window", "bench.lda.sweep",
                               "bench.lda.sync"}
    # the device numbers do not depend on who names the gaps
    named = rt.reduce(spans_trace, span_names=REGISTRY)
    for key in ("busy_s", "window_s", "idle_share_pct", "op_seconds",
                "device_ops", "collective_exposed_s", "module_launches"):
        assert r[key] == named[key], key
    assert r["busy_s"] == pytest.approx(13.5e-6)
    assert [g[1] for g in r["idle_gaps"]] == \
        [g[1] for g in named["idle_gaps"]]


def test_only_the_gaps_that_reach_the_list_are_named(spans_trace):
    r = rt.reduce(spans_trace, top=2, span_names=REGISTRY)
    assert r["idle_gaps"] == [["lda.sweep", pytest.approx(3e-6)],
                              ["lda.dispatch", pytest.approx(2e-6)]]


def test_module_launches_of_the_window(spans_trace, reduced):
    # jit_warm starts before the window; jit_rebuild starts inside it and
    # ends after: launched in the window
    r = rt.reduce(spans_trace)
    assert r["module_launches"] == {"jit_run": 2, "jit_fold_in": 1,
                                    "jit_rebuild": 1}
    # the chip's trace: three of the five runs start inside the window
    assert reduced["module_launches"] == {"jit_busy": 3}
    two = rt.reduce(rt.load(os.path.join(os.path.dirname(FIXTURE),
                                         "two_chips.textproto")))
    assert two["module_launches"] == {"jit_step": 1}     # the first chip's
