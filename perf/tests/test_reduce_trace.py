"""The reduction on a small trace recorded on the chip (a v5e, PR 24):
five runs of one jitted matmul, three before and two after a 20 ms
sleep, under ``bench.*`` spans."""

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
