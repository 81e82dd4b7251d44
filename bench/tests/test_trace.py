"""The trace reduction: interval arithmetic, and a recorded chip trace."""
import pytest

from bench import trace
from bench.harness import BENCH

RECORDED = BENCH / "testdata" / "trace"


def test_union_and_measure():
    assert trace.union([(3, 4), (0, 2), (1, 2.5)]) == [(0, 2.5), (3, 4)]
    assert trace.measure([(0, 1), (0.5, 2), (5, 6)]) == 3


def test_minus_counts_only_what_is_uncovered():
    assert trace.minus([(0, 10)], [(1, 2), (4, 6), (9, 12)]) == 6
    assert trace.minus([(0, 1), (2, 3)], []) == 2
    assert trace.minus([(0, 1)], [(-1, 5)]) == 0


def test_gaps_inside_the_window():
    assert trace.gaps([(1, 2), (1.5, 3), (5, 6)], 0, 8) == [
        (0, 1), (3, 5), (6, 8)]
    assert trace.gaps([], 0, 1) == [(0, 1)]


def test_in_flight_pairs_async_collectives():
    ops = [("collective-permute-start.1", 0.0, 0.1),
           ("collective-permute-done.1", 0.8, 1.0),
           ("all-reduce.2", 2.0, 2.5)]
    assert trace.in_flight(ops) == [(0.0, 1.0), (2.0, 2.5)]


def test_gap_label_is_the_innermost_span():
    host = [("bench.window", 0, 10), ("bench.batch", 1, 9),
            ("bench.step", 2, 3)]
    assert trace.label_gap(host, 2.2, 2.4) == "bench.step"
    assert trace.label_gap(host, 5, 6) == "bench.batch"
    assert trace.label_gap(host, 11, 12) == trace.UNNAMED_GAP


@pytest.fixture(scope="module")
def small():
    """A chip trace (one v5e) of three runs of a jitted 4-step scan
    and three of a cast, inside ``bench.window`` with a ``bench.step``
    span around each scan."""
    from jax.profiler import ProfileData
    return ProfileData.from_file(str(RECORDED / "small.xplane.pb"))


def test_recorded_trace_planes(small):
    host, devices = trace.read_planes(small)
    assert sorted(devices) == [0]
    assert [h[0] for h in host] == ["bench.window"] + ["bench.step"] * 3
    ops = {name for name, _, _ in devices[0]["ops"]}
    assert "%while" in ops and all(" = " not in op for op in ops)
    assert {m[0] for m in devices[0]["modules"]} == {"jit__lambda"}


def test_recorded_trace_reduction(small):
    r = trace.reduce(small, n_devices=1, modules={"any": "jit__lambda"})
    assert r["window_s"] == pytest.approx(0.012775479)
    assert r["busy_s"] == pytest.approx(2.713e-05)
    assert r["idle_share"] == pytest.approx(1 - r["busy_s"] / r["window_s"])
    assert len(r["modules"]["any"]) == 5       # the sixth ran before
    assert sum(r["modules"]["any"]) >= r["busy_s"]   # programs hold ops
    assert r["collective_s"] == r["exposed_collective_s"] == 0
    assert r["noncollective_s"] == pytest.approx(r["busy_s"])
    assert r["top_ops"][0][0] == "%while"
    labels = {label for label, _ in r["idle_gaps"]}
    assert labels <= {trace.UNNAMED_GAP, "bench.step"}
    assert sum(s for _, s in r["idle_gaps"]) <= r["window_s"] - r["busy_s"]
    with pytest.raises(ValueError):
        trace.reduce(small, n_devices=2)
