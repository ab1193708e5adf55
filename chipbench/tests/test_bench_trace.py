"""The trace reduction, on a small trace recorded on a TPU v5e
(``data/probe.xplane.pb``: three rounds of a presence count and a small
matmul inside ``chipbench.window``, ``chipbench.replay`` and
``chipbench.feed`` spans) and on hand-made intervals."""
from pathlib import Path

import numpy as np
import pytest

from chipbench import trace as T

PROBE = Path(__file__).parent / "data" / "probe.xplane.pb"


@pytest.fixture(scope="module")
def probe():
    return T.Trace.from_file(str(PROBE))


@pytest.fixture(scope="module")
def raw():
    """The probe's device ops and window, read straight from the file."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(PROBE))
    window = None
    ops = []
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name == "chipbench.window":
                    window = (ev.start_ns, ev.end_ns)
                if plane.name == "/device:TPU:0" and line.name == "XLA Ops":
                    ops.append((ev.name, ev.start_ns, ev.end_ns))
    return window, ops


def test_probe_has_one_device_and_the_window(probe, raw):
    window, _ = raw
    assert list(probe.devices) == ["/device:TPU:0"]
    assert probe.window == pytest.approx((window[0] * 1e-9, window[1] * 1e-9))
    assert {n for n, _, _ in probe.spans} == {
        "chipbench.window", "chipbench.replay", "chipbench.feed"}


def test_busy_is_the_union_of_op_intervals(probe, raw):
    """Against a brute-force union on a 10 ns grid."""
    (w0, w1), ops = raw
    grid = np.zeros(int((w1 - w0) / 10) + 1, bool)
    for _, s, e in ops:
        s, e = max(s, w0), min(e, w1)
        if e > s:
            grid[int((s - w0) / 10):int((e - w0) / 10)] = True
    busy = grid.sum() * 10e-9
    assert probe.busy_s() == pytest.approx(busy, rel=1e-3, abs=2e-7)
    assert 0 < probe.busy_s() < probe.window_s
    idle = 1 - probe.busy_s() / probe.window_s
    assert 0 < idle < 1


def test_op_seconds_sum_each_name(probe, raw):
    (w0, w1), ops = raw
    want = {}
    for name, s, e in ops:
        s, e = max(s, w0), min(e, w1)
        if e > s:
            want[name] = want.get(name, 0.0) + (e - s) * 1e-9
    got = probe.op_seconds()
    assert set(got) == set(want)
    for name in want:
        assert got[name] == pytest.approx(want[name], rel=1e-9)


def test_kernel_is_found_by_name(probe):
    seconds, count = probe.matching_seconds("%_embedding_bag_grad_streamed")
    assert count == 3
    assert 0 < seconds < probe.busy_s()
    assert probe.matching_seconds("no such kernel") == (0.0, 0)


def test_idle_gaps_cover_the_idle_time(probe):
    gaps = probe.idle_gaps()
    total = sum(s for _, s in gaps)
    assert total == pytest.approx(probe.window_s - probe.busy_s(), rel=1e-9)
    assert {n for n, _ in gaps} <= {"chipbench.feed", "chipbench.replay",
                                    "host"}


def test_no_collectives_in_the_probe(probe):
    assert probe.exposed_collective_s() == 0.0


def _trace(ops, spans=()):
    window = (0.0, 10.0)
    return T.Trace(window, {"/device:TPU:0": [T.Op(n, s, e) for n, s, e
                                               in ops]},
                   [("chipbench.window", *window), *spans])


def test_union_gaps_and_exposed_collectives_by_hand():
    t = _trace([("fusion.1", 1.0, 3.0), ("fusion.2", 2.0, 4.0),
                ("all-reduce.1", 3.5, 6.0), ("fusion.3", 5.0, 5.5),
                ("all-gather.2", 8.0, 9.0)])
    # busy [1, 6] and [8, 9]
    assert t.busy_s() == pytest.approx(6.0)
    # collectives [3.5, 6] and [8, 9]; compute covers [3.5, 4] and [5, 5.5]
    assert t.exposed_collective_s() == pytest.approx(2.5 - 1.0 + 1.0)
    assert T.gaps([(1, 3), (2, 4)], (0, 10)) == [(0, 1), (4, 10)]
    # an operand's name does not make an op a collective
    assert not T.is_collective("%fusion.9 = f32[8] fusion(f32[8] %all-reduce.1)")
    assert T.is_collective("%all-reduce.1 = f32[8] all-reduce(f32[8] %p)")


def test_idle_gaps_are_named_by_the_innermost_span():
    t = _trace([("op", 2.0, 3.0), ("op", 6.0, 7.0)],
               [("chipbench.replay", 1.0, 9.0),
                ("chipbench.step", 1.5, 5.0),
                ("chipbench.feed", 3.5, 4.5)])
    # idle [0, 2] -> mid 1 in replay; [3, 6] -> mid 4.5, the feed's last
    # instant, inside step and replay too; [7, 10] -> mid 8.5 in replay
    assert dict(t.idle_gaps()) == pytest.approx(
        {"chipbench.replay": 2.0 + 3.0, "chipbench.feed": 3.0})


def test_ops_are_clipped_to_the_window():
    ops = T.clip_ops([T.Op("a", -1.0, 1.0), T.Op("b", 9.0, 12.0),
                      T.Op("c", 11.0, 12.0)], (0.0, 10.0))
    assert [(o.name, o.start, o.end) for o in ops] == [
        ("a", 0.0, 1.0), ("b", 9.0, 10.0)]
