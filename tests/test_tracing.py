"""Host spans of ``GBATrainer.replay`` (``repro.tracing``) and the replay
counters recorded at the same boundaries."""
import dataclasses
import glob
import os

import jax
import numpy as np
import pytest

from repro import tracing
from repro.configs.recsys import CRITEO_DEEPFM
from repro.core import GBATrainer, ReplayStats
from repro.data import make_clickstream
from repro.models.recsys import init_recsys
from repro.optim import get_optimizer
from repro.sim.cluster import Schedule, Slot

CFG = dataclasses.replace(CRITEO_DEEPFM, hash_capacity=4096,
                          mlp_dims=(32, 16))
CHILDREN = ["replay.versions", "replay.inputs", "replay.dispatch",
            "replay.readback"]
# dispatch steps of the 4 slots of each global step: versions 1, 2, 1, 3
DISPATCH = [(0, 0, 0, 0), (1, 0, 1, 1), (2, 2, 2, 2), (3, 1, 3, 2)]


def _schedule() -> Schedule:
    return Schedule("gba", 32, [[Slot(4 * k + i, d, d, 1.0)
                                 for i, d in enumerate(ds)]
                                for k, ds in enumerate(DISPATCH)])


def _replay(days=(0,)):
    stream = make_clickstream(CFG, seed=0, batches_per_day=16,
                              batch_size=32)
    opt = get_optimizer("sgd", 0.1)
    params = init_recsys(jax.random.PRNGKey(0), CFG)
    state = opt.init(params)
    trainer, stats = GBATrainer(CFG, opt), ReplayStats()
    for day in days:
        params, state, _, stats = trainer.replay(params, state, _schedule(),
                                                 stream, day, stats=stats)
    return stats


def _session(log_dir: str):
    """A profiler session without the Python call tracer, as the benchmark
    records its window."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return jax.profiler.trace(log_dir, profiler_options=opts)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One day replayed under a profiler session: the store's spans and the
    host events of the session's trace."""
    log_dir = str(tmp_path_factory.mktemp("trace"))
    tracing.reset()
    with _session(log_dir):
        _replay()
    spans = tracing.records()
    tracing.reset()
    from jax.profiler import ProfileData
    path = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    events = [(ev.name, ev.start_ns, ev.end_ns, dict(ev.stats))
              for plane in ProfileData.from_file(path).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for ev in line.events
              if ev.name.startswith("replay.")]
    return spans, sorted(events, key=lambda e: e[1])


def test_no_session_records_nothing():
    tracing.reset()
    assert tracing.span("replay.step", k=0) is tracing.span("other")
    with tracing.span("replay.step", k=0):
        with tracing.span("replay.versions"):
            pass
    _replay()
    assert tracing.records() == []


def test_each_step_records_its_span_and_four_children_in_order(traced):
    spans, _ = traced
    assert len(spans) == 5 * len(DISPATCH)
    for k, ds in enumerate(DISPATCH):
        step = spans[5 * k:5 * k + 5]
        # a span is stored as it ends: the children, then the step
        assert [s.name for s in step] == CHILDREN + ["replay.step"]
        parent = step[-1]
        # the row scatter sums every id of the step's 4 slots of 32
        assert parent.attrs == {"day": 0, "k": k, "stacked": len(set(ds)),
                                "rows": 4 * 32 * CFG.num_fields}
        for child, prev in zip(step[:4], [None] + step[:3]):
            assert child.attrs == {"day": 0, "k": k}
            assert parent.start_ns <= child.start_ns < child.end_ns \
                <= parent.end_ns
            if prev is not None:
                assert prev.end_ns <= child.start_ns


def test_spans_stand_on_the_profilers_host_clock(traced):
    spans, events = traced
    ordered = sorted(spans, key=lambda s: s.start_ns)
    assert [e[0] for e in events] == [s.name for s in ordered]
    # each event carries its span's attributes, for xprof to show
    assert [e[3] for e in events] == [s.attrs for s in ordered]
    durations = np.array([(e[2] - e[1]) - (s.end_ns - s.start_ns)
                          for e, s in zip(events, ordered)])
    assert np.all(np.abs(durations) < 50_000)
    offsets = np.array([e[1] - s.start_ns for e, s in zip(events, ordered)])
    assert np.ptp(offsets) < 50_000


def test_a_span_that_an_exception_leaves_records_its_end(tmp_path):
    tracing.reset()
    with _session(str(tmp_path)):
        with pytest.raises(KeyError):
            with tracing.span("outer", k=1):
                with tracing.span("inner"):
                    raise KeyError
    inner, outer = tracing.records()
    tracing.reset()
    assert (inner.name, inner.attrs) == ("inner", {})
    assert (outer.name, outer.attrs) == ("outer", {"k": 1})
    assert outer.start_ns <= inner.start_ns <= inner.end_ns <= outer.end_ns


def test_stacked_steps_and_step_builds():
    """Steps 1 and 3 stack versions, steps 0 and 2 share one; each step
    variant is built once, by the first step that needs it, and a second
    day builds nothing."""
    stats = _replay(days=(0, 1))
    assert stats.applied_steps == 8
    assert stats.stacked_steps == 4
    assert stats.step_builds == {(True, 4, True): 0, (True, 4, False): 1}
    assert stats.scattered_rows == 8 * 4 * 32 * CFG.num_fields
