"""The benchmark's traffic copies give what the program's own generators
give: the same ids and labels for the same ``(seed, day, index)``, and the
same schedule for the same cluster spec and seed."""
import numpy as np
import pytest

from chipbench.traffic import cluster as C
from chipbench.traffic.clickstream import ClickStream
from repro.configs.recsys import RecsysConfig
from repro.data.clickstream import ClickStream as ProgramStream
from repro.sim import cluster as P


@pytest.mark.parametrize("behavior_len", [0, 5])
@pytest.mark.parametrize("seed", [0, 7, 3_000_000_019])
def test_stream_matches_the_program(seed, behavior_len):
    cfg = RecsysConfig(name="t", model="dien" if behavior_len else "deepfm",
                       num_fields=4, hash_capacity=997, embed_dim=4,
                       mlp_dims=(8,), behavior_len=behavior_len)
    prog = ProgramStream(cfg, seed, 1.2, 8, 64, 32, 0.05)
    ours = ClickStream(hash_capacity=997, num_fields=4,
                       behavior_len=behavior_len, seed=seed, zipf_a=1.2,
                       num_days=8, batch_size=32, drift=0.05)
    for day, index in [(0, 0), (1, 5), (9, 123)]:
        a, b = prog.batch(day, index), ours.batch(day, index)
        assert a.keys() == b.keys()
        for k in a:
            np.testing.assert_array_equal(a[k], b[k])


def _steps(sched):
    return [[(s.batch_index, s.token, s.dispatch_step, s.weight) for s in st]
            for st in sched.steps]


@pytest.mark.parametrize("mode", ["gba", "sync"])
@pytest.mark.parametrize("seed", [0, (3_000_000_019, 2)])
def test_schedule_matches_the_program(mode, seed):
    kw = dict(straggler_frac=0.25, straggler_slowdown=5.0, jitter=0.2,
              seed=seed)
    prog = P.simulate(P.ClusterSpec(num_workers=8, **kw), mode, 400, 64,
                      buffer_size=8, iota=4)
    ours = C.simulate(C.ClusterSpec(num_workers=8, **kw), mode, 400, 64,
                      buffer_size=8, iota=4)
    assert prog.mode == ours.mode
    assert _steps(prog) == _steps(ours)
    assert len(ours.steps) == 50
