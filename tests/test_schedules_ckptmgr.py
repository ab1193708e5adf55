"""LR schedules + checkpoint manager."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.manager import CheckpointManager
from repro.optim.schedules import (constant, inverse_sqrt, step_decay,
                                   warmup_cosine)


def test_warmup_cosine_shape():
    s = warmup_cosine(1.0, warmup_steps=10, total_steps=110)
    assert float(s(0)) == 0.0
    assert abs(float(s(10)) - 1.0) < 1e-6
    assert float(s(5)) == pytest.approx(0.5)
    mid = float(s(60))
    assert 0.1 < mid < 1.0
    assert float(s(110)) == pytest.approx(0.1, abs=1e-6)
    # monotone decay after warmup
    vals = [float(s(t)) for t in range(10, 111, 10)]
    assert all(a >= b - 1e-9 for a, b in zip(vals, vals[1:]))


def test_inverse_sqrt():
    s = inverse_sqrt(2.0, warmup_steps=16)
    assert abs(float(s(16)) - 2.0) < 1e-6
    assert float(s(64)) == pytest.approx(1.0)


def test_step_decay():
    s = step_decay(1.0, boundaries=(10, 20), factors=(0.5, 0.1))
    assert float(s(5)) == 1.0
    assert float(s(15)) == 0.5
    assert float(s(25)) == pytest.approx(0.1)


def test_constant():
    assert float(constant(0.3)(1234)) == pytest.approx(0.3)


def test_schedule_with_optimizer():
    from repro.optim import sgd
    opt = sgd(999.0)  # base lr overridden
    params = {"w": jnp.array([1.0])}
    state = opt.init(params)
    sched = step_decay(0.1, (1,), (0.5,))
    p1, state = opt.update(params, {"w": jnp.array([1.0])}, state,
                           lr_override=sched(0))
    np.testing.assert_allclose(np.asarray(p1["w"]), [0.9], rtol=1e-6)


def test_checkpoint_manager_retention(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    for step in (1, 5, 9):
        mgr.save(step, {"params": {"w": jnp.full((3,), float(step))},
                        "step": jnp.int32(step)})
    assert mgr.steps() == [5, 9]
    step, state = mgr.restore_latest()
    assert step == 9
    np.testing.assert_allclose(np.asarray(state["params"]["w"]),
                               np.full(3, 9.0))
    state5 = mgr.restore(5)
    assert int(state5["step"]) == 5


def test_checkpoint_manager_roundtrip_train_state(tmp_path):
    import jax
    from repro.configs import get_config
    from repro.launch.programs import init_train_state
    from repro.models import transformer as T
    from repro.optim import get_optimizer
    cfg = get_config("mamba2-780m").reduced()
    params = T.init_model(jax.random.PRNGKey(0), cfg)
    opt = get_optimizer("adagrad", 1e-3)
    state = init_train_state(params, opt)
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(0, state)
    _, restored = mgr.restore_latest()
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), rtol=1e-6)
