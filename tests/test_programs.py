"""build_programs: the unified train-program builder.

The equivalence tests build the SAME program twice — once through the
``repro.launch.programs`` factories that ``build_programs`` wraps and
once through ``build_programs`` itself — and require bit-identical
losses and updated parameters.  Both paths jit with donate_argnums=0, so
each path gets its own freshly initialized (identical-by-PRNG) state.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import GBAConfig
from repro.launch.programs import (build_programs, init_fused_train_state,
                                   init_train_state, jit_fused_train_step,
                                   make_train_step)
from repro.models import transformer as T
from repro.optim import get_optimizer

B, S = 2, 16
ARCH = "mamba2-780m"


def _setup():
    cfg = get_config(ARCH).reduced()
    gba = GBAConfig(local_batch=B, buffer_size=1, staleness_tolerance=4)
    key = jax.random.PRNGKey(0)
    batch = {
        "tokens": jax.random.randint(key, (B, S), 0, cfg.vocab_size),
        "labels": jax.random.randint(key, (B, S), 0, cfg.vocab_size),
    }
    return cfg, gba, batch


def _params(cfg):
    return T.init_model(jax.random.PRNGKey(1), cfg)


def _assert_trees_equal(a, b):
    flat_a, flat_b = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(flat_a) == len(flat_b)
    for la, lb in zip(flat_a, flat_b):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


def test_pytree_builder_matches_factories():
    cfg, gba, batch = _setup()
    token = jnp.zeros((), jnp.int32)
    opt = get_optimizer("adam", 1e-3)

    ref_step = make_train_step(cfg, opt, gba)
    ref_state = init_train_state(_params(cfg), opt)
    ref_state2, ref_loss = jax.jit(ref_step)(ref_state, batch, token)

    progs = build_programs(cfg, gba, mode="pytree", optimizer=opt,
                           params=_params(cfg))
    state2, loss = progs.step(progs.state, batch, token)

    assert float(loss) == float(ref_loss)
    _assert_trees_equal(state2["params"], ref_state2["params"])
    assert int(state2["gstep"]) == int(ref_state2["gstep"]) == 1


def test_fused_builder_matches_factories():
    cfg, gba, batch = _setup()
    token = jnp.zeros((), jnp.int32)

    layout, ref_state = init_fused_train_state(_params(cfg), gba)
    ref_step = jit_fused_train_step(cfg, gba, layout)
    ref_state2, ref_loss = ref_step(ref_state, batch, token)

    progs = build_programs(cfg, gba, mode="fused", params=_params(cfg))
    state2, loss = progs.step(progs.state, batch, token)

    assert float(loss) == float(ref_loss)
    _assert_trees_equal(state2["params"], ref_state2["params"])
    np.testing.assert_array_equal(np.asarray(state2["accum"]),
                                  np.asarray(ref_state2["accum"]))


def test_build_programs_validation():
    _, gba, _ = _setup()
    with pytest.raises(ValueError, match="mesh"):
        build_programs(None, gba, mode="wire", loss_fn=lambda p, b: 0.0)
    with pytest.raises(ValueError, match="params or an explicit layout"):
        build_programs(None, gba, mode="fused", loss_fn=lambda p, b: 0.0)
    with pytest.raises(ValueError, match="unknown mode"):
        build_programs(None, gba, mode="nope", loss_fn=lambda p, b: 0.0)
    with pytest.raises(ValueError, match="ModelConfig or a loss_fn"):
        build_programs(None, gba, mode="sync_psum",
                       mesh=jax.sharding.Mesh(
                           np.array(jax.devices()[:1]), ("data",)))
