"""The program's DIEN (``models/recsys.py``) against the benchmark's plain
reference (``chipbench/reference/dien.py``) on seeded weights, at a size
the CPU runs in seconds: D = 4, T = 6 pairs, B = 8, 97 rows.  Both run
float32 at ``highest``; the reference computes each gate with its own
slice of the weights and every product through ``precision.dot``, the
program one fused product a step, so they agree to float32 rounding, not
bit for bit.  Each mutation of the mathematics the comparison must catch
moves the loss or a gradient by far more than the tolerance."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import dien as ref
from repro.configs.recsys import ALIMAMA_DIEN, RecsysConfig
from repro.core.trainer import GBATrainer
from repro.data import make_clickstream
from repro.models import recsys as R
from repro.optim import get_optimizer

CFG = RecsysConfig(name="dien-test", model="dien", num_fields=2,
                   hash_capacity=97, embed_dim=4, mlp_dims=(16, 8),
                   behavior_len=12)
CFG_DICT = {"model": "dien", "num_fields": 2, "hash_capacity": 97,
            "embed_dim": 4, "mlp_dims": [16, 8], "behavior_len": 12}
B = 8
# Relative tolerances, from float32's 6e-8 unit roundoff.  The loss is a
# mean of B * T terms summed in another order: a few ulps, well under
# 1e-6.  A gradient leaf flows back through 2T recurrent steps, each
# computed with fused against sliced products: per leaf the norm of the
# difference is under 1e-6 of the reference's norm (measured: at most
# 8e-7 of the largest element), so 1e-5 leaves ten times that room.
LOSS_RTOL = 1e-5
GRAD_RTOL = 1e-5


def perturbed(params, seed=1):
    """Seeded weights away from the init's zero biases and Dice slopes,
    so that every term of the model moves the loss."""
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return jax.tree.unflatten(tree, [x + 0.3 * jax.random.normal(k, x.shape)
                                     for x, k in zip(leaves, keys)])


@pytest.fixture(scope="module")
def case():
    rng = np.random.default_rng(0)
    cap = CFG.hash_capacity
    batch = {
        "fields": jnp.asarray(rng.integers(0, cap, (B, 2)), jnp.int32),
        "behavior": jnp.asarray(rng.integers(0, cap, (B, 12)), jnp.int32),
        "target": jnp.asarray(rng.integers(0, cap, (B,)), jnp.int32),
        "label": jnp.asarray(rng.integers(0, 2, (B,)), jnp.float32)}
    params = perturbed(ref.init(jax.random.PRNGKey(0), CFG_DICT))
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p, b: {
            "loss_grads": jax.value_and_grad(ref.loss)(p, CFG_DICT, b),
            "logit": ref.logit(p, CFG_DICT, b)})(params, batch)
    loss, grads = want.pop("loss_grads")
    return params, batch, want | {"loss": loss, "grads": grads}


def program(params, batch):
    """Traced afresh on every call, so that a test's mutation applies."""
    with jax.default_matmul_precision("highest"):
        loss, grads, logit = jax.jit(lambda p, b: (
            *jax.value_and_grad(R.recsys_loss)(p, CFG, b),
            R.recsys_logit(p, CFG, b)))(params, batch)
    return {"loss": loss, "grads": grads, "logit": logit}


def gaps(got, want) -> dict:
    """The loss's relative gap, and each gradient leaf's norm of the
    difference over the reference's norm."""
    out = {"loss": abs(float(got["loss"] - want["loss"]))
           / abs(float(want["loss"]))}
    for (path, g), w in zip(
            jax.tree_util.tree_flatten_with_path(got["grads"])[0],
            jax.tree.leaves(want["grads"])):
        out[jax.tree_util.keystr(path)] = float(
            jnp.linalg.norm(g - w) / jnp.linalg.norm(w))
    return out


def within_tolerance(g: dict) -> bool:
    return g["loss"] <= LOSS_RTOL and all(
        v <= GRAD_RTOL for k, v in g.items() if k != "loss")


def test_init_is_the_references_tree():
    key = jax.random.PRNGKey(3)
    got, want = R.init_recsys(key, CFG), ref.init(key, CFG_DICT)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_array_equal(a, b)
    # the published MLP input at D = 18 and two fields
    assert ref.mlp_dims({"num_fields": 2, "embed_dim": 18,
                         "behavior_len": 200, "mlp_dims": [200, 80]}) \
        == (162, 200, 80, 1)


def test_program_equals_reference(case):
    params, batch, want = case
    got = program(params, batch)
    g = gaps(got, want)
    assert within_tolerance(g), g
    # the logit: one MLP over float32 inputs that agree to a few ulps
    np.testing.assert_allclose(got["logit"], want["logit"], rtol=1e-5,
                               atol=1e-6)
    # the auxiliary term is part of the loss the program trains
    assert float(got["loss"]) > float(jnp.mean(
        jax.nn.softplus(-got["logit"] * (2 * batch["label"] - 1))))


def _no_aux(monkeypatch):
    monkeypatch.setattr(R, "AUX_WEIGHT", 0.0)


def _no_attention_gate(monkeypatch):
    scan = R._gru_scan
    monkeypatch.setattr(R, "_gru_scan", lambda p, xs, att=None: scan(p, xs))


def _relu_for_dice(monkeypatch):
    monkeypatch.setattr(R, "_dice", lambda x, alpha: jax.nn.relu(x))


def _swapped_gates(monkeypatch):
    """h = u h + (1 - u) h~ in the extractor: the paper's convention on an
    update gate of 1 - u = s(-(pre-activation)), i.e. with the update
    gate's columns of w, u and b negated."""
    scan = R._gru_scan

    def swapped(p, xs, att=None):
        if att is None:
            d = p["u"].shape[0]
            flip = jnp.ones((3 * d,)).at[:d].set(-1.0)
            p = {k: v * flip for k, v in p.items()}
        return scan(p, xs, att)

    monkeypatch.setattr(R, "_gru_scan", swapped)


@pytest.mark.parametrize("mutate", [_no_aux, _no_attention_gate,
                                    _relu_for_dice, _swapped_gates],
                         ids=["no_aux_loss", "no_attention_gate",
                              "relu_for_dice", "swapped_gru_gates"])
def test_each_mutation_breaks_the_comparison(case, monkeypatch, mutate):
    params, batch, want = case
    mutate(monkeypatch)
    g = gaps(program(params, batch), want)
    assert max(g.values()) > 100 * GRAD_RTOL, g


def test_deepfm_trains_on_the_cross_entropy_unchanged():
    """For DeepFM the trainer differentiates exactly ``bce_loss``, by the
    dense leaves and by the gathered rows: the same loss, the same dense
    gradients, and row gradients that sum, id by id, to its gradients of
    both tables."""
    cfg = RecsysConfig(name="deepfm-test", model="deepfm", num_fields=3,
                       hash_capacity=97, embed_dim=4, mlp_dims=(8,))
    trainer = GBATrainer(cfg, get_optimizer("adam", 1e-3))
    params = perturbed(R.init_recsys(jax.random.PRNGKey(0), cfg))
    rng = np.random.default_rng(1)
    batch = {"fields": jnp.asarray(rng.integers(0, 9, (4, 3)), jnp.int32),
             "label": jnp.asarray([1, 0, 1, 0], jnp.float32)}
    loss, (dense, ids, rows) = jax.jit(trainer._loss_grad_fn)(params, batch)
    want_loss, want = jax.jit(jax.value_and_grad(
        lambda p, b: R.bce_loss(p, cfg, b)))(params, batch)
    assert float(loss) == float(want_loss)
    assert set(dense) | set(rows) == set(want)
    for k, g in dense.items():
        for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(want[k])):
            np.testing.assert_allclose(a, b, rtol=1e-6, err_msg=k)
    assert ids.shape == (4, 3)
    for k, g in rows.items():
        table = jnp.zeros(params[k].shape).at[ids].add(g)
        np.testing.assert_allclose(table, want[k], rtol=1e-6, atol=1e-9,
                                   err_msg=k)


@pytest.mark.parametrize("behavior_len,num_fields", [(11, 2), (2, 2),
                                                      (12, 1)])
def test_layout_it_cannot_read_raises(behavior_len, num_fields):
    cfg = RecsysConfig(name="bad", model="dien", num_fields=num_fields,
                       hash_capacity=97, embed_dim=4, mlp_dims=(8,),
                       behavior_len=behavior_len)
    with pytest.raises(ValueError):
        R.init_recsys(jax.random.PRNGKey(0), cfg)


def test_alimama_preset_trains():
    """The laptop-scale preset's widths (eight fields, 8 pairs, D = 19; a
    smaller table) take a finite loss and gradient, and its extra fields
    reach the MLP."""
    cfg = dataclasses.replace(ALIMAMA_DIEN, hash_capacity=997)
    stream = make_clickstream(cfg, seed=0, batch_size=16)
    batch = {k: jnp.asarray(v) for k, v in stream.batch(0, 0).items()}
    params = R.init_recsys(jax.random.PRNGKey(0), cfg)
    assert params["mlp"]["w0"].shape[0] == 7 * 19 + 4 * 38
    loss, grads = jax.jit(jax.value_and_grad(R.recsys_loss),
                          static_argnums=1)(params, cfg, batch)
    assert np.isfinite(float(loss))
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in jax.tree.leaves(grads))
    assert float(jnp.abs(grads["embed"][batch["fields"][:, 7]]).sum()) > 0
