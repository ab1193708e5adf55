"""The recommendation models split at their table lookup
(``models/recsys.py``): ``lookup`` gathers the rows of ``flat_ids``, and
``recsys_logit`` and ``recsys_loss``, the lookup composed with the rest of
each model, give the values the models gave before the split."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.recsys import RecsysConfig
from repro.models import recsys as R

CFGS = {
    "deepfm": RecsysConfig(name="t-deepfm", model="deepfm", num_fields=5,
                           hash_capacity=257, embed_dim=4, mlp_dims=(8,)),
    "youtubednn": RecsysConfig(name="t-ytb", model="youtubednn",
                               num_fields=3, hash_capacity=257, embed_dim=4,
                               mlp_dims=(8,), behavior_len=6),
    "dien": RecsysConfig(name="t-dien", model="dien", num_fields=3,
                         hash_capacity=257, embed_dim=4, mlp_dims=(8, 4),
                         behavior_len=8),
}
# (logits, loss) of ``case(model)``, float32 at ``highest`` on the CPU, as
# the models computed them when each gathered its rows inside its forward
BEFORE_SPLIT = {
    "deepfm": ([-0.6090894, -1.5061089, 0.8409028, 0.3990376], 0.7391424),
    "youtubednn": ([-0.08258652, -0.028883375, -0.25797614, -0.045956053],
                   0.6757367),
    "dien": ([0.027901828, 0.9189393, 0.30097485, 0.36725926], 2.1960561),
}


def case(model: str):
    """Seeded weights moved off the init's zeros, and a batch of 4."""
    cfg = CFGS[model]
    params = R.init_recsys(jax.random.PRNGKey(0), cfg)
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(1), len(leaves))
    params = jax.tree.unflatten(tree, [x + 0.3 * jax.random.normal(k, x.shape)
                                       for x, k in zip(leaves, keys)])
    rng = np.random.default_rng(0)
    cap = cfg.hash_capacity
    batch = {"fields": jnp.asarray(rng.integers(0, cap, (4, cfg.num_fields)),
                                   jnp.int32),
             "label": jnp.asarray([1, 0, 0, 1], jnp.float32)}
    if cfg.behavior_len:
        batch["behavior"] = jnp.asarray(
            rng.integers(0, cap, (4, cfg.behavior_len)), jnp.int32)
        batch["target"] = jnp.asarray(rng.integers(0, cap, (4,)), jnp.int32)
    return cfg, params, batch


@pytest.mark.parametrize("model", sorted(CFGS))
def test_logit_and_loss_are_unchanged_by_the_split(model):
    cfg, params, batch = case(model)
    with jax.default_matmul_precision("highest"):
        logit = jax.jit(lambda p, b: R.recsys_logit(p, cfg, b))(params, batch)
        loss = jax.jit(lambda p, b: R.recsys_loss(p, cfg, b))(params, batch)
    want_logit, want_loss = BEFORE_SPLIT[model]
    np.testing.assert_allclose(logit, want_logit, rtol=1e-6)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-6)


@pytest.mark.parametrize("model", sorted(CFGS))
def test_lookup_gathers_the_rows_of_flat_ids(model):
    cfg, params, batch = case(model)
    ids = R.flat_ids(batch)
    assert ids.shape == (4, R.ids_per_example(cfg))
    # fields, then the behaviour sequence and the target
    np.testing.assert_array_equal(ids[:, :cfg.num_fields], batch["fields"])
    if cfg.behavior_len:
        np.testing.assert_array_equal(ids[:, cfg.num_fields:-1],
                                      batch["behavior"])
        np.testing.assert_array_equal(ids[:, -1], batch["target"])
    rows = R.lookup(params, batch)
    assert set(rows) == set(R.SPARSE_KEYS) & set(params)
    for k, r in rows.items():
        np.testing.assert_array_equal(r, params[k][ids])
