"""The reference's loss and its control at each precision a float32
configuration may state (``chipbench/reference/train.py``,
``precision.py``), on the CPU: a module's own loss is the one trained; a
module without one keeps the binary cross-entropy of its logit; the
``high`` and ``highest`` controls emulate their lower precision exactly
and tell a small DeepFM cell that states them apart from the reference."""
import copy
import sys
import types

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

from chipbench import check
from chipbench.reference import deepfm, precision, train
from chipbench.runners import recsys_replay as R
from chipbench.tests.test_bench_correct import small


@pytest.fixture(scope="module")
def cell():
    """A small sync DeepFM cell that states ``highest``, set up once: its
    checked steps and slot batches drive each reference below."""
    cfg, traffic = small("deepfm.sync_quiet")
    c = R.Cell(dict(cfg, matmul_precision="highest"), traffic,
               3_000_000_037)
    c.setup()
    c.free()
    return c


def stated(cell, **cfg):
    out = copy.copy(cell)
    out.cfg = dict(cell.cfg, **cfg)
    return out


def reference(cell, **kwargs) -> dict:
    params0 = R.init_params(cell.cfg, cell.seed)
    out = train.Reference(cell.cfg, cell.traffic, **kwargs).run(
        params0, cell.check_steps, cell.slot_batch)
    return out | {"params0": params0}


def module(monkeypatch, name: str, **attrs) -> str:
    """Registers a reference module under ``chipbench.reference.<name>``
    for the test."""
    mod = types.ModuleType(f"chipbench.reference.{name}")
    mod.__dict__.update(attrs)
    monkeypatch.setitem(sys.modules, mod.__name__, mod)
    return name


def deepfm_with(**attrs) -> dict:
    names = ("SPARSE", "init", "logit", "ids", "ids_per_example",
             "forward_flops")
    return {n: getattr(deepfm, n) for n in names} | attrs


# -- the loss ----------------------------------------------------------------

def test_a_modules_own_loss_is_trained(cell, monkeypatch):
    """A term on a leaf that no logit reads: the reference takes its
    gradient, and the rest of the gradient stays the cross-entropy's."""

    def init(key, cfg):
        return deepfm.init(key, cfg) | {
            "aux": jax.random.normal(jax.random.fold_in(key, 7), (4,))}

    def loss(p, cfg, batch):
        return (train.bce(deepfm.logit(p, cfg, batch), batch["label"])
                + 0.5 * jnp.sum(jnp.square(p["aux"])))

    aux = stated(cell, model=module(monkeypatch, "deepfm_aux",
                                    **deepfm_with(init=init, loss=loss)))
    got, plain = reference(aux), reference(cell)
    aux0 = got["params0"]["aux"]
    # every slot's weight is 1 in a sync cell: the dense sum is the mean
    np.testing.assert_allclose(got["grads"][0]["aux"], aux0, rtol=1e-6)
    assert got["losses"][0] == pytest.approx(
        plain["losses"][0] + 0.5 * float(jnp.sum(aux0 ** 2)), rel=1e-6)
    for name in ("embed", "linear", "bias"):
        np.testing.assert_allclose(got["grads"][0][name],
                                   plain["grads"][0][name], rtol=1e-5,
                                   atol=1e-9)
    # the default control's bfloat16 applies to the module's own loss
    low = reference(aux, **train.control(dict(aux.cfg,
                                              matmul_precision="default")))
    assert low["losses"][0] != got["losses"][0]


def test_a_module_without_loss_keeps_the_cross_entropy_bit_for_bit(
        cell, monkeypatch):
    """Without ``loss`` the reference differentiates what it did before
    modules could bring one: ``bce`` of the float32 logit, here written
    out as a module's own loss."""

    def loss(p, cfg, batch):
        return train.bce(deepfm.logit(p, cfg, batch).astype(jnp.float32),
                         batch["label"])

    own = stated(cell, model=module(monkeypatch, "deepfm_bce",
                                    **deepfm_with(loss=loss)))
    for kwargs in ({}, {"dtype": jnp.bfloat16}):
        a, b = reference(cell, **kwargs), reference(own, **kwargs)
        assert a["losses"] == b["losses"]
        for x, y in zip(jax.tree.leaves((a["grads"], a["params"])),
                        jax.tree.leaves((b["grads"], b["params"]))):
            np.testing.assert_array_equal(x, y)


# -- the controls ------------------------------------------------------------

def bf16_parts(x: np.ndarray, parts: int) -> list[np.ndarray]:
    out = []
    for _ in range(parts):
        out.append(x.astype(ml_dtypes.bfloat16).astype(np.float64))
        x = (x - out[-1]).astype(np.float32)
    return out


def numpy_passes(xs, ys, f) -> np.ndarray:
    return sum(f(x, y) for i, x in enumerate(xs) for j, y in enumerate(ys)
               if i + j < len(xs))


@pytest.mark.parametrize("mode", sorted(precision.PARTS))
def test_emulated_products_equal_numpy_exactly(mode):
    rng = np.random.default_rng(0)
    parts = precision.PARTS[mode]
    x = rng.standard_normal((64, 32)).astype(np.float32)
    for got, want in zip(precision.split(jnp.asarray(x), parts),
                         bf16_parts(x, parts)):
        np.testing.assert_array_equal(np.asarray(got, np.float64), want)
    # integers under 2**10: every product and partial sum is exact in
    # float32, so any order of summation gives the same bits
    a = rng.integers(-1023, 1024, (2, 3, 4)).astype(np.float32)
    b = rng.integers(-1023, 1024, (4, 5)).astype(np.float32)
    g = rng.integers(-1023, 1024, (2, 3, 5)).astype(np.float32)
    sa, sb, sg = (bf16_parts(v, parts) for v in (a, b, g))
    assert parts == 1 or np.any(sa[1])

    def f(a, b):
        with precision.emulate(mode):
            return precision.dot(a, b)

    out, vjp = jax.vjp(f, jnp.asarray(a), jnp.asarray(b))
    da, db = vjp(jnp.asarray(g))
    np.testing.assert_array_equal(out, numpy_passes(sa, sb, np.matmul))
    np.testing.assert_array_equal(
        da, numpy_passes(sg, sb, lambda g, b: g @ b.T))
    np.testing.assert_array_equal(db, numpy_passes(
        sg, sa, lambda g, a: np.einsum("nik,nij->kj", a, g)))
    # the plain product keeps the low parts' product
    assert not np.array_equal(out, a @ b)


@pytest.mark.parametrize("precision_", ["default", "high", "highest"])
def test_each_stated_precision_has_a_control(precision_):
    cfg = {"model": "deepfm", "dtype": "float32",
           "matmul_precision": precision_}
    assert train.control(cfg) == {
        "default": {"dtype": jnp.bfloat16}, "high": {"matmuls": "bf16"},
        "highest": {"matmuls": "bf16_3x"}}[precision_]
    assert train.control_name(cfg) == {
        "default": "bfloat16", "high": "bf16",
        "highest": "bf16_3x"}[precision_]


@pytest.mark.parametrize("model,dtype,precision_", [
    ("plain", "float32", "high"), ("plain", "float32", "highest"),
    ("deepfm", "bfloat16", "default"), ("deepfm", "float32", "bfloat16")])
def test_control_refuses(model, dtype, precision_, monkeypatch):
    """No control where none is defined, nor for a module whose products
    bypass ``precision.dot``: its control would equal the reference."""
    module(monkeypatch, "plain", **deepfm_with())
    with pytest.raises(ValueError):
        train.control({"model": model, "dtype": dtype,
                       "matmul_precision": precision_})


@pytest.mark.parametrize("precision_", ["high", "highest"])
def test_matmul_control_differs_from_the_reference(cell, precision_):
    c = stated(cell, matmul_precision=precision_)
    ref = c.reference()
    same = check.compare(ref, ref, c.names, c.cfg)["numbers"]
    ctl = check.compare(c.reference(control=True), ref, c.names,
                        c.cfg)["numbers"]
    assert same["grad_norm_gap.median"]["value"] == 0
    assert ctl["grad_norm_gap.median"]["value"] > 0
