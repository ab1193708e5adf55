"""Quantized gradient routing on the layer-grouped fused-psum wire.

Fast-lane host tests cover the `CompressionPolicy` accounting
(route/wire bytes, compression ratio, state shapes, group_table wire
columns), the quantize/dequantize Pallas kernels (int8 min-max and sign
modes, per-tile sidebands, the error-feedback invariant
``residual + dequantize(quantize(x)) == x`` to float rounding), their
exported launch metas, and the GBA-COLL-005 expected-census helper.

The slow subprocess tests are the tentpole acceptance: on a forced
4-device host mesh, (a) the f32 warmup phase of BOTH lossy schemes is
bit-exact with the uncompressed PR-5 step — params, accum, AND loss over
3 global steps including an Eq.-(1)-decayed slot and non-tile-multiple
leaves; (b) the compressed traces pass GBA-COLL-005 (int8 payload + f32
sidebands only on the wire) and the warmup trace reproduces the PR-5
schedule exactly; (c) onebit sign-of-momentum training converges on a
seeded tiny-DeepFM recsys smoke within a tolerance band of full
precision.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.mesh import make_mesh
from repro.core.compression import CompressionPolicy
from repro.core.flat_sharded import ShardedFlatLayout
from repro.kernels import quantize as Q

_ENV = {"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
        "HOME": os.environ.get("HOME", ""), "JAX_PLATFORMS": "cpu"}
_REPO = Path(__file__).resolve().parents[1]


def _layout(num_shards=4, tile=256, grouped=True):
    params = {"embed": jnp.zeros((33, 9)),
              "blocks": {"l0": {"w": jnp.zeros((41,)),
                                "b": jnp.zeros((7, 5))}},
              "head": jnp.zeros((700,))}
    return ShardedFlatLayout.from_params(
        params, num_shards, tile=tile,
        group_by=(lambda n: n[0]) if grouped else None)


# ---------------------------------------------------------------------------
# policy accounting
# ---------------------------------------------------------------------------

def test_policy_validation():
    with pytest.raises(ValueError):
        CompressionPolicy(scheme="fp4")
    with pytest.raises(ValueError):
        CompressionPolicy(scheme="int8", warmup_steps=-1)
    with pytest.raises(ValueError):
        CompressionPolicy(scheme="onebit", momentum=1.5)
    assert not CompressionPolicy().stateful
    assert CompressionPolicy(scheme="int8").state_names() == ("residual",)
    assert CompressionPolicy(scheme="onebit").state_names() \
        == ("residual", "momentum")


def test_policy_route_and_wire_bytes():
    lay = _layout()
    none, i8, ob = (CompressionPolicy(scheme=s)
                    for s in ("none", "int8", "onebit"))
    g, tile = lay.group_sizes[0], lay.tile
    assert none.route_bytes(g, tile) == g * 4
    # int8: 1 byte/element + (scale, zero-point) f32 per tile
    assert i8.route_bytes(g, tile) == g + 2 * (g // tile) * 4
    assert ob.route_bytes(g, tile) == g + 1 * (g // tile) * 4
    # warmup routes full f32 regardless of scheme
    assert i8.route_bytes(g, tile, warm=True) == g * 4
    assert none.wire_bytes(lay) == lay.padded_total * 4
    assert i8.wire_bytes(lay) == sum(
        i8.route_bytes(gs, tile) for gs in lay.group_sizes)
    assert none.compression_ratio(lay) == 1.0
    # acceptance bound: int8 wire is <= 0.30x of f32
    assert i8.compression_ratio(lay) <= 0.30
    assert ob.compression_ratio(lay) < i8.compression_ratio(lay)
    assert i8.wire_dtype() == "int8" and i8.wire_dtype(warm=True) \
        == "float32"


def test_wire_state_shapes_and_init():
    lay = _layout()
    assert lay.wire_state_shapes(4, "none") == {}
    assert lay.wire_state_shapes(4, "int8") \
        == {"residual": (4, lay.padded_total)}
    assert lay.wire_state_shapes(4, "onebit") \
        == {"residual": (4, lay.padded_total),
            "momentum": (4, lay.padded_total)}
    with pytest.raises(ValueError):
        lay.wire_state_shapes(4, "fp8")
    wire = CompressionPolicy(scheme="onebit").init_wire_state(lay, 4)
    assert set(wire) == {"residual", "momentum"}
    for v in wire.values():
        assert v.shape == (4, lay.padded_total) and v.dtype == jnp.float32
        assert float(jnp.abs(v).max()) == 0.0


def test_group_table_wire_columns():
    lay = _layout()
    i8 = CompressionPolicy(scheme="int8")
    plain = lay.group_table()
    comp = lay.group_table(compress=i8)
    assert [r["key"] for r in plain] == [r["key"] for r in comp]
    for rp, rc in zip(plain, comp):
        assert rp["wire_bytes"] == rp["bytes"]
        assert rp["wire_dtype"] == "float32"
        assert rc["wire_dtype"] == "int8"
        assert rc["wire_bytes"] \
            == i8.route_bytes(rp["bytes"] // 4, lay.tile)
        assert rc["wire_bytes"] < rp["wire_bytes"]


def test_wire_state_specs():
    from jax.sharding import PartitionSpec as P
    from repro.distributed import sharding as S
    lay = _layout(num_shards=1)
    mesh = make_mesh((1,), ("data",))
    assert S.wire_state_specs(lay, mesh, "none") == {}
    specs = S.wire_state_specs(lay, mesh, "onebit")
    assert specs == {"residual": P("data", None),
                     "momentum": P("data", None)}


# ---------------------------------------------------------------------------
# quantize / dequantize kernels (interpret mode)
# ---------------------------------------------------------------------------

def test_minmax_error_feedback_invariant():
    """Per tile: residual + dequantize(quantize(x)) == x to float
    rounding — the error-feedback residual captures exactly what the
    int8 code dropped."""
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 512)) * 3.0
    q, sc, zp, res = Q.quantize_minmax(x, tile=128)
    assert q.dtype == jnp.int8 and q.shape == x.shape
    assert sc.shape == zp.shape == (4, 4)
    deq = Q.dequantize(q, sc, zp, tile=128, mode="minmax")
    np.testing.assert_allclose(np.asarray(res + deq), np.asarray(x),
                               atol=1e-6, rtol=0)
    # the code really is lossy (residual nonzero) but tile-bounded
    assert float(jnp.abs(res).max()) > 0.0
    span = (x.reshape(4, 4, 128).max(-1) - x.reshape(4, 4, 128).min(-1))
    assert float(jnp.abs(res).max()) <= float(span.max()) / 255.0 * 0.51


def test_minmax_constant_tile_exact():
    """A constant tile has span 0 -> scale 0 -> dequant returns the
    zero-point bit-exactly and the residual is exactly zero."""
    x = jnp.full((2, 256), 1.7, jnp.float32)
    q, sc, zp, res = Q.quantize_minmax(x, tile=128)
    deq = Q.dequantize(q, sc, zp, tile=128, mode="minmax")
    np.testing.assert_array_equal(np.asarray(deq), np.asarray(x))
    np.testing.assert_array_equal(np.asarray(res), 0.0)
    np.testing.assert_array_equal(np.asarray(sc), 0.0)


def test_sign_error_feedback_invariant():
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 384))
    q, sc, res = Q.quantize_sign(x, tile=128)
    assert q.dtype == jnp.int8
    vals = np.unique(np.asarray(q))
    assert set(vals.tolist()) <= {-1, 1}
    # per-tile scale is mean |x|
    np.testing.assert_allclose(
        np.asarray(sc),
        np.abs(np.asarray(x)).reshape(3, 3, 128).mean(-1),
        rtol=1e-6)
    deq = Q.dequantize(q, sc, tile=128, mode="sign")
    np.testing.assert_allclose(np.asarray(res + deq), np.asarray(x),
                               atol=1e-5, rtol=0)


def test_quantize_launch_meta_vmem():
    for mode in Q.MODES:
        for meta, formula in (
                (Q.quantize_launch_meta(8, 1 << 14, 2048, mode),
                 Q.quantize_vmem_bytes(8, 1 << 14, 2048, mode)),
                (Q.dequant_launch_meta(8, 1 << 14, 2048, mode),
                 Q.dequant_vmem_bytes(8, 1 << 14, 2048, mode))):
            assert meta.vmem_bytes(meta.vmem_counted) == formula
            assert meta.grid == ((1 << 14) // 2048,)
    with pytest.raises(ValueError):
        Q.quantize_launch_meta(4, 130, 128, "minmax")
    with pytest.raises(ValueError):
        Q.quantize_minmax(jnp.zeros((2, 130)), tile=128)


# ---------------------------------------------------------------------------
# GBA-COLL-005 expected census (unit)
# ---------------------------------------------------------------------------

def test_expected_wire_collectives():
    from repro.analysis.jaxpr_audit import expected_wire_collectives
    lay = _layout()
    m = lay.num_shards
    i8 = CompressionPolicy(scheme="int8", warmup_steps=1)
    ob = CompressionPolicy(scheme="onebit", warmup_steps=1)
    for g, (gsh, ops) in enumerate(zip(
            lay.group_shard_sizes,
            expected_wire_collectives(lay, m, i8))):
        assert ops == [((m, gsh), "int8"),
                       ((m, gsh // lay.tile), "float32"),
                       ((m, gsh // lay.tile), "float32")]
    for gsh, ops in zip(lay.group_shard_sizes,
                        expected_wire_collectives(lay, m, ob)):
        assert ops == [((m, gsh), "int8"),
                       ((m, gsh // lay.tile), "float32")]
    # warmup and none: one f32 operand per group, PR-5 exactly
    for pol in (i8, CompressionPolicy()):
        for gsh, ops in zip(
                lay.group_shard_sizes,
                expected_wire_collectives(lay, m, pol,
                                          warm=pol.stateful)):
            assert ops == [((m, gsh), "float32")]


# ---------------------------------------------------------------------------
# slow: 4-device warmup parity + compressed census (subprocess)
# ---------------------------------------------------------------------------

_WIRE_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.core.compression import CompressionPolicy
from repro.core.flat_sharded import ShardedFlatLayout
from repro.core.gba_shard_map import make_gba_fused_psum_step
from repro.analysis import jaxpr_audit as JA

out = {"devices": jax.device_count()}
from repro.launch.mesh import make_mesh
mesh = make_mesh((4,), ("data",))
key = jax.random.PRNGKey(7)
params = {"embed": jax.random.normal(key, (33, 9)),
          "blocks": {"l0": {"w": jax.random.normal(
                                jax.random.PRNGKey(8), (41,)),
                            "b": jax.random.normal(
                                jax.random.PRNGKey(9), (7, 5))}},
          "head": jax.random.normal(jax.random.PRNGKey(10), (700,))}
iota, lr, m = 2, 0.05, 4
lay = ShardedFlatLayout.from_params(params, m, tile=256,
                                    group_by=lambda n: n[0])

def loss_fn(p, batch):
    s = sum(jnp.sum(l.astype(jnp.float32) ** 2)
            for l in jax.tree.leaves(p))
    return jnp.mean(batch["x"]) * s

def run(pol, warm, steps=3):
    step = jax.jit(make_gba_fused_psum_step(
        mesh, loss_fn, lay, iota=iota, lr=lr, compress=pol, warm=warm))
    pf = lay.ravel(params)
    af = jnp.full((lay.padded_total,), 0.1, jnp.float32)
    wire = pol.init_wire_state(lay, m) if pol and pol.stateful else None
    losses = []
    with mesh:
        for t in range(steps):
            x = jax.random.normal(jax.random.PRNGKey(50 + t), (32,))
            bsh = jax.device_put({"x": x}, NamedSharding(mesh, P("data")))
            # worker 2's slot is 3 steps stale: Eq. (1) decays it to zero
            toks = jnp.array([t, t, t - 3, t], jnp.int32)
            tsh = jax.device_put(toks, NamedSharding(mesh, P("data")))
            if wire is None:
                pf, af, loss = step(pf, af, bsh, tsh, jnp.int32(t))
            else:
                pf, af, loss, wire = step(pf, af, bsh, tsh, jnp.int32(t),
                                          wire)
            losses.append(float(loss))
    return pf, af, losses, wire

def maxdiff(a, b):
    return float(jnp.max(jnp.abs(a - b)))

bp, ba, bl, _ = run(CompressionPolicy(), False)
for scheme in ("int8", "onebit"):
    pol = CompressionPolicy(scheme=scheme, warmup_steps=10)
    wp, wa, wl, wire = run(pol, True)
    out[f"warm_{scheme}_param_err"] = maxdiff(wp, bp)
    out[f"warm_{scheme}_accum_err"] = maxdiff(wa, ba)
    out[f"warm_{scheme}_loss_err"] = max(
        abs(a - b) for a, b in zip(wl, bl))
    out[f"warm_{scheme}_residual_max"] = float(
        jnp.abs(wire["residual"]).max())
    if scheme == "onebit":
        out["warm_momentum_max"] = float(jnp.abs(wire["momentum"]).max())

# compressed runs: error feedback engaged, params stay near baseline
for scheme in ("int8", "onebit"):
    pol = CompressionPolicy(scheme=scheme, warmup_steps=0)
    cp, ca, cl, wire = run(pol, False)
    out[f"{scheme}_param_dev"] = maxdiff(cp, bp)
    out[f"{scheme}_residual_max"] = float(jnp.abs(wire["residual"]).max())
    out[f"{scheme}_finite"] = bool(jnp.isfinite(cp).all())

# census: compressed + warmup traces against GBA-COLL-005 / COLL-001
pol = CompressionPolicy(scheme="int8", warmup_steps=1)
wire0 = pol.init_wire_state(lay, m)
x0 = jax.random.normal(jax.random.PRNGKey(50), (32,))
args = (lay.ravel(params), jnp.full((lay.padded_total,), 0.1),
        {"x": x0}, jnp.zeros((4,), jnp.int32), jnp.int32(0), wire0)
with mesh:
    jc = jax.make_jaxpr(make_gba_fused_psum_step(
        mesh, loss_fn, lay, iota=iota, lr=lr, compress=pol))(*args)
    jw = jax.make_jaxpr(make_gba_fused_psum_step(
        mesh, loss_fn, lay, iota=iota, lr=lr, compress=pol,
        warm=True))(*args)
out["compressed_findings"] = [
    str(f) for f in JA.check_wire_dtypes(jc, lay, m, pol, "t/c")]
out["warm_findings"] = [
    str(f) for f in JA.check_wire_dtypes(jw, lay, m, pol, "t/w",
                                         warm=True)
    ] + [str(f) for f in JA.check_fused_psum_schedule(jw, lay, m, "t/w")]
# a f32 wire past warmup MUST trip the rule (census not vacuous here)
out["leak_findings"] = [
    str(f) for f in JA.check_wire_dtypes(jw, lay, m, pol, "t/leak")]
counts = JA.census_counts(JA.collective_census(jc))
out["compressed_all_to_all"] = counts.get("all_to_all", 0)
out["n_groups"] = lay.num_groups
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def wire_results():
    out = subprocess.run(
        [sys.executable, "-c", _WIRE_SCRIPT], capture_output=True,
        text=True, env=dict(_ENV), cwd=_REPO, timeout=540)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.slow
def test_warmup_bit_exact_with_pr5(wire_results):
    """Acceptance: the f32 warmup phase of BOTH schemes is bit-exact
    with the uncompressed PR-5 step — params, accum, loss — over 3
    global steps with an Eq.-(1)-decayed slot and non-tile-multiple
    leaves.  Residuals stay exactly zero through warmup; the onebit
    momentum EMA is already accumulating."""
    res = wire_results
    assert res["devices"] == 4
    for scheme in ("int8", "onebit"):
        assert res[f"warm_{scheme}_param_err"] == 0.0, res
        assert res[f"warm_{scheme}_accum_err"] == 0.0, res
        assert res[f"warm_{scheme}_loss_err"] == 0.0, res
        assert res[f"warm_{scheme}_residual_max"] == 0.0, res
    assert res["warm_momentum_max"] > 0.0


@pytest.mark.slow
def test_compressed_wire_error_feedback_active(wire_results):
    """Past warmup the lossy wire engages: residuals are nonzero (error
    feedback carries the dropped code), the trained params stay finite
    and near the full-precision trajectory on the quadratic probe."""
    res = wire_results
    for scheme in ("int8", "onebit"):
        assert res[f"{scheme}_finite"], res
        assert res[f"{scheme}_residual_max"] > 0.0, res
    assert res["int8_param_dev"] < 1e-2, res
    assert res["onebit_param_dev"] < 0.5, res


@pytest.mark.slow
def test_compressed_census_coll_005(wire_results):
    """GBA-COLL-005 on the real traces: the compressed program routes
    int8 payload + f32 sidebands only (3 all_to_all per group for int8);
    the warmup program routes f32 and reproduces the PR-5 schedule
    exactly; and a f32 wire checked as past-warmup DOES trip the rule —
    full-precision leakage is a CI failure, not a silent pass."""
    res = wire_results
    assert res["compressed_findings"] == [], res["compressed_findings"]
    assert res["warm_findings"] == [], res["warm_findings"]
    assert res["compressed_all_to_all"] == 3 * res["n_groups"]
    assert res["leak_findings"], "f32 leak past warmup must be flagged"
    assert all("GBA-COLL-005" in f for f in res["leak_findings"])


# ---------------------------------------------------------------------------
# slow: onebit convergence on the tiny-DeepFM recsys smoke (subprocess)
# ---------------------------------------------------------------------------

_RECSYS_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.configs.recsys import RecsysConfig
from repro.core.compression import CompressionPolicy
from repro.core.flat_sharded import ShardedFlatLayout
from repro.core.gba_shard_map import make_gba_fused_psum_step
from repro.models import recsys as R

cfg = RecsysConfig(name="tiny-deepfm", model="deepfm", num_fields=4,
                   hash_capacity=523, embed_dim=8, mlp_dims=(16,))
params = R.init_deepfm(jax.random.PRNGKey(0), cfg)
from repro.launch.mesh import make_mesh
mesh = make_mesh((4,), ("data",))
m, iota, lr, B, steps = 4, 4, 0.4, 64, 40
lay = ShardedFlatLayout.from_params(params, m, tile=256)
teacher = jax.random.normal(jax.random.PRNGKey(99), (cfg.hash_capacity,))

def batch_at(t):
    k = jax.random.PRNGKey(1000 + t)
    ids = jax.random.randint(k, (B, cfg.num_fields), 0, cfg.hash_capacity)
    label = (teacher[ids].sum(axis=1) > 0.0).astype(jnp.float32)
    return {"fields": ids, "label": label}

def loss_fn(p, batch):
    return R.bce_loss(p, cfg, batch)

def run(pol):
    pf = lay.ravel(params)
    af = jnp.full((lay.padded_total,), 0.1, jnp.float32)
    wire = pol.init_wire_state(lay, m) if pol.stateful else None
    steps_fns = {}
    losses = []
    with mesh:
        for t in range(steps):
            warm = pol.stateful and t < pol.warmup_steps
            key = ("warm" if warm else "main", pol.scheme)
            if key not in steps_fns:
                steps_fns[key] = jax.jit(make_gba_fused_psum_step(
                    mesh, loss_fn, lay, iota=iota, lr=lr, compress=pol,
                    warm=warm))
            b = jax.device_put(batch_at(t), NamedSharding(mesh, P("data")))
            toks = jax.device_put(jnp.full((m,), t, jnp.int32),
                                  NamedSharding(mesh, P("data")))
            if wire is None:
                pf, af, loss = steps_fns[key](pf, af, b, toks, jnp.int32(t))
            else:
                pf, af, loss, wire = steps_fns[key](pf, af, b, toks,
                                                    jnp.int32(t), wire)
            losses.append(float(loss))
    return losses

base = run(CompressionPolicy())
ob = run(CompressionPolicy(scheme="onebit", warmup_steps=2, momentum=0.9))
out = {"devices": jax.device_count(), "base": base, "onebit": ob}
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def recsys_results():
    out = subprocess.run(
        [sys.executable, "-c", _RECSYS_SCRIPT], capture_output=True,
        text=True, env=dict(_ENV), cwd=_REPO, timeout=540)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.slow
def test_onebit_converges_on_recsys_smoke(recsys_results):
    """Seeded statistical acceptance: onebit sign-of-momentum training
    (2-step f32 warmup, error feedback) still LEARNS the tiny-DeepFM
    click task — final-window loss clearly below the initial loss — and
    lands within a tolerance band of the full-precision run."""
    res = recsys_results
    assert res["devices"] == 4
    base, ob = res["base"], res["onebit"]
    assert all(np.isfinite(ob)), ob
    # warmup is bit-exact with full precision by construction
    assert ob[0] == base[0] and ob[1] == base[1]
    start, b_end = base[0], float(np.mean(base[-5:]))
    o_end = float(np.mean(ob[-5:]))
    assert b_end < start - 0.03, (start, b_end)      # baseline learns
    assert o_end < start - 0.02, (start, o_end)      # onebit learns too
    assert abs(o_end - b_end) < 0.05, (o_end, b_end)  # tolerance band
