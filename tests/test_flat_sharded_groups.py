"""Layer-grouped ShardedFlatLayout + the layer-grouped fused psum step.

Host-side tests cover the grouped layout geometry (per-group contiguous,
shard-aligned extents; shard-major global ordering; per-group and global
ravel/unravel round trips) and the canonical model grouping
(``models.transformer.param_group_key``), including the acceptance bound:
for the granite-8b smoke layout the per-device peak gathered bytes of the
grouped schedule is the largest layer group, strictly below N_total.

The subprocess test is the tentpole acceptance: on a forced 4-device host
mesh, ``make_gba_fused_psum_step`` on a layer-grouped layout (per-group
``all_gather`` + per-group ``all_to_all``) is bit-exact with the same
step on a single-group layout — the PR-4 full-gather schedule — for
params, accum, AND loss over 3 global steps, with slots decayed to zero
by Eq. (1) and non-tile-multiple leaves.
"""
import functools
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.flat_sharded import ShardedFlatLayout


def _grouped_params():
    """Deliberately non-tile-multiple leaves across three 'layers'."""
    k = jax.random.PRNGKey(0)
    return {"embed": jax.random.normal(k, (33, 9)),            # 297
            "blocks": {"l0": {"w": jnp.arange(41, dtype=jnp.float32),
                              "b": jax.random.normal(k, (7, 5))}},
            "head": jax.random.normal(k, (700,))}


def _first(names):
    return names[0]


@pytest.mark.parametrize("num_shards,tile", [(1, 256), (4, 256), (4, 128),
                                             (8, 256)])
def test_grouped_layout_geometry(num_shards, tile):
    """Every group's extent is a whole number of num_shards*tile chunks,
    groups tile the padded total, and every leaf lands in some shard."""
    layout = ShardedFlatLayout.from_params(_grouped_params(), num_shards,
                                           tile=tile, group_by=_first)
    assert layout.group_keys == ("blocks", "embed", "head")
    assert sum(layout.group_sizes) == layout.padded_total
    assert layout.shard_size == sum(layout.group_shard_sizes)
    for gs, gsn in zip(layout.group_sizes, layout.group_shard_sizes):
        assert gs % (num_shards * tile) == 0
        assert gsn == gs // num_shards
    for g in range(layout.num_groups):
        lo, hi = layout.group_shard_bounds(g)
        assert lo % tile == 0 and (hi - lo) == layout.group_shard_sizes[g]
    covered = sorted(j for s in range(num_shards)
                     for j in layout.leaves_in_shard(s))
    assert set(covered) == set(range(len(layout.sizes)))
    assert layout.peak_gather_bytes == max(layout.group_sizes) * 4
    if num_shards > 1 or tile == 128:
        assert layout.peak_gather_bytes < layout.full_gather_bytes


def test_grouped_roundtrip_and_group_ravel():
    """unravel(ravel(x)) == x bitwise on the shard-major grouped layout;
    per-group ravel/unravel round-trips each group independently, and the
    global flat is exactly the shard-major interleave of the groups."""
    params = _grouped_params()
    layout = ShardedFlatLayout.from_params(params, 4, tile=256,
                                           group_by=_first)
    flat = layout.ravel(params)
    assert flat.shape == (layout.padded_total,)
    for a, b in zip(jax.tree.leaves(layout.unravel(flat)),
                    jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    rows = np.asarray(flat).reshape(layout.num_shards, layout.shard_size)
    for g in range(layout.num_groups):
        gflat = layout.ravel_group(g, params)
        assert gflat.shape == (layout.group_sizes[g],)
        for a, b in zip(layout.unravel_group(g, gflat),
                        [jax.tree.leaves(params)[j]
                         for j in layout.group_leaves(g)]):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        lo, hi = layout.group_shard_bounds(g)
        np.testing.assert_array_equal(rows[:, lo:hi].reshape(-1),
                                      np.asarray(gflat))


def test_single_group_layout_matches_pr4_ordering():
    """group_by=None must reproduce the ungrouped layout bit-for-bit:
    one group, global leaf offsets, plain concatenation order."""
    params = _grouped_params()
    layout = ShardedFlatLayout.from_params(params, 4, tile=256)
    assert layout.num_groups == 1
    flat = np.asarray(layout.ravel(params))
    for off, size, leaf in zip(layout.offsets, layout.sizes,
                               jax.tree.leaves(params)):
        np.testing.assert_array_equal(
            flat[off:off + size],
            np.asarray(leaf.reshape(-1).astype(jnp.float32)))


def test_per_leaf_kernel_apply_rejects_grouped_layouts():
    """Leaves are shard-major-interleaved under grouping — no leaf is one
    contiguous global run, so the per-leaf oracle must refuse."""
    from repro.core.flat_sharded import per_leaf_kernel_apply
    layout = ShardedFlatLayout.from_params(_grouped_params(), 4, tile=256,
                                           group_by=_first)
    with pytest.raises(ValueError, match="single-group"):
        per_leaf_kernel_apply(
            layout, jnp.zeros((layout.padded_total,)),
            jnp.zeros((layout.padded_total,)),
            jnp.zeros((4, layout.padded_total)),
            jnp.zeros((4,), jnp.int32), jnp.int32(0), 0.1, iota=2)


def test_param_group_key_canonical_mapping():
    from repro.models.transformer import param_group_key
    assert param_group_key(("embed",)) == "embed"
    assert param_group_key(("lm_head",)) == "head"
    assert param_group_key(("final_norm", "scale")) == "final_norm"
    assert param_group_key(("blocks", "l0", "attn", "wq")) == "blocks.l0"
    assert param_group_key(("blocks", "l1", "moe", "wo")) == "blocks.l1"
    assert param_group_key(("prefix", "#0", "mlp", "wo")) == "prefix.#0"
    assert param_group_key(("shared_attn", "attn", "wq")) == "shared_attn"
    assert param_group_key(("encoder", "attn", "wk")) == "encoder"


def test_granite8b_smoke_peak_gather_is_largest_group():
    """Acceptance bound: on the granite-8b smoke layout the grouped
    schedule's per-device peak gathered bytes equals the largest layer
    group and is strictly below N_total bytes (what the full-vector
    gather pins)."""
    from repro.configs import get_config
    from repro.models import transformer as T
    cfg = get_config("granite-8b").reduced()
    pshapes = jax.eval_shape(
        functools.partial(T.init_model, cfg=cfg), jax.random.PRNGKey(0))
    layout = ShardedFlatLayout.from_params(pshapes, 4,
                                           group_by=T.param_group_key)
    assert layout.num_groups >= 3
    assert layout.peak_gather_bytes == max(layout.group_sizes) * 4
    assert layout.peak_gather_bytes < layout.total * 4       # < N_total
    assert layout.peak_gather_bytes < layout.full_gather_bytes
    # the grouping covers every leaf exactly once
    assert sorted(j for g in range(layout.num_groups)
                  for j in layout.group_leaves(g)) \
        == list(range(len(layout.sizes)))


# ---------------------------------------------------------------------------
# tentpole acceptance: 4-device grouped vs full-gather parity (subprocess)
# ---------------------------------------------------------------------------

_GROUPED_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.core.flat_sharded import ShardedFlatLayout
from repro.core.gba_shard_map import make_gba_fused_psum_step
from repro.distributed import sharding as S

out = {"devices": jax.device_count()}
from repro.launch.mesh import make_mesh
mesh = make_mesh((4,), ("data",))
key = jax.random.PRNGKey(7)
# non-tile-multiple leaves across three layer groups, tile=256
params = {"embed": jax.random.normal(key, (33, 9)),
          "blocks": {"l0": {"w": jax.random.normal(
                                jax.random.PRNGKey(8), (41,)),
                            "b": jax.random.normal(
                                jax.random.PRNGKey(9), (7, 5))}},
          "head": jax.random.normal(jax.random.PRNGKey(10), (700,))}
iota, lr = 2, 0.05

def loss_fn(p, batch):
    s = sum(jnp.sum(l.astype(jnp.float32) ** 2)
            for l in jax.tree.leaves(p))
    return jnp.mean(batch["x"]) * s

results = {}
for name, gb in (("grouped", lambda n: n[0]), ("full", None)):
    lay = ShardedFlatLayout.from_params(params, 4, tile=256, group_by=gb)
    specs = S.flat_slice_specs(lay, mesh, "data")
    pf = jax.device_put(lay.ravel(params),
                        NamedSharding(mesh, specs["flat"]))
    af = jax.device_put(jnp.full((lay.padded_total,), 0.1, jnp.float32),
                        NamedSharding(mesh, specs["flat"]))
    with mesh:
        step = make_gba_fused_psum_step(mesh, loss_fn, lay, iota=iota,
                                        lr=lr)
        if name == "grouped":
            # structural check via the static auditor's census: one
            # all_to_all and one param all_gather PER GROUP (+1 gather for
            # the tokens), exact shapes in group_table order
            from repro.analysis.jaxpr_audit import (
                census_counts, check_fused_psum_schedule, collective_census)
            x0 = jax.random.normal(jax.random.PRNGKey(50), (32,))
            jaxpr = jax.make_jaxpr(step)(
                lay.ravel(params),
                jnp.full((lay.padded_total,), 0.1, jnp.float32),
                {"x": x0}, jnp.zeros((4,), jnp.int32), jnp.int32(0))
            counts = census_counts(collective_census(jaxpr))
            out["n_groups"] = lay.num_groups
            out["n_all_to_all"] = counts.get("all_to_all", 0)
            out["n_all_gather"] = counts.get("all_gather", 0)
            out["schedule_findings"] = [
                str(f) for f in check_fused_psum_schedule(
                    jaxpr, lay, 4, "test/grouped")]
            out["peak_gather_bytes"] = lay.peak_gather_bytes
            out["full_gather_bytes"] = lay.full_gather_bytes
        jstep = jax.jit(step)
        losses = []
        for t in range(3):
            x = jax.random.normal(jax.random.PRNGKey(50 + t), (32,))
            bsh = jax.device_put({"x": x}, NamedSharding(mesh, P("data")))
            # worker 2's slot is 3 steps stale: Eq. (1) decays it to zero
            toks = jnp.array([t, t, t - 3, t], jnp.int32)
            tsh = jax.device_put(toks, NamedSharding(mesh, P("data")))
            pf, af, loss = jstep(pf, af, bsh, tsh, jnp.int32(t))
            losses.append(float(loss))
    results[name] = (lay.unravel(pf), lay.unravel(af), losses)

gp, ga, gl = results["grouped"]
fp, fa, fl = results["full"]
out["param_err"] = max(float(jnp.max(jnp.abs(a - b))) for a, b in
                       zip(jax.tree.leaves(gp), jax.tree.leaves(fp)))
out["accum_err"] = max(float(jnp.max(jnp.abs(a - b))) for a, b in
                       zip(jax.tree.leaves(ga), jax.tree.leaves(fa)))
out["loss_err"] = max(abs(a - b) for a, b in zip(gl, fl))
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def grouped_results():
    out = subprocess.run(
        [sys.executable, "-c", _GROUPED_SCRIPT], capture_output=True,
        text=True,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "HOME": os.environ.get("HOME", ""), "JAX_PLATFORMS": "cpu"},
        cwd=Path(__file__).resolve().parents[1], timeout=540)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_layer_grouped_step_bit_exact_with_full_gather(grouped_results):
    """Tentpole acceptance: the layer-grouped step (per-group gathers,
    per-group gradient routing) is bit-exact with the PR-4 full-gather
    step — params, accum, AND loss, across 3 global steps that include a
    slot decayed to zero by Eq. (1), on non-tile-multiple leaves."""
    res = grouped_results
    assert res["devices"] == 4
    assert res["param_err"] == 0.0, res
    assert res["accum_err"] == 0.0, res
    assert res["loss_err"] == 0.0, res


def test_layer_grouped_step_collective_schedule(grouped_results):
    """The grouped step's program really is per-group: one all_to_all per
    layer group, one param all_gather per group plus the (M,) token
    gather — and its peak gathered bytes is strictly below the
    full-vector gather's.  Checked through the static auditor's census
    (GBA-COLL-001/002), not jaxpr string matching."""
    res = grouped_results
    assert res["n_groups"] == 3
    assert res["n_all_to_all"] == res["n_groups"]
    assert res["n_all_gather"] == res["n_groups"] + 1
    assert res["schedule_findings"] == [], res["schedule_findings"]
    assert res["peak_gather_bytes"] < res["full_gather_bytes"]
