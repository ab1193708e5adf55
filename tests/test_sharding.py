"""Sharding rule engine: spec trees match param trees, divisibility guards
degrade to replication, and reduced configs jit end-to-end on a tiny mesh."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.launch.mesh import make_mesh
from repro.configs import ARCH_IDS, get_config
from repro.configs.base import GBAConfig, InputShape
from repro.distributed import sharding as S
from repro.launch.mesh import make_smoke_mesh
from repro.launch.steps import abstract_cache, abstract_params, build_step


def _mesh22():
    if jax.device_count() < 4:
        pytest.skip("needs >=4 devices (run under forced host devices)")
    return make_mesh((2, 2), ("data", "model"))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_param_specs_structure_and_rank(arch):
    cfg = get_config(arch)
    mesh = make_smoke_mesh()
    shapes = abstract_params(cfg)
    specs = S.param_specs(shapes, mesh)
    flat_s, tree_s = jax.tree_util.tree_flatten(
        specs, is_leaf=lambda x: isinstance(x, P))
    flat_p, tree_p = jax.tree_util.tree_flatten(shapes)
    assert tree_s == tree_p
    for spec, leaf in zip(flat_s, flat_p):
        assert len(spec) <= leaf.ndim, (spec, leaf.shape)
        for d, ax in zip(leaf.shape, spec):
            if ax is not None:
                size = np.prod([mesh.shape[a] for a in
                                (ax if isinstance(ax, tuple) else (ax,))])
                assert d % size == 0, (arch, spec, leaf.shape)


def test_divisibility_guard_replicates():
    """starcoder2's 24 heads don't divide model=16: heads spec must fall
    back to head_dim (or None), never an invalid axis."""
    cfg = get_config("starcoder2-3b")
    mesh = make_mesh((1, 16), ("data", "model")) \
        if jax.device_count() >= 16 else None
    if mesh is None:
        pytest.skip("needs 16 devices")
    shapes = abstract_params(cfg)
    specs = S.param_specs(shapes, mesh)
    wq = specs["blocks"]["l0"]["attn"]["wq"]
    assert wq[2] != "model" or cfg.resolved_head_dim % 16 == 0


def test_batch_partition_fallback():
    mesh = make_smoke_mesh()
    p = S.batch_partition(mesh, 4, 2)
    assert p[0] in ("data", ("data",))  # P normalizes 1-tuples
    p1 = S.batch_partition(mesh, 3, 2)  # indivisible under >1 devices is ok
    assert isinstance(p1, P)


@pytest.mark.parametrize("kind,shape", [
    ("train", InputShape("t", 64, 8, "train")),
    ("prefill", InputShape("p", 64, 4, "prefill")),
    ("decode", InputShape("d", 64, 8, "decode")),
])
@pytest.mark.parametrize("arch", ["granite-8b", "mamba2-780m",
                                  "phi3.5-moe-42b-a6.6b"])
def test_build_step_lowers_on_smoke_mesh(arch, kind, shape):
    cfg = get_config(arch).reduced()
    mesh = make_smoke_mesh()
    with mesh:
        fn, args = build_step(cfg, shape, mesh)
        lowered = fn.lower(*args)
        compiled = lowered.compile()
    assert compiled.cost_analysis() is not None


# ---------------------------------------------------------------------------
# ShardedFlatLayout: leaf-/tile-aligned slice geometry + spec construction
# (host-side only — no multi-device mesh needed)
# ---------------------------------------------------------------------------

def _odd_params():
    """Deliberately non-tile-multiple leaf sizes."""
    k = jax.random.PRNGKey(0)
    return {"w": jax.random.normal(k, (33, 9)),          # 297
            "b": {"c": jnp.arange(41, dtype=jnp.float32),
                  "d": jax.random.normal(k, (700,))}}


@pytest.mark.parametrize("num_shards,tile", [(1, 256), (4, 256), (4, 128),
                                             (8, 256)])
def test_sharded_flat_layout_geometry(num_shards, tile):
    """Every leaf starts on a tile boundary, every shard slice is a whole
    number of tiles, and padded_total splits exactly across shards."""
    from repro.core.flat_sharded import ShardedFlatLayout
    params = _odd_params()
    layout = ShardedFlatLayout.from_params(params, num_shards, tile=tile)
    assert layout.total == sum(layout.sizes)
    assert layout.padded_total == num_shards * layout.shard_size
    assert layout.shard_size % tile == 0
    for off, size, padded in zip(layout.offsets, layout.sizes,
                                 layout.padded_sizes):
        assert off % tile == 0
        assert padded % tile == 0
        assert padded >= size
    for s in range(num_shards):
        lo, hi = layout.shard_bounds(s)
        assert lo % tile == 0 and hi % tile == 0
        assert hi - lo == layout.shard_size
    covered = sorted(j for s in range(num_shards)
                     for j in layout.leaves_in_shard(s))
    assert set(covered) == set(range(len(layout.sizes)))


def test_sharded_flat_layout_roundtrip_and_padding():
    """ravel zero-fills leaf/tail padding; unravel(ravel(x)) == x
    bitwise for non-tile-multiple leaves."""
    from repro.core.flat_sharded import ShardedFlatLayout
    params = _odd_params()
    layout = ShardedFlatLayout.from_params(params, 4, tile=256)
    flat = layout.ravel(params)
    assert flat.shape == (layout.padded_total,)
    for a, b in zip(jax.tree.leaves(layout.unravel(flat)),
                    jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # padding columns are exactly zero (so Adagrad on them is the identity)
    mask = np.ones(layout.padded_total, bool)
    for off, size in zip(layout.offsets, layout.sizes):
        mask[off:off + size] = False
    assert not np.any(np.asarray(flat)[mask])


def test_flat_slice_specs_and_validation():
    """Spec construction from the layout: flat vectors split over the PS
    axis, buffer columns likewise, scalars replicated; geometry mismatch
    fails loudly at spec-build time."""
    from repro.core.flat_sharded import ShardedFlatLayout
    mesh = make_smoke_mesh()     # (data=1, model=1)
    params = _odd_params()
    layout = ShardedFlatLayout.from_params(params, 1, tile=256)
    specs = S.flat_slice_specs(layout, mesh, "data")
    assert specs["flat"] == P("data")
    assert specs["buffer"]["grads"] == P(None, "data")
    assert specs["buffer"]["tokens"] == P()
    assert specs["buffer"]["fill"] == P()
    bad = ShardedFlatLayout.from_params(params, 4, tile=256)
    with pytest.raises(ValueError, match="shards"):
        S.flat_slice_specs(bad, mesh, "data")
    with pytest.raises(ValueError, match="axis"):
        S.flat_slice_specs(layout, mesh, "ps")


def test_fused_state_specs_tree():
    """fused_state_specs keeps per-leaf model rules for params and slices
    the flat accum/buffer."""
    from repro.core.flat_sharded import ShardedFlatLayout
    mesh = make_smoke_mesh()
    params = _odd_params()
    layout = ShardedFlatLayout.from_params(params, 1, tile=256)
    pshapes = jax.eval_shape(lambda t: t, params)
    pspecs = S.param_specs(pshapes, mesh)
    specs = S.fused_state_specs(layout, mesh, pspecs, "data")
    assert specs["accum"] == P("data")
    assert specs["buffer"]["grads"] == P(None, "data")
    flat_p, tree_p = jax.tree_util.tree_flatten(
        specs["params"], is_leaf=lambda x: isinstance(x, P))
    assert tree_p == jax.tree_util.tree_flatten(pshapes)[1]


def test_cache_specs_long_context_seq_sharding():
    """long_500k (batch=1): KV seq dim takes the data axis.  Uses an
    AbstractMesh so the production (16,16) geometry is testable on 1 CPU
    device (cache_specs only reads mesh.shape)."""
    from jax.sharding import AbstractMesh
    cfg = get_config("gemma2-27b")
    mesh = AbstractMesh((16, 16), ("data", "model"))
    cache = abstract_cache(cfg, 1, 1024)
    specs = S.cache_specs(cache, cfg, mesh, batch=1)
    k_spec = specs["blocks"]["l1"]["attn"]["k"]  # global layer
    assert k_spec[0] is None          # stacked repeats
    assert k_spec[1] is None          # batch=1 unshardable
    assert k_spec[2] == "data"        # sequence-parallel cache
