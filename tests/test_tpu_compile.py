"""Compile rehearsal: every main-path Pallas kernel at real widths, lowered
with ``interpret=False`` and compiled for a described (not attached) TPU
v5e.  Mosaic legality — tile-aligned slices and DMAs, SMEM scalar loads,
VMEM limits — is checked here at no chip time; interpret-mode parity
lives in test_kernels.py / test_embedding_stream.py.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this module.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import embedding_bag, fused_adagrad, gba_apply, quantize


@pytest.fixture(scope="module")
def one_chip():
    # only a missing TPU library skips; any other failure to describe the
    # topology is a regression and fails the tests
    pytest.importorskip("libtpu")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _compile_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_gba_apply_compiles(one_chip):
    m, n = 16, 65536
    txt = _compile_text(
        lambda p, a, b, t, s, lr: gba_apply.gba_apply(
            p, a, b, t, s, lr, iota=4, interpret=False),
        _sds(one_chip, (n,)), _sds(one_chip, (n,)),
        _sds(one_chip, (m, n)), _sds(one_chip, (m,), jnp.int32),
        _sds(one_chip, (), jnp.int32), _sds(one_chip, ()))
    assert "tpu_custom_call" in txt


def test_fused_adagrad_compiles(one_chip):
    n = 65536
    txt = _compile_text(
        lambda p, g, a, lr: fused_adagrad.fused_adagrad(
            p, g, a, lr, interpret=False),
        _sds(one_chip, (n,)), _sds(one_chip, (n,)), _sds(one_chip, (n,)),
        _sds(one_chip, ()))
    assert "tpu_custom_call" in txt


# criteo-deepfm's width (D=16) at a 1M-row table, 512 x 26 ids
V, D, B, F = 1_000_000, 16, 512, 26


def test_embedding_bag_compiles(one_chip):
    txt = _compile_text(
        lambda ids, t: embedding_bag.embedding_bag(ids, t, interpret=False),
        _sds(one_chip, (B, F), jnp.int32), _sds(one_chip, (V, D)))
    assert "tpu_custom_call" in txt


def test_embedding_bag_grad_compiles(one_chip):
    txt = _compile_text(
        lambda ids, g: embedding_bag.embedding_bag_grad(
            ids, g, V, interpret=False),
        _sds(one_chip, (B, F), jnp.int32), _sds(one_chip, (B, D)))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("precision", ["default", "highest"])
def test_scatter_rows_compiles(one_chip, precision):
    """The row scatter at dien-alimama's step: 16 slots x 512 examples x
    203 ids of 18 columns into 800,000 rows, under its own kernel name,
    whatever matmul precision the step is traced under."""
    n = 16 * 512 * 203
    with jax.default_matmul_precision(precision):
        txt = _compile_text(
            lambda ids, rows: embedding_bag.scatter_rows(
                ids, rows, 800_000, interpret=False),
            _sds(one_chip, (n,), jnp.int32), _sds(one_chip, (n, 18)))
    assert "%_scatter_rows_streamed" in txt
    assert "%_embedding_bag_grad_streamed" not in txt


@pytest.mark.parametrize("mode", quantize.MODES)
def test_quantize_compiles(one_chip, mode):
    fn = quantize.quantize_minmax if mode == "minmax" \
        else quantize.quantize_sign
    txt = _compile_text(lambda x: fn(x, tile=2048, interpret=False),
                        _sds(one_chip, (4, 1 << 16)))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("mode", quantize.MODES)
def test_dequantize_compiles(one_chip, mode):
    sb = _sds(one_chip, (4, (1 << 16) // 2048))
    txt = _compile_text(
        lambda q, s, z: quantize.dequantize(
            q, s, z if mode == "minmax" else None, tile=2048, mode=mode,
            interpret=False),
        _sds(one_chip, (4, 1 << 16), jnp.int8), sb, sb)
    assert "tpu_custom_call" in txt


def test_scatter_rows_with_counts_compiles(one_chip):
    """The row scatter with its contributor-count column, at
    dien-alimama's step (18 columns and the count into 32 sublanes)."""
    n = 16 * 512 * 203
    txt = _compile_text(
        lambda ids, rows, counts: embedding_bag.scatter_rows(
            ids.reshape(16, -1), rows.reshape(16, -1, 18), 800_000,
            counts.reshape(16, -1), interpret=False),
        _sds(one_chip, (n,), jnp.int32), _sds(one_chip, (n, 18)),
        _sds(one_chip, (n,)))
    assert "%_scatter_rows_streamed" in txt


def test_replay_step_names_its_phases(one_chip):
    """The DeepFM GBA replay step at a small table: every program scope is
    in the optimized HLO's ``op_name`` metadata, no pooled-backward kernel
    counts presence (the row scatter carries the contributor count), and
    the benchmark's op -> phase map gives a phase to nearly every
    instruction that runs; DIEN's step carries its ``interest`` scope
    inside ``dense``."""
    import json
    import re
    from pathlib import Path

    from chipbench import phases

    root = Path(__file__).resolve().parents[1] / "chipbench"
    cfg = json.loads((root / "configs" / "deepfm-criteo.json").read_text())
    traffic = json.loads((root / "traffic" / "gba_strained.json").read_text())
    cfg = dict(cfg, hash_capacity=20000)
    traffic = dict(traffic, workers=4, buffer_size=4, local_batch=128)
    texts = phases.compiled_texts(cfg, traffic, one_chip)
    assert len(texts) == 2          # versions shared, and stacked
    cap, dim, m = cfg["hash_capacity"], cfg["embed_dim"], traffic["workers"]
    for txt in texts:
        scopes = {phases.scope_of(n)
                  for n in re.findall(r'op_name="([^"]*)"', txt)}
        assert set(phases.SCOPES) <= scopes
        assert not re.search(r"%_embedding_bag_grad_streamed[.\d]* = ", txt)
        assert phases.coverage(txt) >= 0.9

        # known ops land in their phase
        phase_of = phases.module_phases(txt)
        comps, entry = phases._computations(txt)

        def runs(ins, seen=()):
            """The ops an instruction runs, its own and those of the
            computations it calls."""
            out = {ins["op"]}
            for name in re.findall(r"\b(?:calls|to_apply)=%([\w.\-]+)",
                                   ins["rest"]):
                if name not in seen:
                    for inner in comps.get(name, ()):
                        out |= runs(inner, (*seen, name))
            return out

        def phases_of(pick):
            got = [phase_of[i["key"]] for i in comps[entry] if pick(i)]
            assert got
            return set(got)

        # the one row scatter of the step's gathered rows (embed and
        # linear side by side, and the contributor count), under its own
        # kernel name; no slot's table gradient of (D, M * cap)
        assert phases_of(lambda i: i["name"].startswith(
            "_scatter_rows_streamed")) == {"embedding"}
        assert f"f32[{dim},{m * cap}]" not in {i["key"][1]
                                               for i in comps[entry]}
        # Adam's update of the table: a fusion that takes a square root
        # and writes (cap, D)
        assert phases_of(lambda i: i["op"] == "fusion"
                         and f"f32[{cap},{dim}]" in i["key"][1]
                         and "sqrt" in runs(i)) == {"apply"}

    # DIEN's step: its interest layers (GRU, auxiliary loss, attention,
    # AUGRU) carry a scope nested in dense, which the configuration names
    # a phase of its own, and the map still covers the step; one variant
    # (sync) shows it, as the recurrences make each compile slow
    cfg = json.loads((root / "configs" / "dien-alimama.json").read_text())
    cfg = dict(cfg, hash_capacity=20000)
    traffic = json.loads((root / "traffic" / "sync_quiet.json").read_text())
    traffic = dict(traffic, workers=4, local_batch=32)
    scopes = phases.scopes_of(cfg)
    assert "interest" in scopes
    for txt in phases.compiled_texts(cfg, traffic, one_chip):
        names = re.findall(r'op_name="([^"]*)"', txt)
        nested = [n for n in names if re.search(r"\binterest/", n)]
        assert nested and all(re.search(r"\bdense\)*/interest/", n)
                              for n in nested)
        assert {phases.scope_of(n, scopes) for n in names} >= set(scopes)
        assert phases.coverage(txt, scopes) >= 0.9
        assert "interest" in phases.module_phases(txt, scopes).values()
