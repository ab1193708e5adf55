"""DMA-streamed embedding kernels: parity at V >> BLOCK_V, block-boundary
edge cases, bit-exactness vs the PR-1 VMEM-resident backward, the
differentiable table-level wrapper, and the interpret-mode resolution."""
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.embeddings import table as embeddings
from repro.kernels import ref
from repro.kernels import runtime
from repro.kernels.embedding_bag import (BLOCK_D, BLOCK_V, CHUNK_E,
                                         embedding_bag, embedding_bag_grad,
                                         embedding_bag_grad_resident,
                                         lane_dense, scatter_rows,
                                         stream_vmem_bytes)


# ---------------------------------------------------------------------------
# forward parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,f,v,d", [
    (10, 5, 50, 8),              # V smaller than one block
    (100, 26, 1000, 16),
    (33, 3, 101, 7),             # nothing block-multiple
    (64, 26, 100_003, 16),       # V >> BLOCK_V, ~200 streamed tiles
])
def test_streamed_fwd_parity(b, f, v, d):
    key = jax.random.PRNGKey(b)
    ids = jax.random.randint(key, (b, f), 0, v)
    table = jax.random.normal(key, (v, d), jnp.float32)
    out = embedding_bag(ids, table)
    exp = ref.embedding_bag_ref(ids, table)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=1e-5, atol=1e-5)


def test_streamed_fwd_parity_1m_vocab():
    """Production-scale vocabulary: ~2000 vocab blocks, none VMEM-resident.
    The footprint bound of the acceptance criterion is checked explicitly."""
    b, f, v, d = 16, 8, 1_000_000, 16
    key = jax.random.PRNGKey(0)
    ids = jax.random.randint(key, (b, f), 0, v)
    table = jax.random.normal(key, (v, d), jnp.float32)
    out = embedding_bag(ids, table)
    exp = ref.embedding_bag_ref(ids, table)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=1e-4, atol=1e-4)
    vm = stream_vmem_bytes(d)
    bd = vm["block_d"]
    bound = 2 * (BLOCK_V * bd + CHUNK_E * bd) * 4
    assert vm["fwd"] <= bound and vm["bwd"] <= bound
    assert vm["fwd"] < v * d * 4 / 100     # table itself is >100x larger


def test_streamed_fwd_wide_d_tiling():
    """D > BLOCK_D: the output grid's D axis streams per-tile columns."""
    b, f, v, d = 24, 4, 700, 2 * BLOCK_D + 40
    key = jax.random.PRNGKey(3)
    ids = jax.random.randint(key, (b, f), 0, v)
    table = jax.random.normal(key, (v, d), jnp.float32)
    out = embedding_bag(ids, table)
    exp = ref.embedding_bag_ref(ids, table)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=1e-5, atol=1e-5)


def test_streamed_fwd_bf16_table():
    b, f, v, d = 40, 6, 3000, 16
    key = jax.random.PRNGKey(9)
    ids = jax.random.randint(key, (b, f), 0, v)
    table = jax.random.normal(key, (v, d), jnp.bfloat16)
    out = embedding_bag(ids, table)
    exp = ref.embedding_bag_ref(ids, table)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32),
                               rtol=2e-2, atol=2e-2)


def test_streamed_fwd_all_ids_one_block():
    """Every id lands in one vocab block: a single tile is streamed and
    revisited across all entry chunks."""
    b, f, v = 64, 8, 9000
    key = jax.random.PRNGKey(4)
    ids = jax.random.randint(key, (b, f), 100, 500)    # one BLOCK_V block
    table = jax.random.normal(key, (v, 16), jnp.float32)
    out = embedding_bag(ids, table)
    exp = ref.embedding_bag_ref(ids, table)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=1e-5, atol=1e-5)


def test_streamed_fwd_sentinel_padding():
    """Out-of-range ids (the padded-batch sentinel) contribute nothing —
    in particular they no longer gather row 0."""
    v, d = 64, 8
    table = jax.random.normal(jax.random.PRNGKey(1), (v, d), jnp.float32)
    ids = jnp.array([[3, -1], [5, v], [7, 2 * v]], jnp.int32)
    out = embedding_bag(ids, table)
    exp = jnp.stack([table[3], table[5], table[7]])
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=1e-5, atol=1e-5)
    # an all-sentinel batch issues zero gathers and returns zeros
    out0 = embedding_bag(jnp.full((4, 3), v, jnp.int32), table)
    assert float(jnp.abs(out0).max()) == 0.0


def test_streamed_fwd_custom_knobs():
    b, f, v, d = 48, 5, 5000, 24
    key = jax.random.PRNGKey(6)
    ids = jax.random.randint(key, (b, f), 0, v)
    table = jax.random.normal(key, (v, d), jnp.float32)
    out = embedding_bag(ids, table, block_v=128, block_d=8, chunk_e=64)
    exp = ref.embedding_bag_ref(ids, table)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=1e-5, atol=1e-5)


def test_lane_dense_table_matches_narrow():
    """A table padded once to whole lane tiles pools bit-identically to
    the narrow table the kernel pads per call; the extra columns are 0."""
    b, f, v, d = 40, 6, 3000, 16
    key = jax.random.PRNGKey(11)
    ids = jax.random.randint(key, (b, f), 0, v)
    table = jax.random.normal(key, (v, d), jnp.float32)
    wide = embedding_bag(ids, lane_dense(table))
    assert wide.shape == (b, 128)
    assert np.array_equal(np.asarray(wide[:, :d]),
                          np.asarray(embedding_bag(ids, table)))
    assert float(jnp.abs(wide[:, d:]).max()) == 0.0


# ---------------------------------------------------------------------------
# backward parity + resident-kernel regression
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,f,v,d", [(10, 5, 50, 8), (64, 26, 500, 16),
                                     (33, 3, 613, 7)])
def test_streamed_grad_bit_identical_to_resident(b, f, v, d):
    """The streamed backward must reproduce the PR-1 VMEM-resident kernel
    bit-for-bit on the old (VMEM-sized) configs: same chunking, same
    one-hot matmul accumulation order, only the row transport differs."""
    key = jax.random.PRNGKey(b + 7)
    ids = jax.random.randint(key, (b, f), 0, v)
    gout = jax.random.normal(key, (b, d), jnp.float32)
    gt, cnt = embedding_bag_grad(ids, gout, v)
    gtr, cntr = embedding_bag_grad_resident(ids, gout, v)
    assert np.array_equal(np.asarray(gt), np.asarray(gtr))
    assert np.array_equal(np.asarray(cnt), np.asarray(cntr))


@pytest.mark.parametrize("n,v,d", [(300, 50, 11), (4096, 2000, 18),
                                   (1000, 100_003, 1), (257, 700, 260)])
def test_scatter_rows_sums_each_ids_rows(n, v, d):
    """One row an id: each table row is the sum of the rows sent to it,
    and out-of-range ids are dropped."""
    key = jax.random.PRNGKey(n)
    ids = jax.random.randint(key, (n,), -3, v + 3)
    rows = jax.random.normal(jax.random.fold_in(key, 1), (n, d))
    got = scatter_rows(ids, rows, v)
    valid = (ids >= 0) & (ids < v)
    want = jnp.zeros((v, d)).at[jnp.where(valid, ids, v)].add(rows,
                                                              mode="drop")
    assert got.shape == (v, d)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_scatter_rows_moves_rows_exactly():
    """Each id once: the table holds every float32 row bit for bit (three
    bfloat16 parts, each product with the one-hot exact)."""
    v, d = 3000, 18
    key = jax.random.PRNGKey(4)
    ids = jax.random.permutation(key, v)[:1000]
    rows = jax.random.normal(jax.random.fold_in(key, 1), (1000, d)) * 1e3
    got = scatter_rows(ids, rows, v)
    assert np.array_equal(np.asarray(got[ids]), np.asarray(rows))


def _distinct_pair_sums(ids: np.ndarray, counts: np.ndarray,
                        v: int) -> np.ndarray:
    """Each in-range id's sum of ``counts`` over its distinct (leading
    index, id) pairs, one entry a pair."""
    lead = ids.reshape(ids.shape[0], -1)
    cnt = counts.reshape(ids.shape[0], -1)
    out = np.zeros(v, np.float64)
    for slot_ids, slot_cnt in zip(lead, cnt):
        pairs = {}
        for i, c in zip(slot_ids.tolist(), slot_cnt.tolist()):
            if 0 <= i < v:
                pairs.setdefault(i, c)
        for i, c in pairs.items():
            out[i] += c
    return out


def _pair_counts_case(case: str):
    """``(ids, counts, v, d)``: ids led by their slot, counts one value a
    (slot, id) pair."""
    rng = np.random.default_rng(zlib.crc32(case.encode()))
    values = np.array([1.0])
    if case == "repeat_in_slot":     # few ids, so each repeats in a slot
        v, d, ids = 300, 11, rng.integers(0, 20, (3, 40))
    elif case == "across_slots":     # ids 5 and 7 in every slot
        v, d, ids = 300, 18, rng.integers(0, 300, (8, 10))
        ids[:, :2] = (5, 7)
    elif case == "zero_fractional":
        v, d, ids = 300, 11, rng.integers(0, 50, (6, 30))
        values = np.array([0.0, 0.25, 0.5, 1.0, 2.75])
    elif case == "block_boundary":   # ids on both sides of two boundaries
        v, d = 3 * BLOCK_V, 18
        ids = rng.choice([BLOCK_V - 2, BLOCK_V - 1, BLOCK_V, BLOCK_V + 1,
                          2 * BLOCK_V - 1, 2 * BLOCK_V], (4, 64))
        values = np.array([0.0, 0.5, 1.0])
    elif case == "out_of_range":     # dropped, as their rows are
        v, d, ids = 200, 11, rng.integers(-3, 203, (5, 50))
    else:                            # (M, B, n_ids), as a step holds them
        v, d, ids = 700, 18, rng.integers(0, 100, (4, 8, 26))
        values = np.array([0.0, 1.0])
    pair_value = rng.choice(values, (ids.shape[0], v + 6))
    slot = np.arange(ids.shape[0]).reshape((-1,) + (1,) * (ids.ndim - 1))
    counts = pair_value[slot, ids + 3]
    return ids.astype(np.int32), counts.astype(np.float32), v, d


@pytest.mark.parametrize("case", ["repeat_in_slot", "across_slots",
                                  "zero_fractional", "block_boundary",
                                  "out_of_range", "step_shape"])
def test_scatter_rows_counts_each_distinct_pair_once(case):
    """With ``counts``, each id's second output is the exact sum of
    ``counts`` over the distinct (leading index, id) pairs, one entry a
    pair and out-of-range ids dropped; the table is bit for bit the one
    scattered without ``counts``."""
    ids, counts, v, d = _pair_counts_case(case)
    rows = jax.random.normal(jax.random.PRNGKey(v), ids.shape + (d,))
    table, per_id = scatter_rows(ids, rows, v, counts)
    assert per_id.shape == (v,) and per_id.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(per_id, np.float64),
                                  _distinct_pair_sums(ids, counts, v))
    assert np.array_equal(np.asarray(table),
                          np.asarray(scatter_rows(ids, rows, v)))


def test_streamed_grad_parity_1m_vocab():
    b, f, v, d = 16, 8, 1_000_000, 16
    key = jax.random.PRNGKey(2)
    ids = jax.random.randint(key, (b, f), 0, v)
    gout = jax.random.normal(key, (b, d), jnp.float32)
    gt, cnt = embedding_bag_grad(ids, gout, v)
    gt2, cnt2 = ref.embedding_bag_grad_ref(ids, gout, v)
    np.testing.assert_allclose(np.asarray(gt), np.asarray(gt2),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(cnt), np.asarray(cnt2))


def test_streamed_grad_wide_d_tiling():
    b, f, v, d = 12, 3, 300, 2 * BLOCK_D + 4
    key = jax.random.PRNGKey(8)
    ids = jax.random.randint(key, (b, f), 0, v)
    gout = jax.random.normal(key, (b, d), jnp.float32)
    gt, cnt = embedding_bag_grad(ids, gout, v)
    gt2, cnt2 = ref.embedding_bag_grad_ref(ids, gout, v)
    np.testing.assert_allclose(np.asarray(gt), np.asarray(gt2),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(cnt), np.asarray(cnt2))


def test_streamed_grad_bf16_rows_custom_chunks():
    b, f, v, d = 24, 6, 300, 16
    key = jax.random.PRNGKey(5)
    ids = jax.random.randint(key, (b, f), 0, v)
    gout = jax.random.normal(key, (b, d), jnp.bfloat16)
    gt, cnt = embedding_bag_grad(ids, gout, v, block_v=64, chunk_e=32)
    gt2, cnt2 = ref.embedding_bag_grad_ref(ids, gout, v)
    np.testing.assert_allclose(np.asarray(gt, np.float32),
                               np.asarray(gt2, np.float32),
                               rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(np.asarray(cnt), np.asarray(cnt2))


# ---------------------------------------------------------------------------
# table-level differentiable wrapper + presence counts
# ---------------------------------------------------------------------------

def test_pooled_lookup_vjp_matches_autodiff():
    """pooled_lookup's custom VJP (streamed backward) == jax.grad of the
    pure-jnp sum-pool."""
    b, f, v, d = 20, 4, 600, 8
    key = jax.random.PRNGKey(11)
    ids = jax.random.randint(key, (b, f), 0, v)
    tbl = embeddings.init_table(key, v, d)
    target = jax.random.normal(key, (b, d), jnp.float32)

    def loss_kernel(t):
        out = embeddings.pooled_lookup(
            embeddings.EmbeddingTable(t, tbl.last_update), ids)
        return jnp.sum((out - target) ** 2)

    def loss_ref(t):
        return jnp.sum((ref.embedding_bag_ref(ids, t) - target) ** 2)

    g_kernel = jax.grad(loss_kernel)(tbl.table)
    g_ref = jax.grad(loss_ref)(tbl.table)
    np.testing.assert_allclose(np.asarray(g_kernel), np.asarray(g_ref),
                               rtol=1e-4, atol=1e-4)


def test_pooled_lookup_stream_config_knobs():
    b, f, v, d = 16, 3, 400, 8
    key = jax.random.PRNGKey(12)
    ids = jax.random.randint(key, (b, f), 0, v)
    tbl = embeddings.init_table(key, v, d)
    s = embeddings.StreamConfig(block_v=64, block_d=8, chunk_e=32)
    out = embeddings.pooled_lookup(tbl, ids, stream=s)
    exp = ref.embedding_bag_ref(ids, tbl.table)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=1e-5, atol=1e-5)


def test_presence_counts_matches_scatter():
    cap = 1500
    ids = jax.random.randint(jax.random.PRNGKey(13), (7, 11), 0, cap)
    got = embeddings.presence_counts(ids, cap)
    exp = jnp.zeros((cap,), jnp.float32).at[ids.reshape(-1)].add(1.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp))


# ---------------------------------------------------------------------------
# interpret-mode resolution (kernels/runtime)
# ---------------------------------------------------------------------------

def test_runtime_interpret_resolution(monkeypatch):
    # env var wins over the platform probe
    monkeypatch.setenv("REPRO_INTERPRET", "0")
    assert runtime.default_interpret() is False
    monkeypatch.setenv("REPRO_INTERPRET", "1")
    assert runtime.default_interpret() is True
    monkeypatch.delenv("REPRO_INTERPRET")
    # this container has no TPU -> interpret
    assert runtime.default_interpret() is True
    # set_interpret overrides, None restores auto-resolution
    runtime.set_interpret(False)
    try:
        assert runtime.resolve(None) is False
        assert runtime.resolve(True) is True     # per-call override wins
    finally:
        runtime.set_interpret(None)
    assert runtime.resolve(None) is True
    assert runtime.resolve(False) is False
