"""Static auditor: every rule trips on its seeded known-bad fixture
(exactly that rule, nothing else) and every shipped hot path audits
clean — so no rule is vacuous and no hot path regresses silently."""
from __future__ import annotations

import functools
import itertools
import types
from pathlib import Path

import numpy as np

import jax
import jax.numpy as jnp
import pytest
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.analysis import audit as AU
from repro.analysis import dataflow as DF
from repro.analysis import jaxpr_audit as JA
from repro.analysis import pallas_check as PC
from repro.analysis import race_lint as RL
from repro.analysis import retrace_guard as RG
from repro.analysis import rules as R
from repro.analysis.__main__ import (_parse_minimal_toml, load_baseline,
                                     unused_baseline_entries)
from repro.core.flat_sharded import ShardedFlatLayout
from repro.core.gba_shard_map import make_gba_psum_step
from repro.kernels.launch_meta import BlockMeta, LaunchMeta, ScratchMeta
from repro.optim import get_optimizer

SDS = jax.ShapeDtypeStruct
M = 2


def rules_of(findings):
    return sorted({f.rule for f in findings})


def tiny_layout(dtype=jnp.float32, m: int = M):
    params = {"emb": SDS((32,), dtype),
              "layers": {"w": SDS((16, 8), dtype)}}
    layout = ShardedFlatLayout.from_params(
        params, m, tile=8, group_by=lambda path: path[0])
    return params, layout


def fused_trace(dtype=jnp.float32, m: int = M):
    _, layout = tiny_layout(dtype, m)
    batch = {"x": SDS((m * 4,), jnp.float32)}
    return layout, AU.trace_fused_step(layout, m, AU.probe_loss, batch)


# ---------------------------------------------------------------------------
# rule registry + suppressions
# ---------------------------------------------------------------------------

def test_finding_requires_known_rule():
    with pytest.raises(KeyError):
        R.finding("GBA-NOPE-999", "s", "d")
    with pytest.raises(KeyError):
        R.parse_suppressions(["GBA-NOPE-999"])


def test_suppressions_global_and_per_site():
    f1 = R.finding("GBA-TILE-001", "a/k", "x")
    f2 = R.finding("GBA-TILE-001", "b/k", "x")
    f3 = R.finding("GBA-VMEM-002", "a/k", "x")
    sup = R.parse_suppressions(["GBA-TILE-001@a/k"])
    kept, dropped = R.apply_suppressions([f1, f2, f3], sup)
    assert kept == [f2, f3] and dropped == [f1]
    kept, dropped = R.apply_suppressions(
        [f1, f2, f3], R.parse_suppressions(["GBA-TILE-001"]))
    assert kept == [f3] and dropped == [f1, f2]


# ---------------------------------------------------------------------------
# collective census (GBA-COLL-*)
# ---------------------------------------------------------------------------

def test_fused_schedule_clean_and_census_shapes():
    layout, jx = fused_trace()
    assert JA.check_fused_psum_schedule(jx, layout, M, "t") == []
    census = JA.collective_census(jx)
    gathers = [c.in_shapes[0] for c in census if c.op == "all_gather"]
    exp, routes, token = JA.expected_fused_collectives(layout, M)
    assert gathers == exp + [token]
    assert [c.in_shapes[0] for c in census
            if c.op == "all_to_all"] == routes


def test_coll_001_trips_on_mismatched_layout():
    # audit the 2-group trace against a single-group layout: the declared
    # schedule (one gather/route per group, exact shapes) no longer matches
    _, jx = fused_trace()
    params = {"emb": SDS((32,), jnp.float32),
              "layers": {"w": SDS((16, 8), jnp.float32)}}
    other = ShardedFlatLayout.from_params(params, M, tile=8)
    fs = JA.check_fused_psum_schedule(jx, other, M, "t")
    assert rules_of(fs) == ["GBA-COLL-001"]


def test_coll_002_trips_on_vector_psum():
    mesh = AU.abstract_mesh(M)

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=(P("data"),),
                       out_specs=P(), check_vma=False)
    def bad(x):
        return lax.psum(x, "data")

    jx = jax.make_jaxpr(bad)(SDS((M * 4,), jnp.float32))
    assert rules_of(JA.check_scalar_psum_only(jx, "t")) == ["GBA-COLL-002"]


def test_coll_003_trips_on_any_collective():
    _, jx = fused_trace()
    assert rules_of(JA.check_no_collectives(jx, "t")) == ["GBA-COLL-003"]
    clean = jax.make_jaxpr(lambda x: x * 2)(SDS((4,), jnp.float32))
    assert JA.check_no_collectives(clean, "t") == []


def sync_trace():
    params, _ = tiny_layout()
    opt = get_optimizer("adagrad", 1e-3)
    step = make_gba_psum_step(AU.abstract_mesh(M), AU.probe_loss, opt, 4)
    return params, jax.make_jaxpr(step)(
        params, jax.eval_shape(opt.init, params),
        {"x": SDS((M * 4,), jnp.float32)},
        SDS((M,), jnp.int32), SDS((), jnp.int32))


def test_coll_004_sync_clean_and_trips_on_wrong_leaves():
    params, jx = sync_trace()
    leaf_shapes = [l.shape for l in jax.tree.leaves(params)]
    assert JA.check_sync_psum_schedule(jx, leaf_shapes, "t") == []
    fs = JA.check_sync_psum_schedule(jx, [(7, 7)], "t")
    assert rules_of(fs) == ["GBA-COLL-004"]
    # the fused trace is NOT a valid sync schedule (it gathers + routes)
    _, jfused = fused_trace()
    assert "GBA-COLL-004" in rules_of(
        JA.check_sync_psum_schedule(jfused, leaf_shapes, "t"))


def test_coll_005_clean_and_trips_on_f32_leak():
    """The compressed trace checks clean against its own policy; the
    UNCOMPRESSED (f32-wire) trace checked as past-warmup trips
    GBA-COLL-005 exactly — full-precision leakage after warmup is a
    finding, and the warm check accepts the same f32 trace."""
    from repro.core.compression import CompressionPolicy
    _, layout = tiny_layout()
    batch = {"x": SDS((M * 4,), jnp.float32)}
    pol = CompressionPolicy(scheme="int8", warmup_steps=1)
    jc = AU.trace_fused_step(layout, M, AU.probe_loss, batch,
                             compress=pol)
    assert JA.check_wire_dtypes(jc, layout, M, pol, "t") == []
    # known-bad: f32 routing where the policy says the wire is int8
    _, jleak = fused_trace()
    fs = JA.check_wire_dtypes(jleak, layout, M, pol, "t")
    assert rules_of(fs) == ["GBA-COLL-005"]
    # ... but the SAME f32 trace is exactly what warmup must look like
    assert JA.check_wire_dtypes(jleak, layout, M, pol, "t",
                                warm=True) == []


# ---------------------------------------------------------------------------
# dtype lints (GBA-DTYPE-*)
# ---------------------------------------------------------------------------

def test_dtype_001_budget_exact_on_probe_trace():
    layout, jx = fused_trace(jnp.bfloat16)
    budget = AU.widening_budget(layout)
    assert budget == 2 * len(layout.dtypes)     # every leaf is bf16
    assert JA.check_widening_budget(jx, budget, "t") == []
    # one sanctioned cast fewer -> the leaked upcast trips
    fs = JA.check_widening_budget(jx, budget - 1, "t")
    assert rules_of(fs) == ["GBA-DTYPE-001"]


def test_dtype_001_ignores_f32_layouts():
    layout, jx = fused_trace(jnp.float32)
    assert AU.widening_budget(layout) == 0
    assert JA.check_widening_budget(jx, 0, "t") == []


def test_dtype_002_trips_under_x64():
    with jax.enable_x64():
        jx = jax.make_jaxpr(
            lambda x: x.astype(jnp.float64) * 2.0)(SDS((8,), jnp.float32))
    assert rules_of(JA.check_no_f64(jx, "t")) == ["GBA-DTYPE-002"]
    clean = jax.make_jaxpr(lambda x: x * 2.0)(SDS((8,), jnp.float32))
    assert JA.check_no_f64(clean, "t") == []


# ---------------------------------------------------------------------------
# donation + retrace (GBA-DON-001 / GBA-RETRACE-001)
# ---------------------------------------------------------------------------

def _toy_step(state, x):
    return jax.tree.map(lambda s: s + jnp.sum(x), state), jnp.sum(x)


def test_don_001_trips_without_donate_argnums():
    state = {"p": jnp.zeros((8,)), "acc": jnp.zeros((8,))}
    x = SDS((4,), jnp.float32)
    bad = jax.jit(_toy_step).lower(state, x).args_info[0][0]
    assert rules_of(JA.check_donation(bad, "t")) == ["GBA-DON-001"]
    good = jax.jit(_toy_step, donate_argnums=0).lower(state, x)
    assert JA.check_donation(good.args_info[0][0], "t") == []


def test_retrace_001_trips_on_weak_type_alternation():
    # a python scalar traces weak-typed; alternating it with a strong
    # jnp scalar of the same shape/dtype is exactly the leak this guards
    vals = itertools.cycle([jnp.float32(1.0), 1.0])
    fs = RG.check_retrace(lambda x: x * 2, lambda: ((next(vals),), {}), "t")
    assert rules_of(fs) == ["GBA-RETRACE-001"]
    stable = RG.check_retrace(
        lambda x: x * 2, lambda: ((jnp.float32(1.0),), {}), "t")
    assert stable == []


# ---------------------------------------------------------------------------
# Pallas launch rules (GBA-TILE / GBA-VMEM / GBA-GRID)
# ---------------------------------------------------------------------------

def _fixture_meta(inputs, **kw):
    return LaunchMeta(kernel="fixture", grid=kw.pop("grid", (4,)),
                      inputs=inputs, outputs=(), **kw)


def test_tile_001_trips_on_misaligned_block():
    meta = _fixture_meta((
        BlockMeta("x", (64, 1024), jnp.float32, (8, 96),
                  lambda i: (0, i)),))
    assert rules_of(PC.check_launch(meta, "t")) == ["GBA-TILE-001"]


def test_tile_001_bf16_sublane():
    meta = _fixture_meta((
        BlockMeta("x", (64, 256), jnp.bfloat16, (8, 128),
                  lambda i: (0, 0)),))
    # 8 rows is a legal f32 sublane but NOT a legal bf16 one (min 16)
    assert rules_of(PC.check_tiles(meta, "t")) == ["GBA-TILE-001"]
    f32 = _fixture_meta((
        BlockMeta("x", (64, 256), jnp.float32, (8, 128),
                  lambda i: (0, 0)),))
    assert PC.check_tiles(f32, "t") == []


def test_tile_001_whole_axis_exempt():
    # block covers the full (padded) axis -> Mosaic pads internally, legal
    meta = _fixture_meta((
        BlockMeta("x", (4, 100), jnp.float32, (4, 100),
                  lambda i: (0, 0)),))
    assert PC.check_tiles(meta, "t") == []


def test_grid_001_trips_on_out_of_bounds_map():
    meta = _fixture_meta(
        (BlockMeta("x", (64, 1024), jnp.float32, (8, 128),
                   lambda i: (i, 8)),), grid=(8,))
    assert rules_of(PC.check_launch(meta, "t")) == ["GBA-GRID-001"]


def test_vmem_001_trips_on_declared_drift():
    meta = _fixture_meta(
        (BlockMeta("x", (64, 128), jnp.float32, (8, 128),
                   lambda i: (i, 0)),),
        declared_vmem_bytes=123, vmem_counted=("x",), grid=(8,))
    assert rules_of(PC.check_launch(meta, "t")) == ["GBA-VMEM-001"]


def test_vmem_002_trips_on_oversized_residency():
    meta = _fixture_meta((
        BlockMeta("x", (2048, 4096), jnp.float32),))   # 32MiB resident
    assert rules_of(PC.check_launch(meta, "t")) == ["GBA-VMEM-002"]


def test_vmem_counts_scratch():
    meta = _fixture_meta(
        (), scratch=(ScratchMeta("s", (2048, 4096), jnp.float32),))
    assert rules_of(PC.check_vmem(meta, "t")) == ["GBA-VMEM-002"]


# ---------------------------------------------------------------------------
# dataflow taint pass (GBA-FLOW-*)
# ---------------------------------------------------------------------------

IOTA = 4
GSTEP = 9
TOKENS = np.array([9, 8, 4, 0], dtype=np.int32)   # slots 2, 3 are stale
STALE = (GSTEP - TOKENS) > IOTA


def _flow_trace(step_fn, p_dtype=jnp.float32):
    return jax.make_jaxpr(step_fn)(
        SDS((8,), p_dtype), SDS((4, 8), jnp.float32),
        SDS((4,), jnp.int32), SDS((), jnp.int32))


def _flow_seeds(concrete=True):
    return [DF.taint(DF.PARAM), DF.taint(DF.RAW),
            DF.taint(DF.TOKEN, val=TOKENS if concrete else None),
            DF.taint(DF.STEP, val=np.int32(GSTEP) if concrete else None)]


def _decay_weight(tokens, step):
    return ((step - tokens) <= IOTA).astype(jnp.float32)


def test_flow_001_trips_on_decay_bypass():
    def bad(p, g, tokens, step):
        return p - 0.01 * jnp.mean(g, axis=0)       # no Eq. (1) weighting

    outs, _ = DF.analyze(_flow_trace(bad), _flow_seeds(), site="t")
    fs = DF.check_no_raw(outs, ["p"], lambda _: True, "t")
    assert rules_of(fs) == ["GBA-FLOW-001"]

    def good(p, g, tokens, step):
        w = _decay_weight(tokens, step)
        return p - 0.01 * jnp.sum(g * w[:, None], axis=0)

    outs, ctx = DF.analyze(_flow_trace(good), _flow_seeds(), site="t")
    assert DF.check_no_raw(outs, ["p"], lambda _: True, "t") == []
    # the concretely-evaluated mask proves the tombstone weights too
    assert DF.check_tombstone(ctx, STALE, "t") == []


def test_flow_002_trips_on_soft_tombstone_weight():
    def soft(p, g, tokens, step):
        # decays stale slots to 0.01 instead of dropping them: close
        # enough to fool a numeric diff, rejected by the exact-zero rule
        w = jnp.where((step - tokens) <= IOTA, 0.25, 0.01)
        return p - jnp.sum(g * w[:, None], axis=0)

    _, ctx = DF.analyze(_flow_trace(soft), _flow_seeds(), site="t")
    fs = DF.check_tombstone(ctx, STALE, "t")
    assert rules_of(fs) == ["GBA-FLOW-002"]
    assert "EXACTLY" in fs[0].detail
    # without concrete token seeds the mask is unprovable -> also a finding
    _, ctx = DF.analyze(_flow_trace(soft), _flow_seeds(concrete=False),
                        site="t")
    assert rules_of(DF.check_tombstone(ctx, STALE, "t")) == ["GBA-FLOW-002"]


def test_flow_003_trips_when_residual_reaches_apply():
    def bad(p, g, r, tokens, step):
        w = _decay_weight(tokens, step)
        upd = jnp.sum((g + r) * w[:, None], axis=0)   # residual in update
        return p - 0.01 * upd, r

    def good(p, g, r, tokens, step):
        w = _decay_weight(tokens, step)
        upd = jnp.sum(g * w[:, None], axis=0)
        return p - 0.01 * upd, r + upd    # residual -> next quantize only

    args = (SDS((8,), jnp.float32), SDS((4, 8), jnp.float32),
            SDS((4, 8), jnp.float32), SDS((4,), jnp.int32),
            SDS((), jnp.int32))
    seeds = [DF.taint(DF.PARAM), DF.taint(DF.RAW), DF.taint(DF.RESIDUAL),
             DF.taint(DF.TOKEN, val=TOKENS),
             DF.taint(DF.STEP, val=np.int32(GSTEP))]
    outs, _ = DF.analyze(jax.make_jaxpr(bad)(*args), seeds, site="t")
    fs = DF.check_no_residual(outs[:1], ["p"], lambda _: True, "t")
    assert rules_of(fs) == ["GBA-FLOW-003"]
    outs, _ = DF.analyze(jax.make_jaxpr(good)(*args), seeds, site="t")
    assert DF.check_no_residual(outs[:1], ["p"], lambda _: True, "t") == []


def test_flow_004_trips_on_narrow_update_chain():
    bf = jnp.bfloat16

    def bad_arith(p, g, tokens, step):
        w = _decay_weight(tokens, step)
        upd = jnp.sum(g * w[:, None], axis=0)
        return p - (0.01 * upd).astype(bf)            # bf16 subtract

    def bad_nonterminal(p, g, tokens, step):
        w = _decay_weight(tokens, step)
        upd = jnp.sum(g * w[:, None], axis=0)
        return (p.astype(jnp.float32) - 0.01 * upd).astype(bf) * 2

    def good(p, g, tokens, step):
        w = _decay_weight(tokens, step)
        upd = jnp.sum(g * w[:, None], axis=0)
        return (p.astype(jnp.float32) - 0.01 * upd).astype(bf)

    for fn in (bad_arith, bad_nonterminal):
        _, ctx = DF.analyze(_flow_trace(fn, bf), _flow_seeds(),
                            site="t", f32_chain=True)
        assert rules_of(ctx.findings) == ["GBA-FLOW-004"], fn.__name__
    _, ctx = DF.analyze(_flow_trace(good, bf), _flow_seeds(),
                        site="t", f32_chain=True)
    assert ctx.findings == []


def test_flow_005_trips_on_constant_divisor():
    def bad(ids, g, tokens, step):
        w = _decay_weight(tokens, step)
        return jnp.sum(g * w[:, None], axis=0) / 4.0   # mean over M, not
        #                                                over contributors

    def missing(ids, g, tokens, step):
        w = _decay_weight(tokens, step)
        return jnp.sum(g * w[:, None], axis=0)         # no mean at all

    def good(ids, g, tokens, step):
        valid = (ids >= 0).astype(jnp.float32)
        w = _decay_weight(tokens, step) * valid
        num = jnp.sum(g * w[:, None], axis=0)
        return num / jnp.maximum(jnp.sum(w), 1.0)

    args = (SDS((4,), jnp.int32), SDS((4, 8), jnp.float32),
            SDS((4,), jnp.int32), SDS((), jnp.int32))
    seeds = [DF.taint(DF.IDS), DF.taint(DF.RAW), DF.taint(DF.TOKEN),
             DF.taint(DF.STEP)]
    for fn in (bad, missing):
        _, ctx = DF.analyze(jax.make_jaxpr(fn)(*args), seeds, site="t")
        assert rules_of(DF.check_divisor(ctx, "t")) == ["GBA-FLOW-005"], \
            fn.__name__
    _, ctx = DF.analyze(jax.make_jaxpr(good)(*args), seeds, site="t")
    assert DF.check_divisor(ctx, "t") == []


def test_flow_seed_arity_mismatch_raises():
    with pytest.raises(ValueError):
        DF.analyze(_flow_trace(lambda p, g, t, s: p), _flow_seeds()[:2],
                   site="t")


# ---------------------------------------------------------------------------
# serving-thread race lint (GBA-RACE-*)
# ---------------------------------------------------------------------------

RACE_BAD1 = '''
import threading


class Counter:
    def __init__(self):
        self._lock = threading.Lock()
        self.total = 0

    def locked_add(self, n):
        with self._lock:
            self.total += n

    def unlocked_add(self, n):
        self.total += n
'''

RACE_BAD2 = '''
import threading


class Versioned:
    def __init__(self):
        self._lock = threading.Lock()
        self.version = 0
        self.step = 0

    def bump(self):
        with self._lock:
            self.version = self.version + 1
            self.step = self.step + 2

    def view(self):
        return (self.version, self.step)
'''

RACE_BAD3 = '''
import threading


class Publisher:
    def __init__(self):
        self._lock = threading.Lock()
        self._listeners = []
        self.value = 0

    def subscribe(self, fn):
        with self._lock:
            self._listeners.append(fn)

    def _notify(self, v):
        for fn in list(self._listeners):
            fn(v)

    def publish(self, v):
        with self._lock:
            self.value = v
            self._notify(v)
'''

RACE_GOOD_SNAPSHOT = '''
import threading


class Source:
    def __init__(self):
        self._lock = threading.Lock()
        self._snap = (0, 0)

    def update(self, v, s):
        self._snap = (v, s)     # plain rebind of an immutable snapshot

    def view(self):
        snap = self._snap       # ONE unlocked read: consistent by design
        return snap
'''


def test_race_001_trips_on_unlocked_mutation():
    fs, _ = RL.lint_sources({"bad1": RACE_BAD1})
    assert rules_of(fs) == ["GBA-RACE-001"]
    assert "unlocked_add" in fs[0].site


def test_race_002_trips_on_torn_pair():
    fs, _ = RL.lint_sources({"bad2": RACE_BAD2})
    assert rules_of(fs) == ["GBA-RACE-002"]
    assert "view" in fs[0].site and "version" in fs[0].detail


def test_race_003_trips_on_callback_under_lock():
    fs, _ = RL.lint_sources({"bad3": RACE_BAD3})
    assert rules_of(fs) == ["GBA-RACE-003"]
    assert "publish" in fs[0].site


def test_race_snapshot_swap_is_blessed():
    fs, stats = RL.lint_sources({"good": RACE_GOOD_SNAPSHOT})
    assert fs == []
    assert stats["race_classes"] == 1


# ---------------------------------------------------------------------------
# audit baseline file (--baseline .gba-audit.toml)
# ---------------------------------------------------------------------------

def test_baseline_parse_roundtrip(tmp_path):
    text = "\n".join([
        "# comment",
        "[[suppress]]",
        'rule = "GBA-TILE-001"',
        'site = "a/k"   # trailing comment',
        'reason = "deliberate"',
        "[[suppress]]",
        'rule = "GBA-VMEM-002"',
        'reason = "fleet-wide"',
    ])
    p = tmp_path / "b.toml"
    p.write_text(text)
    assert load_baseline(p) == [("GBA-TILE-001", "a/k", "deliberate"),
                                ("GBA-VMEM-002", None, "fleet-wide")]
    # the 3.10 fallback parser agrees with tomllib on the format
    assert _parse_minimal_toml(text)["suppress"][0]["rule"] == "GBA-TILE-001"
    with pytest.raises(ValueError):
        _parse_minimal_toml("rule = unquoted")


def test_baseline_requires_rule_reason_and_file(tmp_path):
    p = tmp_path / "b.toml"
    p.write_text('[[suppress]]\nrule = "GBA-TILE-001"\n')
    with pytest.raises(SystemExit):
        load_baseline(p)                       # reason is mandatory
    p.write_text('[[suppress]]\nreason = "no rule"\n')
    with pytest.raises(SystemExit):
        load_baseline(p)                       # rule is mandatory
    with pytest.raises(SystemExit):
        load_baseline(tmp_path / "missing.toml")


def test_baseline_unused_entries_and_checked_in_file():
    rep = types.SimpleNamespace(
        suppressed=[R.finding("GBA-TILE-001", "a/k", "x")])
    entries = [("GBA-TILE-001", "a/k", "r"), ("GBA-TILE-001", "b/k", "r"),
               ("GBA-VMEM-002", None, "r")]
    assert unused_baseline_entries(entries, [rep]) == entries[1:]
    # the checked-in baseline parses and is (deliberately) empty
    repo_baseline = Path(__file__).resolve().parent.parent / ".gba-audit.toml"
    assert load_baseline(repo_baseline) == []


# ---------------------------------------------------------------------------
# shipped hot paths audit clean
# ---------------------------------------------------------------------------

def test_shipped_kernels_audit_clean():
    rep = AU.audit_kernels()
    assert rep.ok, [str(f) for f in rep.findings]
    for meta in AU.kernel_metas():
        assert meta.total_vmem_bytes() <= PC.VMEM_BUDGET_BYTES


def test_shipped_dataflow_audit_clean():
    rep = AU.audit_dataflow()
    assert rep.ok, [str(f) for f in rep.findings]


def test_shipped_serving_race_free():
    rep = AU.audit_serving()
    assert rep.ok, [str(f) for f in rep.findings]
    # the lint actually saw the serving thread machinery, not an empty set
    assert rep.stats["race_entries"] >= 1
    assert rep.stats["race_guarded_attrs"] >= 1
    assert rep.stats["race_locked_regions"] >= 1


def test_granite_full_matrix_clean():
    rep = AU.audit_arch("granite-8b")
    assert rep.ok, [str(f) for f in rep.findings]
    # census columns the bench gates on exactly
    assert rep.stats["all_gather"] == rep.stats["num_groups"] + 1
    assert rep.stats["all_to_all"] == rep.stats["num_groups"]
    assert rep.stats["psum"] == 1
