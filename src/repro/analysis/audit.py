"""The audit matrix: every registered arch x every hot-path rule.

For each arch (reduced variant — same code paths, tractable trace sizes)
the auditor traces, never executes:

a. the layer-grouped **fused psum step** (``make_gba_fused_psum_step``)
   under a 4-worker :class:`jax.sharding.AbstractMesh` with the real LM
   loss -> GBA-COLL-001/002 (collective census vs ``group_table``) and
   GBA-DTYPE-002;
b. the same step with a **probe loss** whose sanctioned widening-convert
   count is exactly derivable (one forward ``astype(f32)`` + one
   ``ravel_group`` grad cast per non-f32 leaf) -> GBA-DTYPE-001.  The
   real LM loss has legitimate mixed-precision upcasts, so the upcast
   budget is only checkable on the probe;
c. the **sync psum step** (``make_gba_psum_step``) -> GBA-COLL-004;
d. the single-host **fused train step** lowered with the canonical
   ``donate_argnums=0`` -> GBA-DON-001, and traced twice with fresh
   same-shaped args -> GBA-RETRACE-001;
e. the **decode step** -> GBA-COLL-003, GBA-DTYPE-002, GBA-RETRACE-001;
f. the arch's ``gba_apply`` launch meta at its real sharded flat layout
   -> GBA-TILE-001 / GBA-VMEM-001/002 / GBA-GRID-001.

:func:`audit_kernels` covers the arch-independent kernels (streamed
embedding fwd/bwd, fused Adagrad, aggregate, flash decode) at their
bench shapes with the same Pallas rules.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import jax
import jax.numpy as jnp

from repro.analysis import dataflow as DFL
from repro.analysis import jaxpr_audit as JA
from repro.analysis import pallas_check as PC
from repro.analysis import race_lint as RL
from repro.analysis import retrace_guard as RG
from repro.analysis.rules import Finding
from repro.configs import ARCH_IDS, get_config
from repro.configs.base import GBAConfig, InputShape
from repro.core.flat_sharded import ShardedFlatLayout
from repro.core.gba_shard_map import (make_gba_fused_psum_step,
                                      make_gba_psum_step)
from repro.launch.programs import (_loss_from_batch,
                                   init_fused_train_state,
                                   make_fused_train_step)
from repro.launch.steps import (_memory_len, abstract_cache,
                                abstract_params, make_decode_step,
                                model_inputs)
from repro.models import transformer as T
from repro.optim import get_optimizer

SDS = jax.ShapeDtypeStruct

AUDIT_M = 4            # workers / PS shards in the audited abstract mesh
AUDIT_SEQ = 16         # trace-only seq len (shapes don't change collectives)
AUDIT_IOTA = 4
AUDIT_LR = 1e-3


def abstract_mesh(m: int = AUDIT_M, axis: str = "data"):
    """Devices-free mesh: lets make_jaxpr trace shard_map'd steps at any
    worker count on a 1-CPU container."""
    from jax.sharding import AbstractMesh
    return AbstractMesh((m,), (axis,))


def probe_loss(params, batch):
    """Loss with an exactly countable upcast budget: per non-f32 leaf,
    one widening ``astype`` here (forward) + one in ``ravel_group``
    (gradient) and nothing else."""
    sq = sum(jnp.sum(l.astype(jnp.float32) ** 2)
             for l in jax.tree.leaves(params))
    return jnp.mean(batch["x"]) * sq


def widening_budget(layout: ShardedFlatLayout) -> int:
    """Sanctioned widening-convert count of a probe-loss fused-step trace."""
    return 2 * sum(1 for dt in layout.dtypes
                   if jnp.dtype(dt) != jnp.float32)


def arch_layout(cfg, m: int = AUDIT_M) -> ShardedFlatLayout:
    """The arch's real layer-grouped flat layout at ``m`` PS shards,
    built from abstract params (no allocation)."""
    return ShardedFlatLayout.from_params(
        abstract_params(cfg), m, group_by=T.param_group_key)


def trace_fused_step(layout: ShardedFlatLayout, m: int, loss_fn,
                     batch, *, axis: str = "data", compress=None,
                     warm: bool = False):
    """Closed jaxpr of the layer-grouped fused psum step — the artifact
    every GBA-COLL/DTYPE rule (and the bench census columns) reads.
    With a lossy ``compress`` policy the step carries the per-worker
    wire state (residual/momentum), traced as abstract args."""
    step = make_gba_fused_psum_step(
        abstract_mesh(m, axis), loss_fn, layout, iota=AUDIT_IOTA,
        lr=AUDIT_LR, axis=axis, compress=compress, warm=warm)
    flat = SDS((layout.padded_total,), jnp.float32)
    if compress is None or not compress.stateful:
        return jax.make_jaxpr(step)(
            flat, flat, batch, SDS((m,), jnp.int32), SDS((), jnp.int32))
    wire = {name: SDS(shape, jnp.float32) for name, shape in
            layout.wire_state_shapes(m, compress.scheme).items()}
    return jax.make_jaxpr(step)(
        flat, flat, batch, SDS((m,), jnp.int32), SDS((), jnp.int32), wire)


@dataclass
class AuditReport:
    """One audited site group (an arch, or the global kernel set)."""

    name: str
    findings: list[Finding] = field(default_factory=list)
    suppressed: list[Finding] = field(default_factory=list)
    stats: dict = field(default_factory=dict)

    @property
    def ok(self) -> bool:
        return not self.findings


def audit_arch(arch: str, *, m: int = AUDIT_M,
               reduced: bool = True) -> AuditReport:
    """Run the full rule matrix over one registered arch."""
    cfg = get_config(arch)
    if reduced:
        cfg = cfg.reduced()
    rep = AuditReport(arch)
    pshapes = abstract_params(cfg)
    layout = arch_layout(cfg, m)
    def lm_loss(params, batch):
        return _loss_from_batch(params, cfg, batch)

    # a. fused psum step, real LM loss: collective schedule + f64 ban
    site = f"{arch}/fused_psum"
    batch = model_inputs(cfg, InputShape("audit", AUDIT_SEQ, m, "train"))
    jx = trace_fused_step(layout, m, lm_loss, batch)
    rep.findings += JA.check_fused_psum_schedule(jx, layout, m, site)
    rep.findings += JA.check_no_f64(jx, site)
    rep.findings += DFL.flow_fused_step(jx, batch, site=site)
    counts = JA.census_counts(JA.collective_census(jx))
    rep.stats.update(
        all_gather=counts.get("all_gather", 0),
        all_to_all=counts.get("all_to_all", 0),
        psum=counts.get("psum", 0),
        num_groups=layout.num_groups,
        shard_size=layout.shard_size,
        peak_gather_bytes=layout.peak_gather_bytes)

    # b. probe-loss trace: exact widening-convert budget
    probe_batch = {"x": SDS((m * 8,), jnp.float32)}
    jp = trace_fused_step(layout, m, probe_loss, probe_batch)
    rep.findings += JA.check_widening_budget(
        jp, widening_budget(layout), f"{arch}/fused_psum/probe")

    # g. compressed-wire traces (probe loss — COLL-005 only reads the
    # collective census): each lossy scheme's past-warmup jaxpr must
    # carry exactly the declared wire dtypes (no f32 leakage), psum
    # scalars only; the warmup-phase jaxpr must be the PR-5 f32 schedule
    from repro.core.compression import CompressionPolicy
    for scheme in ("int8", "onebit"):
        pol = CompressionPolicy(scheme=scheme, warmup_steps=1)
        site = f"{arch}/fused_psum/{scheme}"
        jc = trace_fused_step(layout, m, probe_loss, probe_batch,
                              compress=pol)
        rep.findings += JA.check_wire_dtypes(jc, layout, m, pol, site)
        rep.findings += JA.check_scalar_psum_only(jc, site)
        rep.findings += JA.check_no_f64(jc, site)
        wire = {name: SDS(shape, jnp.float32) for name, shape in
                layout.wire_state_shapes(m, scheme).items()}
        rep.findings += DFL.flow_fused_step(jc, probe_batch, site=site,
                                            wire=wire)
        if scheme == "int8":
            ccounts = JA.census_counts(JA.collective_census(jc))
            rep.stats.update(
                wire_dtype=pol.wire_dtype(),
                wire_bytes=pol.wire_bytes(layout),
                compression_ratio=round(pol.compression_ratio(layout), 4),
                compressed_all_to_all=ccounts.get("all_to_all", 0))
            jw = trace_fused_step(layout, m, probe_loss, probe_batch,
                                  compress=pol, warm=True)
            wsite = f"{arch}/fused_psum/warmup"
            rep.findings += JA.check_wire_dtypes(jw, layout, m, pol,
                                                 wsite, warm=True)
            rep.findings += JA.check_fused_psum_schedule(jw, layout, m,
                                                         wsite)

    # c. sync psum step: per-leaf grads + scalar loss, nothing else
    opt = get_optimizer("adagrad", AUDIT_LR)
    sync = make_gba_psum_step(abstract_mesh(m), probe_loss, opt, AUDIT_IOTA)
    jsync = jax.make_jaxpr(sync)(
        pshapes, jax.eval_shape(opt.init, pshapes), probe_batch,
        SDS((m,), jnp.int32), SDS((), jnp.int32))
    rep.findings += JA.check_sync_psum_schedule(
        jsync, [l.shape for l in jax.tree.leaves(pshapes)],
        f"{arch}/sync_psum")
    rep.findings += DFL.flow_sync_step(
        jsync, pshapes, jax.eval_shape(opt.init, pshapes),
        site=f"{arch}/sync_psum")

    # d. fused train step: donation + retrace stability + the FLOW
    # taint pass (raw-grad sanitization, exact-zero tombstones, f32
    # master chain) — one .trace() feeds both the lowering and the
    # dataflow jaxpr
    site = f"{arch}/fused_train_step"
    params = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), pshapes)
    gba = GBAConfig(local_batch=2, buffer_size=m,
                    staleness_tolerance=AUDIT_IOTA)
    flat_layout, state = init_fused_train_state(params, gba)
    step = make_fused_train_step(cfg, gba, flat_layout)
    tbatch = model_inputs(cfg, InputShape("audit", AUDIT_SEQ, 2, "train"))
    tok = SDS((), jnp.int32)
    traced = jax.jit(step, donate_argnums=0).trace(state, tbatch, tok)
    # args_info is ((args...), kwargs); the state is positional arg 0
    rep.findings += JA.check_donation(traced.lower().args_info[0][0], site)
    rep.findings += DFL.flow_fused_train_step(
        traced.jaxpr, state, site=site, m=m, iota=AUDIT_IOTA)
    state_sds = jax.tree.map(lambda x: SDS(x.shape, x.dtype), state)
    rep.findings += RG.check_retrace(
        step, lambda: ((state_sds, tbatch, tok), {}), site)

    # d2. pytree train step (build_programs mode="pytree"): the same
    # Eq. (1) contract holds leaf-by-leaf, tombstone and fresh tokens
    # taint-checked on one trace
    site = f"{arch}/pytree_step"
    from repro.launch.programs import (ARCH_ACC_DTYPE, ARCH_OPTIMIZER,
                                       init_train_state, make_train_step)
    popt = get_optimizer(ARCH_OPTIMIZER.get(cfg.name, "adam"), AUDIT_LR)
    pstate = jax.eval_shape(
        lambda p: init_train_state(
            p, popt, ARCH_ACC_DTYPE.get(cfg.name, jnp.float32)), pshapes)
    pstep = make_train_step(cfg, popt, gba)
    jpt = jax.make_jaxpr(pstep)(pstate, tbatch, tok)
    rep.findings += DFL.flow_pytree_step(jpt, pstate, site=site,
                                         iota=AUDIT_IOTA)

    # e. decode step: no collectives, no f64, no retrace
    site = f"{arch}/decode"
    dec = make_decode_step(cfg)
    cache = abstract_cache(cfg, 2, 64, _memory_len(cfg))
    dtok = model_inputs(
        cfg, InputShape("audit", 64, 2, "decode"))["tokens"]
    jdec = jax.make_jaxpr(dec)(pshapes, dtok, cache)
    rep.findings += JA.check_no_collectives(jdec, site)
    rep.findings += JA.check_no_f64(jdec, site)
    rep.findings += RG.check_retrace(
        dec, lambda: ((pshapes, dtok, cache), {}), site)

    # f. the arch's own gba_apply launch at its real shard geometry
    from repro.kernels import gba_apply
    meta = gba_apply.launch_meta(layout.shard_size, m)
    rep.findings += PC.check_launch(meta, f"{arch}/kernels/gba_apply")
    rep.stats["apply_vmem_bytes"] = meta.vmem_bytes(meta.vmem_counted)
    return rep


def kernel_metas():
    """Arch-independent kernel launches at their bench shapes."""
    from repro.kernels import (embedding_bag, flash_decode, fused_adagrad,
                               gba_aggregate, quantize)
    return (
        fused_adagrad.launch_meta(1 << 16),
        gba_aggregate.launch_meta(1 << 16, 8),
        embedding_bag.fwd_launch_meta(32, 26, 100_000, 128),
        embedding_bag.bwd_launch_meta(32, 26, 100_000, 128),
        embedding_bag.scatter_rows_launch_meta(32 * 26, 100_000, 18),
        flash_decode.launch_meta(4, 32_768, 8, 4, 128),
        quantize.quantize_launch_meta(8, 1 << 14, 2048, "minmax"),
        quantize.quantize_launch_meta(8, 1 << 14, 2048, "sign"),
        quantize.dequant_launch_meta(8, 1 << 14, 2048, "minmax"),
        quantize.dequant_launch_meta(8, 1 << 14, 2048, "sign"),
    )


def audit_kernels() -> AuditReport:
    rep = AuditReport("kernels")
    for meta in kernel_metas():
        rep.findings += PC.check_launch(meta, f"kernels/{meta.kernel}")
        rep.stats[f"{meta.kernel}_vmem_bytes"] = meta.total_vmem_bytes()
    return rep


def audit_dataflow() -> AuditReport:
    """Arch-independent dataflow sites: the Alg. 2 aggregate's masked
    divisor (GBA-FLOW-005)."""
    rep = AuditReport("dataflow")
    rep.findings += DFL.flow_aggregate_embedding(
        site="dataflow/aggregate_embedding")
    return rep


def audit_serving() -> AuditReport:
    """GBA-RACE lock-discipline lint over the serving modules + the
    hot-ID cache (see ``race_lint.DEFAULT_MODULES``)."""
    rep = AuditReport("serving")
    findings, stats = RL.lint_default()
    rep.findings += findings
    rep.stats.update(stats)
    return rep


def run_audit(archs=None, *, m: int = AUDIT_M,
              suppressions=()) -> list[AuditReport]:
    """Audit every requested arch plus the global kernel set, the
    dataflow sites, and the serving race lint, applying ``RULE`` /
    ``RULE@site`` suppressions."""
    from repro.analysis.rules import apply_suppressions, parse_suppressions
    sup = parse_suppressions(suppressions)
    reports = [audit_arch(a, m=m) for a in (archs or ARCH_IDS)]
    reports.append(audit_kernels())
    reports.append(audit_dataflow())
    reports.append(audit_serving())
    for rep in reports:
        rep.findings, dropped = apply_suppressions(rep.findings, sup)
        rep.suppressed += dropped
    return reports
