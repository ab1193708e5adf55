"""jaxpr rule family: collective census, dtype lints, donation lint.

Everything here works on TRACED programs — ``jax.make_jaxpr`` output (which
``jax.sharding.AbstractMesh`` lets us build for any mesh size without
devices) and ``jax.jit(...).lower(...)`` argument metadata.  Nothing is
executed or compiled.

The census is the machine-checked form of the collective schedule
documented on ``core.gba_shard_map.make_gba_fused_psum_step``: one tiled
``all_gather`` per layer group (exact ``group_shard_sizes`` shapes, group
order) plus the (M,) token gather, one ``all_to_all`` per group (exact
``(M, group_shard)`` shapes), all gathers issued before any routing, and
the only ``psum`` left the scalar loss.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.analysis.rules import Finding, finding

# primitive names across jax versions: psum lowers as "psum" or "psum2"
_COLLECTIVES = ("all_gather", "all_to_all", "psum", "reduce_scatter",
                "ppermute", "all_reduce")


def _canon(name: str) -> str | None:
    for c in _COLLECTIVES:
        if name == c or (name.startswith(c) and name[len(c):].isdigit()):
            return c
    return None


def iter_eqns(jaxpr):
    """Depth-first walk over every eqn, descending into sub-jaxprs
    (pjit/closed_call/cond/scan/while/shard_map/custom_vjp/pallas_call)
    at their call site, so program order is preserved."""
    from jax.extend.core import ClosedJaxpr, Jaxpr

    closed = getattr(jaxpr, "jaxpr", None)
    if closed is not None and not isinstance(jaxpr, Jaxpr):
        jaxpr = closed
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                if isinstance(sub, ClosedJaxpr):
                    yield from iter_eqns(sub.jaxpr)
                elif isinstance(sub, Jaxpr):
                    yield from iter_eqns(sub)


@dataclass(frozen=True)
class Collective:
    """One collective eqn: canonical op name + operand/result avals."""

    op: str
    in_shapes: tuple[tuple[int, ...], ...]
    out_shapes: tuple[tuple[int, ...], ...]
    in_dtypes: tuple[str, ...]

    def scalar_only(self) -> bool:
        return all(s == () for s in self.in_shapes)


def collective_census(jaxpr) -> list[Collective]:
    """All collectives in program order (recursing into sub-jaxprs)."""
    out = []
    for eqn in iter_eqns(jaxpr):
        op = _canon(eqn.primitive.name)
        if op is None:
            continue
        out.append(Collective(
            op,
            tuple(tuple(v.aval.shape) for v in eqn.invars
                  if hasattr(v, "aval")),
            tuple(tuple(v.aval.shape) for v in eqn.outvars),
            tuple(str(v.aval.dtype) for v in eqn.invars
                  if hasattr(v, "aval")),
        ))
    return out


def census_counts(census: list[Collective]) -> dict[str, int]:
    counts: dict[str, int] = {}
    for c in census:
        counts[c.op] = counts.get(c.op, 0) + 1
    return counts


# ---------------------------------------------------------------------------
# GBA-COLL rules
# ---------------------------------------------------------------------------

def expected_fused_collectives(layout, m: int):
    """The declared schedule of ``make_gba_fused_psum_step`` for this
    layout: (per-group gather operand shapes, per-group all_to_all
    operand shapes, token-gather operand shape)."""
    gathers = [(layout.group_shard_sizes[g],)
               for g in range(layout.num_groups)]
    routes = [(m, layout.group_shard_sizes[g])
              for g in range(layout.num_groups)]
    return gathers, routes, (1,)


def check_fused_psum_schedule(jaxpr, layout, m: int,
                              site: str) -> list[Finding]:
    """GBA-COLL-001 + GBA-COLL-002 over a traced fused-psum step."""
    census = collective_census(jaxpr)
    findings = []
    exp_gathers, exp_routes, token = expected_fused_collectives(layout, m)

    gathers = [c for c in census if c.op == "all_gather"]
    routes = [c for c in census if c.op == "all_to_all"]
    got_gathers = [c.in_shapes[0] for c in gathers]
    got_routes = [c.in_shapes[0] for c in routes]
    if got_gathers != exp_gathers + [token]:
        findings.append(finding(
            "GBA-COLL-001", site,
            f"all_gather operands {got_gathers} != per-group "
            f"{exp_gathers} + token {token} (group_table order)"))
    if got_routes != exp_routes:
        findings.append(finding(
            "GBA-COLL-001", site,
            f"all_to_all operands {got_routes} != per-group {exp_routes}"))
    # schedule property: every param gather is issued before any routing
    first_route = next((i for i, c in enumerate(census)
                        if c.op == "all_to_all"), len(census))
    late_gather = [c.in_shapes[0] for c in census[first_route:]
                   if c.op == "all_gather" and c.in_shapes[0] != token]
    if late_gather:
        findings.append(finding(
            "GBA-COLL-001", site,
            f"param gathers {late_gather} issued after gradient routing"))
    stray = [c.op for c in census
             if c.op not in ("all_gather", "all_to_all", "psum")]
    if stray:
        findings.append(finding(
            "GBA-COLL-001", site, f"unexpected collectives {stray}"))
    findings += check_scalar_psum_only(jaxpr, site, census=census)
    return findings


def expected_wire_collectives(layout, m: int, policy, warm: bool = False):
    """The declared wire of a compressed fused-psum step: per-group
    ``all_to_all`` operand ``(shape, dtype)`` lists under ``policy``.

    Past warmup each group routes its int8 payload plus the per-tile f32
    sideband(s) — scale and zero-point for int8 min-max, the single norm
    for onebit sign; during warmup (or scheme ``none``) each group routes
    one f32 ``(m, group_shard)`` operand, the PR-5 wire."""
    per_group = []
    for g in range(layout.num_groups):
        gsh = layout.group_shard_sizes[g]
        if warm or policy.scheme == "none":
            per_group.append([((m, gsh), "float32")])
            continue
        n_tiles = gsh // layout.tile
        ops = [((m, gsh), "int8"), ((m, n_tiles), "float32")]
        if policy.scheme == "int8":
            ops.append(((m, n_tiles), "float32"))    # zero-point sideband
        per_group.append(ops)
    return per_group


def check_wire_dtypes(jaxpr, layout, m: int, policy, site: str,
                      warm: bool = False) -> list[Finding]:
    """GBA-COLL-005: every ``all_to_all``/``all_gather`` operand dtype on
    a traced fused-psum step matches the declared ``CompressionPolicy``.

    Routing: the flattened per-group (shape, dtype) sequence must equal
    :func:`expected_wire_collectives` exactly — an f32 ``(m,
    group_shard)`` operand in a past-warmup trace is full-precision
    leakage and fails CI.  Gathers: params always travel f32 (compression
    is a routing-stage transform) and the token gather stays int32."""
    census = collective_census(jaxpr)
    findings = []
    expected = [op for group in
                expected_wire_collectives(layout, m, policy, warm=warm)
                for op in group]
    routes = [(c.in_shapes[0], c.in_dtypes[0])
              for c in census if c.op == "all_to_all"]
    if routes != expected:
        findings.append(finding(
            "GBA-COLL-005", site,
            f"all_to_all wire {routes} != declared "
            f"{policy.scheme}{' warmup' if warm else ''} wire {expected}"))
    token = (1,)
    for c in census:
        if c.op != "all_gather":
            continue
        want = "int32" if c.in_shapes[0] == token else "float32"
        if c.in_dtypes[0] != want:
            findings.append(finding(
                "GBA-COLL-005", site,
                f"all_gather operand {c.in_shapes[0]} has dtype "
                f"{c.in_dtypes[0]}, expected {want} (params travel full "
                f"precision; compression is routing-stage only)"))
    return findings


def check_scalar_psum_only(jaxpr, site: str, census=None) -> list[Finding]:
    """GBA-COLL-002: psum reduces scalars only."""
    census = collective_census(jaxpr) if census is None else census
    bad = [c.in_shapes for c in census
           if c.op == "psum" and not c.scalar_only()]
    if bad:
        return [finding("GBA-COLL-002", site,
                        f"non-scalar psum operands: {bad}")]
    return []


def check_no_collectives(jaxpr, site: str) -> list[Finding]:
    """GBA-COLL-003: the path launches no collectives at all."""
    counts = census_counts(collective_census(jaxpr))
    if counts:
        return [finding("GBA-COLL-003", site, f"collectives found: {counts}")]
    return []


def check_sync_psum_schedule(jaxpr, leaf_shapes, site: str) -> list[Finding]:
    """GBA-COLL-004: the sync step psums exactly the per-leaf decayed
    gradients plus one scalar loss; no gathers or routing."""
    census = collective_census(jaxpr)
    findings = []
    others = census_counts([c for c in census if c.op != "psum"])
    if others:
        findings.append(finding(
            "GBA-COLL-004", site,
            f"sync step should only psum; found {others}"))
    psummed = [s for c in census if c.op == "psum" for s in c.in_shapes]
    want = sorted([tuple(s) for s in leaf_shapes] + [()])
    if sorted(psummed) != want:
        findings.append(finding(
            "GBA-COLL-004", site,
            f"psum operand shapes {sorted(psummed)} != per-leaf "
            f"gradients + scalar loss {want}"))
    return findings


# ---------------------------------------------------------------------------
# GBA-DTYPE rules
# ---------------------------------------------------------------------------

def widening_converts(jaxpr, min_elements: int = 8):
    """All float->wider-float convert_element_type eqns with at least
    ``min_elements`` elements: (shape, src_dtype, dst_dtype) list."""
    out = []
    for eqn in iter_eqns(jaxpr):
        if eqn.primitive.name != "convert_element_type":
            continue
        src = eqn.invars[0].aval
        dst = eqn.outvars[0].aval
        if (jnp.issubdtype(src.dtype, jnp.floating)
                and jnp.issubdtype(dst.dtype, jnp.floating)
                and dst.dtype.itemsize > src.dtype.itemsize
                and math.prod(src.shape) >= min_elements):
            out.append((tuple(src.shape), str(src.dtype), str(dst.dtype)))
    return out


def check_widening_budget(jaxpr, budget: int, site: str,
                          min_elements: int = 8) -> list[Finding]:
    """GBA-DTYPE-001: at most ``budget`` widening float converts.  Run on
    probe-loss traces where the sanctioned count (per-leaf ravel/loss
    casts) is exactly derivable — a real mixed-precision LM forward has
    legitimate upcasts this rule would misflag."""
    got = widening_converts(jaxpr, min_elements)
    if len(got) > budget:
        sample = got[:6]
        return [finding(
            "GBA-DTYPE-001", site,
            f"{len(got)} widening float converts > sanctioned {budget} "
            f"(per-leaf ravel/loss casts); e.g. {sample}")]
    return []


def check_no_f64(jaxpr, site: str) -> list[Finding]:
    """GBA-DTYPE-002: float64 never appears on a hot path."""
    hits = []
    for eqn in iter_eqns(jaxpr):
        for v in eqn.outvars:
            dt = getattr(v.aval, "dtype", None)
            if dt is not None and dt == jnp.float64:
                hits.append((eqn.primitive.name, tuple(v.aval.shape)))
    if hits:
        return [finding("GBA-DTYPE-002", site,
                        f"float64 values produced by {hits[:6]}")]
    return []


# ---------------------------------------------------------------------------
# GBA-DON donation lint
# ---------------------------------------------------------------------------

def undonated_paths(args_info) -> list[str]:
    """Leaves of a ``lowered.args_info`` subtree whose buffer is NOT
    donated, as readable path strings."""
    out = []
    for path, info in jax.tree_util.tree_flatten_with_path(args_info)[0]:
        if not getattr(info, "donated", False):
            out.append(jax.tree_util.keystr(path))
    return out


def check_donation(args_info, site: str) -> list[Finding]:
    """GBA-DON-001: every array leaf of the state argument is donated."""
    bad = undonated_paths(args_info)
    if bad:
        sample = ", ".join(bad[:8]) + ("..." if len(bad) > 8 else "")
        return [finding(
            "GBA-DON-001", site,
            f"{len(bad)} state leaves not donated (double-allocated on "
            f"every step): {sample}")]
    return []
