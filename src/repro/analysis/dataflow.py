"""GBA-FLOW: staleness-taint abstract interpretation over traced jaxprs.

The PR-6 census proves the collective *schedule*; this pass proves the
*dataflow* of every training mode.  Each input aval is seeded with a
provenance tag set drawn from a small lattice::

    raw        per-token gradient before Eq. (1) weighting
    decayed    gradient after a decay-mask multiply (sanitized)
    residual   quantization error-feedback state
    decay_mask the Eq. (1) weight ((gstep - tokens) <= iota)
    pad_mask   a validity mask derived from comparing ids to a bound
    token      per-slot token (arrival order) values
    step       the global step counter
    ids        embedding-row indices
    param      optimizer state (params / accumulators / f32 master)

and the interpreter walks every eqn — descending into ``jit`` /
``cond`` / ``scan`` / ``while`` / ``shard_map`` / ``custom_vjp`` /
``pallas_call`` sub-jaxprs — propagating tags by union plus three
special transfer rules:

* a comparison mixing ``token`` and ``step`` taints produces a
  ``decay_mask`` (the Eq. (1) threshold); a comparison of ``ids``
  against an untainted bound produces a ``pad_mask``;
* a multiply of a ``raw``/``decayed`` value by a ``decay_mask`` operand
  *sanitizes*: ``raw`` is cleared, ``decayed`` is added, and the event
  is recorded (with the concretely-evaluated mask when the token seeds
  were concrete — that is how FLOW-002 proves tombstone weights are
  EXACTLY zero, not just small);
* the quantize Pallas kernel is the one sanctioned producer/consumer of
  ``residual``: its payload-shaped f32 output keeps the tag, every
  other output (the int8 payload and the f32 sidebands) drops it.

Alongside tags, the interpreter forward-evaluates a *concrete* numpy
value for vars whose inputs are all concretely known (token seeds, the
global step, literals), capped at :data:`MAX_CONCRETE` elements.  This
is what lets FLOW-002 check the actual weight of a tombstone slot
inside the ``gba_apply`` kernel without running it.

Checks (see ``rules.RULES`` for the contracts):

* **FLOW-001** no ``raw`` tag on a params/optimizer-state output;
* **FLOW-002** every concretely-evaluated decay mask gives weight 0.0
  to stale slots and nonzero weight to fresh ones;
* **FLOW-003** no ``residual`` tag on a params/optimizer-state output;
* **FLOW-004** no sub-f32 float arithmetic on ``decayed`` values, and
  every narrowing float convert is a terminal downcast;
* **FLOW-005** a gradient aggregate is divided by a divisor carrying
  both ``pad_mask`` and ``decay_mask`` (never by a constant).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable

import numpy as np

import jax
import jax.numpy as jnp

from repro.analysis.rules import Finding, finding

# tags -------------------------------------------------------------------
RAW = "raw"
DECAYED = "decayed"
RESIDUAL = "residual"
DECAY_MASK = "decay_mask"
PAD_MASK = "pad_mask"
TOKEN = "token"
STEP = "step"
IDS = "ids"
PARAM = "param"

MAX_CONCRETE = 1 << 16   # cap forward-evaluated arrays (elements)
_SCAN_FIXPOINT_ITERS = 16


@dataclass(frozen=True)
class Taint:
    """Tag set + optional concretely-known value for one var."""

    tags: frozenset
    val: Any = None      # np.ndarray when the value is concretely known

    def with_tags(self, tags) -> "Taint":
        return Taint(frozenset(tags), self.val)

    def drop_val(self) -> "Taint":
        return self if self.val is None else Taint(self.tags, None)


EMPTY = Taint(frozenset())


def taint(*tags, val=None) -> Taint:
    if val is not None:
        val = np.asarray(val)
        if val.size > MAX_CONCRETE:
            val = None
    return Taint(frozenset(tags), val)


@dataclass
class FlowContext:
    """Events recorded while interpreting one trace."""

    site: str
    sanitize_masks: list = field(default_factory=list)  # np arrays or None
    div_events: list = field(default_factory=list)      # (num_tags, den_tags,
    #                                                      den_is_const)
    findings: list = field(default_factory=list)
    f32_chain: bool = False   # enable FLOW-004 narrow-arith checks


# -- concrete forward evaluation ----------------------------------------

def _np_binop(fn):
    return lambda vals, params, aval: fn(vals[0], vals[1])


def _np_convert(vals, params, aval):
    return np.asarray(vals[0]).astype(params["new_dtype"])


def _np_broadcast(vals, params, aval):
    shape = tuple(params["shape"])
    bd = tuple(params["broadcast_dimensions"])
    tmp_shape = [1] * len(shape)
    for src_dim, dst_dim in enumerate(bd):
        tmp_shape[dst_dim] = np.shape(vals[0])[src_dim]
    return np.broadcast_to(np.reshape(vals[0], tmp_shape), shape)


def _np_reshape(vals, params, aval):
    v = vals[0]
    if params.get("dimensions") is not None:
        v = np.transpose(v, params["dimensions"])
    return np.reshape(v, params["new_sizes"])


def _np_slice(vals, params, aval):
    idx = tuple(slice(s, l, (st or 1)) for s, l, st in zip(
        params["start_indices"], params["limit_indices"],
        params.get("strides") or [1] * len(params["start_indices"])))
    return np.asarray(vals[0])[idx]


def _np_select_n(vals, params, aval):
    pred = np.asarray(vals[0]).astype(np.int64)
    cases = np.broadcast_arrays(*vals[1:])
    return np.choose(pred, cases, mode="clip")


def _np_reduce(fn):
    def run(vals, params, aval):
        return fn(np.asarray(vals[0]), axis=tuple(params["axes"]))
    return run


def _np_iota(vals, params, aval):
    shape = tuple(params["shape"])
    dim = params["dimension"]
    r = np.arange(shape[dim], dtype=params["dtype"])
    bshape = [1] * len(shape)
    bshape[dim] = shape[dim]
    return np.broadcast_to(np.reshape(r, bshape), shape)


def _np_dynamic_slice(vals, params, aval):
    op = np.asarray(vals[0])
    sizes = params["slice_sizes"]
    starts = [int(np.asarray(i)) for i in vals[1:]]
    idx = tuple(slice(min(max(s, 0), d - n), min(max(s, 0), d - n) + n)
                for s, d, n in zip(starts, op.shape, sizes))
    return op[idx]


def _np_dynamic_update_slice(vals, params, aval):
    op = np.array(vals[0])
    upd = np.asarray(vals[1])
    starts = [int(np.asarray(i)) for i in vals[2:]]
    idx = tuple(slice(min(max(s, 0), d - n), min(max(s, 0), d - n) + n)
                for s, d, n in zip(starts, op.shape, upd.shape))
    op[idx] = upd
    return op


_NP_EVAL: dict[str, Callable] = {
    "add": _np_binop(np.add), "sub": _np_binop(np.subtract),
    "mul": _np_binop(np.multiply), "div": _np_binop(np.true_divide),
    "max": _np_binop(np.maximum), "min": _np_binop(np.minimum),
    "rem": _np_binop(np.fmod), "pow": _np_binop(np.power),
    "lt": _np_binop(np.less), "le": _np_binop(np.less_equal),
    "gt": _np_binop(np.greater), "ge": _np_binop(np.greater_equal),
    "eq": _np_binop(np.equal), "ne": _np_binop(np.not_equal),
    "and": _np_binop(np.bitwise_and), "or": _np_binop(np.bitwise_or),
    "xor": _np_binop(np.bitwise_xor),
    "not": lambda vals, params, aval: np.bitwise_not(vals[0]),
    "neg": lambda vals, params, aval: np.negative(vals[0]),
    "abs": lambda vals, params, aval: np.abs(vals[0]),
    "sign": lambda vals, params, aval: np.sign(vals[0]),
    "sqrt": lambda vals, params, aval: np.sqrt(vals[0]),
    "floor": lambda vals, params, aval: np.floor(vals[0]),
    "ceil": lambda vals, params, aval: np.ceil(vals[0]),
    "integer_pow": lambda vals, params, aval: np.power(vals[0],
                                                       params["y"]),
    "is_finite": lambda vals, params, aval: np.isfinite(vals[0]),
    "stop_gradient": lambda vals, params, aval: vals[0],
    "copy": lambda vals, params, aval: vals[0],
    "convert_element_type": _np_convert,
    "broadcast_in_dim": _np_broadcast,
    "reshape": _np_reshape,
    "squeeze": lambda vals, params, aval: np.squeeze(
        vals[0], axis=tuple(params["dimensions"])),
    "expand_dims": lambda vals, params, aval: np.expand_dims(
        vals[0], axis=tuple(params["dimensions"])),
    "transpose": lambda vals, params, aval: np.transpose(
        vals[0], params["permutation"]),
    "slice": _np_slice,
    "rev": lambda vals, params, aval: np.flip(
        vals[0], axis=tuple(params["dimensions"])),
    "concatenate": lambda vals, params, aval: np.concatenate(
        vals, axis=params["dimension"]),
    "select_n": _np_select_n,
    "reduce_sum": _np_reduce(np.sum), "reduce_max": _np_reduce(np.max),
    "reduce_min": _np_reduce(np.min), "reduce_prod": _np_reduce(np.prod),
    "reduce_and": _np_reduce(np.all), "reduce_or": _np_reduce(np.any),
    "iota": _np_iota,
    "dynamic_slice": _np_dynamic_slice,
    "dynamic_update_slice": _np_dynamic_update_slice,
}


def _concrete(prim_name, in_taints, params, out_avals):
    """Forward-evaluate one eqn when all inputs are concrete.  Returns a
    list aligned with out_avals (``None`` entries = unknown)."""
    fn = _NP_EVAL.get(prim_name)
    if fn is None or any(t.val is None for t in in_taints):
        return [None] * len(out_avals)
    try:
        out = fn([t.val for t in in_taints], params, out_avals[0])
    except Exception:
        return [None] * len(out_avals)
    out = np.asarray(out)
    if out.size > MAX_CONCRETE:
        return [None] * len(out_avals)
    return [out] + [None] * (len(out_avals) - 1)


# -- jaxpr plumbing ------------------------------------------------------

def _is_literal(v) -> bool:
    return hasattr(v, "val")


def _read(env, v) -> Taint:
    if _is_literal(v):
        val = np.asarray(v.val)
        return Taint(frozenset(), val if val.size <= MAX_CONCRETE else None)
    return env.get(v, EMPTY)


def _union(taints) -> frozenset:
    tags = frozenset()
    for t in taints:
        tags |= t.tags
    return tags


_ARITH = {"add", "sub", "mul", "div", "max", "min", "neg", "abs",
          "dot_general", "reduce_sum", "reduce_max", "reduce_min",
          "sqrt", "rsqrt", "exp", "log", "integer_pow", "pow", "rem",
          "sign", "tanh", "logistic", "erf", "cumsum", "cumprod"}

# consumers a terminal downcast may legally feed (pure data movement)
_TERMINAL_OK = {"reshape", "squeeze", "expand_dims", "broadcast_in_dim",
                "transpose", "slice", "concatenate", "copy", "rev",
                "dynamic_update_slice", "swap", "convert_element_type"}

_CMP = {"lt", "le", "gt", "ge", "eq", "ne"}

_COLLECTIVES = {"psum", "all_gather", "all_to_all", "ppermute",
                "pbroadcast", "reduce_scatter", "pmax", "pmin"}


def _is_narrow_float(dtype) -> bool:
    return (jnp.issubdtype(dtype, jnp.floating)
            and np.dtype(dtype).itemsize < 4)


def _sub_closed(params):
    """Best-effort extraction of a single ClosedJaxpr from call params."""
    for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
        sub = params.get(key)
        if sub is None:
            continue
        if hasattr(sub, "jaxpr"):       # ClosedJaxpr
            return sub
        if hasattr(sub, "eqns"):        # open Jaxpr
            return jax.extend.core.ClosedJaxpr(sub, ())
    return None


def _join(a: Taint, b: Taint) -> Taint:
    val = a.val if (a.val is not None and b.val is not None
                    and np.shape(a.val) == np.shape(b.val)
                    and np.array_equal(a.val, b.val)) else None
    return Taint(a.tags | b.tags, val)


class _Interp:
    """One taint interpretation of one (closed) jaxpr tree."""

    def __init__(self, ctx: FlowContext):
        self.ctx = ctx

    # -- special transfer rules ----------------------------------------

    def _compare(self, ins, out_tags):
        if (TOKEN in out_tags and STEP in out_tags):
            out_tags = out_tags | {DECAY_MASK}
        if IDS in out_tags and any(not t.tags for t in ins):
            # ids compared against a literal / untainted bound:
            # the validity (padding / capacity) mask
            out_tags = out_tags | {PAD_MASK}
        return out_tags

    def _mul(self, ins, out_tags):
        for data, mask in ((ins[0], ins[1]), (ins[1], ins[0])):
            if (DECAY_MASK in mask.tags and RAW not in mask.tags
                    and (RAW in data.tags or DECAYED in data.tags)):
                self.ctx.sanitize_masks.append(
                    None if mask.val is None else np.asarray(
                        mask.val, dtype=np.float64))
                return (out_tags - {RAW}) | {DECAYED}
        return out_tags

    # -- eqn dispatch ---------------------------------------------------

    def eqn_taints(self, eqn, ins):
        name = eqn.primitive.name
        params = eqn.params

        if name in ("jit", "closed_call", "core_call", "xla_call",
                    "custom_jvp_call", "custom_vjp_call",
                    "custom_vjp_call_jaxpr", "remat", "checkpoint",
                    "custom_lin", "remat2"):
            sub = _sub_closed(params)
            if sub is not None and len(sub.jaxpr.invars) == len(ins):
                return self.run(sub.jaxpr, sub.consts, ins)
            tags = _union(ins)
            return [Taint(tags) for _ in eqn.outvars]

        if name == "cond":
            branches = params["branches"]
            ops = ins[1:]
            outs = None
            for br in branches:
                b_outs = self.run(br.jaxpr, br.consts, ops)
                outs = b_outs if outs is None else [
                    _join(a, b) for a, b in zip(outs, b_outs)]
            return outs

        if name == "scan":
            closed = params["jaxpr"]
            nc, ncar = params["num_consts"], params["num_carry"]
            consts_in = ins[:nc]
            carry = [t.drop_val() for t in ins[nc:nc + ncar]]
            xs = [t.drop_val() for t in ins[nc + ncar:]]
            outs = carry + [EMPTY] * (len(eqn.outvars) - ncar)
            for _ in range(_SCAN_FIXPOINT_ITERS):
                outs = self.run(closed.jaxpr, closed.consts,
                                consts_in + carry + xs)
                new_carry = [Taint(c.tags | o.tags)
                             for c, o in zip(carry, outs[:ncar])]
                if all(n.tags == c.tags
                       for n, c in zip(new_carry, carry)):
                    break
                carry = new_carry
            return ([Taint(t.tags) for t in outs[:ncar]]
                    + [Taint(t.tags) for t in outs[ncar:]])

        if name == "while":
            body = params["body_jaxpr"]
            nb = params["body_nconsts"]
            ncond = params["cond_nconsts"]
            consts_in = ins[ncond:ncond + nb]
            carry = [t.drop_val() for t in ins[ncond + nb:]]
            for _ in range(_SCAN_FIXPOINT_ITERS):
                outs = self.run(body.jaxpr, body.consts, consts_in + carry)
                new_carry = [Taint(c.tags | o.tags)
                             for c, o in zip(carry, outs)]
                if all(n.tags == c.tags
                       for n, c in zip(new_carry, carry)):
                    break
                carry = new_carry
            return carry

        if name == "shard_map":
            sub = params["jaxpr"]          # open Jaxpr
            in_specs = params["in_specs"]
            seeded = []
            for i, t in enumerate(ins):
                split = i < len(in_specs) and any(
                    ax is not None for ax in in_specs[i])
                seeded.append(t.drop_val() if split else t)
            return self.run(sub, (), seeded)

        if name == "pallas_call":
            return self._pallas(eqn, ins)

        if name in _COLLECTIVES:
            tags = _union(ins)
            return [Taint(tags) for _ in eqn.outvars]

        # -- leaf primitive: tag union + special rules + concrete eval --
        out_tags = _union(ins)
        if name in _CMP:
            out_tags = self._compare(ins, out_tags)
        elif name == "mul":
            out_tags = self._mul(ins, out_tags)
        elif name == "div":
            num, den = ins[0], ins[1]
            if RAW in num.tags or DECAYED in num.tags:
                self.ctx.div_events.append(
                    (num.tags, den.tags,
                     _is_literal(eqn.invars[1]) or not den.tags))

        out_avals = [v.aval for v in eqn.outvars]
        vals = _concrete(name, ins, params, out_avals)

        if self.ctx.f32_chain and name in _ARITH and DECAYED in out_tags:
            narrow = [v for v in list(eqn.invars) + list(eqn.outvars)
                      if hasattr(v.aval, "dtype")
                      and _is_narrow_float(v.aval.dtype)]
            if narrow:
                self.ctx.findings.append(finding(
                    "GBA-FLOW-004", self.ctx.site,
                    f"'{name}' on a decayed-gradient value uses "
                    f"{narrow[0].aval.dtype} — the update chain must stay "
                    f"f32 until the final downcast"))

        return [Taint(out_tags, val) for val in vals]

    # -- pallas kernels --------------------------------------------------

    def _pallas(self, eqn, ins):
        params = eqn.params
        gm = params.get("grid_mapping")
        kj = params.get("jaxpr")
        if gm is None or kj is None:
            tags = _union(ins)
            return [Taint(tags) for _ in eqn.outvars]
        n_scalar = getattr(gm, "num_index_operands", 0)
        n_in = getattr(gm, "num_inputs", 0)
        n_out = getattr(gm, "num_outputs", 0)

        ref_env = {}
        kvars = kj.invars
        for i, v in enumerate(kvars[:n_scalar]):
            ref_env[v] = ins[i]                      # scalar prefetch: keep
        for i, v in enumerate(kvars[n_scalar:n_scalar + n_in]):
            ref_env[v] = ins[n_scalar + i].drop_val()  # blocked: shape lies
        for v in kvars[n_scalar + n_in:]:
            ref_env[v] = EMPTY                       # outputs + scratch

        self._run_refs(kj, ref_env)

        outs = [ref_env.get(v, EMPTY).drop_val()
                for v in kvars[n_scalar + n_in:n_scalar + n_in + n_out]]

        kname = str(params.get("name") or "")
        if "quant" in kname and "dequant" not in kname:
            # the quantize kernel is the sanctioned residual producer:
            # only its payload-shaped f32 output carries the residual
            # forward; the int8 payload and the sidebands drop it.
            pay = eqn.invars[n_scalar].aval if len(eqn.invars) > n_scalar \
                else None
            fixed = []
            for v, t in zip(eqn.outvars, outs):
                is_res = (pay is not None
                          and v.aval.shape == pay.shape
                          and v.aval.dtype == np.float32)
                fixed.append(t if is_res
                             else Taint(t.tags - {RESIDUAL}, t.val))
            outs = fixed
        return outs

    def _run_refs(self, jaxpr, env):
        """Interpret a kernel body where Ref vars mutate in ``env``."""
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            ins = [_read(env, v) for v in eqn.invars]
            if name == "get":
                ref_t = ins[0]
                val = None
                if ref_t.val is not None:
                    try:
                        if len(ins) == 1:
                            val = ref_t.val
                        else:
                            idx = tuple(int(np.asarray(t.val))
                                        for t in ins[1:])
                            val = np.asarray(ref_t.val)[idx]
                    except Exception:
                        val = None
                outs = [Taint(ref_t.tags, val)]
            elif name == "swap":
                old = ins[0]
                env[eqn.invars[0]] = Taint(old.tags | ins[1].tags, None)
                outs = [Taint(old.tags, None)]
            elif name == "addupdate":
                env[eqn.invars[0]] = Taint(_union(ins), None)
                outs = []
            elif name == "run_scoped":
                sub = eqn.params.get("jaxpr")
                if sub is not None:
                    scoped = dict(env)
                    for v in sub.invars:
                        scoped[v] = EMPTY
                    self._run_refs(sub, scoped)
                    for v in jaxpr.invars:      # refs visible both scopes
                        if v in scoped:
                            env[v] = scoped[v]
                outs = [EMPTY for _ in eqn.outvars]
            else:
                outs = self.eqn_taints(eqn, ins)
            for v, t in zip(eqn.outvars, outs):
                if type(v).__name__ != "DropVar":
                    env[v] = t

    # -- driver ----------------------------------------------------------

    def run(self, jaxpr, consts, in_taints):
        env = {}
        for v, c in zip(jaxpr.constvars, consts):
            env[v] = c if isinstance(c, Taint) else taint(val=np.asarray(c))
        for v, t in zip(jaxpr.invars, in_taints):
            env[v] = t
        narrow_converts = []
        for eqn in jaxpr.eqns:
            ins = [_read(env, v) for v in eqn.invars]
            outs = self.eqn_taints(eqn, ins)
            for v, t in zip(eqn.outvars, outs):
                if type(v).__name__ != "DropVar":
                    env[v] = t
            if (self.ctx.f32_chain
                    and eqn.primitive.name == "convert_element_type"
                    and hasattr(eqn.invars[0], "aval")
                    and jnp.issubdtype(eqn.invars[0].aval.dtype, jnp.floating)
                    and np.dtype(eqn.invars[0].aval.dtype).itemsize
                    > np.dtype(eqn.outvars[0].aval.dtype).itemsize
                    and jnp.issubdtype(eqn.outvars[0].aval.dtype,
                                       jnp.floating)
                    and DECAYED in _read(env, eqn.outvars[0]).tags):
                narrow_converts.append(eqn.outvars[0])
        if narrow_converts:
            self._check_terminal(jaxpr, narrow_converts)
        return [_read(env, v) for v in jaxpr.outvars]

    def _check_terminal(self, jaxpr, narrow_vars):
        """FLOW-004: a narrowing downcast of a decayed value must be
        terminal — it may feed outputs and data movement, never further
        compute."""
        consumers: dict = {}
        for eqn in jaxpr.eqns:
            for v in eqn.invars:
                if not _is_literal(v):
                    consumers.setdefault(v, []).append(eqn)
        out_set = set(jaxpr.outvars)
        for nv in narrow_vars:
            frontier = [nv]
            seen = set()
            while frontier:
                v = frontier.pop()
                if v in seen:
                    continue
                seen.add(v)
                for eqn in consumers.get(v, ()):
                    if eqn.primitive.name in _TERMINAL_OK:
                        for ov in eqn.outvars:
                            if type(ov).__name__ != "DropVar":
                                frontier.append(ov)
                    else:
                        self.ctx.findings.append(finding(
                            "GBA-FLOW-004", self.ctx.site,
                            f"narrowed ({nv.aval.dtype}) update value "
                            f"feeds '{eqn.primitive.name}' — the downcast "
                            f"must be the final op of the update chain"))
                        return


# -- public API ----------------------------------------------------------

def analyze(closed, in_taints, *, site, f32_chain=False):
    """Run the taint pass over a ClosedJaxpr.  Returns
    ``(out_taints, ctx)``; FLOW-004 findings accumulate in ``ctx``."""
    jaxpr = closed.jaxpr if hasattr(closed, "jaxpr") else closed
    consts = getattr(closed, "consts", ())
    if len(in_taints) != len(jaxpr.invars):
        raise ValueError(
            f"{site}: seeded {len(in_taints)} taints for "
            f"{len(jaxpr.invars)} invars")
    ctx = FlowContext(site=site, f32_chain=f32_chain)
    outs = _Interp(ctx).run(jaxpr, consts, list(in_taints))
    return outs, ctx


def seed_taints(args, specs) -> list[Taint]:
    """Flatten ``args`` (a tuple of pytrees, one per traced positional
    arg) into per-invar taints.  ``specs[i]`` is a :class:`Taint`
    applied to every leaf of ``args[i]``, or a callable
    ``(path_str, leaf) -> Taint``."""
    if len(args) != len(specs):
        raise ValueError("one spec per traced positional arg")
    out = []
    for arg, spec in zip(args, specs):
        leaves = jax.tree_util.tree_flatten_with_path(arg)[0]
        for path, leaf in leaves:
            if callable(spec) and not isinstance(spec, Taint):
                out.append(spec(jax.tree_util.keystr(path), leaf))
            else:
                out.append(spec)
    return out


def out_paths(tree) -> list[str]:
    """Leaf key-paths of a pytree, aligned with its flatten order — used
    to name which traced output a finding refers to."""
    return [jax.tree_util.keystr(p)
            for p, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


# -- checks --------------------------------------------------------------

def check_no_raw(out_taints, paths, guard, site) -> list[Finding]:
    """FLOW-001 over the update-state outputs selected by ``guard``
    (a predicate over the output path)."""
    out = []
    for t, p in zip(out_taints, paths):
        if guard(p) and RAW in t.tags:
            out.append(finding(
                "GBA-FLOW-001", site,
                f"raw per-token gradient reaches update output '{p}' "
                f"without passing the Eq. (1) decay multiply"))
    return out


def check_no_residual(out_taints, paths, guard, site) -> list[Finding]:
    """FLOW-003 over the update-state outputs selected by ``guard``."""
    out = []
    for t, p in zip(out_taints, paths):
        if guard(p) and RESIDUAL in t.tags:
            out.append(finding(
                "GBA-FLOW-003", site,
                f"error-feedback residual reaches update output '{p}' — "
                f"the residual may only feed the next quantize"))
    return out


def check_tombstone(ctx, stale_rows, site) -> list[Finding]:
    """FLOW-002: every concretely-evaluated decay mask must weight the
    stale slots (``stale_rows`` bool array, length M) EXACTLY 0.0 and
    the fresh slots nonzero."""
    stale_rows = np.asarray(stale_rows, dtype=bool)
    m = stale_rows.size
    out = []
    concrete = [w for w in ctx.sanitize_masks if w is not None]
    if not concrete:
        out.append(finding(
            "GBA-FLOW-002", site,
            "no concretely-evaluable decay mask found on the update path "
            "— tombstone weights cannot be proven exactly zero"))
        return out
    for w in concrete:
        flat = np.asarray(w, dtype=np.float64).reshape(-1)
        if flat.size % m:
            continue                     # mask not per-slot shaped
        per_slot = flat.reshape(m, -1)
        bad_stale = stale_rows & np.any(per_slot != 0.0, axis=1)
        bad_fresh = (~stale_rows) & np.all(per_slot == 0.0, axis=1)
        if bad_stale.any():
            out.append(finding(
                "GBA-FLOW-002", site,
                f"tombstone slot(s) {np.where(bad_stale)[0].tolist()} get "
                f"nonzero decay weight "
                f"{per_slot[bad_stale].reshape(-1)[:4].tolist()} — the "
                f"contract is weight EXACTLY 0, not just small"))
            break
        if bad_fresh.any():
            out.append(finding(
                "GBA-FLOW-002", site,
                f"fresh slot(s) {np.where(bad_fresh)[0].tolist()} get "
                f"decay weight 0 — live gradients must not be dropped"))
            break
    return out


# -- audited sites -------------------------------------------------------

def flow_fused_step(closed, batch, *, site, wire=None) -> list[Finding]:
    """FLOW-001 (and FLOW-003 when ``wire`` state is traced) on a
    layer-grouped fused psum step: args ``(param_flat, accum_flat,
    batch, tokens, gstep[, wire])``, outputs ``(new_p, new_a, loss
    [, new_wire])``."""
    seeds = [taint(PARAM), taint(PARAM)]
    seeds += [taint(RAW)] * len(jax.tree.leaves(batch))
    seeds += [taint(TOKEN), taint(STEP)]
    if wire is not None:
        for path, _ in jax.tree_util.tree_flatten_with_path(wire)[0]:
            is_res = "residual" in jax.tree_util.keystr(path)
            seeds.append(taint(RESIDUAL) if is_res else taint(RAW))
    outs, _ = analyze(closed, seeds, site=site)
    paths = ["new_param_flat", "new_accum_flat"]
    guard = lambda p: True
    return (check_no_raw(outs[:2], paths, guard, site)
            + check_no_residual(outs[:2], paths, guard, site))


def _tomb_tokens(m: int, step: int, iota: int) -> np.ndarray:
    """Buffer token seeds with one tombstone slot (index 1: staler than
    ``iota`` by exactly one — the Alg. 1 excluded-slot encoding) among
    fresh slots; slot m-1 is overwritten by the pushed token."""
    tokens = np.full((m,), step, dtype=np.int32)
    if m > 1:
        tokens[1] = step - iota - 1
    tokens[m - 1] = 0        # replaced by the push before the apply
    return tokens


def flow_fused_train_step(closed, state, *, site, m, iota,
                          f32_chain=True, step_seed=9) -> list[Finding]:
    """FLOW-001/002/004 on the single-host fused train step.  The
    buffer is seeded at fill m-1 with concrete tokens (one tombstone)
    so the decay weight inside ``gba_apply`` concretely evaluates."""
    tokens_seed = _tomb_tokens(m, step_seed, iota)

    def state_spec(path, leaf):
        if "tokens" in path:
            return taint(TOKEN, val=tokens_seed)
        if "fill" in path:
            return taint(val=np.int32(m - 1))
        if "step" in path:
            return taint(STEP, val=np.int32(step_seed))
        if "grads" in path:
            return taint(RAW)
        return taint(PARAM)          # params + accum

    seeds = seed_taints((state,), [state_spec])
    # batch leaves fill the gap between the state and the trailing token
    jaxpr = closed.jaxpr if hasattr(closed, "jaxpr") else closed
    n_batch = len(jaxpr.invars) - len(seeds) - 1
    seeds += ([taint(RAW)] * n_batch
              + [taint(TOKEN, val=np.int32(step_seed))])

    outs, ctx = analyze(closed, seeds, site=site, f32_chain=f32_chain)
    paths = out_paths(state) + ["loss"]
    guard = lambda p: ("params" in p or "accum" in p)
    final_tokens = tokens_seed.copy()
    final_tokens[m - 1] = step_seed
    stale = (step_seed - final_tokens) > iota
    return (check_no_raw(outs, paths, guard, site)
            + check_tombstone(ctx, stale, site)
            + list(ctx.findings))


def flow_pytree_step(closed, state, *, site, iota,
                     step_seed=9) -> list[Finding]:
    """FLOW-001/002 on the per-leaf pytree train step.  One token per
    micro-step, so the taint pass runs twice over the one trace: a
    tombstone token must weight exactly 0, a fresh token nonzero.
    (FLOW-004 is not asserted here: the pytree mode deliberately
    accumulates in the arch's ``acc_dtype``; the f32-master contract
    belongs to the fused/flat path.)"""
    n_state = len(jax.tree.leaves(state))
    jaxpr = closed.jaxpr if hasattr(closed, "jaxpr") else closed
    n_batch = len(jaxpr.invars) - n_state - 1
    findings: list[Finding] = []
    paths = out_paths(state) + ["loss"]
    guard = lambda p: ("params" in p or "opt" in p or "acc" in p)
    for token_val, stale in ((step_seed - iota - 1, [True]),
                             (step_seed, [False])):
        def state_spec(path, leaf):
            if "gstep" in path:
                return taint(STEP, val=np.int32(step_seed))
            if "micro" in path:
                return taint(val=np.int32(0))
            return taint(PARAM)
        seeds = ([state_spec(p, None) for p in out_paths(state)]
                 + [taint(RAW)] * n_batch
                 + [taint(TOKEN, val=np.int32(token_val))])
        outs, ctx = analyze(closed, seeds, site=site)
        findings += check_no_raw(outs, paths, guard, site)
        findings += check_tombstone(ctx, np.asarray(stale), site)
        if findings:
            break
    return findings


def flow_sync_step(closed, pshapes, opt_shapes, *, site) -> list[Finding]:
    """FLOW-001 on the sync psum step ``(params, opt, batch, tokens,
    gstep) -> (params, opt, loss)``."""
    n_p = len(jax.tree.leaves(pshapes))
    n_o = len(jax.tree.leaves(opt_shapes))
    jaxpr = closed.jaxpr if hasattr(closed, "jaxpr") else closed
    n_batch = len(jaxpr.invars) - n_p - n_o - 2
    seeds = ([taint(PARAM)] * (n_p + n_o) + [taint(RAW)] * n_batch
             + [taint(TOKEN), taint(STEP)])
    outs, _ = analyze(closed, seeds, site=site)
    if len(outs) != n_p + n_o + 1:
        return [finding("GBA-FLOW-001", site,
                        f"sync step output arity {len(outs)} != params "
                        f"({n_p}) + opt ({n_o}) + loss — cannot prove "
                        f"the update path")]
    paths = (out_paths(pshapes) + out_paths(opt_shapes))
    return check_no_raw(outs[:-1], paths, lambda p: True, site)


def flow_aggregate_embedding(*, site, m=4, n=8, dim=8, capacity=64,
                             iota=4) -> list[Finding]:
    """FLOW-005 on the Alg. 2 per-ID aggregate: the divide that turns
    the scattered sum into a mean must be by the masked contributor
    count."""
    from functools import partial

    from repro.core.gba import aggregate_embedding
    SDS = jax.ShapeDtypeStruct
    args = (SDS((m, n), jnp.int32), SDS((m, n, dim), jnp.float32),
            SDS((m,), jnp.int32), SDS((capacity,), jnp.int32),
            SDS((), jnp.int32))
    closed = jax.make_jaxpr(
        partial(aggregate_embedding, iota=iota, capacity=capacity))(*args)
    seeds = seed_taints(args, [taint(IDS), taint(RAW), taint(TOKEN),
                               taint(STEP), taint(STEP)])
    _, ctx = analyze(closed, seeds, site=site)
    return check_divisor(ctx, site)


def check_divisor(ctx, site) -> list[Finding]:
    """FLOW-005: some divide of a gradient aggregate must exist, and
    every such divide's divisor must carry both masks."""
    out = []
    grad_divs = [(n, d, const) for n, d, const in ctx.div_events
                 if RAW in n or DECAYED in n]
    if not grad_divs:
        out.append(finding(
            "GBA-FLOW-005", site,
            "no divide of the gradient aggregate found — the mean over "
            "contributors is missing"))
        return out
    for _, den, const in grad_divs:
        if const or PAD_MASK not in den or DECAY_MASK not in den:
            have = sorted(den & {PAD_MASK, DECAY_MASK})
            out.append(finding(
                "GBA-FLOW-005", site,
                "aggregate divisor is "
                + ("a constant" if const else f"masked only by {have}")
                + " — the divisor must count exactly the valid "
                "(non-padding, non-tombstone) contributors"))
            break
    return out
