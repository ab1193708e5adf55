"""Idle time by the program's host span, busy time by the program's phase.

Host side.  While a profiler session records, ``repro.tracing`` keeps the
program's own spans in memory (``replay.step`` and its children
``replay.versions``, ``replay.inputs``, ``replay.dispatch``,
``replay.readback``) on the host's monotonic clock.  The benchmark's
``chipbench.step`` span opens in the first ``stream.batch`` call of a step,
inside that step's ``replay.inputs``, so the two clocks differ by the
median of (``chipbench.step`` start - ``replay.inputs`` start) over the
window's steps, once the two lists are aligned step for step.  Each idle
stretch of the trace is then named by the innermost program span open over
it (``host`` where none is); a gap that outlasts one span is split among
the spans it covers, so a gap that runs from one step's readback through
the next step's versions, inputs and dispatch counts in all four.

Device side.  The compiled replay step carries the program's
``jax.named_scope`` phases (``embedding``, ``dense``, ``aggregate``,
``apply``, and the scopes a configuration lists under ``phases``, each
nested inside one of the four) in each instruction's ``op_name``; the
innermost one it names is its phase, so ops under ``dense/interest``
count as ``interest`` where the configuration lists it, and ``dense``
keeps the rest.  Each step variant the cell
runs is compiled again, from the same abstract arguments as ``rehearse.py``
under the configuration's matmul precision (a hit in the checkout's
compilation cache), and every instruction is keyed by its name and result
shape, the head of a trace event's name.  An instruction the compiler
added without a scope (a copy, a prefetch, a layout change) takes the
phase of the first instruction that reads it, else of its operands; a
fusion without one takes the most common phase of the computing
instructions it fuses, where they have one.  A key that two variants give different phases is left out.

Both sides return ``None`` where the program has nothing to read: no span
store, no spans in the window, no scopes in the compiled step, or no
instruction in it that carries the phase asked for.  The
device side also returns ``None`` where the phased ops cover less than
``MIN_PHASED`` of the window's busy time: the map no longer fits the
executable the window ran (a recompile that differs, clashing keys,
truncated event names), and a phase that lost its ops would read as a
gain.
"""
from __future__ import annotations

import collections
import functools
import re
import statistics

from chipbench import trace as T

# the program's phases; a configuration may add scopes nested in them
SCOPES = ("embedding", "dense", "aggregate", "apply")
STEP_SPAN = "chipbench.step"
ANCHOR = "replay.inputs"
OUTSIDE = "host"
# share of the busy time the phased ops must cover; the rest is the
# programs outside the step (the version stack, the loss's mean)
MIN_PHASED = 0.85
MOVES = frozenset({"parameter", "constant", "tuple", "get-tuple-element",
                   "bitcast", "reshape", "transpose", "copy", "broadcast",
                   "convert", "slice", "concatenate", "pad", "iota"})

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%(?P<name>[\w.\-]+) = (?P<shape>.*?) "
                    r"(?P<op>[a-z][a-z0-9\-]*)\((?P<rest>.*)$")
_COMP = re.compile(r"^(?P<entry>ENTRY )?%(?P<name>[\w.\-]+) .*\{\s*$")
_LAYOUT = re.compile(r"\{[^{}]*\}|/\*[^*]*\*/")
_REF = re.compile(r"%([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_RUNS = re.compile(r"\b(?:body|condition)=%([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_INNER = re.compile(r"\b(?:calls|to_apply)=%([\w.\-]+)")

Key = tuple[str, str]


# -- host side ---------------------------------------------------------------

def program_spans():
    """The program's recorded spans, or ``None`` where the program keeps
    none (a checkout without ``repro.tracing``)."""
    try:
        from repro import tracing
    except ImportError:
        return None
    return tracing.records()


def clock_offset(step_starts: list[float], anchor_starts: list[float]
                 ) -> float | None:
    """Seconds to add to a program time to put it on the trace's clock.

    The ``i``-th benchmark step and the ``i + shift``-th anchor are the same
    global step for one shift: the one whose differences spread least
    (their interquartile range); the offset is their median.  The window's
    first step has no anchor (the session began inside it) and its last,
    aborted, step has no benchmark step, so the lists are compared at every
    shift that pairs at least half of the shorter one."""
    a, b = sorted(step_starts), sorted(anchor_starts)
    need = max(3, min(len(a), len(b)) // 2)
    best = None
    for shift in range(-len(a) + 1, len(b)):
        d = [a[i] - b[i + shift] for i in range(len(a))
             if 0 <= i + shift < len(b)]
        if len(d) < need:
            continue
        q = statistics.quantiles(d, n=4)
        spread = q[2] - q[0]
        if best is None or spread < best[0]:
            best = (spread, statistics.median(d))
    return None if best is None else best[1]


def innermost(spans: list[tuple[str, float, float]]
              ) -> list[tuple[float, float, str]]:
    """The stretches of time that some span covers, each with the
    innermost span open over it.  The spans come from one thread, so they
    nest."""
    ordered = sorted(spans, key=lambda s: (s[1], -s[2]))
    points = sorted({t for _, s, e in spans for t in (s, e)})
    out, stack, i = [], [], 0
    for a, b in zip(points, points[1:]):
        while i < len(ordered) and ordered[i][1] <= a:
            stack.append(ordered[i])
            i += 1
        stack = [s for s in stack if s[2] > a]
        if stack:
            out.append((a, b, stack[-1][0]))
    return out


def idle_by_span(trace, spans) -> dict[str, float] | None:
    """Idle seconds of the trace's window per innermost program span open
    over them (``host`` where none is), averaged over the devices.  An
    idle gap that spans several program spans is split among them."""
    if not spans or not trace.devices:
        return None
    steps = [s for n, s, _ in trace.spans if n == STEP_SPAN]
    anchors = [s.start_ns * 1e-9 for s in spans if s.name == ANCHOR]
    offset = clock_offset(steps, anchors)
    if offset is None:
        return None
    pieces = innermost([(s.name, s.start_ns * 1e-9 + offset,
                         s.end_ns * 1e-9 + offset) for s in spans])
    out: dict[str, float] = collections.defaultdict(float)
    for ops in trace.devices.values():
        j = 0
        for gs, ge in T.gaps([(o.start, o.end) for o in ops], trace.window):
            while j < len(pieces) and pieces[j][1] <= gs:
                j += 1
            named = 0.0
            for ps, pe, name in pieces[j:]:
                if ps >= ge:
                    break
                part = min(pe, ge) - max(ps, gs)
                out[name] += part
                named += part
            out[OUTSIDE] += (ge - gs) - named
    n = len(trace.devices)
    return {k: v / n for k, v in out.items()}


# -- device side -------------------------------------------------------------

def op_key(text: str) -> Key | None:
    """An instruction's name and its result shape without layouts, from a
    line of HLO text or a trace event's name."""
    m = _INSTR.match(text)
    if m is None:
        return None
    return m["name"], _LAYOUT.sub("", m["shape"])


def scopes_of(cfg: dict) -> tuple[str, ...]:
    """The phases of a configuration's step: the program's four and the
    configuration's own ``phases``."""
    return SCOPES + tuple(cfg.get("phases", ()))


@functools.lru_cache(maxsize=None)
def _scope_pattern(scopes: tuple[str, ...]) -> re.Pattern:
    return re.compile(r"\b(" + "|".join(map(re.escape, scopes)) + r")\b")


def scope_of(op_name: str, scopes: tuple[str, ...] = SCOPES) -> str | None:
    """The innermost of ``scopes`` named in an ``op_name``."""
    found = _scope_pattern(scopes).findall(op_name)
    return found[-1] if found else None


def _computations(text: str, scopes: tuple[str, ...] = SCOPES
                  ) -> tuple[dict[str, list[dict]], str | None]:
    comps: dict[str, list[dict]] = {}
    entry, current = None, None
    for line in text.splitlines():
        c = _COMP.match(line)
        if c is not None:
            current = comps.setdefault(c["name"], [])
            if c["entry"]:
                entry = c["name"]
            continue
        m = _INSTR.match(line)
        if m is None or current is None:
            continue
        op_name = _OP_NAME.search(m["rest"])
        current.append({
            "name": m["name"], "key": (m["name"],
                                       _LAYOUT.sub("", m["shape"])),
            "op": m["op"], "rest": m["rest"],
            "phase": scope_of(op_name.group(1), scopes) if op_name
            else None})
    return comps, entry


def _own_phase(comps, ins: dict, seen=()) -> str | None:
    """An instruction's own phase; for one without it that runs a
    computation (a fusion's fused instructions, a scatter's or reduce's
    reducer, which keeps the op's name where the compiler dropped it from
    the op), the most common phase among that computation's instructions
    that compute: data movement (a reshape, transpose, copy) inherits the
    scope of whatever the compiler folded into it, so it does not vote."""
    inner = _INNER.search(ins["rest"])
    if ins["phase"] is not None or inner is None or inner.group(1) in seen:
        return ins["phase"]
    name = inner.group(1)
    votes = collections.Counter(
        _own_phase(comps, i, (*seen, name)) for i in comps.get(name, ())
        if i["op"] not in MOVES)
    votes.pop(None, None)
    return votes.most_common(1)[0][0] if votes else None


def _propagate(instrs: list[dict]) -> None:
    """Give each instruction without a phase the phase of its first user
    that has one; where no user has one, that of its first operand that
    has one; until nothing changes."""
    names = {i["name"] for i in instrs}
    operands = {i["name"]: [r for r in _REF.findall(i["rest"]) if r in names]
                for i in instrs}
    users = collections.defaultdict(list)
    for i in instrs:
        for r in operands[i["name"]]:
            users[r].append(i["name"])
    phase = {i["name"]: i["phase"] for i in instrs}

    def fill(near) -> bool:
        changed = False
        for name, p in phase.items():
            if p is None:
                phase[name] = next((phase[n] for n in near[name] if phase[n]),
                                   None)
                changed |= phase[name] is not None
        return changed

    while fill(users) or fill(operands):
        pass
    for i in instrs:
        i["phase"] = phase[i["name"]]


def _called(ins: dict) -> list[str]:
    """Computations an instruction runs as ops of their own: loop bodies
    and conditions, conditional branches."""
    out = _RUNS.findall(ins["rest"])
    for group in _BRANCHES.findall(ins["rest"]):
        out += _REF.findall(group)
    return out


def module_phases(text: str, scopes: tuple[str, ...] = SCOPES
                  ) -> dict[Key, str | None]:
    """Phase (or ``None``) of every instruction that runs as an op of its
    own in one compiled module: the entry computation's and, from there,
    those of loop bodies, conditions and branches, which fall back on the
    phase of the instruction that runs them."""
    comps, entry = _computations(text, scopes)
    out: dict[Key, str | None] = {}
    todo, seen = [(entry, None)], set()
    while todo:
        name, outer = todo.pop()
        if name in seen or name not in comps:
            continue
        seen.add(name)
        instrs = comps[name]
        for ins in instrs:
            ins["phase"] = _own_phase(comps, ins)
        _propagate(instrs)
        for ins in instrs:
            ins["phase"] = ins["phase"] or outer
            out[ins["key"]] = ins["phase"]
            todo += [(c, ins["phase"]) for c in _called(ins)]
    return out


def op_phases(texts: list[str], scopes: tuple[str, ...] = SCOPES
              ) -> dict[Key, str] | None:
    """One key -> phase map over every step variant's compiled text;
    ``None`` when no instruction carries one of ``scopes``."""
    pattern = _scope_pattern(scopes)
    if not any(pattern.search(m) for t in texts for m in _OP_NAME.findall(t)):
        return None
    out: dict[Key, str] = {}
    clash: set[Key] = set()
    for text in texts:
        for key, phase in module_phases(text, scopes).items():
            if phase is None:
                continue
            if out.get(key, phase) != phase:
                clash.add(key)
            out[key] = phase
    for key in clash:
        del out[key]
    return out


def coverage(text: str, scopes: tuple[str, ...] = SCOPES) -> float:
    """Share of a module's executed instructions that get a phase."""
    phases = list(module_phases(text, scopes).values())
    return sum(p is not None for p in phases) / max(len(phases), 1)


def phase_seconds(trace, phase_of: dict[Key, str]) -> dict[str, float]:
    """Per phase, the union of its ops' intervals in the window, averaged
    over the devices; ops that match no key go under ``None``."""
    out: dict = collections.defaultdict(float)
    for ops in trace.devices.values():
        by_phase = collections.defaultdict(list)
        for o in ops:
            by_phase[phase_of.get(op_key(o.name))].append((o.start, o.end))
        for phase, iv in by_phase.items():
            out[phase] += T.union_length(iv)
    n = max(len(trace.devices), 1)
    return {k: v / n for k, v in out.items()}


def phased_share(trace, phase_of: dict[Key, str]) -> float:
    """Share of the busy time, averaged over the devices, in which an op
    with a phase ran."""
    busy = trace.busy_s()
    if busy <= 0:
        return 0.0
    phased = sum(T.union_length([(o.start, o.end) for o in ops
                                 if op_key(o.name) in phase_of])
                 for ops in trace.devices.values())
    return phased / len(trace.devices) / busy


def compiled_texts(cfg: dict, traffic: dict, sharding=None) -> list[str]:
    """The optimized HLO text of each replay-step variant the cell runs
    (GBA: versions shared and stacked; sync: shared), compiled from
    abstract arguments as ``rehearse.py`` builds them, on ``sharding``
    (``None``: the default device, as the runner's arrays)."""
    import jax
    import jax.numpy as jnp
    from chipbench.reference import model_module
    from chipbench.rehearse import sds
    from chipbench.runners import recsys_replay as R
    from repro.embeddings.table import StreamConfig

    trainer, optimizer = R.make_trainer(cfg, traffic)
    trainer.embed_stream = StreamConfig(interpret=False)
    mod = model_module(cfg)
    params = jax.eval_shape(lambda k: mod.init(k, cfg),
                            jax.random.PRNGKey(0))
    opt = jax.eval_shape(optimizer.init, params)
    m, lb = R.slots_per_step(traffic), traffic["local_batch"]

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    batch = {"fields": arg((m, lb, cfg["num_fields"]), jnp.int32),
             "label": arg((m, lb), jnp.float32)}
    if cfg["behavior_len"]:
        batch["behavior"] = arg((m, lb, cfg["behavior_len"]), jnp.int32)
        batch["target"] = arg((m, lb), jnp.int32)
    gba = traffic["mode"] == "gba"
    texts = []
    with jax.default_matmul_precision(cfg["matmul_precision"]):
        for shared in ([True, False] if gba else [True]):
            step = trainer._make_step(gba, m, shared)
            args = (sds(params, sharding, None if shared else m),
                    sds(params, sharding), sds(opt, sharding), batch,
                    arg((m,), jnp.int32), arg((m,), jnp.float32),
                    arg((), jnp.int32), arg((cfg["hash_capacity"],),
                                            jnp.int32))
            texts.append(step.lower(*args).compile().as_text())
    return texts


# -- what the metric readers call --------------------------------------------

_MAPS: dict[str, dict | None] = {}
_DONE: dict[tuple[str, int], dict | None] = {}


def _once(kind: str, rec, compute):
    key = (kind, id(rec))
    if key not in _DONE:
        _DONE[key] = compute()
    return _DONE[key]


def idle_share(rec, span: str) -> float | None:
    """Share of the window, in %, idle while ``span`` was the innermost
    program span."""
    if rec.trace is None:
        return None
    idle = _once("idle", rec,
                 lambda: idle_by_span(rec.trace, program_spans()))
    if idle is None:
        return None
    return 100.0 * idle.get(span, 0.0) / rec.trace.window_s


def device_ms(rec, phase: str) -> float | None:
    """Device milliseconds per global step in ops of ``phase``; ``None``
    where no instruction of the compiled step carries ``phase`` (a scope
    the program lost would otherwise read as a gain), or where the phases
    cover less than ``MIN_PHASED`` of the busy time."""
    if rec.trace is None or not rec.steps:
        return None

    def compute():
        import json
        cell = json.dumps([rec.cfg, rec.traffic], sort_keys=True)
        if cell not in _MAPS:
            _MAPS[cell] = op_phases(compiled_texts(rec.cfg, rec.traffic),
                                    scopes_of(rec.cfg))
        phase_of = _MAPS[cell]
        if phase_of is None or phased_share(rec.trace, phase_of) < MIN_PHASED:
            return None
        return phase_of, phase_seconds(rec.trace, phase_of)

    found = _once("device", rec, compute)
    if found is None or phase not in found[0].values():
        return None
    return 1e3 * found[1].get(phase, 0.0) / rec.steps
