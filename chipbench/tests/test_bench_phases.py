"""Idle time by program span and busy time by program phase
(``chipbench/phases.py``): on hand-made intervals and HLO, and on a probe
recorded on a TPU v5e (``data/phases.*``: two traced steps of a small GBA
DeepFM cell and its step variants' compiled text;
``record_phases_probe.py`` records it)."""
import gzip
import json
import re
from pathlib import Path
from types import SimpleNamespace

import pytest

from chipbench import phases as P
from chipbench import trace as T
from repro.tracing import Span

DATA = Path(__file__).parent / "data"
MS = 1e-3


# -- host side ---------------------------------------------------------------

OFFSET = 1234.5          # trace clock - program clock, seconds
# step lengths in ms: the host's pace varies, which ties each benchmark
# step to one program step
LENGTHS = [20.0, 23.0, 19.0, 25.0, 21.0, 20.0, 24.0, 22.0, 19.0, 26.0]
STARTS = [5.0 + sum(LENGTHS[:k]) * MS for k in range(len(LENGTHS))]
# per step, ms from its start: versions, inputs, dispatch, then readback
# until 0.2 ms before the step's end; the device runs from 13.5 ms to
# 1 ms before the end
CHILDREN = [("replay.versions", 0.0, 10.0), ("replay.inputs", 10.0, 12.0),
            ("replay.dispatch", 12.0, 13.0), ("replay.readback", 13.0, -0.2)]
LAG = 3e-6               # inputs start -> benchmark step start


def _ns(t: float) -> int:
    return round(t * 1e9)


def _program_step(k: int, upto: str | None = None) -> list[Span]:
    t0, out = STARTS[k], []
    for name, s, e in CHILDREN:
        end = t0 + (e if e > 0 else LENGTHS[k] + e) * MS
        out.append(Span(name, _ns(t0 + s * MS), _ns(end), {"k": k}))
        if name == upto:
            break
    return out + [Span("replay.step", _ns(t0), out[-1].end_ns, {"k": k})]


def _window():
    """Steps 0-9 of a day; the session and the window open inside step 2's
    inputs (steps 0-2 record nothing but step 2's dispatch and readback)
    and the window closes inside step 9's inputs, the aborted step."""
    spans = [s for s in _program_step(2) if s.name in ("replay.dispatch",
                                                       "replay.readback")]
    for k in range(3, 9):
        spans += _program_step(k)
    spans += _program_step(9, upto="replay.inputs")
    window = (STARTS[2] + 11 * MS + OFFSET, STARTS[9] + 10 * MS + LAG + OFFSET)
    bench = [("chipbench.step", STARTS[k] + 10 * MS + LAG + OFFSET,
              STARTS[k + 1] + 10 * MS + LAG + OFFSET) for k in range(2, 9)]
    ops = [T.Op("%fusion.1 = f32[8]{0} fusion()",
                STARTS[k] + 13.5 * MS + OFFSET,
                STARTS[k] + (LENGTHS[k] - 1) * MS + OFFSET)
           for k in range(2, 9)]
    trace = T.Trace(window, {"/device:TPU:0": T.clip_ops(ops, window)},
                    [("chipbench.window", *window), *bench])
    return trace, spans


def test_clock_offset_pairs_each_step_with_its_inputs_span():
    trace, spans = _window()
    steps = [s for n, s, _ in trace.spans if n == "chipbench.step"]
    anchors = [s.start_ns * 1e-9 for s in spans if s.name == P.ANCHOR]
    # 7 benchmark steps (2-8) against 7 anchors (3-9): one step apart
    assert P.clock_offset(steps, anchors) == pytest.approx(OFFSET + LAG,
                                                           abs=1e-6)
    # anchors recorded before the window do not move it
    early = [4.0, 4.03]
    assert P.clock_offset(steps, early + anchors) == pytest.approx(
        OFFSET + LAG, abs=1e-6)
    assert P.clock_offset(steps[:2], anchors[:2]) is None


def test_idle_time_is_split_among_the_program_spans_open_over_it():
    trace, spans = _window()
    idle = P.idle_by_span(trace, spans)
    # the device works from 13.5 ms to 1 ms before each step's end, the
    # readback ends 0.2 ms before it.  The window opens 11 ms
    # into step 2, in its inputs, which has no record (the host, 1 ms).
    # Each of steps 2-8 then idles 1 ms in dispatch, 0.5 + 0.8 ms in
    # readback and 0.2 ms between steps (the host); steps 3-9 10 ms in
    # versions; steps 3-8 2 ms in inputs; the window closes as step 9's
    # inputs opens.  The offset takes in the 3 us from an inputs span's
    # start to the benchmark step's, which the host's share gains.
    assert idle == pytest.approx({
        "host": 1 * MS + 7 * 0.2 * MS + LAG,
        "replay.dispatch": 7 * 1 * MS,
        "replay.readback": 7 * 1.3 * MS,
        "replay.versions": 7 * 10 * MS,
        "replay.inputs": 6 * 2 * MS}, abs=1e-8)
    assert sum(idle.values()) == pytest.approx(
        trace.window_s - trace.busy_s(), abs=1e-9)
    assert P.idle_by_span(trace, []) is None


def test_innermost_spans_cover_time_once():
    got = P.innermost([("step", 0.0, 10.0), ("a", 1.0, 3.0),
                       ("b", 3.0, 4.0), ("other", 12.0, 13.0)])
    assert got == [(0.0, 1.0, "step"), (1.0, 3.0, "a"), (3.0, 4.0, "b"),
                   (4.0, 10.0, "step"), (12.0, 13.0, "other")]


# -- device side -------------------------------------------------------------

HLO = """HloModule jit_step, is_scheduled=true

%fused_computation.1 (param_0.1: f32[8], param_1.1: s32[8]) -> f32[8,4] {
  %param_0.1 = f32[8]{0} parameter(0)
  %transpose.1 = f32[8]{0} transpose(%param_0.1), dimensions={0}, metadata={op_name="jit(step)/vmap(transpose(jvp(dense)))/add_any"}
  %param_1.1 = s32[8]{0} parameter(1)
  ROOT %scatter.1 = f32[8,4]{1,0} scatter(%transpose.1, %param_1.1), to_apply=%region_1
}

%fused_computation.2 (param_0.2: f32[8]) -> f32[8] {
  %param_0.2 = f32[8]{0} parameter(0)
  %multiply.2 = f32[8]{0} multiply(%param_0.2, %param_0.2), metadata={op_name="jit(step)/vmap(jvp(dense))/mul"}
  ROOT %add.2 = f32[8]{0} add(%multiply.2, %param_0.2), metadata={op_name="jit(step)/vmap(jvp(dense))/add"}
}

%body.1 (p.1: (s32[], f32[8,4])) -> (s32[], f32[8,4]) {
  %p.1 = (s32[], f32[8,4]{1,0}) parameter(0)
  %get-tuple-element.1 = s32[] get-tuple-element(%p.1), index=0
  %get-tuple-element.2 = f32[8,4]{1,0} get-tuple-element(%p.1), index=1
  ROOT %tuple.2 = (s32[], f32[8,4]{1,0}) tuple(%get-tuple-element.1, %get-tuple-element.2)
}

%cond.1 (p.2: (s32[], f32[8,4])) -> pred[] {
  %p.2 = (s32[], f32[8,4]{1,0}) parameter(0)
  ROOT %constant.9 = pred[] constant(false)
}

ENTRY %main.1 (a.1: f32[8], ids.1: s32[8]) -> f32[8,4] {
  %a.1 = f32[8]{0:T(1024)} parameter(0), metadata={op_name="params['embed']"}
  %ids.1 = s32[8]{0} parameter(1)
  %copy.1 = f32[8]{0:T(1024)S(1)} copy(%a.1)
  %gather.1 = f32[8]{0} gather(%copy.1, %ids.1), metadata={op_name="jit(step)/dense/embedding/gather"}
  %fusion.2 = f32[8]{0} fusion(%gather.1), kind=kLoop, calls=%fused_computation.2
  %fusion.1 = f32[8,4]{1,0:T(8,128)} fusion(%fusion.2, %ids.1), kind=kLoop, calls=%fused_computation.1
  %constant.1 = s32[] constant(0)
  %tuple.1 = (s32[], f32[8,4]{1,0}) tuple(%constant.1, %fusion.1)
  %while.1 = (s32[], f32[8,4]{1,0}) while(%tuple.1), condition=%cond.1, body=%body.1, metadata={op_name="jit(step)/aggregate/jit(_embedding_bag_grad_streamed)/while"}
  %get-tuple-element.3 = f32[8,4]{1,0} get-tuple-element(%while.1), index=1
  ROOT %multiply.1 = f32[8,4]{1,0} multiply(%get-tuple-element.3, %get-tuple-element.3), metadata={op_name="jit(step)/apply/mul"}
}
"""


def test_scope_is_the_innermost_program_scope():
    assert P.scope_of("jit(step)/vmap(transpose(jvp(embedding)))/"
                      "scatter-add") == "embedding"
    assert P.scope_of("jit(step)/dense/embedding/gather") == "embedding"
    assert P.scope_of("jit(step)/aggregate/jit(_embedding_bag_grad_"
                      "streamed)/while") == "aggregate"
    assert P.scope_of("params['embed']") is None


def test_op_key_reads_trace_events_and_hlo_lines_alike():
    event = ("%fusion.5 = s32[8192]{0:T(1024)S(1)} fusion(s32[8192]"
             "{0:T(1024)S(1)} %sort.3), kind=kLoop, calls=%fused.12")
    assert P.op_key(event) == ("fusion.5", "s32[8192]")
    tup = ("%_embedding_bag_grad_streamed.1 = (f32[16000000,128]{1,0:T(8,128)"
           "}, f32[16,1,1000000]{2,1,0:T(1,128)}) custom-call(s32[1,8]"
           "{1,0} %r), custom_call_target=\"tpu_custom_call\"")
    assert P.op_key(tup) == ("_embedding_bag_grad_streamed.1",
                             "(f32[16000000,128], f32[16,1,1000000])")
    assert P.op_key("  ROOT %tuple.2 = (s32[], /*index=1*/f32[8,4]{1,0}) "
                    "tuple(%a, %b)") == ("tuple.2", "(s32[], f32[8,4])")
    assert P.op_key("not an instruction") is None


def test_module_phases_fill_unscoped_instructions():
    got = {k[0]: v for k, v in P.module_phases(HLO).items()}
    assert got == {
        # a parameter and a layout copy take the phase of their reader
        "a.1": "embedding", "copy.1": "embedding", "gather.1": "embedding",
        # a fusion without metadata: the phase its computing ops name ...
        "fusion.2": "dense",
        # ... or, where only data movement votes, its reader's
        "fusion.1": "aggregate",
        "ids.1": "embedding", "constant.1": "aggregate",
        "tuple.1": "aggregate", "while.1": "aggregate",
        "get-tuple-element.3": "apply", "multiply.1": "apply",
        # a loop's body and condition fall back on the loop's phase
        "p.1": "aggregate", "get-tuple-element.1": "aggregate",
        "get-tuple-element.2": "aggregate", "tuple.2": "aggregate",
        "p.2": "aggregate", "constant.9": "aggregate"}
    assert P.coverage(HLO) == 1.0


def test_op_phases_leave_out_clashing_keys_and_unscoped_modules():
    other = HLO.replace('op_name="jit(step)/apply/mul"',
                        'op_name="jit(step)/dense/mul"')
    got = P.op_phases([HLO, other])
    assert ("multiply.1", "f32[8,4]") not in got
    assert got[("while.1", "(s32[], f32[8,4])")] == "aggregate"
    bare = re.sub(r', metadata=\{[^}]*\}', "", HLO)
    assert P.op_phases([bare]) is None


def test_phase_seconds_take_each_phases_union():
    ops = [T.Op("%while.1 = (s32[], f32[8,4]{1,0}) while(%tuple.1)", 0.0,
                4.0),
           T.Op("%tuple.2 = (s32[], f32[8,4]{1,0}) tuple(%a, %b)", 1.0, 2.0),
           T.Op("%multiply.1 = f32[8,4]{1,0} multiply(%g, %g)", 5.0, 6.0),
           T.Op("%other.7 = f32[2]{0} add(%x, %y)", 6.0, 6.5)]
    trace = T.Trace((0.0, 10.0), {"/device:TPU:0": ops})
    got = P.phase_seconds(trace, P.op_phases([HLO]))
    assert got == pytest.approx({"aggregate": 4.0, "apply": 1.0,
                                 None: 0.5})


SCATTER_HLO = """HloModule jit_step, is_scheduled=true

%region_1 (a.1: f32[], b.1: f32[]) -> f32[] {
  %a.1 = f32[] parameter(0), metadata={op_name="scatter-add"}
  %b.1 = f32[] parameter(1), metadata={op_name="scatter-add"}
  ROOT %add.1 = f32[] add(%a.1, %b.1), metadata={op_name="jit(step)/vmap(transpose(jvp(embedding)))/add"}
}

%fused_computation.1 (param_0.1: f32[8,4], param_1.1: s32[8], param_2.1: f32[8,4]) -> f32[8,4] {
  %param_0.1 = f32[8,4]{1,0} parameter(0)
  %param_1.1 = s32[8]{0} parameter(1)
  %param_2.1 = f32[8,4]{1,0} parameter(2)
  ROOT %scatter.1 = f32[8,4]{1,0} scatter(%param_0.1, %param_1.1, %param_2.1), to_apply=%region_1
}

ENTRY %main.1 (g.1: f32[8,4], ids.1: s32[8], z.1: f32[8,4]) -> f32[8,4] {
  %g.1 = f32[8,4]{1,0} parameter(0)
  %ids.1 = s32[8]{0} parameter(1)
  %z.1 = f32[8,4]{1,0} parameter(2)
  %fusion.1 = f32[8,4]{1,0} fusion(%z.1, %ids.1, %g.1), kind=kCustom, calls=%fused_computation.1
  ROOT %multiply.1 = f32[8,4]{1,0} multiply(%fusion.1, %fusion.1), metadata={op_name="jit(step)/aggregate/mul"}
}
"""


def test_a_scatter_without_its_name_takes_its_reducers_phase():
    """The compiler drops the op name from a table gradient's scatter but
    keeps it on the scatter's reducer: the fusion is embedding work,
    though its reader aggregates."""
    got = {k[0]: v for k, v in P.module_phases(SCATTER_HLO).items()}
    assert got["fusion.1"] == "embedding"
    assert got["multiply.1"] == "aggregate"


def test_device_ms_falls_silent_where_the_phases_miss_the_busy_time(
        monkeypatch):
    phase_of = P.op_phases([HLO])
    cfg, traffic = {"name": "probe"}, {"mode": "gba"}
    monkeypatch.setitem(P._MAPS, json.dumps([cfg, traffic], sort_keys=True),
                        phase_of)
    monkeypatch.setattr(P, "_DONE", {})

    def rec(unmatched_s):
        ops = [T.Op("%while.1 = (s32[], f32[8,4]{1,0}) while(%tuple.1)", 0.0,
                    4.0),
               T.Op("%other.7 = f32[2]{0} add(%x, %y)", 4.0,
                    4.0 + unmatched_s)]
        trace = T.Trace((0.0, 10.0), {"/device:TPU:0": ops})
        return SimpleNamespace(trace=trace, steps=2, cfg=cfg,
                               traffic=traffic)

    fits, stale = rec(0.5), rec(1.0)
    assert P.phased_share(fits.trace, phase_of) == pytest.approx(4 / 4.5)
    assert P.device_ms(fits, "aggregate") == pytest.approx(2000.0)
    assert P.device_ms(fits, "dense") == 0.0
    # 80% of the busy time has a phase: the map no longer fits
    assert P.phased_share(stale.trace, phase_of) == pytest.approx(0.8)
    assert P.device_ms(stale, "aggregate") is None


# -- the probe ---------------------------------------------------------------

@pytest.fixture(scope="module")
def probe():
    """The probe's trace, its step programs' runs on the device (the
    module line) and the compiled text of its step variants."""
    from jax.profiler import ProfileData
    raw = gzip.decompress((DATA / "phases.xplane.pb.gz").read_bytes())
    runs = [(ev.start_ns * 1e-9, ev.end_ns * 1e-9)
            for plane in ProfileData.from_serialized_xspace(raw).planes
            if plane.name.startswith(T.DEVICE_PREFIX)
            for line in plane.lines if line.name == "XLA Modules"
            for ev in line.events if ev.name.startswith("jit_step(")]
    trace = T.Trace.from_profile(ProfileData.from_serialized_xspace(raw))
    with gzip.open(DATA / "phases.hlo.txt.gz", "rt") as f:
        texts = [t for t in re.split(r"(?m)^(?=HloModule )", f.read())
                 if t.strip()]
    return trace, runs, texts


def test_probe_step_busy_time_gets_a_phase(probe):
    trace, runs, texts = probe
    assert len(texts) == 2 and runs
    phase_of = P.op_phases(texts)
    ops = [o for o in trace.devices["/device:TPU:0"]
           if any(s <= o.start and o.end <= e for s, e in runs)]
    busy = T.union_length([(o.start, o.end) for o in ops])
    named = T.union_length([(o.start, o.end) for o in ops
                            if P.op_key(o.name) in phase_of])
    assert named >= 0.9 * busy > 0
    assert P.phased_share(trace, phase_of) >= P.MIN_PHASED
    seconds = P.phase_seconds(trace, phase_of)
    assert all(seconds[p] > 0 for p in P.SCOPES)
    # the version stack's own programs run outside the step and count in
    # no phase
    assert seconds[None] > 0


# -- a configuration's own phases --------------------------------------------

@pytest.fixture(scope="module")
def nested_hlo() -> str:
    """The CPU-compiled text of a small vmapped gradient step with a scope
    ``interest`` nested inside ``dense``, as a model would nest its own
    mechanism."""
    import jax
    import jax.numpy as jnp

    def loss(w, x):
        with jax.named_scope("dense"):
            h = jnp.tanh(x @ w)
            with jax.named_scope("interest"):
                h = h + jnp.sin(h @ w)
            return jnp.sum(h * h)

    step = jax.jit(jax.vmap(jax.grad(loss), in_axes=(None, 0)))
    return step.lower(jnp.ones((8, 8)), jnp.ones((4, 3, 8))).compile(
    ).as_text()


def test_a_configured_phase_takes_the_ops_nested_in_it(nested_hlo):
    scopes = P.scopes_of({"phases": ["interest"]})
    assert scopes == (*P.SCOPES, "interest")
    own, four = P.op_phases([nested_hlo], scopes), P.op_phases([nested_hlo])
    assert own.keys() == four.keys()
    nested = {k for k, v in own.items() if v == "interest"}
    assert nested and {four[k] for k in nested} == {"dense"}
    assert {own[k] for k in own.keys() - nested} == {"dense"}
    assert "interest" not in four.values()
    assert P.scope_of("jit(step)/vmap(transpose(jvp(dense)))/interest/mul",
                      scopes) == "interest"
    assert P.scope_of("jit(step)/vmap(transpose(jvp(dense)))/interest/mul"
                      ) == "dense"


def test_device_ms_is_silent_for_a_phase_no_instruction_carries(
        nested_hlo, monkeypatch):
    monkeypatch.setattr(P, "_MAPS", {})
    monkeypatch.setattr(P, "_DONE", {})
    monkeypatch.setattr(P, "compiled_texts", lambda cfg, traffic:
                        [nested_hlo])
    declared = {"name": "nested", "phases": ["interest", "absent"]}
    phase_of = P.op_phases([nested_hlo], P.scopes_of(declared))
    # one op of each phase the step carries, a millisecond each
    keys = {v: k for k, v in phase_of.items()}
    ops = [T.Op(f"%{name} = {shape} fusion()", i * MS, (i + 1) * MS)
           for i, (name, shape) in enumerate(keys.values())]
    assert set(keys) == {"dense", "interest"}

    def rec(cfg):
        trace = T.Trace((0.0, 10 * MS), {"/device:TPU:0": ops})
        return SimpleNamespace(trace=trace, steps=1, cfg=cfg,
                               traffic={"mode": "sync"})

    own, four = rec(declared), rec({"name": "nested"})
    assert P.device_ms(own, "interest") == pytest.approx(1.0)
    assert P.device_ms(own, "dense") == pytest.approx(1.0)
    # declared, or one of the four, but carried by no instruction
    assert P.device_ms(own, "absent") is None
    assert P.device_ms(own, "embedding") is None
    # without the configuration's list the nested ops are dense
    assert P.device_ms(four, "interest") is None
    assert P.device_ms(four, "dense") == pytest.approx(2.0)


def test_probe_map_is_unchanged_by_configured_phases(probe):
    """The probe's map, as the four fixed scopes gave it (577 keys, the
    sha256 of their sorted JSON), for a configuration without ``phases``
    and for one that lists a scope the probe's step does not carry."""
    import hashlib
    _, _, texts = probe
    maps = [P.op_phases(texts), P.op_phases(texts, P.scopes_of({})),
            P.op_phases(texts, P.scopes_of({"phases": ["interest"]}))]
    assert maps[0] == maps[1] == maps[2]
    items = json.dumps(sorted([list(k), v] for k, v in maps[0].items()))
    assert len(maps[0]) == 577
    assert hashlib.sha256(items.encode()).hexdigest() == (
        "ca4734fd78db126592f2a22f860095fbfeae0175008fe9ea8001b042e81ec7d2")
    assert [P.coverage(t) for t in texts] == [1.0, 1.0]
