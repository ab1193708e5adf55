"""One entry point for every compiled training program: ``build_programs``.

Historically the launcher grew six parallel factories (``make_train_step``,
``make_fused_train_step``, ``make_wire_psum_steps`` and their three state
initializers), and every call site — ``launch.train``, the switching
harness, the auditor, the benches — re-assembled the same (factory, init,
specs, device_put) choreography by hand.  ``build_programs`` owns that
choreography: given a model config (or a raw loss function), a GBA config
and a mode, it returns a :class:`TrainPrograms` bundle holding the jitted
step(s), the initialized (and, when sharded, device_put) state, the flat
layout and the wire state.

Modes
-----
``pytree``
    The per-leaf XLA step (:func:`make_train_step`): pytree gradient
    accumulator + any optimizer, M-slot GBA under ``lax.cond``.
``fused``
    The single-entry fused flat-buffer step (:func:`jit_fused_train_step`):
    ONE ``gba_apply`` launch per global step (per PS shard when ``mesh``
    has a multi-device ``axis``), state donated, sharded state placed with
    ``fused_state_specs``.
``wire``
    The worker-parallel layer-grouped fused-psum pair
    (:func:`make_wire_psum_steps`): ``(warm_step, compressed_step)`` with
    an optional quantized routing wire; ``wire_state`` initialized and
    placed.  With ``compress=None`` both entries are the same uncompressed
    program — this is also the async program of the switching harness.
``sync_psum``
    The pytree all-reduce sync program
    (:func:`repro.core.gba_shard_map.make_gba_psum_step`) with Adagrad —
    the switching harness's sync mode.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.configs.base import GBAConfig, ModelConfig
from repro.core.staleness import threshold_decay
from repro.models import transformer as T
from repro.optim import Optimizer, get_optimizer

# the paper's GBA mode runs Adam (Tab. 5.1, "Others"); the 1T MoE cannot hold
# Adam's two f32 moments at 512 chips, so it trains with Adagrad — the very
# optimizer the paper uses for its async mode
ARCH_OPTIMIZER = {"kimi-k2-1t-a32b": "adagrad"}
ARCH_ACC_DTYPE = {"kimi-k2-1t-a32b": jnp.bfloat16}


# ---------------------------------------------------------------------------
# loss closure
# ---------------------------------------------------------------------------

def _loss_from_batch(params, cfg: ModelConfig, batch: dict) -> jax.Array:
    memory = batch.get("image_embeds")
    if "frames" in batch:
        memory = T.encode_audio(params, cfg, batch["frames"])
    return T.lm_loss(params, cfg, batch["tokens"], batch["labels"],
                     memory=memory)


def make_loss_fn(cfg: ModelConfig):
    """Standalone ``(params, batch) -> scalar loss`` closure over ``cfg``
    — the signature the shard_map step builders
    (:func:`repro.core.gba_shard_map.make_gba_psum_step` /
    ``make_gba_fused_psum_step``) and the switching harness
    (:class:`repro.launch.switch_driver.SwitchDriver`) consume."""
    def loss_fn(params, batch):
        return _loss_from_batch(params, cfg, batch)
    return loss_fn


def _resolve_loss(cfg: ModelConfig | None, loss_fn: Callable | None):
    if loss_fn is not None:
        return loss_fn
    if cfg is None:
        raise ValueError("build_programs needs a ModelConfig or a loss_fn")
    return make_loss_fn(cfg)


# ---------------------------------------------------------------------------
# pytree mode: per-leaf accumulator + arbitrary optimizer
# ---------------------------------------------------------------------------

def init_train_state(params: Any, optimizer: Optimizer,
                     acc_dtype=jnp.float32) -> dict:
    return {
        "params": params,
        "opt": optimizer.init(params),
        "acc": jax.tree.map(
            lambda p: jnp.zeros(p.shape, acc_dtype), params),
        "micro": jnp.zeros((), jnp.int32),
        "gstep": jnp.zeros((), jnp.int32),
    }


def make_train_step(cfg: ModelConfig, optimizer: Optimizer,
                    gba: GBAConfig):
    """Returns train_step(state, batch, token) -> (state, loss)."""
    m = gba.buffer_size
    iota = gba.staleness_tolerance

    def train_step(state, batch, token):
        loss, grads = jax.value_and_grad(_loss_from_batch)(
            state["params"], cfg, batch)
        # token-control decay at the step this slot lands in (Eq. 1)
        w = threshold_decay(token[None], state["gstep"], iota)[0]
        acc = jax.tree.map(
            lambda a, g: a + (g.astype(a.dtype) * (w / m).astype(a.dtype)),
            state["acc"], grads)
        micro = state["micro"] + 1
        is_full = (micro % m) == 0

        def apply(operands):
            params, opt, acc = operands
            params, opt = optimizer.update(params, acc, opt)
            zeros = jax.tree.map(jnp.zeros_like, acc)
            return params, opt, zeros

        def noop(operands):
            return operands

        params, opt, acc = jax.lax.cond(
            is_full, apply, noop, (state["params"], state["opt"], acc))
        new_state = {"params": params, "opt": opt, "acc": acc,
                     "micro": micro,
                     "gstep": state["gstep"] + is_full.astype(jnp.int32)}
        return new_state, loss

    return train_step


# ---------------------------------------------------------------------------
# fused mode: flat (M, N) buffer + one gba_apply launch (per PS shard)
# ---------------------------------------------------------------------------

def init_fused_train_state(params: Any, gba: GBAConfig,
                           initial_accum: float = 0.1,
                           mesh: Mesh | None = None, axis: str = "data",
                           tile: int | None = None,
                           layer_groups: bool = True):
    """State for the fused flat-buffer GBA step: params stay a pytree (the
    model consumes them), the Adagrad accumulator and the M-slot gradient
    buffer live flat.  Returns (layout, state).

    With a ``mesh`` whose ``axis`` has >1 device the flat arrays use the
    sharding-aware :class:`repro.core.flat_sharded.ShardedFlatLayout`
    (leaf- and tile-aligned slices, one per PS shard); otherwise the
    single-host ``FlatLayout``.  ``layer_groups`` (default on) makes the
    sharded layout layer-grouped under the model's canonical grouping
    (``models.transformer.param_group_key``): each layer group's extent
    is contiguous and shard-aligned, so the layer-grouped collective
    schedule (``core.gba_shard_map.make_gba_fused_psum_step``) gathers
    one group at a time — per-device peak gathered bytes is the largest
    group (``layout.peak_gather_bytes``), not the whole vector.  Pass
    ``layer_groups=False`` for the ungrouped PR-4 layout.
    """
    if mesh is not None and mesh.shape[axis] > 1:
        from repro.core.flat_sharded import init_sharded_flat_buffer
        from repro.kernels.gba_apply import BLOCK_N
        layout, buffer = init_sharded_flat_buffer(
            params, gba.buffer_size, mesh.shape[axis],
            tile or BLOCK_N,
            group_by=T.param_group_key if layer_groups else None)
        total = layout.padded_total
    else:
        from repro.core.gba import init_flat_buffer
        layout, buffer = init_flat_buffer(params, gba.buffer_size)
        total = layout.total
    state = {
        "params": params,
        "accum": jnp.full((total,), initial_accum, jnp.float32),
        "buffer": buffer,
    }
    return layout, state


def make_fused_train_step(cfg: ModelConfig, gba: GBAConfig, layout,
                          lr: float = 1e-3, eps: float = 1e-10,
                          mesh: Mesh | None = None, axis: str = "data"):
    """Adagrad GBA step on the flat buffer: push the raveled gradient; on
    the M-th microstep ONE ``gba_apply`` kernel launch does the token-decay
    aggregation and the Adagrad update for the whole dense module (vs the
    per-leaf aggregate -> optimizer XLA chain of ``make_train_step``).

    With a ``mesh`` and a :class:`~repro.core.flat_sharded.ShardedFlatLayout`
    the apply branch routes through ``make_sharded_apply``: the buffer
    columns are sliced over ``axis`` (``P(None, axis)``) and every PS
    shard launches ``gba_apply`` on its own contiguous tile-aligned slice
    — still one launch per shard per global step, bit-exact with the
    single-host path.  Without a mesh the layout is the single-host
    ``FlatLayout`` and the apply is one global launch.

    The param ravel/unravel lives INSIDE the apply branch: the M-1
    buffer-fill microsteps pay only the gradient ravel (which feeds the
    buffer anyway), not two whole-model copies.
    """
    from repro.core.gba import flat_buffer_push
    from repro.kernels import ops
    iota = gba.staleness_tolerance

    sharded_apply = None
    if mesh is not None:
        from repro.core.flat_sharded import (ShardedFlatLayout,
                                             make_sharded_apply)
        if isinstance(layout, ShardedFlatLayout):
            sharded_apply = make_sharded_apply(mesh, layout, axis=axis,
                                               iota=iota, eps=eps)

    def train_step(state, batch, token):
        loss, grads = jax.value_and_grad(_loss_from_batch)(
            state["params"], cfg, batch)
        new_buffer, is_full = flat_buffer_push(
            state["buffer"], layout.ravel(grads), token)

        def do_apply(operands):
            params, accum, grads_buf, tokens, step = operands
            if sharded_apply is not None:
                flat_p, new_accum = sharded_apply(
                    layout.ravel(params), accum, grads_buf, tokens, step,
                    jnp.asarray(lr, jnp.float32))
            else:
                flat_p, new_accum = ops.gba_apply_flat(
                    layout.ravel(params), accum, grads_buf, tokens, step,
                    lr, iota=iota, eps=eps)
            return layout.unravel(flat_p), new_accum

        def do_noop(operands):
            params, accum, *_ = operands
            return params, accum

        params, accum = jax.lax.cond(
            is_full, do_apply, do_noop,
            (state["params"], state["accum"], new_buffer["grads"],
             new_buffer["tokens"], state["buffer"]["step"]))
        return {"params": params, "accum": accum,
                "buffer": new_buffer}, loss

    return train_step


def jit_fused_train_step(cfg: ModelConfig, gba: GBAConfig, layout,
                         lr: float = 1e-3, eps: float = 1e-10,
                         mesh: Mesh | None = None, axis: str = "data"):
    """The canonical jitted form of :func:`make_fused_train_step`: state is
    DONATED (``donate_argnums=0``), so the flat (M, shard) buffer, the
    Adagrad accumulator, and the params reuse their buffers every step
    instead of double-allocating.  The static auditor's GBA-DON-001 rule
    checks this property; launchers should jit through here rather than
    wrapping ``make_fused_train_step`` ad hoc."""
    return jax.jit(
        make_fused_train_step(cfg, gba, layout, lr=lr, eps=eps,
                              mesh=mesh, axis=axis),
        donate_argnums=0)


# ---------------------------------------------------------------------------
# wire mode: worker-parallel fused-psum pair with optional quantized wire
# ---------------------------------------------------------------------------

def make_wire_psum_steps(cfg: ModelConfig | None, gba: GBAConfig, layout,
                         mesh: Mesh, *, compress=None, lr: float = 1e-3,
                         eps: float = 1e-10, axis: str = "data",
                         loss_fn: Callable | None = None):
    """Jitted (warm_step, compressed_step) pair for the worker-parallel
    layer-grouped fused-psum schedule (``core.gba_shard_map``) with an
    optional quantized wire (``core.compression.CompressionPolicy``).

    Both phases share the model loss (``_loss_from_batch``, or a caller
    ``loss_fn`` for non-LM workloads).  With a lossy policy the two
    entries are SEPARATE jitted programs — warmup routes f32 (PR-5
    bit-exact), the compressed phase routes int8 + the per-tile sideband
    — and the driver (``launch.train``) switches at the
    ``compress.warmup_steps`` boundary by calling the other function,
    i.e. a re-jit, so each phase's jaxpr carries exactly one wire dtype
    (auditor rule GBA-COLL-005).  With ``compress=None`` / scheme
    ``"none"`` both entries are the same 5-arg uncompressed step.
    """
    from repro.core.gba_shard_map import make_gba_fused_psum_step

    build = functools.partial(
        make_gba_fused_psum_step, mesh, _resolve_loss(cfg, loss_fn), layout,
        iota=gba.staleness_tolerance, lr=lr, eps=eps, axis=axis,
        compress=compress)
    if compress is None or not compress.stateful:
        step = jax.jit(build())
        return step, step
    return jax.jit(build(warm=True)), jax.jit(build(warm=False))


def init_wire_state(layout, compress, mesh: Mesh, axis: str = "data"):
    """Zero per-worker wire state (residual, and momentum for onebit)
    placed with ``distributed.sharding.wire_state_specs`` —
    ``(M, padded_total)`` f32 rows sharded ``P(axis, None)`` so worker
    ``w``'s row lives with worker ``w``.  ``None`` for lossless
    policies."""
    from repro.distributed import sharding as S
    if compress is None or not compress.stateful:
        return None
    wire = compress.init_wire_state(layout, mesh.shape[axis])
    specs = S.wire_state_specs(layout, mesh, compress.scheme, axis)
    return jax.device_put(wire, S.to_named(specs, mesh))


# ---------------------------------------------------------------------------
# the bundle
# ---------------------------------------------------------------------------

@dataclass
class TrainPrograms:
    """Everything a launcher needs to run one training mode: the jitted
    step(s), the initialized (placed) state, the flat layout, the wire
    state and the resolved optimizer.  Which fields are populated depends
    on ``mode`` — see :func:`build_programs`."""

    mode: str
    gba: GBAConfig
    mesh: Mesh | None = None
    axis: str = "data"
    cfg: ModelConfig | None = None
    optimizer: Optimizer | None = None
    layout: Any = None
    state: Any = None
    state_specs: Any = None
    wire_state: Any = None
    # jitted programs
    step: Callable | None = None            # pytree / fused / sync_psum
    warm_step: Callable | None = None       # wire mode (== compressed when
    compressed_step: Callable | None = None  # the policy is lossless)
    compress: Any = None
    notes: dict = field(default_factory=dict)

    def wire_step_for(self, async_steps_taken: int) -> Callable:
        """The wire-mode entry for the given number of async global steps
        already taken: the warmup program until ``compress.warmup_steps``,
        the compressed program after (re-jit boundary, GBA-COLL-005)."""
        if self.compress is None or not self.compress.stateful:
            return self.warm_step
        return (self.warm_step
                if async_steps_taken < self.compress.warmup_steps
                else self.compressed_step)


def _resolve_layer_groups(layer_groups) -> bool:
    if isinstance(layer_groups, str):
        return layer_groups in ("auto", "on")
    return bool(layer_groups)


def build_programs(cfg: ModelConfig | None, gba: GBAConfig, *,
                   mode: str = "fused", params: Any = None,
                   mesh: Mesh | None = None, axis: str = "data",
                   layer_groups: bool | str = "auto", compress=None,
                   optimizer: Optimizer | None = None, lr: float = 1e-3,
                   eps: float = 1e-10, acc_dtype=None,
                   initial_accum: float = 0.1, tile: int | None = None,
                   layout: Any = None, loss_fn: Callable | None = None,
                   place_state: bool = True) -> TrainPrograms:
    """Build the compiled program bundle for one training mode.

    ``cfg`` may be ``None`` when ``loss_fn`` is given (non-LM workloads,
    e.g. the switching harness's toy losses).  ``params`` initializes the
    state; pass ``params=None`` with an explicit ``layout`` to build
    steps only (the switching harness owns its own state).  Sharded fused
    state is device_put with ``fused_state_specs`` unless
    ``place_state=False``.
    """
    from repro.distributed import sharding as S

    if mode == "pytree":
        opt = optimizer or get_optimizer(
            ARCH_OPTIMIZER.get(cfg.name, "adam") if cfg else "adam", lr)
        step = jax.jit(make_train_step(cfg, opt, gba), donate_argnums=0)
        state = None
        if params is not None:
            dt = acc_dtype or (ARCH_ACC_DTYPE.get(cfg.name, jnp.float32)
                               if cfg else jnp.float32)
            state = init_train_state(params, opt, dt)
        return TrainPrograms(mode=mode, gba=gba, mesh=mesh, axis=axis,
                             cfg=cfg, optimizer=opt, state=state, step=step)

    if mode == "fused":
        state = None
        if params is not None:
            layout, state = init_fused_train_state(
                params, gba, initial_accum, mesh, axis, tile,
                _resolve_layer_groups(layer_groups))
        if layout is None:
            raise ValueError("fused mode needs params or an explicit layout")
        step = jit_fused_train_step(cfg, gba, layout, lr=lr, eps=eps,
                                    mesh=mesh, axis=axis)
        specs = None
        from repro.core.flat_sharded import ShardedFlatLayout
        if (state is not None and mesh is not None
                and isinstance(layout, ShardedFlatLayout)):
            pspecs = S.param_specs(
                jax.eval_shape(lambda t: t, params), mesh)
            specs = S.fused_state_specs(layout, mesh, pspecs, axis)
            if place_state:
                state = jax.device_put(state, S.to_named(specs, mesh))
        return TrainPrograms(mode=mode, gba=gba, mesh=mesh, axis=axis,
                             cfg=cfg, layout=layout, state=state,
                             state_specs=specs, step=step)

    if mode == "wire":
        if mesh is None:
            raise ValueError("wire mode needs a mesh")
        state = None
        if layout is None:
            if params is None:
                raise ValueError(
                    "wire mode needs params or an explicit layout")
            layout, fused_state = init_fused_train_state(
                params, gba, initial_accum, mesh, axis, tile,
                _resolve_layer_groups(layer_groups))
            state = {"param_flat": jnp.asarray(layout.ravel(params)),
                     "accum": fused_state["accum"]}
        warm, comp = make_wire_psum_steps(
            cfg, gba, layout, mesh, compress=compress, lr=lr, eps=eps,
            axis=axis, loss_fn=loss_fn)
        wire = init_wire_state(layout, compress, mesh, axis)
        return TrainPrograms(mode=mode, gba=gba, mesh=mesh, axis=axis,
                             cfg=cfg, layout=layout, state=state,
                             wire_state=wire, warm_step=warm,
                             compressed_step=comp, compress=compress)

    if mode == "sync_psum":
        from repro.core.gba_shard_map import make_gba_psum_step
        if mesh is None:
            raise ValueError("sync_psum mode needs a mesh")
        opt = optimizer or get_optimizer("adagrad", lr, eps=eps,
                                         initial_accum=initial_accum)
        step = jax.jit(make_gba_psum_step(
            mesh, _resolve_loss(cfg, loss_fn), opt,
            gba.staleness_tolerance, axis=axis))
        state = None
        if params is not None:
            state = {"params": params, "opt": opt.init(params)}
        return TrainPrograms(mode=mode, gba=gba, mesh=mesh, axis=axis,
                             cfg=cfg, optimizer=opt, state=state, step=step)

    raise ValueError(
        f"unknown mode {mode!r}: expected pytree|fused|wire|sync_psum")
