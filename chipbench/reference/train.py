"""Plain parameter-server training steps: the reference the recsys cells'
``correct`` is decided against.

It follows GBA (arXiv:2205.11048, Alg. 2) as the configuration and the
traffic state it, one slot at a time, in ``jax.numpy``:

- each slot's gradient of the model's loss, taken at the parameter
  version of the slot's dispatch step;
- the dense module: the weighted sum of the slot gradients over M, where
  the weight is the schedule's Eq. (1) decision (0 or 1);
- the sparse module (the model's ``SPARSE`` leaves): in GBA mode a slot
  whose token is staler than iota still gives the rows whose ids were not
  updated since its token (per-ID relaxation); each row's sum is divided
  by the number of slots that gave it; in sync mode the weighted sum over
  the weighted number of slots that touched the id;
- Adam on every leaf, and ``last_update`` stamped with the step on every
  row some slot gave.

The loss is the model module's own ``loss(p, cfg, batch)`` where it
defines one, else the binary cross-entropy of its ``logit``.

Nothing of the program is imported.  The reference runs float32 at
``highest`` matmul precision.  Its control (:func:`control`) runs one step
below the precision the configuration states, emulated so that the CPU and
the TPU compute the same thing (``CONTROLS``):

- ``default``: bfloat16, which casts the parameters, the model's
  arithmetic and the aggregation to it and keeps Adam in float32;
- ``high``: every matrix product in one bfloat16 pass with float32 sums
  (``precision.py``'s ``bf16``), as ``default`` runs on the TPU;
- ``highest``: every matrix product as three bfloat16 passes
  (``bf16_3x``), as ``high`` runs on the TPU.

The last two need a module whose products all go through
``precision.dot`` (``MATMULS_VIA_DOT``); for any other module the control
would equal the reference, so :func:`control` refuses it.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import model_module, precision

# the matmul precision a float32 configuration states -> its control's
# arguments to Reference
CONTROLS = {"default": {"dtype": jnp.bfloat16},
            "high": {"matmuls": "bf16"},
            "highest": {"matmuls": "bf16_3x"}}


def control(cfg: dict) -> dict:
    """The control: one step below the configuration's precision."""
    stated = cfg["matmul_precision"]
    if cfg["dtype"] != "float32" or stated not in CONTROLS:
        raise ValueError(f"no control is defined for {cfg['dtype']} at "
                         f"{stated}")
    kwargs = CONTROLS[stated]
    if "matmuls" in kwargs and not getattr(model_module(cfg),
                                           "MATMULS_VIA_DOT", False):
        raise ValueError(
            f"model {cfg['model']!r} states {stated} but does not compute "
            "its products through precision.dot: its control would equal "
            "the reference")
    return dict(kwargs)


def control_name(cfg: dict) -> str:
    """What the control computes in bfloat16: ``bfloat16`` (everything)
    or the matmul emulation's name."""
    kwargs = control(cfg)
    return kwargs.get("matmuls") or jnp.dtype(kwargs["dtype"]).name


def bce(logit: jax.Array, label: jax.Array) -> jax.Array:
    return jnp.mean(jnp.maximum(logit, 0) - logit * label
                    + jnp.log1p(jnp.exp(-jnp.abs(logit))))


def adam(params, grads, state, opt: dict):
    count = state["count"] + 1
    b1, b2, lr, eps = opt["b1"], opt["b2"], opt["lr"], opt["eps"]
    bc1 = 1.0 - b1 ** count
    bc2 = 1.0 - b2 ** count
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, state["m"], grads)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, state["v"],
                     grads)
    params = jax.tree.map(
        lambda p, m, v: p - lr * (m / bc1) / (jnp.sqrt(v / bc2) + eps),
        params, m, v)
    return params, {"m": m, "v": v, "count": count}


class Reference:
    """Runs the first steps of a schedule and keeps what is compared."""

    def __init__(self, cfg: dict, traffic: dict, dtype=jnp.float32,
                 matmuls: str | None = None):
        """``dtype``: of the parameters and the model's arithmetic;
        ``matmuls``: the emulation (``precision.PARTS``) every product of
        the model traces under, ``None`` for plain products."""
        self.cfg = cfg
        self.traffic = traffic
        self.dtype = dt = jnp.dtype(dtype)
        mod = model_module(cfg)
        sparse = mod.SPARSE
        gba = traffic["mode"] == "gba"
        cap = cfg["hash_capacity"]

        def loss(p, batch):
            p = jax.tree.map(lambda x: x.astype(dt), p)
            with precision.emulate(matmuls):
                if hasattr(mod, "loss"):
                    return mod.loss(p, cfg, batch).astype(jnp.float32)
                return bce(mod.logit(p, cfg, batch).astype(jnp.float32),
                           batch["label"])

        def accumulate(acc, version, batch, weight, slot_ok, token,
                       last_update, m):
            """Add one slot's gradient to the step's sums."""
            loss_v, g = jax.value_and_grad(loss)(version, batch)
            g = jax.tree.map(lambda x: x.astype(dt), g)
            touched = jnp.zeros((cap,), dt).at[
                mod.ids(batch).reshape(-1)].set(1)
            w = weight.astype(dt)
            if gba:
                fresh = (last_update <= token).astype(dt)
                row = touched * jnp.where(slot_ok, jnp.ones_like(fresh),
                                          fresh)
                num = {n: g[n] * (row[:, None] if g[n].ndim == 2 else row)
                       for n in sparse if n in g}
                cnt = row
                # rows of a slot past iota that the relaxation keeps, and
                # those it leaves out
                stale = jnp.logical_not(slot_ok)
                kept = jnp.where(stale, jnp.sum(row, dtype=jnp.float32), 0.)
                out = jnp.where(stale, jnp.sum(touched, dtype=jnp.float32)
                                - kept, 0.)
            else:
                num = {n: g[n] * w for n in sparse if n in g}
                cnt = touched * w
                kept = out = jnp.float32(0)
            dense = {n: jax.tree.map(lambda x: x * (w / m), g[n])
                     for n in g if n not in sparse}
            new = {"dense": dense, "sparse": num, "count": cnt,
                   "relaxed": jnp.stack([kept, out])}
            if acc is not None:
                new = jax.tree.map(jnp.add, acc, new)
            return new, loss_v

        self._first = jax.jit(lambda *a: accumulate(None, *a),
                              static_argnums=(6,))
        self._more = jax.jit(accumulate, static_argnums=(7,))

        @jax.jit
        def apply(params, state, acc, last_update, k):
            div = jnp.maximum(acc["count"], 1)
            grads = dict(acc["dense"])
            for n, s in acc["sparse"].items():
                grads[n] = s / (div[:, None] if s.ndim == 2 else div)
            grads = jax.tree.map(lambda x: x.astype(jnp.float32), grads)
            params, state = adam(params, grads, state, cfg["optimizer"])
            last_update = jnp.where(acc["count"] > 0, k, last_update)
            return params, state, last_update, grads

        self._apply = apply

    def run(self, params0: Any, steps, batch_of) -> dict:
        """``steps``: the first global steps of a schedule (lists of slots);
        ``batch_of(slot)``: that slot's batch as arrays.  Returns the
        per-step mean loss, the aggregated gradients of the first and the
        last step, the parameters and ``last_update`` after the last step,
        and the rows of stale slots the relaxation kept and left out in
        the last step."""
        with jax.default_matmul_precision("highest"):
            return self._run(params0, steps, batch_of)

    def _run(self, params0, steps, batch_of) -> dict:
        iota = self.traffic["iota"]
        params = params0
        state = {"m": jax.tree.map(jnp.zeros_like, params),
                 "v": jax.tree.map(jnp.zeros_like, params),
                 "count": jnp.zeros((), jnp.float32)}
        last_update = jnp.zeros((self.cfg["hash_capacity"],), jnp.int32)
        versions, losses, grads_at = [], [], []
        for k, slots in enumerate(steps):
            versions.append(params)
            acc, slot_losses = None, []
            for slot in slots:
                args = (versions[slot.dispatch_step], batch_of(slot),
                        jnp.float32(slot.weight),
                        jnp.bool_(k - slot.token <= iota),
                        jnp.int32(slot.token), last_update)
                if acc is None:
                    acc, loss = self._first(*args, len(slots))
                else:
                    acc, loss = self._more(acc, *args, len(slots))
                slot_losses.append(loss)
            params, state, last_update, grads = self._apply(
                params, state, acc, last_update, jnp.int32(k))
            if k in (0, len(steps) - 1):
                grads_at.append(grads)
            losses.append(float(np.mean(np.asarray(slot_losses,
                                                   np.float64))))
        kept, out = (int(x) for x in np.asarray(acc["relaxed"]))
        return {"losses": losses, "grads": grads_at, "params": params,
                "last_update": last_update,
                "relaxed_rows": {"kept": kept, "left_out": out}}
