"""Pallas TPU kernel: fused Adagrad update.

Adagrad is the paper's optimizer for the async/GBA modes (Tab. 5.1).  The
naive XLA form reads grad, reads accum, writes accum, reads accum again,
writes param — this kernel does one VMEM pass per block: accum += g^2;
param -= lr * g / (sqrt(accum) + eps), with both outputs aliased in-place.

NOTE: when the gradient comes from the GBA buffer, the train path uses
``repro.kernels.gba_apply`` instead, which fuses the buffer aggregation
with this update in the same pass (the gradient never hits HBM).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import runtime
from repro.kernels.launch_meta import (BlockMeta, LaunchMeta, block_specs,
                                       _round_up_static)

BLOCK = 4096


def adagrad_vmem_bytes(block: int = BLOCK) -> int:
    """Per-grid-step VMEM residency: lr + param/grad/accum in blocks +
    param/accum out blocks, all f32."""
    return 4 + 5 * block * 4


def launch_meta(n: int, param_dtype=jnp.float32,
                grad_dtype=jnp.float32) -> LaunchMeta:
    """Static launch geometry for an (n,)-param fused Adagrad update; the
    pallas_call builds its specs from this.  param -> new_param and
    accum -> new_accum are aliased in-place (the docstring's claim, now
    declared to XLA and audited by GBA-DON rules)."""
    np_ = _round_up_static(n, BLOCK)
    return LaunchMeta(
        kernel="fused_adagrad",
        grid=(np_ // BLOCK,),
        inputs=(
            BlockMeta("lr", (1,), jnp.float32, (1,), lambda i: (0,)),
            BlockMeta("param", (np_,), param_dtype, (BLOCK,),
                      lambda i: (i,)),
            BlockMeta("grad", (np_,), grad_dtype, (BLOCK,),
                      lambda i: (i,)),
            BlockMeta("accum", (np_,), jnp.float32, (BLOCK,),
                      lambda i: (i,)),
        ),
        outputs=(
            BlockMeta("new_param", (np_,), param_dtype, (BLOCK,),
                      lambda i: (i,)),
            BlockMeta("new_accum", (np_,), jnp.float32, (BLOCK,),
                      lambda i: (i,)),
        ),
        aliases=((1, 0), (3, 1)),
        declared_vmem_bytes=adagrad_vmem_bytes(BLOCK),
        vmem_counted=("lr", "param", "grad", "accum", "new_param",
                      "new_accum"),
    )


def _kernel(lr_ref, param_ref, grad_ref, accum_ref, new_param_ref,
            new_accum_ref, *, eps: float):
    g = grad_ref[...].astype(jnp.float32)
    a = accum_ref[...].astype(jnp.float32) + g * g
    p = param_ref[...].astype(jnp.float32)
    p = p - lr_ref[0] * g / (jnp.sqrt(a) + eps)
    new_param_ref[...] = p.astype(new_param_ref.dtype)
    new_accum_ref[...] = a


def fused_adagrad(param: jax.Array, grad: jax.Array, accum: jax.Array,
                  lr: jax.Array, *, eps: float = 1e-10,
                  interpret: bool | None = None
                  ) -> tuple[jax.Array, jax.Array]:
    """1-D fused update.  param/grad/accum: (N,) -> (new_param, new_accum)."""
    return _fused_adagrad(param, grad, accum, lr, eps=eps,
                          interpret=runtime.resolve(interpret))


@functools.partial(jax.jit, static_argnames=("eps", "interpret"))
def _fused_adagrad(param, grad, accum, lr, *, eps: float, interpret: bool):
    n = param.shape[0]
    pad = (-n) % BLOCK
    if pad:
        param = jnp.pad(param, (0, pad))
        grad = jnp.pad(grad, (0, pad))
        accum = jnp.pad(accum, (0, pad))
    np_ = n + pad
    meta = launch_meta(n, param.dtype, grad.dtype)
    new_param, new_accum = pl.pallas_call(
        functools.partial(_kernel, eps=eps),
        grid=meta.grid,
        in_specs=block_specs(meta.inputs),
        out_specs=block_specs(meta.outputs),
        input_output_aliases=meta.pallas_aliases(),
        out_shape=[
            jax.ShapeDtypeStruct((np_,), param.dtype),
            jax.ShapeDtypeStruct((np_,), jnp.float32),
        ],
        interpret=interpret,
    )(jnp.asarray(lr, jnp.float32).reshape(1), param, grad, accum)
    return new_param[:n], new_accum[:n]
