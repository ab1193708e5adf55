"""Pallas TPU kernel: fused GBA aggregate-and-apply.

The PS-side apply path of GBA (Alg. 2 lines 20/22 + the optimizer step)
previously ran as two kernels with an HBM round-trip between them:
``gba_aggregate`` reduced the (M, N) buffer to an aggregated gradient in
HBM, then ``fused_adagrad`` read it back alongside param/accum.  This
kernel merges both: for each N-block it computes the token-decay weights on
the scalar core, reduces the buffer column in VMEM, and immediately applies
the Adagrad update — the aggregated gradient never touches HBM.

Per-block traffic: read M rows of the buffer + param + accum, write new
param + accum — (M + 4) * BLOCK_N elements vs (M + 2) + (5) for the
two-kernel chain, i.e. the fusion removes two full reads and one full
write of an N-sized tensor per apply.

Inputs are flat (N,) vectors — ``repro.core.gba.FlatLayout`` ravels a
dense parameter pytree into exactly this shape so the whole apply is ONE
kernel launch instead of a per-leaf chain.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import runtime
from repro.kernels.launch_meta import (BlockMeta, LaunchMeta, block_specs,
                                       _round_up_static)

BLOCK_N = 2048


def apply_vmem_bytes(m: int, block_n: int = BLOCK_N,
                     buf_itemsize: int = 4) -> int:
    """Per-launch VMEM residency of one grid step: the (m, BLOCK_N) buffer
    block plus param/accum in and out blocks (f32).  Shard-size
    independent — a PS shard's launch holds exactly this much regardless
    of its slice length (benchmarks/bench_kernels gba_apply_sharded
    rows)."""
    return m * block_n * buf_itemsize + 4 * block_n * 4


def launch_meta(n: int, m: int, param_dtype=jnp.float32,
                buf_dtype=jnp.float32) -> LaunchMeta:
    """Static launch geometry for an (n,)-param, (m, n)-buffer apply.
    The real ``pallas_call`` below builds its specs FROM this, so the
    auditor (repro.analysis.pallas_check) checks the launch that runs.

    The in-place aliases donate param -> new_param and accum -> new_accum
    at the kernel level (array-input indices; ``pallas_aliases()`` shifts
    them past the 4 scalar-prefetch operands)."""
    n_pad = _round_up_static(n, BLOCK_N)
    buf_itemsize = jnp.dtype(buf_dtype).itemsize
    return LaunchMeta(
        kernel="gba_apply",
        grid=(n_pad // BLOCK_N,),
        num_scalar_prefetch=4,
        inputs=(
            BlockMeta("param", (n_pad,), param_dtype, (BLOCK_N,),
                      lambda i, *_: (i,)),
            BlockMeta("accum", (n_pad,), jnp.float32, (BLOCK_N,),
                      lambda i, *_: (i,)),
            BlockMeta("buffer", (m, n_pad), buf_dtype, (m, BLOCK_N),
                      lambda i, *_: (0, i)),
        ),
        outputs=(
            BlockMeta("new_param", (n_pad,), param_dtype, (BLOCK_N,),
                      lambda i, *_: (i,)),
            BlockMeta("new_accum", (n_pad,), jnp.float32, (BLOCK_N,),
                      lambda i, *_: (i,)),
        ),
        aliases=((0, 0), (1, 1)),
        declared_vmem_bytes=apply_vmem_bytes(m, BLOCK_N, buf_itemsize),
        vmem_counted=("param", "accum", "buffer", "new_param", "new_accum"),
    )


def _kernel(tokens_ref, step_ref, iota_ref, lr_ref, param_ref, accum_ref,
            buf_ref, new_param_ref, new_accum_ref, *, eps: float):
    """buf: (M, BLOCK_N) VMEM; param/accum: (BLOCK_N,); scalars in SMEM.

    The M decay weights are scalars read one by one from SMEM (the scalar
    core cannot load SMEM vectors); the rows are summed in worker order."""
    m = buf_ref.shape[0]
    g = None
    for k in range(m):
        keep = (step_ref[0] - tokens_ref[k]) <= iota_ref[0]   # Eq. (1)
        w = keep.astype(jnp.float32) / jnp.float32(m)
        row = buf_ref[k, :].astype(jnp.float32) * w
        g = row if g is None else g + row
    a = accum_ref[...].astype(jnp.float32) + g * g
    p = param_ref[...].astype(jnp.float32)
    p = p - lr_ref[0] * g / (jnp.sqrt(a) + eps)
    new_param_ref[...] = p.astype(new_param_ref.dtype)
    new_accum_ref[...] = a


def gba_apply(param: jax.Array, accum: jax.Array, buffer: jax.Array,
              tokens: jax.Array, step: jax.Array, lr: jax.Array, *,
              iota: int, eps: float = 1e-10, interpret: bool | None = None
              ) -> tuple[jax.Array, jax.Array]:
    """Single-pass decay-aggregate + Adagrad apply.

    param/accum: (N,), buffer: (M, N), tokens: (M,) ->
    (new_param (N,), new_accum (N,)).  ``interpret=None`` resolves
    through ``repro.kernels.runtime``.
    """
    return _gba_apply(param, accum, buffer, tokens, step, lr, iota=iota,
                      eps=eps, interpret=runtime.resolve(interpret))


@functools.partial(jax.jit, static_argnames=("iota", "eps", "interpret"))
def _gba_apply(param, accum, buffer, tokens, step, lr, *, iota: int,
               eps: float, interpret: bool):
    n = param.shape[0]
    m = buffer.shape[0]
    pad = (-n) % BLOCK_N
    if pad:
        param = jnp.pad(param, (0, pad))
        accum = jnp.pad(accum, (0, pad))
        buffer = jnp.pad(buffer, ((0, 0), (0, pad)))
    n_pad = n + pad
    meta = launch_meta(n, m, param.dtype, buffer.dtype)

    new_param, new_accum = pl.pallas_call(
        functools.partial(_kernel, eps=eps),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=meta.num_scalar_prefetch,
            grid=meta.grid,
            in_specs=block_specs(meta.inputs),
            out_specs=block_specs(meta.outputs),
        ),
        out_shape=[
            jax.ShapeDtypeStruct((n_pad,), param.dtype),
            jax.ShapeDtypeStruct((n_pad,), jnp.float32),
        ],
        input_output_aliases=meta.pallas_aliases(),
        interpret=interpret,
    )(tokens.astype(jnp.int32),
      jnp.asarray(step, jnp.int32).reshape(1),
      jnp.full((1,), iota, jnp.int32),
      jnp.asarray(lr, jnp.float32).reshape(1),
      param, accum, buffer)
    return new_param[:n], new_accum[:n]
