"""jit'd public wrappers over the Pallas kernels.

``interpret=None`` resolves from the platform at first use — off a TPU the
kernels interpret, on a TPU they compile (``repro.kernels.runtime``).
Override the process-wide default with the ``REPRO_INTERPRET`` env var or
``set_interpret``; every wrapper also takes a per-call ``interpret=``.
The tree-level helpers apply the kernels across parameter pytrees; the
pooled-lookup wrappers expose the streamed embedding kernels' capacity
knobs (``block_v``/``block_d``/``chunk_e``).
"""
from __future__ import annotations

import collections
from typing import Any

import jax

from repro.kernels.embedding_bag import embedding_bag, embedding_bag_grad
from repro.kernels.fused_adagrad import fused_adagrad
from repro.kernels.gba_aggregate import gba_aggregate
from repro.kernels.gba_apply import gba_apply
from repro.kernels.quantize import dequantize, quantize_minmax, quantize_sign
from repro.kernels.runtime import set_interpret  # noqa: F401  (re-export)

# Python-level invocation census of the eager wrappers below.  This is
# the structural evidence the serving stack leans on: a hot-ID cache hit
# must leave ``kernel_calls["pooled_lookup"]`` unchanged — the batch
# never reached the streamed kernel (gated as ``audit_hit_skips_kernel``
# in the serving bench and asserted by tests/test_serving_live.py).
# Counts wrapper INVOCATIONS (including cached jit executions), not
# traces — exactly what "did this request touch the kernel path" means.
kernel_calls: collections.Counter = collections.Counter()


def gba_aggregate_tree(grads_stacked: Any, tokens: jax.Array,
                       step: jax.Array, *, iota: int,
                       interpret: bool | None = None) -> Any:
    """Kernel-backed version of repro.core.gba.aggregate_dense: flattens
    each leaf to (M, -1), runs the fused kernel, restores shapes."""
    def per_leaf(g):
        m = g.shape[0]
        flat = g.reshape(m, -1)
        out = gba_aggregate(flat, tokens, step, iota=iota,
                            interpret=interpret)
        return out.reshape(g.shape[1:])

    return jax.tree.map(per_leaf, grads_stacked)


def gba_apply_flat(param_flat: jax.Array, accum_flat: jax.Array,
                   buffer: jax.Array, tokens: jax.Array, step: jax.Array,
                   lr, *, iota: int, eps: float = 1e-10,
                   interpret: bool | None = None
                   ) -> tuple[jax.Array, jax.Array]:
    """Fused decay-aggregate + Adagrad over the flat (M, N) buffer — the
    single-launch PS apply path (see repro.core.gba.FlatLayout)."""
    return gba_apply(param_flat, accum_flat, buffer, tokens, step, lr,
                     iota=iota, eps=eps, interpret=interpret)


def quantize_wire(payload: jax.Array, *, tile: int, mode: str,
                  interpret: bool | None = None):
    """Quantize a routing payload with fused error feedback.

    ``mode="minmax"`` -> ``(qvals, scale, zero, residual)``;
    ``mode="sign"``   -> ``(qvals, scale, residual)`` (no zero-point).
    See ``repro.kernels.quantize``.
    """
    if mode == "minmax":
        return quantize_minmax(payload, tile=tile, interpret=interpret)
    if mode == "sign":
        return quantize_sign(payload, tile=tile, interpret=interpret)
    raise ValueError(f"unknown quantize mode {mode!r}")


def dequantize_wire(qvals: jax.Array, scale: jax.Array,
                    zero: jax.Array | None = None, *, tile: int, mode: str,
                    interpret: bool | None = None) -> jax.Array:
    """Reconstruct the f32 payload from routed wire arrays (see
    ``repro.kernels.quantize.dequantize``)."""
    return dequantize(qvals, scale, zero, tile=tile, mode=mode,
                      interpret=interpret)


def adagrad_apply_tree(params: Any, grads: Any, accums: Any, lr, *,
                       interpret: bool | None = None) -> tuple[Any, Any]:
    """Fused Adagrad over a pytree (flattening each leaf to 1-D)."""
    def per_leaf(p, g, a):
        np_, na = fused_adagrad(p.reshape(-1), g.reshape(-1), a.reshape(-1),
                                lr, interpret=interpret)
        return np_.reshape(p.shape), na.reshape(a.shape)

    out = jax.tree.map(per_leaf, params, grads, accums)
    is2 = lambda t: isinstance(t, tuple)
    new_p = jax.tree.map(lambda t: t[0], out, is_leaf=is2)
    new_a = jax.tree.map(lambda t: t[1], out, is_leaf=is2)
    return new_p, new_a


def pooled_lookup(ids: jax.Array, table: jax.Array, *,
                  block_v: int | None = None, block_d: int | None = None,
                  chunk_e: int | None = None,
                  interpret: bool | None = None) -> jax.Array:
    """Streamed pooled lookup: the (V, D) table stays in HBM; VMEM holds
    O(block_v * block_d + chunk_e * block_d) scratch regardless of V."""
    kernel_calls["pooled_lookup"] += 1
    return embedding_bag(ids, table, block_v=block_v, block_d=block_d,
                         chunk_e=chunk_e, interpret=interpret)


def pooled_lookup_grad(ids: jax.Array, grad_out: jax.Array, capacity: int,
                       *, block_v: int | None = None,
                       block_d: int | None = None,
                       chunk_e: int | None = None,
                       interpret: bool | None = None
                       ) -> tuple[jax.Array, jax.Array]:
    """Streamed sorted-scatter backward with per-ID contributor counts."""
    kernel_calls["pooled_lookup_grad"] += 1
    return embedding_bag_grad(ids, grad_out, capacity, block_v=block_v,
                              block_d=block_d, chunk_e=chunk_e,
                              interpret=interpret)
