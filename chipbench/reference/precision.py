"""The reference modules' matrix products, and their emulation one step
below the precision a float32 configuration states.

A module that sets ``MATMULS_VIA_DOT = True`` computes every matrix
product through :func:`dot`, which is plain ``a @ b`` unless the
:class:`Reference` traces it under :func:`emulate`.  There it rounds its
operands as the TPU's matrix unit does at the lower precision, and
multiplies the parts exactly (a product of two bfloat16 numbers fits in
float32) with float32 sums, so that the CPU and the TPU compute the same
thing:

- ``bf16`` (the control of ``high``): one pass, each operand rounded to
  bfloat16, as ``default`` runs on the TPU;
- ``bf16_3x`` (the control of ``highest``): each operand split into a
  bfloat16 high part and a bfloat16 low part, ``lo = bf16(x - hi)``, and
  ``hi·hi + hi·lo + lo·hi``, as ``high`` runs on the TPU.

The backward pass's products are emulated the same way, as the TPU
computes them at the precision in force.
"""
from __future__ import annotations

import contextlib
import contextvars
import functools

import jax
import jax.numpy as jnp

# each emulation and the bfloat16 parts it splits an operand into
PARTS = {"bf16": 1, "bf16_3x": 2}

_MODE: contextvars.ContextVar[str | None] = contextvars.ContextVar(
    "matmuls", default=None)


@contextlib.contextmanager
def emulate(mode: str | None):
    """While it is open, :func:`dot` traces the emulation ``mode`` (one of
    ``PARTS``; ``None``: plain products)."""
    if mode is not None and mode not in PARTS:
        raise ValueError(f"no matmul emulation {mode!r}")
    token = _MODE.set(mode)
    try:
        yield
    finally:
        _MODE.reset(token)


def dot(a: jax.Array, b: jax.Array) -> jax.Array:
    """``a @ b``, or its emulation where one is open."""
    mode = _MODE.get()
    if mode is None:
        return a @ b
    return _emulated(a, b, PARTS[mode])


def split(x: jax.Array, parts: int) -> list[jax.Array]:
    """``x`` as ``parts`` bfloat16 numbers (held in float32) whose sum is
    ``x`` to the precision of the parts."""
    x = x.astype(jnp.float32)
    out = []
    for _ in range(parts):
        out.append(x.astype(jnp.bfloat16).astype(jnp.float32))
        x = x - out[-1]
    return out


def _exact(a, b):
    # bfloat16 operands: the products are exact, the sums float32
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def _passes(f, xs, ys):
    """Sum of ``f(x_i, y_j)`` over the part pairs the TPU multiplies: the
    high parts together, and each high part with the other's low part."""
    return sum(f(x, y) for i, x in enumerate(xs) for j, y in enumerate(ys)
               if i + j < len(xs))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _emulated(a, b, parts):
    return _passes(_exact, split(a, parts), split(b, parts))


def _emulated_fwd(a, b, parts):
    return _emulated(a, b, parts), (a, b)


def _emulated_bwd(parts, res, g):
    a, b = res
    gs = split(g, parts)

    def da(g_part, b_part):
        return jax.vjp(lambda x: _exact(x, b_part), a)[1](g_part)[0]

    def db(g_part, a_part):
        return jax.vjp(lambda y: _exact(a_part, y), b)[1](g_part)[0]

    return (_passes(da, gs, split(b, parts)).astype(a.dtype),
            _passes(db, gs, split(a, parts)).astype(b.dtype))


_emulated.defvjp(_emulated_fwd, _emulated_bwd)
