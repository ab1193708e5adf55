"""Static Pallas launch geometry, exported instead of buried in closures.

Every kernel in this package describes its launch — grid, per-operand
BlockSpec blocks and index maps, VMEM scratch, scalar-prefetch count,
in-place aliases, and the *declared* VMEM cap its docstring/bench rows
advertise — as a :class:`LaunchMeta` built by a ``*_launch_meta()``
function next to the kernel.  The simple 1-D kernels (``gba_apply``,
``fused_adagrad``, ``gba_aggregate``) construct their real
``pallas_call`` specs FROM the meta (single source of truth); the
DMA-streamed kernels (``embedding_bag``, ``flash_decode``) build their
VMEM scratch from it and mirror the block specs, which the static
auditor (``repro.analysis.pallas_check``) then cross-checks: tile
alignment against per-dtype TPU min tiles (GBA-TILE-001), recomputed
vs declared VMEM residency (GBA-VMEM-001), total residency under the
per-core budget (GBA-VMEM-002), and index-map bounds over the whole
grid (GBA-GRID-001) — all without executing or compiling anything.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Callable

import jax.numpy as jnp

# memory spaces a BlockMeta can live in
VMEM, SMEM, ANY = "vmem", "smem", "any"


def _round_up_static(x: int, mult: int) -> int:
    return -(-x // mult) * mult


@dataclass(frozen=True)
class BlockMeta:
    """One pallas_call operand: its (padded) array, block, and index map.

    ``block`` is the BlockSpec block shape; ``index_map`` maps grid
    indices to BLOCK indices (the BlockSpec convention).  Operands in
    ``ANY`` memory space (HBM-resident, DMA-streamed by the kernel body)
    carry ``block=None`` and contribute nothing to VMEM residency.
    """

    name: str
    array_shape: tuple[int, ...]
    dtype: Any
    block: tuple[int, ...] | None = None
    index_map: Callable[..., tuple[int, ...]] | None = None
    memory_space: str = VMEM

    @property
    def itemsize(self) -> int:
        return jnp.dtype(self.dtype).itemsize

    def block_bytes(self) -> int:
        if self.memory_space != VMEM:
            return 0
        # a VMEM operand with no block spec is fully resident
        shape = self.block if self.block is not None else self.array_shape
        return math.prod(shape) * self.itemsize


@dataclass(frozen=True)
class ScratchMeta:
    """One VMEM scratch buffer (DMA semaphores are not VMEM residency)."""

    name: str
    shape: tuple[int, ...]
    dtype: Any

    def bytes(self) -> int:
        return math.prod(self.shape) * jnp.dtype(self.dtype).itemsize


@dataclass(frozen=True)
class LaunchMeta:
    """Complete static description of one pallas_call launch."""

    kernel: str
    grid: tuple[int, ...]
    inputs: tuple[BlockMeta, ...]
    outputs: tuple[BlockMeta, ...]
    scratch: tuple[ScratchMeta, ...] = ()
    num_scalar_prefetch: int = 0
    # array-input index (position within ``inputs``) -> output index,
    # NOT counting scalar-prefetch operands; ``pallas_aliases`` shifts
    aliases: tuple[tuple[int, int], ...] = ()
    # the VMEM cap the kernel declares (apply_vmem_bytes-style) and which
    # block/scratch names that formula counts; None = no declared cap
    declared_vmem_bytes: int | None = None
    vmem_counted: tuple[str, ...] = ()

    def pallas_aliases(self) -> dict[int, int]:
        """``input_output_aliases`` for the real pallas_call: flat input
        positions COUNT the scalar-prefetch operands."""
        return {self.num_scalar_prefetch + i: o for i, o in self.aliases}

    def named_bytes(self) -> dict[str, int]:
        """VMEM bytes per named block/scratch (ANY-space operands = 0)."""
        out: dict[str, int] = {}
        for bm in self.inputs + self.outputs:
            out[bm.name] = bm.block_bytes()
        for sm in self.scratch:
            out[sm.name] = sm.bytes()
        return out

    def vmem_bytes(self, names: tuple[str, ...] | None = None) -> int:
        """Recomputed VMEM residency over ``names`` (default: everything).
        ``names=self.vmem_counted`` reproduces what the declared formula
        is supposed to cover."""
        by_name = self.named_bytes()
        if names is None:
            return sum(by_name.values())
        missing = [n for n in names if n not in by_name]
        if missing:
            raise KeyError(f"{self.kernel}: unknown block names {missing}")
        return sum(by_name[n] for n in names)

    def total_vmem_bytes(self) -> int:
        return self.vmem_bytes(None)


def block_specs(blocks: tuple[BlockMeta, ...]):
    """BlockMeta tuple -> the real pallas BlockSpec list (imports pallas
    lazily so the dataclasses stay importable without a TPU toolchain)."""
    from jax.experimental import pallas as pl

    specs = []
    for bm in blocks:
        if bm.memory_space == ANY:
            specs.append(pl.BlockSpec(memory_space=pl.ANY))
        else:
            specs.append(pl.BlockSpec(bm.block, bm.index_map))
    return specs


def scratch_shapes(scratch: tuple[ScratchMeta, ...]):
    """ScratchMeta tuple -> pltpu.VMEM scratch list (semaphores are
    appended by the kernel itself — they are not VMEM residency)."""
    from jax.experimental.pallas import tpu as pltpu

    return [pltpu.VMEM(sm.shape, sm.dtype) for sm in scratch]
