"""The operations and bytes each measured piece of work requires, computed
from the configuration's shapes.  These count what the algorithm needs,
not what one implementation moves: a padded output or a recomputation
does not count.

FLOPs count a multiply and an add as two; elementwise nonlinearities count
one each; embedding lookups count none.  A training step's backward pass
costs two forward passes' matmul FLOPs (gradients of the input and of the
weight) and one forward pass's elementwise FLOPs.  Each model's forward
count is its reference module's ``forward_flops``, found by the
configuration's ``model``.
"""
from __future__ import annotations

from chipbench.reference import model_module


def mlp_flops(dims) -> int:
    """Forward FLOPs of one example through an MLP with ReLU between
    layers: a matmul and a bias add per layer, a ReLU on every hidden."""
    dims = list(dims)
    mm = sum(2 * a * b for a, b in zip(dims[:-1], dims[1:]))
    bias = sum(dims[1:])
    relu = sum(dims[1:-1])
    return mm + bias + relu


def train_flops_per_example(cfg: dict) -> int:
    """Forward and backward FLOPs one example requires."""
    mm, ew = model_module(cfg).forward_flops(cfg)
    return 3 * mm + 2 * ew


def presence_counts_bytes(cfg: dict, slots: int, local_batch: int) -> int:
    """Bytes one global step's presence count requires: every slot's ids
    read once (int32) and one float32 count per (slot, table row)
    written."""
    ids = slots * local_batch * model_module(cfg).ids_per_example(cfg)
    return 4 * ids + 4 * slots * cfg["hash_capacity"]
