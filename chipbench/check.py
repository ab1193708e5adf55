"""The numbers that decide ``correct`` for a training cell, and their
limits.

Two aggregated gradients are read: the first step's, and the last checked
step's, which in a GBA cell is the first step that holds a slot Eq. (1)
drops (``recsys_replay.check_length``).  Each is compared leaf by leaf, by
the gap between the program's norm and the reference's (not the norm of
their difference), over the larger of the reference's norm of that leaf
and of the median leaf.  The first gradient gives two numbers: the worst
leaf of the sparse module (the model's ``SPARSE`` tables, summed row by
row over the slots that give a row and divided by their count), and the
median leaf's gap over all leaves.  The output bias is not compared on
its own: its gradient, the batch mean of sigmoid(logit) - label, nearly
cancels on some seeds, where a sound run's gap reaches what the control's
does.  The dense module's worst leaf is not compared: a
pre-activation that rounds to the other side of a ReLU moves one
example's share of a bias on one seed in three, at any precision, so its
worst leaf swings by a step that a lower precision need not exceed; the
median leaf is steady from seed to seed (PERF.md).  The last gradient
gives one number, the worse of its sparse worst leaf and its median leaf:
by then Adam has moved every element by about its rate whatever the
rounding, so the program and the reference have drifted apart and a lower
precision no longer stands out; the number holds the drop step's
aggregation against gross faults (PERF.md).

The parameters' change over the checked steps is compared the same way,
by its worst leaf, leaving out leaves whose first reference gradient is
under a thousandth of the median leaf's: Adam moves those by round-off
alone.  ``last_update`` after the checked steps is compared row by row:
the number of rows whose step differs, exactly.  The loss is compared by
its worst checked step's gap relative to the reference's loss.

The limits are the configuration's own (``correct_limits`` in its file),
set from the readings PERF.md gives.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import model_module

# A leaf whose reference gradient is under this share of the median leaf's
# is left out of the change comparison.
NEGLIGIBLE_GRAD = 1e-3
GRAD_NUMBERS = ("grad_norm_gap.sparse", "grad_norm_gap.median")


@jax.jit
def leaf_norms(tree) -> list:
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for x in jax.tree.leaves(tree)]


@jax.jit
def change_norms(after, before) -> list:
    return [jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)
                                        - b.astype(jnp.float32))))
            for a, b in zip(jax.tree.leaves(after), jax.tree.leaves(before))]


@jax.jit
def adam_grad_norms(m_after, m_before, b1) -> list:
    """Norms of the gradient Adam took in one step, from its first moment
    before and after: g = (m_after - b1 m_before) / (1 - b1)."""
    return [jnp.sqrt(jnp.sum(jnp.square((a - b1 * b) / (1 - b1))))
            for a, b in zip(jax.tree.leaves(m_after),
                            jax.tree.leaves(m_before))]


def leaf_names(tree) -> list[str]:
    return [jax.tree_util.keystr(p)
            for p, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def host(xs) -> np.ndarray:
    return np.asarray([float(x) for x in xs], np.float64)


def leaf_gaps(prog: np.ndarray, ref: np.ndarray, keep, floor=None
              ) -> np.ndarray:
    """Each leaf's |prog - ref| over max(ref, floor), where the floor is
    the median kept leaf's reference norm unless given; 0 where not
    kept."""
    floor = np.median(ref[keep]) if floor is None else floor
    return np.where(keep, np.abs(prog - ref) / np.maximum(ref, floor), 0.0)


def norm_gap(prog: np.ndarray, ref: np.ndarray, keep, floor=None
             ) -> tuple[float, int]:
    """The worst kept leaf's gap, and its index."""
    gaps = leaf_gaps(prog, ref, keep, floor)
    i = int(np.argmax(gaps))
    return float(gaps[i]), i


def grad_gaps(prog: np.ndarray, ref: np.ndarray, names: list[str],
              cfg: dict) -> tuple[dict, str]:
    """One step's gradient numbers, and its worst sparse leaf."""
    mod = model_module(cfg)
    sparse = np.asarray([n in {f"['{s}']" for s in mod.SPARSE}
                         for n in names])
    gap_s, si = norm_gap(prog, ref, sparse, np.median(ref))
    gap_m = float(np.median(leaf_gaps(prog, ref, np.ones(len(names), bool))))
    return dict(zip(GRAD_NUMBERS, (gap_s, gap_m))), names[si]


def compare(prog: dict, ref: dict, names: list[str], cfg: dict) -> dict:
    """``prog``/``ref``: ``losses`` (per checked step), ``grad_norms``
    (per leaf, host arrays, of the first and the last checked step),
    ``change_norms`` (per leaf) and ``last_update`` (per row).  Returns each number with its limit, the
    gradient numbers of each of the two steps, and the worst leaves."""
    limits = cfg["correct_limits"]
    by_step, worst = zip(*(grad_gaps(p, r, names, cfg) for p, r in
                           zip(prog["grad_norms"], ref["grad_norms"])))
    g_first = ref["grad_norms"][0]
    moved = g_first >= NEGLIGIBLE_GRAD * np.median(g_first)
    change_gap, ci = norm_gap(prog["change_norms"], ref["change_norms"],
                              moved)
    values = dict(by_step[0])
    values["grad_norm_gap.last_step"] = max(
        by_step[-1]["grad_norm_gap.sparse"],
        by_step[-1]["grad_norm_gap.median"])
    values["change_norm_gap"] = change_gap
    values["loss_gap"] = max(abs(a - b) / abs(b) for a, b in
                             zip(prog["losses"], ref["losses"]))
    values["last_update_rows"] = float(np.sum(
        np.asarray(prog["last_update"]) != np.asarray(ref["last_update"])))
    return {
        "numbers": {k: {"value": v, "limit": limits[k]}
                    for k, v in values.items()},
        "correct": all(np.isfinite(v) and v <= limits[k]
                       for k, v in values.items()),
        "by_step": list(by_step),
        "worst_leaf": {"grad.sparse": list(worst), "change": names[ci]},
        "left_out": [n for n, m in zip(names, moved) if not m],
    }
