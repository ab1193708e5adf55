"""DIEN's reference module as the harness reads it: the work one example
requires, counted by hand at the published widths, and the ids it
touches, the same ones the program's trainer counts."""
import json
from pathlib import Path

import jax.numpy as jnp
import numpy as np

from chipbench import work
from chipbench.reference import dien

ROOT = Path(__file__).resolve().parents[2]
CFG = json.loads((ROOT / "chipbench" / "configs"
                  / "dien-alimama.json").read_text())


def test_forward_flops_by_hand():
    # T = 100 pairs, H = 36, MLP 162-200-80-1
    gru_step = 2 * 36 * 108 + 2 * 36 * 108       # input and recurrent
    mm = (100 * gru_step                           # interest extractor
          + 100 * gru_step                         # AUGRU
          + 99 * 2 * 2 * 36                        # auxiliary inner products
          + 2 * 36 * 36 + 100 * 2 * 36             # W e_a, <h_t, W e_a>
          + 2 * (162 * 200 + 200 * 80 + 80 * 1))   # MLP
    ew = (100 * 14 * 36 + 100 * 15 * 36            # GRU, AUGRU steps
          + 99 * 7                                 # auxiliary loss terms
          + 299                                    # softmax over 100
          + 99 * 36 + 36                           # sum_t i_t, e_a * it
          + (200 + 80 + 1) + 11 * (200 + 80))      # biases, Dice
    assert dien.forward_flops(CFG) == (mm, ew) == (3_231_408, 112_353)
    assert work.train_flops_per_example(CFG) == 3 * mm + 2 * ew


def test_ids_are_those_the_trainer_counts():
    from chipbench.runners.recsys_replay import program_config
    from repro.core.trainer import GBATrainer
    from repro.optim import get_optimizer
    assert dien.ids_per_example(CFG) == 203
    rng = np.random.default_rng(0)
    m, b = 3, 4
    batches = {"fields": rng.integers(0, 99, (m, b, 2)),
               "behavior": rng.integers(0, 99, (m, b, 200)),
               "target": rng.integers(0, 99, (m, b))}
    trainer = GBATrainer(program_config(CFG), get_optimizer("adam", 1e-3))
    flat = np.asarray(trainer._flat_ids(
        {k: jnp.asarray(v) for k, v in batches.items()}, m))
    for s in range(m):
        ids = dien.ids({k: jnp.asarray(v[s]) for k, v in batches.items()})
        assert ids.shape == (b, 203)
        np.testing.assert_array_equal(np.sort(flat[s]),
                                      np.sort(np.asarray(ids).reshape(-1)))
