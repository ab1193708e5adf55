"""``chipbench/work.py`` against counts made by hand."""
import json
from pathlib import Path

from chipbench import work
from chipbench.reference import deepfm

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def load(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


def test_mlp_flops_by_hand():
    # 4 -> 3 -> 1: matmuls 2*4*3 + 2*3*1 = 30, biases 3 + 1, one ReLU of 3
    assert work.mlp_flops((4, 3, 1)) == 30 + 4 + 3


def test_deepfm_forward_by_hand():
    cfg = {"model": "deepfm", "num_fields": 2, "embed_dim": 2,
           "mlp_dims": [3], "behavior_len": 0}
    mm, ew = deepfm.forward_flops(cfg)
    # MLP 4 -> 3 -> 1
    assert mm == 2 * 4 * 3 + 2 * 3 * 1
    # first order 1 add; FM: sum over fields 2, its square 2, squares 4,
    # their sum 2, difference 2, sum over D 1, halving 1 = 14; MLP biases
    # 4 and ReLU 3; the logit's 3 adds
    assert ew == 1 + 14 + 7 + 3


def test_deepfm_criteo_is_about_2_6_mflop_an_example():
    cfg = load("deepfm-criteo")
    mm, _ = deepfm.forward_flops(cfg)
    # 260*400 + 400*400 + 400*400 + 400*1 multiply-adds
    assert mm == 2 * (260 * 400 + 400 * 400 + 400 * 400 + 400)
    assert 2.54e6 < work.train_flops_per_example(cfg) < 2.56e6


def test_train_flops_count_backward_twice_the_matmuls():
    cfg = load("deepfm-criteo")
    mm, ew = deepfm.forward_flops(cfg)
    assert work.train_flops_per_example(cfg) == 3 * mm + 2 * ew


def test_work_finds_the_model_by_name():
    cfg = {"model": "deepfm", "num_fields": 3, "embed_dim": 2,
           "mlp_dims": [4], "hash_capacity": 10}
    mm, ew = deepfm.forward_flops(cfg)
    assert work.train_flops_per_example(cfg) == 3 * mm + 2 * ew
    assert work.presence_counts_bytes(cfg, 1, 1) == 4 * 3 + 4 * 10


def test_presence_counts_bytes_by_hand():
    cfg = {"model": "deepfm", "num_fields": 26, "behavior_len": 0,
           "hash_capacity": 1000}
    # 2 slots x 4 rows x 26 ids read as int32, 2 x 1000 f32 counts written
    assert work.presence_counts_bytes(cfg, 2, 4) == 4 * 208 + 4 * 2000
    assert deepfm.ids_per_example(cfg) == 26
