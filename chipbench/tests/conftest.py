"""The CPU size of a cell whose model touches many ids an example.

``test_bench_correct.small`` cuts every cell to 4000 table rows, a size
made for DeepFM's 26 ids an example: a step then leaves many rows
untouched, so some rows a stale slot reads stay un-updated since its
token and the per-ID relaxation has rows to keep and to leave out.
DIEN touches 203 ids an example, which at 4000 rows reach nearly every
row in every step: a GBA token one off then changes no row the
relaxation keeps, and the fault goes unseen.  So a model that touches
more ids an example keeps 4000 rows for every 26 of them.  Every width,
the batch and the steps stay as ``small`` sets them: at a smaller batch
the reference's ``bf16_3x`` control no longer fails ``correct`` on every
seed on the CPU.
"""
import pytest

# the ids an example and the rows ``small`` was made for
SMALL_IDS = 26
SMALL_ROWS = 4000


def by_ids(small):
    def sized(cell: str) -> tuple[dict, dict]:
        from chipbench.reference import model_module
        cfg, traffic = small(cell)
        per = model_module(cfg).ids_per_example(cfg)
        if per > SMALL_IDS:
            cfg = dict(cfg, hash_capacity=SMALL_ROWS * (per // SMALL_IDS))
        return cfg, traffic
    return sized


@pytest.fixture(autouse=True)
def _size_by_ids(request, monkeypatch):
    if request.module.__name__.endswith(".test_bench_correct"):
        monkeypatch.setattr(request.module, "small",
                            by_ids(request.module.small))
