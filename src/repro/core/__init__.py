"""GBA — the paper's primary contribution: token-controlled global-batch
gradient aggregation with staleness decay, plus the semi-synchronous
baselines and the continual-training driver."""
from repro.core.continual import (ContinualResult, ModeSetup, default_setups,
                                  pretrain_sync, run_continual,
                                  schedule_for_day)
from repro.core.flat_sharded import (ShardedFlatLayout,
                                     init_sharded_flat_buffer,
                                     make_sharded_apply,
                                     sharded_flat_push_and_maybe_apply)
from repro.core.gba import (FlatLayout, aggregate_dense, aggregate_embedding,
                            buffer_push_and_maybe_apply, decay_weights,
                            flat_buffer_push, flat_buffer_push_and_maybe_apply,
                            init_buffer, init_flat_buffer)
from repro.core.staleness import (DECAY_FNS, exponential_decay, linear_decay,
                                  threshold_decay)
from repro.core.tokens import (TokenList, TokenListExhausted,
                               num_global_steps, token_for_batch,
                               token_list)
from repro.core.trainer import GBATrainer, ReplayStats, evaluate

__all__ = [
    "ContinualResult", "DECAY_FNS", "FlatLayout", "GBATrainer", "ModeSetup",
    "ReplayStats", "ShardedFlatLayout", "TokenList", "TokenListExhausted",
    "aggregate_dense", "aggregate_embedding",
    "buffer_push_and_maybe_apply", "decay_weights", "default_setups",
    "evaluate", "exponential_decay", "flat_buffer_push",
    "flat_buffer_push_and_maybe_apply",
    "init_buffer", "init_flat_buffer", "init_sharded_flat_buffer",
    "linear_decay", "make_sharded_apply", "num_global_steps",
    "pretrain_sync", "run_continual", "schedule_for_day",
    "sharded_flat_push_and_maybe_apply", "threshold_decay",
    "token_for_batch", "token_list",
]
