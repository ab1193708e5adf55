"""The whole step's share, in %, of the chip's bf16 peak: the forward and
backward FLOPs every computed example requires (chipbench/work.py; all
slots, kept or dropped, since GBA takes every slot's gradient), times
examples per second of the traced window, over chips times the peak.  The
models run float32; no float32 peak is published."""
from chipbench import work


def read(rec):
    if rec.trace is None:
        return None
    flops = work.train_flops_per_example(rec.cfg) * rec.all_examples
    return 100.0 * flops / rec.window_s / (rec.chips * rec.peak.bf16_flops)
