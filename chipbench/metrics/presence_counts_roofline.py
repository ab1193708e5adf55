"""Share, in %, of its roofline that the presence-count kernel
(``embedding_bag_grad`` as ``presence_counts`` calls it) reaches: the bytes
the count requires (every slot's ids read, one float32 count per slot and
table row written; chipbench/work.py) over the HBM bandwidth, divided by
the summed device time of the kernel's events.  It needs no FLOPs, so the
byte bound holds.  Silent when the trace shows no such kernel."""
from chipbench import work
from chipbench.runners.recsys_replay import slots_per_step

KERNEL = "%_embedding_bag_grad_streamed"


def read(rec):
    if rec.trace is None:
        return None
    seconds, count = rec.trace.matching_seconds(KERNEL)
    if count == 0 or seconds <= 0:
        return None
    need = rec.steps * work.presence_counts_bytes(
        rec.cfg, slots_per_step(rec.traffic), rec.traffic["local_batch"])
    return 100.0 * need / rec.peak.hbm_bytes / seconds
