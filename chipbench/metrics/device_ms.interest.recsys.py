"""Device milliseconds per global step in the ops of the compiled replay
step that run under DIEN's ``interest`` scope, nested in ``dense`` (the
GRU interest extractor, its auxiliary loss, the attention and the AUGRU,
forward and backward; chipbench/phases.py): the union of their intervals
in the traced window over the window's steps.  Silent where no
instruction of the step carries the scope, or where the phases cover less
than 85% of the busy time (phases.MIN_PHASED)."""
from chipbench import phases


def read(rec):
    return phases.device_ms(rec, "interest")
