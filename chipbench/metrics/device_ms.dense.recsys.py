"""Device milliseconds per global step in the ops of the compiled replay
step that run under the program's ``dense`` scope (chipbench/phases.py):
the union of their intervals in the traced window over the window's
steps.  Silent where the compiled step carries no scopes, or where the
phases cover less than 85% of the busy time (phases.MIN_PHASED)."""
from chipbench import phases


def read(rec):
    return phases.device_ms(rec, "dense")
