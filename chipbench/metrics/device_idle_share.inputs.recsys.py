"""Share of the traced window, in %, in which the chip ran no operation
while the program's innermost open host span was ``replay.inputs``
(chipbench/phases.py).  Silent where the program records no spans."""
from chipbench import phases


def read(rec):
    return phases.idle_share(rec, "replay.inputs")
