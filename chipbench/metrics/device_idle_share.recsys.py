"""Share of the traced window, in %, in which the chip ran no operation:
1 - (union of the device's op intervals) / window."""


def read(rec):
    if rec.trace is None or not rec.trace.devices:
        return None
    return 100.0 * (1.0 - rec.trace.busy_s() / rec.trace.window_s)
