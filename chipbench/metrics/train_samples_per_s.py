"""Examples in kept slots (weight > 0) of every global step completed in
the window, over the window's wall time."""


def read(rec):
    return rec.kept_examples / rec.window_s
