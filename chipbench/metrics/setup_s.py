"""Set-up time: process start to window start (imports, device init, data
pool, weights, compile or cache load, checked steps, warm-up day)."""


def read(rec):
    return rec.setup_s
