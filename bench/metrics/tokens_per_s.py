"""Training tokens of every call in the window over the whole window, from
its start to the last call's materialized metrics (host clock)."""


def read(rec):
    return sum(r["tokens"] for r in rec.rounds) / rec.window_s
