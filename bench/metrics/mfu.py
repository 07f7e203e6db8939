"""The whole step's share of the chips' bf16 peak: required operations of
the calls in the window (``bench/flops.py``) over the window times the
chips times the peak (host clock)."""


def read(rec):
    if rec.peak_flops is None:
        return None
    ops = rec.flops_per_call * len(rec.rounds)
    return 100.0 * ops / (rec.window_s * rec.chips * rec.peak_flops)
