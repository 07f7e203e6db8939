"""Share of the traced window in which no operation ran on a chip, mean
over the cell's chips (device trace)."""


def read(rec):
    if rec.trace is None or not rec.trace.chips:
        return None
    w = rec.trace.window_s()
    busy = rec.trace.busy_s()
    return 100.0 * sum(1.0 - b / w for b in busy.values()) / len(busy)
