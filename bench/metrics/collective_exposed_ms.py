"""Per call, the time collective operations run on a chip while no other
operation runs there, mean over the cell's chips (device trace)."""


def read(rec):
    if rec.trace is None or not rec.trace.chips:
        return None
    exposed = rec.trace.collective_exposed_s()
    return 1e3 * sum(exposed.values()) / len(exposed) / len(rec.rounds)
