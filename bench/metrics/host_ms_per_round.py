"""Mean host time per call inside ``RingSession.step``: drawing the batch,
moving it to the device and dispatching; the wait in ``materialize`` is left
out (host clock)."""


def read(rec):
    return 1e3 * sum(r["t1"] - r["t0"] for r in rec.rounds) / len(rec.rounds)
