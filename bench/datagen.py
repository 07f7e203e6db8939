"""The one traffic generator: seeded per-client Markov token streams.

Every client ``u`` has its own affine bigram process, ``next = (a_u * cur +
b_u) mod vocab`` with a fresh uniform token in a ``noise`` share of the
positions, so the clients' distributions differ (RingAda's setting of
private per-client data).  Every draw makes new rows: no row repeats within
a run.  The same seed gives the same clients and the same sequence of
batches.  Copied in spirit from the program's ``data/pipeline.py``, which
samples rows from a fixed pool instead.

A traffic file's ``data`` block names the parameters: ``clients`` and
``noise``; the batch shape comes from the file's ``backend``,
``n_stages``, ``n_microbatches``, ``batch_size`` and ``seq_len``.
"""
from __future__ import annotations

from typing import Any, Dict, List

import numpy as np


def seed_sequence(seed: int, *tags: int) -> np.random.SeedSequence:
    """Any whole number, however large or negative, as a numpy seed."""
    return np.random.SeedSequence([seed % 2 ** 64, *tags])


class Feed:
    """Batches for ``RingSession.step``: ``(None, tokens, labels)`` with
    ``[S, M, mb, seq]`` int32 arrays for the ring backends, ``{"tokens",
    "labels"}`` with ``[B, seq]`` for the pjit backend.  ``history`` keeps
    the first ``keep`` batches for the correctness check."""

    def __init__(self, traffic: Dict[str, Any], vocab: int, seed: int, *,
                 keep: int = 3):
        data = traffic["data"]
        if data["kind"] != "markov":
            raise ValueError(f"unknown traffic kind {data['kind']!r}")
        self.traffic, self.vocab = traffic, vocab
        self.noise = float(data["noise"])
        self.n_clients = int(data["clients"])
        crng = np.random.default_rng(seed_sequence(seed, 1))
        self.a = crng.integers(3, 23, size=self.n_clients) * 2 + 1
        self.b = crng.integers(1, vocab - 1, size=self.n_clients)
        self.rng = np.random.default_rng(seed_sequence(seed, 2))
        self.keep = keep
        self.history: List[Any] = []

    def _rows(self, client: np.ndarray, seq: int) -> np.ndarray:
        """[n, seq + 1] int32 rows, row i drawn from client ``client[i]``."""
        n = client.shape[0]
        a, b = self.a[client], self.b[client]
        out = np.empty((n, seq + 1), np.int64)
        cur = self.rng.integers(0, self.vocab, size=n)
        jump = self.rng.random((seq, n)) < self.noise
        fresh = self.rng.integers(0, self.vocab, size=(seq, n))
        for t in range(seq + 1):
            out[:, t] = cur
            if t < seq:
                cur = np.where(jump[t], fresh[t], (a * cur + b) % self.vocab)
        return out.astype(np.int32)

    def next(self):
        t = self.traffic
        seq = t["seq_len"]
        if t["backend"] == "pjit":
            B = t["batch_size"]
            rows = self._rows(np.arange(B) % self.n_clients, seq)
            batch = {"tokens": np.ascontiguousarray(rows[:, :-1]),
                     "labels": np.ascontiguousarray(rows[:, 1:])}
        else:
            S, M, mb = t["n_stages"], t["n_microbatches"], t["batch_size"]
            if self.n_clients != S:
                raise ValueError(f"a ring of {S} stages needs {S} clients, "
                                 f"the traffic has {self.n_clients}")
            client = np.repeat(np.arange(S), M * mb)
            rows = self._rows(client, seq).reshape(S, M, mb, seq + 1)
            batch = (None, np.ascontiguousarray(rows[..., :-1]),
                     np.ascontiguousarray(rows[..., 1:]))
        if len(self.history) < self.keep:
            self.history.append(batch)
        return batch
