"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer metrics
read: per chip, the intervals in which an operation ran, the collective
operations among them, the time each operation took, and the idle gaps,
each named by the benchmark's own host span that was open during it.

Device planes are ``/device:TPU:<n>``; their operations are the events of
the ``XLA Ops`` line.  Host spans are the benchmark's ``TraceAnnotation``
events (names starting ``bench.``) on the host plane; the window is the
``bench.window`` span.  Host and device events share the trace's clock.
"""
from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
COLLECTIVE = re.compile(r"all-gather|all-reduce|collective-permute|"
                        r"reduce-scatter|all-to-all|ppermute|send|recv",
                        re.IGNORECASE)
WINDOW_SPAN = "bench.window"
HOST_SPAN_PREFIX = "bench."

Intervals = np.ndarray          # [n, 2] float64 seconds, sorted, disjoint


def union(iv: np.ndarray) -> Intervals:
    """Merge ``[n, 2]`` intervals into sorted disjoint ones."""
    if len(iv) == 0:
        return np.zeros((0, 2))
    iv = iv[np.argsort(iv[:, 0])]
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out)


def length(iv: Intervals) -> float:
    return float(np.sum(iv[:, 1] - iv[:, 0])) if len(iv) else 0.0


def clip(iv: Intervals, lo: float, hi: float) -> Intervals:
    if len(iv) == 0:
        return iv
    c = np.stack([np.maximum(iv[:, 0], lo), np.minimum(iv[:, 1], hi)], 1)
    return c[c[:, 1] > c[:, 0]]


def subtract(a: Intervals, b: Intervals) -> Intervals:
    """Parts of disjoint sorted ``a`` not covered by disjoint sorted ``b``."""
    out = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return np.asarray(out) if out else np.zeros((0, 2))


def gaps(busy: Intervals, lo: float, hi: float) -> Intervals:
    return subtract(np.asarray([[lo, hi]]), busy)


def self_times(iv: np.ndarray) -> np.ndarray:
    """Each event's duration less that of the events nested directly in
    it (events of one line nest or follow each other)."""
    order = np.lexsort((-(iv[:, 1] - iv[:, 0]), iv[:, 0])) if len(iv) else []
    own = iv[:, 1] - iv[:, 0]
    out = own.copy()
    stack: List[int] = []
    for i in order:
        while stack and iv[stack[-1], 1] <= iv[i, 0]:
            stack.pop()
        if stack:
            out[stack[-1]] -= own[i]
        stack.append(i)
    return np.maximum(out, 0.0)


class Trace:
    """One trace, reduced.  Times in seconds on the trace's clock."""

    def __init__(self, path: str):
        from jax.profiler import ProfileData

        data = ProfileData.from_file(path)
        self.window: Optional[Tuple[float, float]] = None
        self.spans: List[Tuple[str, float, float]] = []   # host, bench.*
        self.chips: Dict[int, Dict[str, object]] = {}
        for plane in data.planes:
            m = DEVICE_PLANE.match(plane.name)
            if m:
                self.chips[int(m.group(1))] = self._device(plane)
            elif plane.name.startswith("/host:"):
                self._host(plane)

    def _host(self, plane) -> None:
        for line in plane.lines:
            for ev in line.events:
                name = ev.name
                if not name.startswith(HOST_SPAN_PREFIX):
                    continue
                s, e = ev.start_ns * 1e-9, ev.end_ns * 1e-9
                if name == WINDOW_SPAN:
                    self.window = (s, e)
                else:
                    self.spans.append((name, s, e))

    @staticmethod
    def _device(plane) -> Dict[str, object]:
        """Ops of the ``XLA Ops`` line: intervals, short names (``%fusion.3``
        of ``%fusion.3 = bf16[...] fusion(...)``) and self time (a loop's
        event spans the events of its body, which are counted once)."""
        rows, names = [], []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                rows.append((ev.start_ns * 1e-9, ev.end_ns * 1e-9))
                names.append(ev.name.split(" = ", 1)[0])
        iv = np.asarray(rows) if rows else np.zeros((0, 2))
        coll = np.asarray([bool(COLLECTIVE.search(n)) for n in names],
                          bool)
        return {"ops": iv, "names": names, "collective": coll,
                "self": self_times(iv)}

    # -- reductions over the window ----------------------------------------
    def _lohi(self) -> Tuple[float, float]:
        if self.window is None:
            raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
        return self.window

    def window_s(self) -> float:
        lo, hi = self._lohi()
        return hi - lo

    def busy(self, chip: int) -> Intervals:
        lo, hi = self._lohi()
        return clip(union(self.chips[chip]["ops"]), lo, hi)

    def busy_s(self) -> Dict[int, float]:
        return {c: length(self.busy(c)) for c in self.chips}

    def collective_exposed_s(self) -> Dict[int, float]:
        """Per chip: time in which a collective op runs and no other op."""
        lo, hi = self._lohi()
        out = {}
        for c, d in self.chips.items():
            coll = clip(union(d["ops"][d["collective"]]), lo, hi)
            other = clip(union(d["ops"][~d["collective"]]), lo, hi)
            out[c] = length(subtract(coll, other))
        return out

    def op_seconds(self) -> Dict[str, float]:
        """Self seconds per op name, of the ops that start in the window,
        mean over chips."""
        lo, hi = self._lohi()
        tot: Dict[str, float] = defaultdict(float)
        for d in self.chips.values():
            iv = d["ops"]
            inside = (iv[:, 0] >= lo) & (iv[:, 0] < hi) if len(iv) else []
            for name, t, keep in zip(d["names"], d["self"], inside):
                if keep:
                    tot[name] += float(t)
        n = max(len(self.chips), 1)
        return {k: v / n for k, v in tot.items()}

    def idle_gaps(self) -> List[Tuple[str, float]]:
        """Every idle gap of every chip, named by the host span open at its
        middle (``host`` when none is), longest first."""
        lo, hi = self._lohi()
        spans = sorted(self.spans, key=lambda x: x[1])
        out = []
        for c in self.chips:
            for s, e in gaps(self.busy(c), lo, hi):
                mid = 0.5 * (s + e)
                name = next((n for n, a, b in spans if a <= mid < b), "host")
                out.append((name, float(e - s)))
        return sorted(out, key=lambda x: -x[1])

    def breakdown(self, top: int = 10) -> Dict[str, list]:
        ops = sorted(self.op_seconds().items(), key=lambda x: -x[1])[:top]
        return {"device_ops": [[n, t] for n, t in ops],
                "idle_gaps": [[n, t] for n, t in self.idle_gaps()[:top]]}
