"""RoundMetrics + the session callback hooks.

``RoundMetrics`` is the structured record one ``RingSession.step`` returns.
Scalar fields that come out of a fused executor round are DEVICE arrays until
``materialize()`` is called — the session materializes in batches (once per
logging interval), preserving the executor's async-dispatch contract: holding
an unmaterialized RoundMetrics never forces a host sync.

Callbacks observe *materialized* metrics only, so a callback can never
accidentally sync the device mid-interval.  The hook points:

    on_start(session)            before the first step of ``run``
    on_round(session, metrics)   once per step, at materialization time
    on_end(session, history)     after the last step (history = list of dicts)
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import jax

from repro import scopes
from repro.core.executor import scalarize as _scalarize


@dataclass(eq=False)                       # identity hash: the session tracks
class RoundMetrics:                        # live instances in a WeakSet
    """One training step/round, structured.

    ``loss`` (and ``extras`` values) may be device arrays before
    ``materialize()``; every other field is host-side from birth.
    """

    step: int                          # global step AFTER this round
    boundary: int                      # frozen repeats from the bottom
    depth: int                         # unfrozen blocks from the top
    loss: Any                          # scalar (device array until materialized)
    compile_count: int = 0             # executables built so far (cumulative)
    tokens: int = 0                    # tokens consumed by this round
    tokens_per_sec: Optional[float] = None   # filled at materialization
    wall_s: Optional[float] = None           # since run() start
    cache: Optional[Dict[str, float]] = None  # actcache stats, if caching
    cache_hit: Optional[bool] = None
    extras: Dict[str, Any] = field(default_factory=dict)
    materialized: bool = False

    def materialize(self, *, wall_s: Optional[float] = None,
                    tokens_per_sec: Optional[float] = None) -> "RoundMetrics":
        """Host-sync every device value -> a new, fully-scalar RoundMetrics."""
        if self.materialized:
            # already scalar (e.g. a loss-driven policy synced early): just
            # fill in the timing fields the flush supplies
            return dataclasses.replace(
                self,
                wall_s=self.wall_s if wall_s is None else wall_s,
                tokens_per_sec=(self.tokens_per_sec if tokens_per_sec is None
                                else tokens_per_sec))
        with jax.profiler.TraceAnnotation(scopes.SYNC):
            loss = _scalarize(self.loss)
            extras = {k: _scalarize(v) for k, v in self.extras.items()}
        return dataclasses.replace(
            self, loss=loss, extras=extras,
            wall_s=self.wall_s if wall_s is None else wall_s,
            tokens_per_sec=(self.tokens_per_sec if tokens_per_sec is None
                            else tokens_per_sec),
            materialized=True)

    def flush_(self) -> "RoundMetrics":
        """Host-sync IN PLACE (``materialize`` returns a copy; this mutates).

        The session calls this on every outstanding metric before a
        donation-invalidating backend call (``repartition``, checkpoint
        load): a lazy device value read after its buffers were donated away
        would be garbage.  Idempotent; timing fields are left for the run
        loop's flush to fill."""
        if not self.materialized:
            with jax.profiler.TraceAnnotation(scopes.SYNC):
                self.loss = _scalarize(self.loss)
                self.extras = {k: _scalarize(v)
                               for k, v in self.extras.items()}
            self.materialized = True
        return self

    def to_dict(self) -> Dict[str, Any]:
        """Flat history dict (the shape ``launch/train.py`` always logged):
        loss/boundary/step/depth/wall_s at the top, cache stats as cache_*,
        extras merged in."""
        assert self.materialized, "materialize() before to_dict()"
        out = {"loss": self.loss, "boundary": self.boundary,
               "step": self.step, "depth": self.depth}
        if self.wall_s is not None:
            out["wall_s"] = self.wall_s
        if self.tokens_per_sec is not None:
            out["tokens_per_sec"] = round(self.tokens_per_sec, 2)
        out["compile_count"] = self.compile_count
        if self.cache is not None:
            out.update(self.cache)
            out["cache_hit"] = self.cache_hit
        out.update(self.extras)
        return out


# ---------------------------------------------------------------------------
# Callbacks
# ---------------------------------------------------------------------------


class Callback:
    """Base class: override any subset of the hooks."""

    def on_start(self, session) -> None:
        pass

    def on_round(self, session, metrics: RoundMetrics) -> None:
        pass

    def on_end(self, session, history: List[Dict[str, Any]]) -> None:
        pass


class LoggingCallback(Callback):
    """Per-interval progress lines, plus a guaranteed final-state line (the
    cadence follows materialization batches, so fused async behavior is
    preserved)."""

    def __init__(self, log=print, every: int = 1):
        self.log = log
        self.every = max(every, 1)
        self._n = 0
        self._last_step: Optional[int] = None

    def _emit(self, d: Dict[str, Any]) -> None:
        self._last_step = d["step"]
        cache = ""
        if "cache_hit_rate" in d:
            cache = (f" cache[hit={d['cache_hit_rate']:.0%} "
                     f"inval={d['cache_invalidations']:.0f}]")
        acc = d.get("accuracy", d.get("f1"))
        acc = "" if acc is None else f" acc/f1={acc:.3f}"
        tps = d.get("tokens_per_sec")
        tps = "" if tps is None else f" {tps:,.0f} tok/s"
        # a round that shrank/grew/repartitioned the ring gets a marker so
        # the loss blip right after it reads as recovery, not divergence
        el = ""
        if d.get("layout_changed"):
            surv = d.get("survivors")
            el = (" [elastic]" if surv is None
                  else f" [elastic S={len(surv)}]")
        self.log(f"step {d['step']:5d} b={d['boundary']:2d} "
                 f"d={d['depth']:2d} loss={d['loss']:.4f}"
                 f"{acc}{cache}{tps}{el} ({d.get('wall_s')}s)")

    def on_round(self, session, m: RoundMetrics) -> None:
        self._n += 1
        if (self._n - 1) % self.every == 0:
            self._emit(m.to_dict())

    def on_end(self, session, history) -> None:
        # the run's final state always gets a line, aligned interval or not
        if history and history[-1]["step"] != self._last_step:
            self._emit(history[-1])


class CheckpointCallback(Callback):
    """``session.save(path)`` every N observed rounds (and at on_end).

    Rounds are observed at materialization time, so the effective checkpoint
    granularity is bounded below by ``run``'s ``log_every`` — and the state
    saved is the session's CURRENT state (a flush delivering many rounds at
    once produces ONE save, not one per round)."""

    def __init__(self, path: str, every: int = 50):
        if every < 1:
            raise ValueError(f"every must be >= 1, got {every}")
        self.path = path
        self.every = every
        self._n = 0
        self._saved_at: Optional[int] = None

    def _save_once(self, session) -> None:
        if session.step_count != self._saved_at:
            session.save(self.path)
            self._saved_at = session.step_count

    def on_round(self, session, m: RoundMetrics) -> None:
        self._n += 1
        if self._n % self.every == 0:
            self._save_once(session)

    def on_end(self, session, history) -> None:
        self._save_once(session)


class BenchCaptureCallback(Callback):
    """Captures the perf trajectory (loss / tokens-per-sec / compile counts /
    cache hit rate per round) for benchmark harnesses."""

    def __init__(self):
        self.rounds: List[Dict[str, Any]] = []

    def on_round(self, session, m: RoundMetrics) -> None:
        self.rounds.append(m.to_dict())

    def result(self) -> Dict[str, Any]:
        if not self.rounds:
            return {}
        last = self.rounds[-1]
        tps = [r["tokens_per_sec"] for r in self.rounds
               if r.get("tokens_per_sec")]
        out = {"rounds": len(self.rounds),
               "final_loss": last["loss"],
               "final_boundary": last["boundary"],
               "compile_count": last["compile_count"],
               "boundary_trace": [r["boundary"] for r in self.rounds]}
        if tps:
            out["tokens_per_sec_steady"] = tps[-1]
        if "cache_hit_rate" in last:
            out["cache_hit_rate"] = last["cache_hit_rate"]
        return out
