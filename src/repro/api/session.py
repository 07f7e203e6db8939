"""RingSession: ONE pluggable training API over backends, policies, caching.

The paper's system is one coherent loop — ring pipeline, top-down scheduled
unfreezing, early-stopped backprop — and this facade is its single entry
point.  Every execution path is a :mod:`~repro.api.backends` adapter, every
unfreeze rule a :mod:`~repro.api.policies` policy, and a new scenario is a
~50-line plugin instead of a new driver:

    from repro.api import RingSession, LossPlateauPolicy

    sess = RingSession.create(cfg, tc, backend="cached", slots_per_epoch=8,
                              policy=LossPlateauPolicy(patience=3))
    history = sess.run(64, log_every=8)        # list of metric dicts
    sess.save("ckpt/ring")                     # params + Adam moments +
                                               # policy + data cursor
    sess2 = RingSession.restore("ckpt/ring", cfg, tc,
                                policy=LossPlateauPolicy(patience=3))
    sess2.run(64)                              # continues bit-identically

Contracts the session enforces (on top of the per-backend ones documented in
``backends.py``):

  * **monotone boundary** — the boundary reported by every step may never
    increase, whatever policy produced it; violations raise immediately
    (the activation cache's invalidation model depends on this, see
    ``core/unfreeze.py``);
  * **async metrics** — fused-backend metrics stay on device between logging
    intervals; ``run`` materializes them in batches.  A loss-driven policy
    (``wants_loss=True``) opts into one host sync per round — the documented
    price of adaptive unfreezing;
  * **bit-reproducible resume** — ``save`` persists params, optimizer
    moments, the policy's host state, the data cursor, and the step counter;
    ``restore`` + ``run`` replays exactly what the uninterrupted run would
    have produced (pinned by tests/test_api_session.py).
"""
from __future__ import annotations

import json
import time
import warnings
import weakref
from typing import Any, Dict, List, Optional, Sequence

import jax

from repro import scopes
from repro.checkpoint import checkpoint as ckpt
from repro.configs.base import ModelConfig, TrainConfig
from repro.core.elastic import parse_chaos_events
from repro.core.partition import parse_device_profiles, spans_from_profiles
from repro.core.simulator import ChurnEvent

from .backends import (CachedBackend, ChaosBackend, FusedBackend, PjitBackend,
                       ReferenceBackend)
from .data import PjitDataSource, RingDataSource
from .metrics import Callback, RoundMetrics
from .policies import resolve_policy
from .tenants import TenantGroup

BACKENDS = {"reference": ReferenceBackend, "fused": FusedBackend,
            "cached": CachedBackend, "pjit": PjitBackend}


class RingSession:
    """Facade over (backend, policy, data); build with :meth:`create` or
    :meth:`restore`, drive with :meth:`step` / :meth:`run`."""

    def __init__(self, cfg: ModelConfig, tc: TrainConfig, backend, policy,
                 data, *, callbacks: Sequence[Callback] = (),
                 create_args: Optional[Dict[str, Any]] = None):
        self.cfg, self.tc = cfg, tc
        self.backend, self.policy, self.data = backend, policy, data
        self.callbacks: List[Callback] = list(callbacks)
        self.step_count = 0
        self._last_boundary: Optional[int] = None
        self._create_args = create_args or {"backend": backend.name}
        # every un-materialized RoundMetrics this session has handed out —
        # flushed (host-synced in place) before any donation-invalidating
        # backend call (repartition / load), see flush_metrics()
        self._live_metrics: "weakref.WeakSet[RoundMetrics]" = weakref.WeakSet()
        # an elastic (chaos-wrapped) backend shrinks/repartitions INSIDE its
        # step() — it must flush pending device metrics first, and only the
        # session knows which ones are live
        if hasattr(backend, "flush_hook"):
            backend.flush_hook = self.flush_metrics

    # ------------------------------------------------------------------
    @classmethod
    def create(cls, cfg: ModelConfig, tc: TrainConfig, *,
               backend: Any = "fused", policy: Any = None,
               n_stages: Optional[int] = None,
               slots_per_epoch: Optional[int] = None,
               cache_capacity: Optional[int] = None,
               packed: bool = True, cache_dtype: str = "native",
               impl: str = "jnp", params: Optional[Dict[str, Any]] = None,
               spans: Any = None, device_profiles: Any = None,
               tenants: int = 1, elastic: bool = False, chaos: Any = (),
               data: Any = None, callbacks: Sequence[Callback] = (),
               log=print) -> "RingSession":
        """Wire a session from names: backend in {'pjit', 'reference',
        'fused', 'cached'} (or a ready Backend instance), policy in
        {'interval', 'plateau', None=paper rule} (or an UnfreezePolicy).
        Every named backend is built through ONE ``Backend.build`` call —
        each adapter validates or ignores the kwargs it doesn't support.

        ``cached`` needs ``slots_per_epoch`` (the cache's key space);
        ``cache_capacity`` defaults to it (x ``tenants``).  ``packed``
        (fused/cached) selects the packed-conveyor Phase A (one
        ``S*M + F - 1``-tick stream per round, ``T*S*M + F - 1`` with
        tenants; False = the per-owner scan, kept for A/B benchmarking);
        ``cache_dtype`` in {'native', 'f32', 'bf16', 'int8'} compresses the
        activation-cache entries (bf16 halves, int8 quarters the bytes per
        entry).  ``data=None`` builds the standard synthetic per-client
        datasets exactly as ``launch/train.py`` always did, so session runs
        are comparable to the seed drivers.

        Multi-tenant personalization (``tenants=T > 1``, fused/cached only):
        ONE frozen trunk serves T adapter sets — batches gain a tenant axis
        ([S, T, M, mb, seq], per-tenant data streams from seeds
        ``tc.seed + 7919*t``), metrics gain ``tenant_losses``, the cache
        partitions per tenant, and :attr:`tenants` exposes per-tenant
        :class:`~repro.api.tenants.TenantGroup` handles (save/load one
        tenant's adapters+moments through an ``AdapterStore``).  Per tenant,
        the joint session trains bit-identically to T independent
        single-tenant sessions (tests/test_tenants.py).

        Heterogeneous rings (ring backends only): ``device_profiles`` — one
        speed (float) or ``partition.DeviceProfile`` per stage, in ring order
        — runs the paper's Algorithm-1 speed-weighted block assignment
        (e.g. speeds ``[1.0, 1.25, 0.5, 0.75]`` over 14 blocks give the
        paper's 4:5:2:3 spans); ``spans`` pins an explicit layout (sizes
        list like ``[4, 5, 2, 3]`` or ``[(begin, end)]`` pairs) and wins
        over profiles.  The layout rides in checkpoints and must match on
        restore (the stage-stacked Adam moments are laid out per span).
        """
        policy = resolve_policy(policy, tc)
        S = n_stages or tc.n_stages
        if tenants < 1:
            raise ValueError(f"tenants must be >= 1, got {tenants}")
        if isinstance(backend, str):
            if backend not in BACKENDS:
                raise ValueError(f"unknown backend {backend!r}; "
                                 f"known: {sorted(BACKENDS)}")
            be = BACKENDS[backend].build(
                cfg, tc, policy, n_stages=S, spans=spans,
                device_profiles=device_profiles, params=params,
                slots_per_epoch=slots_per_epoch,
                cache_capacity=cache_capacity, packed=packed,
                cache_dtype=cache_dtype, impl=impl, tenants=tenants, log=log)
        else:
            be = backend
            # a ready instance already embeds the policy that drives its
            # schedule — that object MUST also be the one the session
            # observes losses into, or a loss-driven policy would never
            # unfreeze (and the monotone check would blame the wrong rule).
            policy = getattr(be, "policy", policy)
            if getattr(be, "T", 1) != tenants and tenants != 1:
                raise ValueError(
                    f"tenants={tenants} conflicts with the ready backend's "
                    f"T={getattr(be, 'T', 1)} — the instance decides")
            tenants = getattr(be, "T", 1)
            if isinstance(be, CachedBackend) and data is None \
                    and not slots_per_epoch:
                raise ValueError(
                    "a CachedBackend needs slot-keyed batches: pass "
                    "slots_per_epoch (for the default data source) or a "
                    "slot-yielding data= — with streaming draws every round "
                    "would silently bypass the cache (0% hits)")
        S0 = getattr(be, "S", S)           # pre-churn ring size
        if elastic or chaos:
            if be.kind == "pjit":
                raise ValueError(
                    "elastic/chaos is a ring feature — the pjit baseline has "
                    "no span layout to shrink or repartition")
            specs = [chaos] if isinstance(chaos, (str, ChurnEvent)) \
                else list(chaos)
            events = (list(parse_chaos_events(
                          [e for e in specs if isinstance(e, str)]))
                      + [e for e in specs if isinstance(e, ChurnEvent)])
            be = ChaosBackend(be, events=events, elastic=elastic,
                              device_profiles=device_profiles, log=log)
        if data is None:
            # an elastic ring keeps the ORIGINAL fanout: the source always
            # yields S0 client rows and ChaosBackend trims to survivors, so
            # the data cursor (and save -> resume) is churn-independent
            data = (PjitDataSource(cfg, tc) if be.kind == "pjit"
                    else RingDataSource(cfg, tc, S0,
                                        slots_per_epoch=slots_per_epoch,
                                        tenants=tenants))
        be_spans = getattr(be, "spans", None)
        create_args = {"backend": be.name,
                       # the ORIGINAL ring size: an elastic session's data
                       # source (and restore) is anchored to it even after
                       # churn shrinks the live ring below it
                       "n_stages": S0 if be.kind != "pjit" else None,
                       "slots_per_epoch": slots_per_epoch,
                       "cache_capacity": cache_capacity, "impl": impl,
                       "packed": packed, "cache_dtype": cache_dtype,
                       "tenants": tenants, "elastic": elastic,
                       # span layout rides in the checkpoint so restore
                       # rebuilds the same heterogeneous partition (JSON:
                       # list of [begin, end] pairs)
                       "spans": ([list(sp) for sp in be_spans]
                                 if be_spans is not None else None)}
        return cls(cfg, tc, be, policy, data, callbacks=callbacks,
                   create_args=create_args)

    # ------------------------------------------------------------------
    @property
    def n_tenants(self) -> int:
        return getattr(self.backend, "T", 1)

    @property
    def tenants(self) -> List[TenantGroup]:
        """Per-tenant handles (see :class:`~repro.api.tenants.TenantGroup`);
        a single-tenant session returns one group for tenant 0."""
        return [TenantGroup(self, t) for t in range(self.n_tenants)]

    # ------------------------------------------------------------------
    def step(self, batch: Any = None) -> RoundMetrics:
        """One backend step (a full ring round for ring backends, one
        optimizer step for pjit).  Returns possibly-device metrics; call
        ``.materialize()`` (or use :meth:`run`) to host-sync them.

        Under ``jax.profiler.trace`` the call records a ``ringada.round``
        span holding ``ringada.data`` (drawing the batch) and then
        ``ringada.dispatch`` (the backend step), see :mod:`repro.scopes`."""
        with jax.profiler.StepTraceAnnotation(scopes.ROUND,
                                              step_num=self.step_count):
            return self._step(batch)

    def _step(self, batch: Any) -> RoundMetrics:
        if batch is None:
            with jax.profiler.TraceAnnotation(scopes.DATA):
                batch = self.data.next()
        with jax.profiler.TraceAnnotation(scopes.DISPATCH):
            raw = self.backend.step(batch)
        if raw.get("layout_changed"):
            # an elastic shrink/grow/repartition happened INSIDE the step:
            # span edges (and so boundary alignment granularity) moved, so
            # the monotone check re-seeds from this round's boundary, the
            # checkpointed layout/membership follow the live ring, and a
            # plateau policy skips the recovery blip (geometry artifact,
            # not training signal)
            self._last_boundary = None
            be_spans = getattr(self.backend, "spans", None)
            self._create_args["spans"] = ([list(sp) for sp in be_spans]
                                          if be_spans is not None else None)
            surv = getattr(self.backend, "survivors", None)
            if surv is not None:
                self._create_args["survivors"] = list(surv)
            if hasattr(self.policy, "suspend"):
                self.policy.suspend(1)
        boundary = raw["boundary"]
        if self._last_boundary is not None and boundary > self._last_boundary:
            raise RuntimeError(
                f"unfreeze boundary increased {self._last_boundary} -> "
                f"{boundary} at step {raw['step']} (policy "
                f"{self.policy!r}): RingAda schedules are monotone top-down "
                f"and the activation cache's invalidation contract depends "
                f"on it (see core/unfreeze.py)")
        self._last_boundary = boundary
        self.step_count = raw["step"]
        m = RoundMetrics(step=raw["step"], boundary=boundary,
                         depth=raw["depth"], loss=raw["loss"],
                         compile_count=self.backend.compile_count,
                         tokens=raw.get("tokens", 0),
                         cache=raw.get("cache"),
                         cache_hit=raw.get("cache_hit"),
                         extras=raw.get("extras", {}))
        if self.policy.wants_loss:
            m = m.materialize()            # adaptive policies pay 1 sync/round
            self.policy.observe(self.step_count, m.loss)
        else:
            self._live_metrics.add(m)      # flushed before layout changes
        return m

    def flush_metrics(self) -> None:
        """Host-sync (in place) every un-materialized RoundMetrics this
        session has handed out.  Called before any backend operation that
        invalidates live device buffers (repartition's donated restack,
        checkpoint load): a history entry must never read post-swap bits."""
        for m in list(self._live_metrics):
            m.flush_()
        self._live_metrics.clear()

    def repartition(self, spans: Any) -> None:
        """Switch the ring's span layout mid-run (elastic membership /
        re-profiling).  Pending device metrics are flushed FIRST — the
        restack donates the live param/moment buffers, and a lazy metric
        materialized after that donation would read freed memory (pinned by
        tests/test_tenants.py)."""
        self.flush_metrics()
        self.backend.repartition(spans)
        be_spans = getattr(self.backend, "spans", None)
        self._create_args["spans"] = ([list(sp) for sp in be_spans]
                                      if be_spans is not None else None)

    def run(self, steps: int, *, log_every: int = 1,
            callbacks: Optional[Sequence[Callback]] = None,
            ) -> List[Dict[str, Any]]:
        """Drive ``steps`` backend steps off the session's data source.

        Metrics are materialized once per ``log_every`` interval (the fused
        async-dispatch contract) and EVERY step lands in the returned history
        (as flat dicts).  Callbacks fire per materialized step.
        """
        cbs = self.callbacks + list(callbacks or [])
        for cb in cbs:
            cb.on_start(self)
        history: List[Dict[str, Any]] = []
        pending: List[RoundMetrics] = []
        t0 = last_t = time.perf_counter()
        tokens_acc = 0

        def flush():
            nonlocal last_t, tokens_acc
            now = time.perf_counter()
            dt = now - last_t
            tps = tokens_acc / dt if dt > 0 and tokens_acc else None
            for pm in pending:
                mm = pm.materialize(wall_s=round(now - t0, 2),
                                    tokens_per_sec=tps)
                history.append(mm.to_dict())
                for cb in cbs:
                    cb.on_round(self, mm)
            pending.clear()
            last_t, tokens_acc = now, 0

        for i in range(steps):
            m = self.step()
            pending.append(m)
            tokens_acc += m.tokens
            if i % log_every == 0 or i == steps - 1:
                flush()
        flush()
        for cb in cbs:
            cb.on_end(self, history)
        return history

    # ------------------------------------------------------------------
    # persistence: the canonical surface is save(path) /
    # RingSession.restore(path, cfg, tc, ...) / export_adapters(tenant=...);
    # load() and export_params() remain as deprecated shims.
    # ------------------------------------------------------------------
    def export_adapters(self, tenant: int = 0) -> Dict[str, Any]:
        """One tenant's trainable set as a flat ``{"adapter", "head"}``
        bundle — the unit an :class:`~repro.api.tenants.AdapterStore`
        persists and serving hot-swaps.  Ring backends only (the pjit
        backend's trainable set isn't adapter-shaped)."""
        d = getattr(self.backend, "driver", None)
        if d is None or not hasattr(d, "export_adapters"):
            raise NotImplementedError(
                f"backend {self.backend.name!r} has no adapter bundle "
                f"surface; use backend.state() for its full params")
        return d.export_adapters(tenant)

    def export_params(self) -> Dict[str, Any]:
        """Deprecated: use ``backend.export_params()`` for the full canonical
        tree, or :meth:`export_adapters` for the trainable bundle."""
        warnings.warn(
            "RingSession.export_params() is deprecated — use "
            "session.backend.export_params() (full canonical tree) or "
            "session.export_adapters(tenant=...) (trainable bundle)",
            DeprecationWarning, stacklevel=2)
        return self.backend.export_params()

    def save(self, path: str) -> None:
        """Persist the complete resumable state: params + Adam moments (via
        ``checkpoint.save(..., opt_state=...)``), the policy's host state,
        the data cursor, and the step counter.  Adapter-only params payload
        (the backbone is frozen + seed-derived, so it reconstructs exactly)."""
        st = self.backend.state()
        extra = {
            "session": "RingSession/v1",
            "format": st["format"],
            "seed": self.tc.seed,
            "last_boundary": self._last_boundary,
            "policy": {"type": type(self.policy).__name__,
                       "state": self.policy.state()},
            "data": self.data.state(),
            **self._create_args,
        }
        ckpt.save(path, st["params"], step=self.step_count,
                  opt_state=st["opt"], adapters_only=True, extra=extra)

    def load(self, path: str) -> "RingSession":
        """Deprecated: use the classmethod :meth:`restore` — it rebuilds the
        session with the checkpoint's recorded shape arguments before
        loading, which this method cannot do."""
        warnings.warn(
            "RingSession.load() is deprecated — use "
            "RingSession.restore(path, cfg, tc, ...) instead",
            DeprecationWarning, stacklevel=2)
        return self._load_into(path)

    def _load_into(self, path: str) -> "RingSession":
        """Load a checkpoint into this (freshly created, same-config)
        session.  Raises on backend-format or policy-type mismatch instead of
        silently reinterpreting moments."""
        self.flush_metrics()               # load swaps the live buffers
        st = self.backend.state()
        params, meta = ckpt.restore(path, st["params"])
        ex = meta["extra"]
        if ex.get("format") != st["format"]:
            raise ValueError(
                f"checkpoint {path!r} was saved by a {ex.get('format')!r} "
                f"backend but this session runs {st['format']!r} — optimizer "
                f"moments are laid out per-format (stage-stacked vs full-"
                f"size) and cannot be reinterpreted across families. "
                f"Recreate the session with the saved backend.")
        saved_policy = ex.get("policy", {})
        if saved_policy.get("type") != type(self.policy).__name__:
            raise ValueError(
                f"checkpoint {path!r} was driven by policy "
                f"{saved_policy.get('type')!r} but this session has "
                f"{type(self.policy).__name__!r} — pass the matching policy "
                f"to restore() so the depth sequence continues correctly.")
        opt = ckpt.restore_opt(path, st["opt"])
        self.backend.load_state(params, opt, step=meta["step"])
        self.policy.load_state(saved_policy.get("state", {}))
        self.data.load_state(ex["data"])
        self.step_count = meta["step"]
        self._last_boundary = ex.get("last_boundary")
        return self

    @classmethod
    def restore(cls, path: str, cfg: ModelConfig, tc: TrainConfig, *,
                policy: Any = None, backend: Any = None, log=print,
                **create_kwargs) -> "RingSession":
        """Rebuild a session from a checkpoint.  Backend/shape arguments
        default to what the checkpoint recorded; the policy must be supplied
        with the same type it was saved with (its host state is restored).

        A checkpoint saved AFTER an elastic shrink records the surviving
        original-device indices; restore rebuilds the ring at the original
        size, replays the membership (shrinking away the dead stages and
        repartitioning to the saved spans) and only then loads — so the
        stage-stacked moments land on the exact geometry they were saved
        from, with no checkpoint-format special case.

        Restoring with ``elastic=True`` and ``device_profiles`` describing a
        fleet whose Algorithm-1 layout differs from the checkpoint's spans
        does not abort: the saved layout is loaded first (moments are laid
        out per span), then the ring repartitions live to the fleet's layout.
        """
        with open(path + ".json") as f:
            meta = json.load(f)
        ex = meta["extra"]
        if backend is None:
            backend = ex.get("backend", "fused")
        for k in ("n_stages", "slots_per_epoch", "cache_capacity", "impl",
                  "packed", "cache_dtype", "spans", "tenants", "elastic"):
            if k in ex and ex[k] is not None:
                create_kwargs.setdefault(k, ex[k])
        if backend == "pjit":
            # a ring checkpoint's span layout means nothing to pjit; let the
            # format-mismatch check produce the real diagnostic
            create_kwargs.pop("spans", None)
        surv = ex.get("survivors")
        saved_spans = create_kwargs.get("spans")
        if surv is not None and len(surv) < int(ex.get("n_stages") or 0):
            # post-shrink checkpoint: build at the original size with the
            # default layout (the saved spans describe the SHRUNK ring and
            # would mis-size an S0 build), then replay the membership
            create_kwargs.pop("spans", None)
            create_kwargs["elastic"] = True
        sess = cls.create(cfg, tc, backend=backend, policy=policy, log=log,
                          **create_kwargs)
        if surv is not None and len(surv) < int(ex.get("n_stages") or 0):
            sess.backend.restore_membership(surv, spans=saved_spans)
            sess._create_args["spans"] = saved_spans
            sess._create_args["survivors"] = list(surv)
        sess._load_into(path)
        if create_kwargs.get("elastic") \
                and create_kwargs.get("device_profiles") is not None:
            profs = parse_device_profiles(create_kwargs["device_profiles"])
            live = getattr(sess.backend, "spans", None)
            if live is not None and len(profs) == len(live):
                desired = [list(sp) for sp in
                           spans_from_profiles(cfg.repeats, profs)]
                if desired != [list(sp) for sp in live]:
                    log(f"[elastic] checkpoint layout "
                        f"{[e - b for b, e in live]} is stale for the given "
                        f"fleet -> repartitioning to "
                        f"{[e - b for b, e in desired]}")
                    sess.repartition(desired)
        return sess
