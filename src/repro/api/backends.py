"""Backend adapters: one ``step`` protocol over every training path.

The seed repo grew four divergent drivers — the unfused ``RingTrainer``
oracle, the fused ``RingExecutor``, the executor + ``ActivationCache``
combination, and the pjit staged-recompile loop — each hand-wired in
``launch/train.py``.  A :class:`Backend` adapts each one to a single surface
the :class:`~repro.api.session.RingSession` can drive:

    class Backend(Protocol):
        kind: str                 # "ring" | "pjit" (selects the data source)
        name: str                 # CLI/back-compat name
        steps_per_call: int       # global steps one step() advances
        compile_count: int        # executables built so far
        @classmethod
        def build(cls, cfg, tc, policy, *, n_stages, spans, device_profiles,
                  params, slots_per_epoch, cache_capacity, packed,
                  cache_dtype, impl, tenants, log) -> Backend
        def step(self, batch) -> dict           # raw metrics (may hold device arrays)
        def state(self) -> dict                 # {"format", "params", "opt"}
        def load_state(self, params, opt, *, step) -> None
        def export_params(self) -> params tree  # canonical [R, ...] layout

    ``build`` is the one constructor the session calls: every backend takes
    the SAME keyword surface and validates/ignores what it doesn't support
    (pjit rejects spans, reference/pjit reject tenants > 1, cached requires
    ``slots_per_epoch``), so ``RingSession.create`` is a single dispatch
    instead of a per-backend kwarg ladder.

Protocol contracts every adapter honors:

  * **monotone boundary** — the backend evaluates its (injected) policy's
    ``depth_at`` per step/round; the resulting boundary may never increase
    (re-checked here and in ``core/executor.py``);
  * **donation** — fused/pjit steps donate params + optimizer moments, so a
    caller must treat the trees it handed in as consumed; ``state()`` always
    returns the LIVE trees;
  * **cache invalidation** — the cached backend's activation cache is keyed
    ``(slot, boundary)`` and cleared wholesale on every boundary drop and on
    ``load_state`` (a restored session never serves pre-restore activations).

``state()["format"]`` tags the optimizer-state layout (ring moments are
stage-stacked ``[S, lps, ...]``; pjit moments are full-size ``[R, ...]`` per
pattern entry).  Checkpoints restore only into a backend with the same
format — the session raises a clear error instead of silently reshaping
moments across families.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, TrainConfig
from repro.core import pipeline as pl
from repro.core import training
from repro.core.elastic import StragglerDetector
from repro.core.partition import (DeviceProfile, parse_device_profiles,
                                  span_sizes, spans_from_profiles,
                                  uniform_assignment)
from repro.core.simulator import ChurnEvent
from repro.core.unfreeze import depth_to_boundary
from repro.models import params as prm
from repro.optim import adamw

CACHE_STAT_KEYS = ("cache_hits", "cache_misses", "cache_hit_rate",
                   "cache_evictions", "cache_invalidations", "cache_bypasses",
                   "cache_entries", "cache_capacity", "cache_dtype",
                   "cache_bytes_per_entry", "cache_buffer_bytes")


def _default_params(cfg: ModelConfig, tc: TrainConfig):
    return prm.materialize(prm.param_defs(cfg), jax.random.key(tc.seed),
                           cfg.dtype)


def _validate_ring(cfg: ModelConfig, n_stages: int) -> None:
    """The ring-mode preconditions that used to live in launch/train.py.

    (The historical repeats-divisible-by-stages precondition is gone: the
    ragged-span pipeline runs any contiguous layout, and ``spans=None``
    falls back to the most balanced split.)
    """
    if cfg.head_out is not None:
        raise ValueError(
            f"ring backends train with the LM objective, but this config has "
            f"a task head (head_out={cfg.head_out}) — the loss would be "
            f"garbage/NaN. Use an LM config, or reduce with head_out=None "
            f"like examples/ring_finetune.py.")
    if cfg.repeats < n_stages:
        raise ValueError(
            f"ring training needs at least one block per stage: "
            f"cfg.repeats={cfg.repeats} < n_stages={n_stages}.")


def _block_weight_mb(cfg: ModelConfig) -> float:
    """Per-block weight footprint (MB) — the memory cost Algorithm 1 charges
    a device per assigned block when DeviceProfile budgets are finite."""
    kind = cfg.pattern[0][0]
    n = prm.count_params(prm.block_defs(cfg, kind)) * cfg.layers_per_repeat
    return n * jnp.dtype(cfg.dtype).itemsize / 2**20


def _resolve_ring_spans(cfg: ModelConfig, n_stages: int, spans,
                        device_profiles):
    """(spans, device_profiles) -> canonical span layout (None = balanced).

    ``device_profiles`` (speeds or DeviceProfile objects, ring order) runs
    the paper's Algorithm-1 speed-weighted assignment; an explicit ``spans``
    ([(b, e)] pairs or a sizes list like [4, 5, 2, 3]) wins over both.
    Profiles with FINITE ``memory_mb`` budgets also bind the assignment's
    memory-feasibility constraint, charged at the per-block weight footprint
    (bare speeds — the CLI path — leave memory unconstrained).
    """
    if spans is None and device_profiles is not None:
        import math

        profiles = parse_device_profiles(device_profiles)
        if len(profiles) != n_stages:
            raise ValueError(
                f"{len(profiles)} device profiles for a {n_stages}-stage "
                f"ring — pass exactly one per stage, in ring order")
        mem = None
        if any(math.isfinite(p.memory_mb) for p in profiles):
            mem = [_block_weight_mb(cfg)] * cfg.repeats
        spans = spans_from_profiles(cfg.repeats, profiles, layer_mem_mb=mem)
    return pl.resolve_spans(cfg.repeats, n_stages, spans)


class _RingBackendBase:
    """Shared plumbing for the three ring adapters (mesh, batch unpacking,
    canonical <-> stage-stacked param translation, opt-state format tag,
    span-layout resolution)."""

    kind = "ring"
    T = 1                                  # tenants (multi-tenant overrides)

    def __init__(self, cfg: ModelConfig, tc: TrainConfig, policy, *,
                 n_stages: int, spans=None, device_profiles=None):
        from repro.launch.mesh import require_devices

        _validate_ring(cfg, n_stages)
        require_devices(n_stages)
        self.cfg, self.tc, self.policy = cfg, tc, policy
        self.S = n_stages
        self.spans = _resolve_ring_spans(cfg, n_stages, spans,
                                         device_profiles)
        self.mesh = pl.make_ring_mesh(n_stages)

    # -- shared surface -------------------------------------------------
    @property
    def steps_per_call(self) -> int:
        return self.S                      # one round = S initiator steps

    @property
    def format(self) -> str:
        """Opt-state layout tag.  Non-default span layouts are part of the
        format: adapter moments are padded [S, max_span, ...] per the layout,
        so a checkpoint only restores into the same layout.  Multi-tenant
        sessions append ``/T{T}`` — tenant-stacked moments ([S, T, ...]) are
        a different layout family from single-tenant ones."""
        default = tuple(uniform_assignment(self.cfg.repeats, self.S))
        if self.spans == default:
            tag = f"ring/S{self.S}"
        else:
            sig = "-".join(str(n) for n in span_sizes(self.spans))
            tag = f"ring/S{self.S}/spans{sig}"
        return tag if self.T == 1 else f"{tag}/T{self.T}"

    def export_params(self) -> Dict[str, Any]:
        return self.driver.export_params()

    @staticmethod
    def _unpack(batch) -> Tuple[Optional[int], Any, Any]:
        if len(batch) == 3:
            return batch
        tokens, labels = batch
        return None, tokens, labels

    def _depth_of(self, boundary: int) -> int:
        return self.cfg.repeats - boundary

    def _restack(self, params: Dict[str, Any]) -> None:
        d = self.driver
        if hasattr(d, "load_canonical"):
            # the executor owns its canonical <-> stacked translation (and at
            # T > 1 the tree is tenant-stacked — only it knows that layout)
            d.load_canonical(params)
            return
        d.stage_blocks, d.shared = pl.stage_stack(params, self.cfg, self.S,
                                                  spans=self.spans)
        d._params_rest = {k: v for k, v in params.items() if k != "blocks"}

    def repartition(self, spans) -> None:
        """Switch the live span layout (executor-backed backends only); the
        session flushes pending device metrics before calling this."""
        d = self.driver
        if not hasattr(d, "repartition"):
            raise NotImplementedError(
                f"backend {self.name!r} cannot repartition mid-run")
        d.repartition(pl.resolve_spans(self.cfg.repeats, self.S, spans))
        self.spans = d.spans

    def shrink(self, dead_stage: int, *, spans=None, profiles=None) -> None:
        """Live S -> S-1 shrink (executor-backed backends only): drop stage
        ``dead_stage`` and reassign its span over the survivors.  The caller
        flushes pending device metrics first — the restack donates the
        buffers they point at."""
        d = self.driver
        if not hasattr(d, "shrink"):
            raise NotImplementedError(
                f"backend {self.name!r} cannot shrink mid-run — use "
                f"backend='fused' or 'cached'")
        d.shrink(dead_stage, spans=spans, profiles=profiles)
        self.S, self.mesh, self.spans = d.S, d.mesh, d.spans

    def grow(self, profile=None, *, spans=None, profiles=None) -> None:
        """Inverse of ``shrink``: a device joins, S grows by one."""
        d = self.driver
        if not hasattr(d, "grow"):
            raise NotImplementedError(
                f"backend {self.name!r} cannot grow mid-run — use "
                f"backend='fused' or 'cached'")
        d.grow(profile, spans=spans, profiles=profiles)
        self.S, self.mesh, self.spans = d.S, d.mesh, d.spans


class ReferenceBackend(_RingBackendBase):
    """The unfused ``RingTrainer`` oracle: S dispatches per round, host-side
    optimizer, one loss sync per iteration (metrics are host floats)."""

    name = "reference"

    def __init__(self, cfg, tc, policy, *, n_stages: int, params=None,
                 spans=None, device_profiles=None):
        from repro.core.ring import RingTrainer

        super().__init__(cfg, tc, policy, n_stages=n_stages, spans=spans,
                         device_profiles=device_profiles)
        if params is None:
            params = _default_params(cfg, tc)
        self.driver = RingTrainer(cfg, tc, self.mesh, params,
                                  n_stages, tc.n_microbatches, schedule=policy,
                                  spans=self.spans)

    @classmethod
    def build(cls, cfg, tc, policy, *, n_stages, spans=None,
              device_profiles=None, params=None, slots_per_epoch=None,
              cache_capacity=None, packed=True, cache_dtype="native",
              impl="jnp", tenants=1, log=print) -> "ReferenceBackend":
        if tenants > 1:
            raise ValueError(
                "tenants > 1 needs the fused executable (tenant-stacked "
                "adapters + the T-tenant conveyor) — use backend='fused' or "
                "'cached'; the reference oracle is single-tenant")
        return cls(cfg, tc, policy, n_stages=n_stages, params=params,
                   spans=spans, device_profiles=device_profiles)

    @property
    def compile_count(self) -> int:
        return self.driver.n_executables

    def step(self, batch) -> Dict[str, Any]:
        _, tokens, labels = self._unpack(batch)
        with jax.set_mesh(self.mesh):
            m = self.driver.round(tokens, labels)
        return {"loss": m["loss"], "boundary": m["boundary"],
                "depth": self._depth_of(m["boundary"]), "step": m["step"],
                "tokens": int(tokens.size)}

    def state(self) -> Dict[str, Any]:
        d = self.driver
        opt = {"m": {"adapter": d.m_ad, "head": d.m_hd},
               "v": {"adapter": d.v_ad, "head": d.v_hd},
               "count": jnp.int32(d.step)}
        return {"format": self.format, "params": self.export_params(),
                "opt": opt}

    def load_state(self, params, opt, *, step: int) -> None:
        self._restack(params)
        d = self.driver
        d.m_ad, d.m_hd = opt["m"]["adapter"], opt["m"]["head"]
        d.v_ad, d.v_hd = opt["v"]["adapter"], opt["v"]["head"]
        d.step = step


class FusedBackend(_RingBackendBase):
    """The fused ``RingExecutor``: one donated executable per boundary,
    metrics stay on device until the session materializes them."""

    name = "fused"

    def __init__(self, cfg, tc, policy, *, n_stages: int, params=None,
                 cache_capacity: int = 0, packed: bool = True,
                 cache_dtype: str = "native", spans=None,
                 device_profiles=None, tenants: int = 1):
        from repro.core.executor import RingExecutor

        super().__init__(cfg, tc, policy, n_stages=n_stages, spans=spans,
                         device_profiles=device_profiles)
        self.T = tenants
        # params=None: the executor builds the seeded weights directly in
        # their stage placement — no canonical copy is ever held here.
        self.driver = RingExecutor(cfg, tc, self.mesh, params,
                                   n_stages, tc.n_microbatches,
                                   cache_capacity=cache_capacity,
                                   schedule=policy, packed=packed,
                                   cache_dtype=cache_dtype, spans=self.spans,
                                   tenants=tenants)

    @classmethod
    def build(cls, cfg, tc, policy, *, n_stages, spans=None,
              device_profiles=None, params=None, slots_per_epoch=None,
              cache_capacity=None, packed=True, cache_dtype="native",
              impl="jnp", tenants=1, log=print) -> "FusedBackend":
        return cls(cfg, tc, policy, n_stages=n_stages, params=params,
                   packed=packed, cache_dtype=cache_dtype, spans=spans,
                   device_profiles=device_profiles, tenants=tenants)

    @property
    def compile_count(self) -> int:
        return self.driver.n_executables

    def step(self, batch) -> Dict[str, Any]:
        slot, tokens, labels = self._unpack(batch)
        with jax.set_mesh(self.mesh):
            m = self.driver.round(tokens, labels, slot=slot)
        raw = {"loss": m["loss"], "boundary": m["boundary"],
               "depth": self._depth_of(m["boundary"]), "step": m["step"],
               "tokens": int(tokens.size),
               "extras": {"losses": m["losses"], "mode": m["mode"]}}
        if self.T > 1:
            raw["extras"]["tenant_losses"] = m["tenant_losses"]
        if self.driver.cache is not None:
            raw["cache"] = {k: m[k] for k in CACHE_STAT_KEYS}
            raw["cache_hit"] = m["cache_hit"]
            if self.T > 1:
                raw["cache"]["tenant_cache_hits"] = m["tenant_cache_hits"]
                raw["cache"]["tenant_cache_misses"] = m["tenant_cache_misses"]
        return raw

    def state(self) -> Dict[str, Any]:
        return {"format": self.format, "params": self.export_params(),
                "opt": self.driver.opt_state}

    def load_state(self, params, opt, *, step: int) -> None:
        self._restack(params)
        d = self.driver
        d.opt_state = opt
        d.step = step
        d._last_boundary = None            # monotone check re-seeds post-load
        if d.cache is not None:
            d.cache.invalidate()           # never serve pre-restore activations


class CachedBackend(FusedBackend):
    """Fused executor + the frozen-trunk activation cache (Phase-A skip).

    Requires slot-keyed batches (``slots_per_epoch`` on the data source) —
    streaming draws would never revisit a key, so constructing this backend
    without a positive capacity is an error rather than a silent no-op.
    """

    name = "cached"

    def __init__(self, cfg, tc, policy, *, n_stages: int, cache_capacity: int,
                 params=None, packed: bool = True,
                 cache_dtype: str = "native", spans=None,
                 device_profiles=None, tenants: int = 1):
        if cache_capacity < 1:
            raise ValueError(
                f"CachedBackend needs cache_capacity >= 1 (got "
                f"{cache_capacity}); use FusedBackend for uncached rounds")
        super().__init__(cfg, tc, policy, n_stages=n_stages, params=params,
                         cache_capacity=cache_capacity, packed=packed,
                         cache_dtype=cache_dtype, spans=spans,
                         device_profiles=device_profiles, tenants=tenants)

    @classmethod
    def build(cls, cfg, tc, policy, *, n_stages, spans=None,
              device_profiles=None, params=None, slots_per_epoch=None,
              cache_capacity=None, packed=True, cache_dtype="native",
              impl="jnp", tenants=1, log=print) -> "CachedBackend":
        if not slots_per_epoch:
            raise ValueError(
                "backend='cached' needs slots_per_epoch >= 1: the "
                "activation cache keys on stable batch slots — with "
                "streaming draws no key ever repeats. Use "
                "backend='fused' for non-repeating data.")
        cap = (cache_capacity if cache_capacity is not None
               else slots_per_epoch * tenants)
        # T tenants each own a (tenant, slot, boundary) key per slot, so the
        # thrash threshold scales with T as well.
        if 0 < cap < slots_per_epoch * tenants:
            # round-robin slots + LRU: every slot is evicted before its
            # revisit — all capture cost, zero hits
            log(f"WARNING: cache_capacity {cap} < slots_per_epoch "
                f"{slots_per_epoch}"
                + (f" x tenants {tenants}" if tenants > 1 else "")
                + ": the cache will thrash (0% hits, capture overhead every "
                  "round) — raise the capacity or use backend='fused'")
        return cls(cfg, tc, policy, n_stages=n_stages, cache_capacity=cap,
                   params=params, packed=packed, cache_dtype=cache_dtype,
                   spans=spans, device_profiles=device_profiles,
                   tenants=tenants)


class PjitBackend:
    """The staged-recompile pjit path: single- or multi-device data/tensor
    parallel steps, one jitted+donated step fn per distinct boundary."""

    kind = "pjit"
    name = "pjit"
    steps_per_call = 1

    def __init__(self, cfg: ModelConfig, tc: TrainConfig, policy, *,
                 impl: str = "jnp", params: Optional[Dict[str, Any]] = None):
        self.cfg, self.tc, self.policy = cfg, tc, policy
        self.impl = impl
        self._params = params if params is not None else _default_params(cfg, tc)
        self._opt = adamw.init(training.full_trainable(self._params))
        self._fns: Dict[int, Any] = {}      # boundary -> jitted step
        self._step = 0

    @classmethod
    def build(cls, cfg, tc, policy, *, n_stages=None, spans=None,
              device_profiles=None, params=None, slots_per_epoch=None,
              cache_capacity=None, packed=True, cache_dtype="native",
              impl="jnp", tenants=1, log=print) -> "PjitBackend":
        if spans is not None or device_profiles is not None:
            raise ValueError(
                "spans/device_profiles describe the ring's stage layout "
                "— they have no meaning for the pjit backend")
        if tenants > 1:
            raise ValueError(
                "tenants > 1 is a ring concept (T adapter sets over one "
                "frozen ring trunk) — use backend='fused' or 'cached'")
        return cls(cfg, tc, policy, impl=impl, params=params)

    @property
    def format(self) -> str:
        return "pjit"

    @property
    def compile_count(self) -> int:
        return len(self._fns)

    def _fn(self, boundary: int):
        if boundary not in self._fns:
            fn = training.make_step(self.cfg, self.tc, boundary,
                                    impl=self.impl)
            self._fns[boundary] = jax.jit(fn, donate_argnums=(0, 1))
        return self._fns[boundary]

    def step(self, batch: Dict[str, Any]) -> Dict[str, Any]:
        depth = self.policy.depth_at(self._step, self.cfg.n_layers)
        boundary = depth_to_boundary(self.cfg, depth)
        self._params, self._opt, metrics = self._fn(boundary)(
            self._params, self._opt, batch)
        self._step += 1
        extras = {k: v for k, v in metrics.items() if k != "loss"}
        return {"loss": metrics["loss"], "boundary": boundary, "depth": depth,
                "step": self._step, "tokens": int(batch["tokens"].size),
                "extras": extras}

    def export_params(self) -> Dict[str, Any]:
        return self._params

    def state(self) -> Dict[str, Any]:
        return {"format": self.format, "params": self._params,
                "opt": self._opt}

    def load_state(self, params, opt, *, step: int) -> None:
        self._params = params
        self._opt = opt
        self._step = step


class ChaosBackend:
    """Fault-injection + elasticity wrapper over a ring backend.

    Wraps any executor-backed ring backend and, per ``step``:

      1. fires every pending :class:`~repro.core.simulator.ChurnEvent` whose
         round has arrived (``round=3`` means rounds 0-2 ran on the old
         fleet) — a ``crash``/``leave`` shrinks the inner ring live (with
         ``elastic=True``; without it the crash raises, which is exactly
         what the un-wrapped ring would do by stalling), a ``slowdown``
         degrades that device's ground-truth speed, a ``join`` reclaims a
         previously-dead device's slot;
      2. trims the round's ``[S0, ...]`` batch to the survivors' original
         rows (the data source keeps producing at the original ring size,
         which is what makes save -> resume bit-reproducible across a
         shrink);
      3. delegates to the inner backend;
      4. synthesizes per-stage wall times from the ground-truth speeds
         (``span_size / speed`` — the SPMD tick model; real deployments
         would use measured stage timings) into ``extras["stage_times"]``;
      5. with ``elastic=True``, feeds those timings to a
         :class:`~repro.core.elastic.StragglerDetector` and applies its
         (hysteresis-gated) repartition proposal.

    Any round that changed the ring layout is flagged
    ``raw["layout_changed"]`` so the session can re-seed its monotone-
    boundary check and suspend plateau policies for the blip.  Everything
    else (``state``/``load_state``/``format``/``export_params``/...)
    delegates to the inner backend untouched.
    """

    def __init__(self, inner, *, events: Sequence[ChurnEvent] = (),
                 elastic: bool = False, device_profiles=None, log=print):
        self.inner = inner
        self.elastic = elastic
        self.log = log
        self.events: List[ChurnEvent] = sorted(events, key=lambda e: e.round)
        if device_profiles is not None:
            profs = parse_device_profiles(device_profiles)
            if len(profs) != inner.S:
                raise ValueError(
                    f"{len(profs)} device profiles for a {inner.S}-stage "
                    f"ring")
        else:
            profs = [DeviceProfile(1.0, float("inf"))
                     for _ in range(inner.S)]
        # keyed by ORIGINAL device index — survivors map stage -> original
        self.profiles: Dict[int, DeviceProfile] = dict(enumerate(profs))
        self.speeds: Dict[int, float] = {
            i: p.compute_speed for i, p in self.profiles.items()}
        self.survivors: List[int] = list(range(inner.S))
        self.detector: Optional[StragglerDetector] = (
            StragglerDetector(profs, inner.cfg.repeats) if elastic else None)
        self.flush_hook = None              # session assigns: flush metrics
        self.round_idx = 0
        self.shrinks = 0
        self.repartitions = 0

    def __getattr__(self, name):
        return getattr(self.inner, name)

    def _flush(self) -> None:
        if self.flush_hook is not None:
            self.flush_hook()

    def _survivor_profiles(self) -> List[DeviceProfile]:
        if self.detector is not None:
            return self.detector.fleet      # EWMA-refit speeds
        return [self.profiles[d] for d in self.survivors]

    def _apply(self, ev: ChurnEvent) -> bool:
        """Fire one event against the live ring; True if the layout moved."""
        if ev.kind in ("crash", "leave"):
            if ev.device not in self.survivors:
                raise ValueError(
                    f"churn {ev.kind} targets device {ev.device}, which is "
                    f"not alive (survivors: {self.survivors})")
            if not self.elastic:
                raise RuntimeError(
                    f"device {ev.device} {'crashed' if ev.kind == 'crash' else 'left'} "
                    f"at round {self.round_idx} and the ring is not elastic "
                    f"— run with elastic=True (--elastic) to shrink and "
                    f"continue")
            stage = self.survivors.index(ev.device)
            self._flush()
            self.survivors.pop(stage)
            if self.detector is not None:
                self.detector.remove(stage)
            old = [list(sp) for sp in self.inner.spans]
            self.inner.shrink(stage, profiles=self._survivor_profiles())
            self.shrinks += 1
            self.log(f"[elastic] device {ev.device} {ev.kind} at round "
                     f"{self.round_idx}: ring {len(self.survivors) + 1} -> "
                     f"{len(self.survivors)} stages, spans {old} -> "
                     f"{[list(sp) for sp in self.inner.spans]} "
                     f"(cache re-captures next round)")
            return True
        if ev.kind == "slowdown":
            if ev.device not in self.survivors:
                raise ValueError(
                    f"churn slowdown targets device {ev.device}, which is "
                    f"not alive (survivors: {self.survivors})")
            self.speeds[ev.device] /= ev.factor
            self.log(f"[elastic] device {ev.device} slowed {ev.factor}x at "
                     f"round {self.round_idx}"
                     + ("" if self.elastic else
                        " (not elastic: the ring will limp, not repartition)"))
            return False                    # detector discovers it from timings
        # join: only a previously-dead device's slot can be reclaimed — the
        # data source still owns exactly S0 rows, so a genuinely new device
        # would have no data stream to serve.
        if ev.device in self.survivors:
            raise ValueError(f"churn join: device {ev.device} is already "
                             f"in the ring")
        if ev.device not in self.profiles:
            raise ValueError(
                f"churn join: device {ev.device} was never part of the "
                f"original fleet — only rejoining devices are supported "
                f"(the data source owns the original rows)")
        if not self.elastic:
            raise RuntimeError(
                f"device {ev.device} rejoined at round {self.round_idx} and "
                f"the ring is not elastic — run with elastic=True (--elastic)")
        prof = ev.profile or self.profiles[ev.device]
        stage = sum(1 for d in self.survivors if d < ev.device)
        self._flush()
        self.survivors.insert(stage, ev.device)
        if self.detector is not None:
            self.detector.insert(stage, prof)
        self.inner.grow(profiles=self._survivor_profiles())
        self.log(f"[elastic] device {ev.device} rejoined at round "
                 f"{self.round_idx}: ring {len(self.survivors) - 1} -> "
                 f"{len(self.survivors)} stages, spans "
                 f"{[list(sp) for sp in self.inner.spans]}")
        return True

    def step(self, batch) -> Dict[str, Any]:
        layout_changed = False
        while self.events and self.events[0].round <= self.round_idx:
            layout_changed |= self._apply(self.events.pop(0))
        if len(self.survivors) != len(self.profiles):
            rows = np.asarray(self.survivors)
            if len(batch) == 3:
                slot, tokens, labels = batch
                batch = (slot, tokens[rows], labels[rows])
            else:
                tokens, labels = batch
                batch = (tokens[rows], labels[rows])
        raw = self.inner.step(batch)
        stage_times = [(e - b) / self.speeds[dev] for (b, e), dev
                       in zip(self.inner.spans, self.survivors)]
        extras = raw.setdefault("extras", {})
        extras["stage_times"] = stage_times
        extras["survivors"] = list(self.survivors)
        if self.detector is not None:
            self.detector.observe(self.inner.spans, stage_times)
            prop = self.detector.propose(self.inner.spans)
            if prop is not None:
                self._flush()
                old = [list(sp) for sp in self.inner.spans]
                self.inner.repartition(prop)
                self.repartitions += 1
                layout_changed = True
                self.log(f"[elastic] straggler repartition at round "
                         f"{self.round_idx}: spans {old} -> "
                         f"{[list(sp) for sp in self.inner.spans]} "
                         f"(EWMA speeds "
                         f"{[round(s, 3) for s in self.detector.speeds]})")
        if layout_changed:
            raw["layout_changed"] = True
            extras["layout_changed"] = True
        self.round_idx += 1
        return raw

    def restore_membership(self, survivors: Sequence[int],
                           spans=None) -> None:
        """Replay a checkpoint's saved fleet state onto a freshly-built
        full-size ring: shrink away every device missing from ``survivors``
        (in stage order), then repartition to the exact saved ``spans`` —
        run BEFORE ``load_state`` so the stage-stacked moments land on the
        right geometry."""
        for dead in [d for d in list(self.survivors) if d not in survivors]:
            stage = self.survivors.index(dead)
            self.survivors.pop(stage)
            if self.detector is not None:
                self.detector.remove(stage)
            self.inner.shrink(stage, profiles=self._survivor_profiles())
            self.shrinks += 1
        if list(survivors) != self.survivors:
            raise ValueError(
                f"saved survivors {list(survivors)} are not a subset of the "
                f"original fleet {sorted(self.profiles)}")
        if spans is not None:
            self.inner.repartition(spans)
