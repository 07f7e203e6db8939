"""Training driver: a thin CLI shell over ``repro.api.RingSession``.

Every mode is a (backend, policy) pair on the one session facade:

  * ``--mode pjit`` (default): staged-recompile data/tensor-parallel training
    (``PjitBackend``); ``--scheme all_hot`` maps to the PipeAdapter-style
    baseline policy (every adapter trainable from step 0).
  * ``--mode ring``: shard_map ring pipeline across ``--stages`` devices
    (needs >= stages local devices, e.g.
    ``XLA_FLAGS=--xla_force_host_platform_device_count=4``).
    ``--trainer fused`` (default) is the donated single-executable
    ``FusedBackend`` — with ``--slots-per-epoch`` it upgrades to the
    ``CachedBackend`` (frozen-trunk Phase-A skip); ``--trainer reference``
    is the unfused ``ReferenceBackend`` oracle.

``--policy plateau`` swaps the paper's k-step rule for adaptive
loss-plateau unfreezing in either mode.  ``--save``/``--resume`` round-trip
the full session state (params + Adam moments + policy + data cursor) in
BOTH modes via ``RingSession.save``/``restore``.

Usage:
    PYTHONPATH=src python -m repro.launch.train --arch mbert-squad --steps 120 \
        --reduced --mode pjit
"""
from __future__ import annotations

import argparse
import json
import time
from typing import Any, Dict, Optional

from repro.api import (ExplicitPolicy, LoggingCallback, RingSession,
                       resolve_policy)
from repro.configs import TrainConfig, get_config
from repro.launch.compile_cache import use_compile_cache


def train_pjit(cfg, tc: TrainConfig, *, steps: int, log_every: int = 10,
               scheme: str = "ringada", impl: str = "jnp",
               save_path: Optional[str] = None, resume: Optional[str] = None,
               policy: Any = None, log=print) -> Dict[str, Any]:
    """Single-process training with the paper's unfreeze schedule — a shell
    over ``RingSession`` with the pjit backend.

    scheme: 'ringada' (scheduled unfreezing) | 'all_hot' (PipeAdapter/Single-
    style baseline: every adapter trainable from step 0).

    Note (vs the pre-session loop): the returned history now carries EVERY
    step (host-synced in log_every batches, so async dispatch is unchanged),
    not just the logged ones, and ``step`` counts from 1 (the value AFTER the
    update) rather than 0.
    """
    if scheme not in ("ringada", "all_hot"):
        raise ValueError(f"scheme must be 'ringada' or 'all_hot', got {scheme!r}")
    if scheme == "all_hot":
        if policy not in (None, "interval"):
            raise ValueError("scheme='all_hot' fixes the policy (every "
                             "adapter hot from step 0) — drop --policy")
        policy = ExplicitPolicy((cfg.n_layers,))
    policy = resolve_policy(policy, tc)
    if resume:
        sess = RingSession.restore(resume, cfg, tc, backend="pjit",
                                   policy=policy, impl=impl, log=log)
    else:
        sess = RingSession.create(cfg, tc, backend="pjit", policy=policy,
                                  impl=impl, log=log)
    t0 = time.time()
    history = sess.run(steps, log_every=log_every,
                       callbacks=[LoggingCallback(log, every=log_every)])
    if save_path:
        sess.save(save_path)
    st = sess.backend.state()
    return {"history": history, "params": st["params"], "opt_state": st["opt"],
            "session": sess, "wall_s": time.time() - t0}


def train_ring(cfg, tc: TrainConfig, *, rounds: int, n_stages: int,
               log_every: int = 1, trainer: str = "fused",
               slots_per_epoch: Optional[int] = None,
               cache_capacity: Optional[int] = None,
               packed: bool = True, cache_dtype: str = "native",
               device_speeds: Optional[Any] = None,
               tenants: int = 1, adapter_store: Optional[str] = None,
               chaos: Any = (), elastic: bool = False,
               save_path: Optional[str] = None, resume: Optional[str] = None,
               policy: Any = None, log=print) -> Dict[str, Any]:
    """Ring-pipeline training across ``n_stages`` devices — a shell over
    ``RingSession`` with the matching ring backend.

    trainer='fused' (default): the donated single-executable round; with
    ``slots_per_epoch`` this becomes the cached backend (steady-state
    revisits of a (slot, boundary) key skip Phase A entirely; a boundary drop
    invalidates the cache).  trainer='reference': the unfused oracle.
    ``cache_capacity`` defaults to ``slots_per_epoch``; 0 disables the cache
    while keeping slotted batches.  ``packed=False`` reverts Phase A to the
    per-owner scan (the packed conveyor is on by default); ``cache_dtype``
    compresses cache entries ('bf16' halves, 'int8' quarters the bytes per
    entry — see ``core/actcache.py`` for the accuracy tradeoff).

    ``device_speeds`` (one relative speed per stage, ring order — the CLI's
    ``--device-speeds 1.0,0.5,2.0,1.0``) runs the paper's speed-weighted
    layer assignment: faster devices get proportionally larger contiguous
    block spans (Algorithm 1; the 4:5:2:3 example).  The resulting span
    layout is recorded in ``--save`` checkpoints and restored by
    ``--resume``.

    ``tenants=T > 1`` (fused/cached) trains T per-tenant adapter sets over
    one shared frozen trunk in a single joint conveyor; ``adapter_store``
    exports every tenant's adapters+moments as named ``AdapterStore``
    bundles (``tenant0``, ``tenant1``, ...) after the run — directly
    hot-servable by ``launch/serve.py --adapter-store``.

    ``chaos`` (the CLI's repeatable ``--chaos ROUND:EVENT:DEVICE[:FACTOR]``)
    injects churn events mid-run; ``elastic=True`` lets the ring absorb them
    live — a crash shrinks the ring to the survivors (checkpoint-free, see
    README "Fault tolerance"), a slowdown is picked up by the straggler
    detector and repartitioned away.  Without ``elastic``, a crash raises.
    """
    if trainer not in ("fused", "reference"):
        raise ValueError(f"trainer must be 'fused' or 'reference', "
                         f"got {trainer!r}")
    if tenants > 1 and trainer != "fused":
        raise ValueError("--tenants > 1 needs the fused executor "
                         "(--trainer fused)")
    if trainer == "reference":
        backend = "reference"
    else:
        cap = (cache_capacity if cache_capacity is not None
               else (slots_per_epoch or 0))
        backend = "cached" if (slots_per_epoch and cap) else "fused"
    if resume:
        if device_speeds is not None:
            raise ValueError(
                "--device-speeds cannot be combined with --resume: the span "
                "layout is part of the checkpointed state (stage-stacked "
                "Adam moments are laid out per span), so resume always "
                "restores the saved layout. To repartition, start a fresh "
                "run with the new speeds, or use RingExecutor.repartition "
                "programmatically.")
        # the checkpoint records backend/stages/slots/capacity/spans;
        # re-deriving them from (possibly omitted) CLI flags would silently
        # resume a slotted cached run as fused+streaming — a different data
        # sequence.
        # chaos rounds are relative to THIS run (the wrapper's round counter
        # starts at 0 on resume); elastic defaults to the checkpointed value
        kw: Dict[str, Any] = {}
        if chaos:
            kw["chaos"] = chaos
        if elastic:
            kw["elastic"] = True
        sess = RingSession.restore(resume, cfg, tc, policy=policy, log=log,
                                   **kw)
        if sess.backend.kind != "ring":
            raise ValueError(
                f"--resume checkpoint was saved by the "
                f"{sess.backend.name!r} backend; resume it with --mode pjit")
    else:
        sess = RingSession.create(cfg, tc, backend=backend, policy=policy,
                                  n_stages=n_stages,
                                  slots_per_epoch=slots_per_epoch,
                                  cache_capacity=cache_capacity,
                                  packed=packed, cache_dtype=cache_dtype,
                                  device_profiles=device_speeds,
                                  tenants=tenants, chaos=chaos,
                                  elastic=elastic, log=log)
        if device_speeds is not None:
            log(f"heterogeneous ring: speeds {list(device_speeds)} -> spans "
                f"{[list(sp) for sp in sess.backend.spans]}")
    t0 = time.time()
    history = sess.run(rounds, log_every=log_every,
                       callbacks=[LoggingCallback(log, every=log_every)])
    if save_path:
        sess.save(save_path)
    if adapter_store:
        from repro.api import AdapterStore

        store = AdapterStore(adapter_store)
        for group in sess.tenants:
            group.save_to(store, f"tenant{group.index}")
        log(f"exported {sess.n_tenants} adapter bundle(s) to {adapter_store}")
    return {"history": history, "trainer": sess.backend.driver,
            "session": sess, "wall_s": time.time() - t0}


def main() -> None:
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mbert-squad")
    ap.add_argument("--mode", choices=["pjit", "ring"], default="pjit")
    ap.add_argument("--scheme", choices=["ringada", "all_hot"],
                    default="ringada")
    ap.add_argument("--policy", choices=["interval", "plateau"],
                    default="interval",
                    help="unfreeze policy: the paper's k-step rule, or "
                         "adaptive loss-plateau unfreezing")
    ap.add_argument("--trainer", choices=["fused", "reference"],
                    default="fused",
                    help="ring backend: fused RingExecutor or the unfused "
                         "RingTrainer oracle")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--rounds", type=int, default=25)
    ap.add_argument("--stages", type=int, default=4)
    ap.add_argument("--reduced", action="store_true",
                    help="train the reduced (CPU-sized) variant")
    ap.add_argument("--layers", type=int, default=None,
                    help="override the block count (applied after --reduced; "
                         "must be a multiple of the arch's layers-per-repeat "
                         "— e.g. 14 runs the paper's 4:5:2:3 heterogeneous "
                         "example with --device-speeds)")
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--microbatches", type=int, default=8,
                    help="ring mode: microbatches in flight per round")
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--unfreeze-interval", type=int, default=40)
    ap.add_argument("--slots-per-epoch", type=int, default=0,
                    help="ring mode: epoch-stable batch slots (the activation "
                         "cache's key space; e.g. 8 enables the Phase-A-skip "
                         "cache); 0 (default) = streaming random batches, "
                         "cache off — the pre-cache behavior")
    ap.add_argument("--cache-capacity", type=int, default=None,
                    help="ring mode: boundary-activation cache entries "
                         "(default: slots-per-epoch; 0 disables the cache)")
    ap.add_argument("--no-cache", action="store_true",
                    help="ring mode: disable the frozen-trunk activation "
                         "cache (use for streaming/non-repeating data)")
    ap.add_argument("--cache-dtype", choices=["native", "f32", "bf16", "int8"],
                    default="native",
                    help="ring mode: activation-cache storage precision — "
                         "'native' stores entries exactly as captured, "
                         "'bf16' halves and 'int8' (per-row scales) quarters "
                         "the bytes per entry, fitting 2-4x more slots in "
                         "the same --cache-capacity memory budget")
    ap.add_argument("--tenants", type=int, default=1,
                    help="ring mode (fused/cached): train this many "
                         "per-tenant adapter sets over ONE shared frozen "
                         "trunk in a single joint conveyor; per tenant the "
                         "result is bit-identical to an independent run")
    ap.add_argument("--adapter-store", default=None,
                    help="ring mode: export each tenant's trained adapters + "
                         "Adam moments to this AdapterStore directory "
                         "(entries tenant0, tenant1, ...) — servable by "
                         "launch/serve.py --adapter-store without a restart")
    ap.add_argument("--device-speeds", default=None,
                    help="ring mode: comma-separated relative compute speeds, "
                         "one per stage in ring order (e.g. "
                         "'1.0,0.5,2.0,1.0') — runs the paper's "
                         "speed-weighted layer assignment so faster devices "
                         "hold larger contiguous block spans (Algorithm 1); "
                         "default: balanced spans")
    ap.add_argument("--chaos", action="append", default=[],
                    metavar="ROUND:EVENT:DEVICE[:FACTOR]",
                    help="ring mode: inject a churn event (repeatable) — "
                         "EVENT in {crash, leave, slowdown, join}, ROUND is "
                         "when it fires (rounds before it run on the old "
                         "fleet), DEVICE is the ORIGINAL stage index, FACTOR "
                         "is the slowdown multiplier (default 2.0). E.g. "
                         "--chaos 3:crash:2 kills device 2 before round 3; "
                         "crashes need --elastic to survive")
    ap.add_argument("--elastic", action=argparse.BooleanOptionalAction,
                    default=False,
                    help="ring mode: absorb churn live — crashes shrink the "
                         "ring to the survivors (checkpoint-free recovery), "
                         "stragglers are EWMA-detected from stage timings "
                         "and repartitioned away (hysteresis-gated)")
    ap.add_argument("--no-packed", action="store_true",
                    help="ring mode: revert Phase A to the per-owner scan "
                         "(S separate M+F-1-tick pipelines per round) "
                         "instead of the default packed conveyor (one "
                         "S*M+F-1-tick stream, saving (S-1)(F-1) ticks)")
    ap.add_argument("--save", default=None,
                    help="checkpoint path (both modes): params + Adam "
                         "moments + policy + data cursor")
    ap.add_argument("--resume", default=None,
                    help="resume bit-reproducibly from a --save checkpoint "
                         "(ring mode restores the SAVED backend/stages/"
                         "slots/cache configuration; the corresponding "
                         "flags are ignored)")
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.layers:
        import dataclasses
        per = cfg.layers_per_repeat
        if args.layers % per:
            raise SystemExit(f"--layers {args.layers} must be a multiple of "
                             f"{cfg.name}'s layers-per-repeat ({per})")
        cfg = dataclasses.replace(cfg, n_layers=args.layers,
                                  repeats=args.layers // per)
    tc = TrainConfig(batch_size=args.batch_size, seq_len=args.seq_len,
                     learning_rate=args.lr, steps=args.steps,
                     unfreeze_interval=args.unfreeze_interval,
                     n_microbatches=args.microbatches)
    if args.mode == "pjit":
        if args.chaos or args.elastic:
            raise SystemExit("--chaos/--elastic are ring-mode features "
                             "(--mode ring)")
        out = train_pjit(cfg, tc, steps=args.steps, scheme=args.scheme,
                         policy=args.policy, save_path=args.save,
                         resume=args.resume)
    else:
        speeds = ([float(s) for s in args.device_speeds.split(",")]
                  if args.device_speeds else None)
        out = train_ring(cfg, tc, rounds=args.rounds, n_stages=args.stages,
                         trainer=args.trainer, policy=args.policy,
                         slots_per_epoch=args.slots_per_epoch or None,
                         cache_capacity=0 if args.no_cache
                         else args.cache_capacity,
                         packed=not args.no_packed,
                         cache_dtype=args.cache_dtype,
                         device_speeds=speeds,
                         tenants=args.tenants,
                         adapter_store=args.adapter_store,
                         chaos=args.chaos, elastic=args.elastic,
                         save_path=args.save, resume=args.resume)
    print(json.dumps(out["history"][-1], default=float))


if __name__ == "__main__":
    main()
