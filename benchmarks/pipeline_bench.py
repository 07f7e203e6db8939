"""Ring-pipeline benchmark.

Four sections:
  1. analytic tick counts per unfreeze depth (incl. the cached Phase-A skip
     and the packed conveyor's per-round totals), cross-checked against the
     discrete-event simulator (``ringada_packed`` with ``n_owners=S``),
  2. simulated round time + utilization (discrete-event MPMD model),
  3. **fused-vs-reference-vs-cached**: real wall-clock steps/sec, executable
     counts and per-executable memory (incl. donation aliasing) for the fused
     ``RingExecutor`` against the unfused ``RingTrainer``, plus
       * packed-conveyor Phase A vs the per-owner scan (direct rounds at the
         steady boundary — the first-visit/capture cost the conveyor cuts),
       * multi-tenant packing (per-tenant steps/sec at T in {1, 4} on the
         tenant conveyor — the fill/drain bubble amortizes over T),
       * the frozen-trunk activation cache's steady state per storage dtype
         (f32 / bf16 / int8: bytes per entry, hit rate, loss drift),
       * the ``repro.api.RingSession`` facade over the cached path.
     Runs in a subprocess so the parent process keeps its 1-device backend;
     device count comes from ``--devices`` (CI runs 2 and 4).
  4. per-mode executable memory: peak live bytes for packed / scan / cached.

Emits ``BENCH_ring.json`` (schema ``BENCH_ring/v2``; ``--out`` overrides the
path) so the perf trajectory — reference vs fused vs cached, packed-vs-scan
round ratio, cache bytes/entry + hit rate per dtype, compile counts — is
tracked across PRs.  CI uploads it from both a 2- and a 4-device CPU mesh and
gates on ``--check``: cached speedup >= ``CACHED_SPEEDUP_FLOOR`` (1.15 — see
``check_bench_ring``'s threshold note), packed strictly faster than the scan
wherever F >= 2, bf16 entries matching the f32 hit rate at half the bytes,
and the elastic crash-recovery round <= 2x the cached steady round in sim
ticks (the "elastic" section also records the measured recovery-round ms
from a real chaos drill: crash one device mid-run, shrink, re-capture).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from typing import Dict, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_OUT = os.path.join(ROOT, "BENCH_ring.json")

_FUSED_SCRIPT = r"""
import os, time, json
S = int(os.environ.get("BENCH_RING_DEVICES", "4"))
os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={S}"
os.environ["JAX_PLATFORMS"] = "cpu"
import jax, jax.numpy as jnp
from repro.core.pipeline import make_ring_mesh
from repro.configs import TrainConfig, get_config
from repro.core.executor import RingExecutor
from repro.core.ring import RingTrainer
from repro.models import params as prm

# Edge-device regime: tiny per-client microbatches over small adapters — the
# setting where RingAda claims its win and where dispatch / host-sync /
# staged-recompile overheads dominate.
M, mb, seq = 4, 1, 32
cfg = get_config("stablelm-3b").reduced(n_layers=4, repeats=4,
                                        d_model=128, d_ff=256)
mesh = make_ring_mesh(S)
tokens = jax.random.randint(jax.random.key(1), (S, M, mb, seq), 0,
                            cfg.vocab_size)
labels = jax.random.randint(jax.random.key(2), (S, M, mb, seq), 0,
                            cfg.vocab_size)

def fresh_params():
    return prm.materialize(prm.param_defs(cfg), jax.random.key(0), cfg.dtype)

def sync(last):
    if hasattr(last["loss"], "block_until_ready"):
        last["loss"].block_until_ready()             # fused: one final sync

def time_rounds(step, rounds, reps=3):
    # Best-of-reps wall time for `rounds` back-to-back rounds (seconds).
    # Host-CPU collectives jitter by 50%+ run-to-run; a single timing window
    # is too noisy to gate CI on, the min of a few windows is stable.
    best = None
    for _ in range(reps):
        t0 = time.time()
        last = None
        for r in range(rounds):
            last = step(r)
        sync(last)
        dt = time.time() - t0
        best = dt if best is None else min(best, dt)
    return best

out = {"mesh_devices": S}
with jax.set_mesh(mesh):
    # 1. end-to-end: the paper's schedule walks every boundary; each bump
    #    recompiles S executables on the reference path, 1 on the fused path.
    SCHED_ROUNDS = 8
    tc_sched = TrainConfig(learning_rate=1e-3, unfreeze_interval=S,
                           n_microbatches=M, batch_size=mb, seq_len=seq)
    for name, cls in (("reference", RingTrainer), ("fused", RingExecutor)):
        drv = cls(cfg, tc_sched, mesh, fresh_params(), S, M)
        t0 = time.time()
        last = None
        for _ in range(SCHED_ROUNDS):
            last = drv.round(tokens, labels)
        sync(last)
        dt = time.time() - t0
        out.setdefault("schedule", {})[name] = {
            "steps_per_sec": S * SCHED_ROUNDS / dt,
            "wall_s": dt,
            "n_executables": drv.n_executables,
        }

    # 2. steady state: fixed boundary, compile excluded.  'fused' is the
    #    packed conveyor (the default); 'fused_scan' the per-owner Phase A —
    #    their direct-round ratio is the conveyor's win on every
    #    first-visit/capture round (saves (S-1)(F-1) of S(M+F-1) ticks).
    ROUNDS = 16
    tc_fix = TrainConfig(learning_rate=1e-3, unfreeze_interval=10**6,
                         n_microbatches=M, batch_size=mb, seq_len=seq)
    drivers = {}
    for name, mk in (
            ("reference", lambda: RingTrainer(cfg, tc_fix, mesh,
                                              fresh_params(), S, M)),
            ("fused", lambda: RingExecutor(cfg, tc_fix, mesh, fresh_params(),
                                           S, M, packed=True)),
            ("fused_scan", lambda: RingExecutor(cfg, tc_fix, mesh,
                                                fresh_params(), S, M,
                                                packed=False))):
        drv = mk()
        t0 = time.time()
        drv.round(tokens, labels)                    # warmup: compile
        compile_s = time.time() - t0
        dt = time_rounds(lambda r: drv.round(tokens, labels), ROUNDS)
        rec = {"steps_per_sec": S * ROUNDS / dt, "compile_s": compile_s,
               "round_ms": 1e3 * dt / ROUNDS,
               "n_executables": drv.n_executables}
        stats = jax.devices()[0].memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            rec["device_peak_bytes"] = stats["peak_bytes_in_use"]
        out.setdefault("steady", {})[name] = rec
        drivers[name] = drv
    out["steady_boundary"] = drivers["fused"].boundary_at(0)
    out["frozen_stages"] = (out["steady_boundary"]
                            // drivers["fused"].lps)
    out["n_micro"] = M
    out["lps"] = drivers["fused"].lps
    out["packed_scan_ratio"] = (out["steady"]["fused"]["round_ms"]
                                / out["steady"]["fused_scan"]["round_ms"])

    # 2b. multi-tenant packing: T adapter sets on ONE ring.  The tenant
    #     conveyor chains T*S*M microbatches through a single fill/drain
    #     (T*S*M + F - 1 ticks), so the bubble amortizes over T and the
    #     per-tenant round cost must stay well under 2x the solo round
    #     (gated in check_bench_ring; the analytic per-tenant cost is
    #     S*M + (F-1)/T ticks, i.e. *below* 1x solo in tick units).
    T_HI = 4
    ROUNDS_T = 8
    tok4 = jnp.broadcast_to(tokens[:, None], (S, T_HI) + tokens.shape[1:])
    lab4 = jnp.broadcast_to(labels[:, None], (S, T_HI) + labels.shape[1:])
    drv4 = RingExecutor(cfg, tc_fix, mesh, fresh_params(), S, M,
                        tenants=T_HI, packed=True)
    t0 = time.time()
    drv4.round(tok4, lab4)                           # warmup: compile
    compile4_s = time.time() - t0
    dt4 = time_rounds(lambda r: drv4.round(tok4, lab4), ROUNDS_T)
    t1_ms = out["steady"]["fused"]["round_ms"]       # same geometry, T=1
    t4_ms = 1e3 * dt4 / ROUNDS_T
    out["tenants"] = {
        "T1": {"round_ms": t1_ms,
               "per_tenant_steps_per_sec":
                   out["steady"]["fused"]["steps_per_sec"]},
        "T4": {"round_ms": t4_ms, "compile_s": compile4_s,
               "per_tenant_steps_per_sec": S * ROUNDS_T / dt4,
               "n_executables": drv4.n_executables},
        # per-tenant share of the T=4 round vs the whole T=1 round
        "per_tenant_round_ratio": (t4_ms / T_HI) / t1_ms,
    }

    # 3. actcache steady state at the highest scheduled boundary (F = S-1),
    #    per storage dtype: epoch 0 captures each slot's boundary
    #    activations, every later epoch enters the pipeline at stage F (no
    #    embed / all_gather / Phase A), dequantizing on device.  The f32 run
    #    doubles as the headline 'cached' record.
    N_SLOTS = 2
    for dt_name in ("f32", "bf16", "int8"):
        drv = RingExecutor(cfg, tc_fix, mesh, fresh_params(), S, M,
                           cache_capacity=N_SLOTS, cache_dtype=dt_name)
        t0 = time.time()
        for sl in range(N_SLOTS):
            drv.round(tokens, labels, slot=sl)   # capture epoch (+compile)
        last = drv.round(tokens, labels, slot=0)     # first hit: compile cached
        sync(last)
        compile_s = time.time() - t0
        dt = time_rounds(
            lambda r: drv.round(tokens, labels, slot=r % N_SLOTS), ROUNDS)
        last = drv.round(tokens, labels, slot=0)
        stats = drv.cache.stats()
        rec = {
            "steps_per_sec": S * ROUNDS / dt, "compile_s": compile_s,
            "round_ms": 1e3 * dt / ROUNDS,
            "n_executables": drv.n_executables,
            "boundary": drv.boundary_at(0),
            "final_loss": float(last["loss"]),
            "cache_hit_rate": stats["cache_hit_rate"],
            "cache_hits": stats["cache_hits"],
            "cache_misses": stats["cache_misses"],
            "bytes_per_entry": stats["cache_bytes_per_entry"],
            "buffer_bytes": stats["cache_buffer_bytes"],
            "compile_counts": drv.compile_counts(),
        }
        out.setdefault("cache_dtypes", {})[dt_name] = rec
        if dt_name == "f32":
            out["steady"]["cached"] = rec
    for dt_name, rec in out["cache_dtypes"].items():
        rec["loss_drift_vs_f32"] = abs(
            rec["final_loss"] - out["cache_dtypes"]["f32"]["final_loss"])

    # 4. the RingSession facade over the same cached path: the API adds only
    #    thin host-side dispatch over the same executables, so its steady
    #    state must track the raw driver (the facade-overhead ratio is
    #    recorded in BENCH_ring.json to catch regressions).
    from repro.api import BenchCaptureCallback, RingSession
    sess = RingSession.create(cfg, tc_fix, backend="cached", n_stages=S,
                              slots_per_epoch=N_SLOTS)
    sess.run(N_SLOTS + 1, log_every=N_SLOTS + 1)   # capture epoch + compile
    cap = BenchCaptureCallback()
    t0 = time.time()
    sess.run(ROUNDS, log_every=ROUNDS, callbacks=[cap])
    dt = time.time() - t0
    out["steady"]["session_cached"] = {
        "steps_per_sec": S * ROUNDS / dt,
        "round_ms": 1e3 * dt / ROUNDS,
        "n_executables": cap.result()["compile_count"],
        "cache_hit_rate": cap.result().get("cache_hit_rate", 0.0),
    }

    # per-executable memory analysis: the fused step aliases (donates) params
    # + moments; packed holds the whole [S*M] conveyor live (temp bytes) where
    # the scan holds one owner's [M]; the cached executable takes the ring
    # buffer instead of tokens.
    def mem_record(ma):
        return {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,   # donated: no second copy
            "peak_bytes": (ma.argument_size_in_bytes + ma.output_size_in_bytes
                           + ma.temp_size_in_bytes - ma.alias_size_in_bytes),
        }

    abstract = lambda t: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), t)
    for name in ("fused", "fused_scan"):
        ex = drivers[name]
        b = ex.boundary_at(0)
        ma = ex._fn(b).lower(
            abstract(ex.stage_blocks), abstract(ex.shared),
            abstract(ex.opt_state), abstract(tokens),
            abstract(labels)).compile().memory_analysis()
        if ma is not None:
            key = "packed" if name == "fused" else "scan"
            out.setdefault("mode_memory", {})[key] = mem_record(ma)
            if name == "fused":
                out["fused_memory"] = mem_record(ma)
    ref = drivers["reference"]
    b = drivers["fused"].boundary_at(0)
    ma_ref = ref._fn(0, b).lower(
        abstract(ref.stage_blocks), abstract(ref.shared),
        abstract(tokens), abstract(labels)).compile().memory_analysis()
    if ma_ref is not None:
        out["reference_memory"] = mem_record(ma_ref)

out["speedup"] = (out["schedule"]["fused"]["steps_per_sec"]
                  / out["schedule"]["reference"]["steps_per_sec"])
out["steady_speedup"] = (out["steady"]["fused"]["steps_per_sec"]
                         / out["steady"]["reference"]["steps_per_sec"])
out["cached_speedup_vs_fused"] = (out["steady"]["cached"]["steps_per_sec"]
                                  / out["steady"]["fused"]["steps_per_sec"])
out["session_facade_ratio"] = (out["steady"]["session_cached"]["steps_per_sec"]
                               / out["steady"]["cached"]["steps_per_sec"])
print(json.dumps(out))
"""

_ELASTIC_SCRIPT = r"""
import os, time, json
S = int(os.environ.get("BENCH_RING_DEVICES", "4"))
os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={S}"
os.environ["JAX_PLATFORMS"] = "cpu"
import dataclasses
from repro.api import RingSession
from repro.configs import TrainConfig, get_config

# Chaos recovery drill: steady cached ring at S stages, crash the last
# device mid-run, measure the checkpoint-free recovery round (shrink +
# moment restack + cache re-capture, INCLUDING the new geometry's compiles)
# against the cached steady rounds on either side of it.
cfg = dataclasses.replace(get_config("stablelm-3b").reduced(
    n_layers=2 * S, repeats=2 * S, d_model=64, d_ff=128), dtype="float32")
tc = TrainConfig(learning_rate=1e-3, batch_size=S, seq_len=16,
                 unfreeze_interval=10**6, n_stages=S, n_microbatches=2)
KILL = 4                           # the crash fires BEFORE round index KILL
sess = RingSession.create(cfg, tc, backend="cached", slots_per_epoch=1,
                          chaos=f"{KILL}:crash:{S - 1}", elastic=True,
                          log=lambda *a: None)
rows = []
for r in range(KILL + 5):
    t0 = time.perf_counter()
    m = sess.step().materialize()
    rows.append({"ms": (time.perf_counter() - t0) * 1e3,
                 "hit": bool(m.cache_hit),
                 "changed": bool(m.extras.get("layout_changed"))})
rec = next(i for i, row in enumerate(rows) if row["changed"])
refill = next(i for i in range(rec, len(rows)) if rows[i]["hit"]) - rec
print(json.dumps({
    "stages": S,
    "survivors": list(m.extras["survivors"]),
    "spans": [list(sp) for sp in sess.backend.spans],
    "recovery_round_ms": rows[rec]["ms"],
    # cheapest hit round on each side (the first hit at a geometry still
    # pays that geometry's cached-executable compile, min() skips it)
    "steady_round_ms_before": min(r["ms"] for r in rows[1:rec] if r["hit"]),
    "steady_round_ms_after": min(r["ms"] for r in rows[rec + refill + 1:]),
    "rounds_to_cache_refill_measured": refill,
}))
"""


def bench_fused_vs_reference(log=print, devices: int = 4) -> Dict:
    """Run the fused-vs-reference comparison in an n-device subprocess."""
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"),
               BENCH_RING_DEVICES=str(devices))
    env.pop("XLA_FLAGS", None)
    try:
        res = subprocess.run([sys.executable, "-c", _FUSED_SCRIPT], env=env,
                             capture_output=True, text=True, timeout=1800)
    except subprocess.TimeoutExpired:
        return {"skipped": "timeout"}
    if res.returncode != 0:
        return {"skipped": res.stderr[-2000:]}
    out = json.loads(res.stdout.strip().splitlines()[-1])
    for name in ("reference", "fused"):
        r = out["schedule"][name]
        log(f"  schedule {name:10s}: {r['steps_per_sec']:6.2f} steps/s "
            f"end-to-end ({r['wall_s']:.1f}s, {r['n_executables']} "
            f"executables over all boundaries)")
    for name in ("reference", "fused", "fused_scan", "cached"):
        r = out["steady"][name]
        log(f"  steady   {name:10s}: {r['steps_per_sec']:6.2f} steps/s "
            f"({r['round_ms']:.0f} ms/round, compile {r['compile_s']:.1f}s, "
            f"{r['n_executables']} executable(s))")
    log(f"  packed conveyor: {out['packed_scan_ratio']:.2f}x the scan's "
        f"round time at F={out['frozen_stages']} "
        f"(first-visit/capture rounds)")
    ten = out.get("tenants")
    if ten:
        log(f"  tenants: T=1 {ten['T1']['per_tenant_steps_per_sec']:6.2f} "
            f"steps/s/tenant ({ten['T1']['round_ms']:.0f} ms/round), "
            f"T=4 {ten['T4']['per_tenant_steps_per_sec']:6.2f} "
            f"({ten['T4']['round_ms']:.0f} ms/round) — per-tenant share "
            f"{ten['per_tenant_round_ratio']:.2f}x the solo round")
    for dt_name, r in out.get("cache_dtypes", {}).items():
        log(f"  cache[{dt_name:5s}]: {r['bytes_per_entry']:>8d} B/entry, "
            f"hit rate {r['cache_hit_rate']:.0%}, "
            f"{r['round_ms']:.0f} ms/round, "
            f"loss drift vs f32 {r['loss_drift_vs_f32']:.2e}")
    r = out["steady"]["session_cached"]
    log(f"  steady   session   : {r['steps_per_sec']:6.2f} steps/s "
        f"({r['round_ms']:.0f} ms/round) — RingSession facade at "
        f"{out['session_facade_ratio']:.2f}x the raw cached driver")
    for key in ("fused_memory", "reference_memory"):
        if key in out:
            fm = out[key]
            log(f"  {key.split('_')[0]:9s} executable: "
                f"peak={fm['peak_bytes'] / 2**20:.1f} MiB "
                f"(donation aliases {fm['alias_bytes'] / 2**20:.1f} MiB)")
    for key, fm in out.get("mode_memory", {}).items():
        log(f"  mode {key:6s} executable: peak={fm['peak_bytes'] / 2**20:.1f} "
            f"MiB (temps {fm['temp_bytes'] / 2**20:.1f} MiB)")
    c = out["steady"]["cached"]
    log(f"  actcache: hit rate {c['cache_hit_rate']:.0%} at boundary "
        f"{c['boundary']}, compiles {c['compile_counts']}")
    log(f"  speedup: {out['speedup']:.2f}x end-to-end, "
        f"{out['steady_speedup']:.2f}x steady-state fused-vs-reference, "
        f"{out['cached_speedup_vs_fused']:.2f}x steady-state cached-vs-fused")
    return out


def bench_elastic(log=print, devices: int = 4) -> Dict:
    """Run the measured chaos recovery drill in an n-device subprocess:
    crash one device mid-run under ``--elastic`` and price the
    checkpoint-free recovery round against its neighboring cached rounds."""
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"),
               BENCH_RING_DEVICES=str(devices))
    env.pop("XLA_FLAGS", None)
    try:
        res = subprocess.run([sys.executable, "-c", _ELASTIC_SCRIPT], env=env,
                             capture_output=True, text=True, timeout=1800)
    except subprocess.TimeoutExpired:
        return {"skipped": "timeout"}
    if res.returncode != 0:
        return {"skipped": res.stderr[-2000:]}
    out = json.loads(res.stdout.strip().splitlines()[-1])
    log(f"  crash {out['stages']} -> {len(out['spans'])} stages: recovery "
        f"round {out['recovery_round_ms']:.0f} ms (cached steady "
        f"{out['steady_round_ms_before']:.0f} ms before, "
        f"{out['steady_round_ms_after']:.0f} ms after), cache refilled in "
        f"{out['rounds_to_cache_refill_measured']} round(s)")
    return out


def _tick_ledger(S: int, M: int, frozen: int) -> Dict[str, float]:
    """Phase-A tick closed forms + discrete-event cross-check for the
    measured bench geometry (S stages, M microbatches, F frozen stages)."""
    from repro.core.partition import DeviceProfile
    from repro.core.pipeline import pipeline_tick_counts
    from repro.core.simulator import LayerProfile, SimConfig, simulate_round

    t_scan = pipeline_tick_counts(S, M, boundary=frozen, lps=1)
    t_packed = pipeline_tick_counts(S, M, boundary=frozen, lps=1, packed=True)
    row: Dict[str, float] = {
        "phase_a_round_ticks_scan": t_scan["phase_a_round_ticks"],
        "phase_a_round_ticks_packed": t_packed["phase_a_round_ticks"],
        "phase_a_saved_ticks": t_packed["phase_a_saved_ticks"],
    }
    if 0 < frozen < S:
        fz = LayerProfile(1.0, 0.0, 1.0, 1.0, 0.1, 0.0)
        hot = LayerProfile(0.0, 0.0, 1.0, 1.0, 0.1, 0.0)
        lay = [fz] * frozen + [hot] * (S - frozen)
        dev = [DeviceProfile(1.0, 4096)] * S
        sim = SimConfig(n_layers=S, n_devices=S, n_microbatches=M)
        row["sim_round_scan"] = simulate_round(
            "ringada", sim, lay, dev, unfreeze_depth=S - frozen,
            n_owners=S).time_per_round_s
        row["sim_round_packed"] = simulate_round(
            "ringada_packed", sim, lay, dev, unfreeze_depth=S - frozen,
            n_owners=S).time_per_round_s
    return row


def check_hetero(out_or_bench: Dict, gate) -> None:
    """Gate: the speed-weighted partition beats uniform on the skewed mesh."""
    het = out_or_bench.get("hetero")
    if not het:
        return
    gate(het["weighted_round_s"] < het["uniform_round_s"],
         f"speed-weighted spans {het['weighted_spans']} round "
         f"{het['weighted_round_s']:.3f}s < uniform "
         f"{het['uniform_round_s']:.3f}s on skewed mesh "
         f"{het['device_speeds']}")


def write_bench_ring(out: Dict, path: str, log=print) -> Optional[Dict]:
    """Condense the measured section into BENCH_ring.json (schema v2).

    Machine-readable perf trajectory (tracked across PRs, uploaded by CI
    from both the 2- and 4-device meshes): steady-state steps/sec for
    reference / fused(packed) / scan / cached, the packed-vs-scan round
    ratio with its tick-count ledger, per-dtype cache bytes/entry + hit
    rate, per-mode executable peak bytes, and per-boundary compile counts.
    """
    fvr = out.get("fused_vs_reference", {})
    if "steady" not in fvr:
        log(f"  BENCH_ring.json NOT written ({path}): bench skipped "
            f"({fvr.get('skipped', 'no data')[:200]})")
        return None
    steady = fvr["steady"]
    cached = steady["cached"]
    frozen = fvr.get("frozen_stages", 0)
    # tick ledger for the MEASURED geometry (the section-1 table uses the
    # simulator's 12-block model — different M/lps; publishing those numbers
    # next to packed_scan_ratio would compare two configurations)
    tick_row = _tick_ledger(fvr.get("mesh_devices", 4),
                            fvr.get("n_micro", 4), frozen)
    bench = {
        "schema": "BENCH_ring/v2",
        "mesh_devices": fvr.get("mesh_devices", 4),
        "boundary": cached["boundary"],
        "frozen_stages": frozen,
        "steady_steps_per_sec": {
            name: steady[name]["steps_per_sec"]
            for name in ("reference", "fused", "fused_scan", "cached")},
        "steady_round_ms": {
            name: steady[name]["round_ms"]
            for name in ("reference", "fused", "fused_scan", "cached")},
        "packed_scan_ratio": fvr.get("packed_scan_ratio"),
        "phase_a_ticks": {
            "packed": tick_row.get("phase_a_round_ticks_packed"),
            "scan": tick_row.get("phase_a_round_ticks_scan"),
            "saved": tick_row.get("phase_a_saved_ticks"),
            "simulated_packed": tick_row.get("sim_round_packed"),
            "simulated_scan": tick_row.get("sim_round_scan"),
        },
        "cache_dtypes": {
            name: {k: r.get(k) for k in
                   ("bytes_per_entry", "buffer_bytes", "cache_hit_rate",
                    "round_ms", "steps_per_sec", "loss_drift_vs_f32")}
            for name, r in fvr.get("cache_dtypes", {}).items()},
        "mode_memory_peak_bytes": {
            k: v.get("peak_bytes")
            for k, v in fvr.get("mode_memory", {}).items()},
        "speedup_fused_vs_reference": fvr["steady_speedup"],
        "speedup_cached_vs_fused": fvr["cached_speedup_vs_fused"],
        "speedup_schedule_fused_vs_reference": fvr["speedup"],
        "session_facade_ratio": fvr.get("session_facade_ratio"),
        "session_steps_per_sec": fvr["steady"].get(
            "session_cached", {}).get("steps_per_sec"),
        # multi-tenant packing: per-tenant steps/sec at T in {1, 4} and the
        # per-tenant share of the T=4 round vs the solo round (gated < 2.0)
        "tenants": fvr.get("tenants"),
        "cache_hit_rate": cached["cache_hit_rate"],
        "compile_counts": cached["compile_counts"],
        "n_executables": {
            name: steady[name]["n_executables"]
            for name in ("reference", "fused", "cached")},
        # simulated skewed-mesh result: speed-weighted assign_layers spans
        # vs the uniform split (deterministic -> gated by --check)
        "hetero": out.get("hetero"),
        # checkpoint-free crash recovery: sim-tick prices (gated) plus the
        # measured recovery-round ms from the chaos drill subprocess
        "elastic": out.get("elastic"),
    }
    with open(path, "w") as f:
        json.dump(bench, f, indent=1, sort_keys=True)
        f.write("\n")
    log(f"  wrote {path}: cached {bench['steady_steps_per_sec']['cached']:.2f} "
        f"steps/s = {bench['speedup_cached_vs_fused']:.2f}x fused "
        f"({bench['cache_hit_rate']:.0%} hit rate), packed/scan "
        f"{bench['packed_scan_ratio']:.2f}")
    return bench


CACHED_SPEEDUP_FLOOR = 1.15


def check_bench_ring(path: str, log=print) -> bool:
    """The CI regression gate over a written BENCH_ring.json.

    Fails when the cached steady state stops clearly beating the fused
    executor, when the packed conveyor stops beating the per-owner scan on
    first-visit/capture rounds (only meaningful at F >= 2 — at F <= 1 there
    are no cross-owner bubbles to save, so the ratio gate is skipped),
    when bf16 entries stop matching the f32 hit rate at half the bytes,
    when the T=4 tenant conveyor's per-tenant round stops staying under 2x
    the solo round (the bubble must amortize over tenants), when the
    speed-weighted partition stops beating the uniform split on the skewed
    simulated mesh (deterministic discrete-event model, no jitter), or when
    a checkpoint-free crash recovery (one full re-capture round at the
    survivor geometry) stops costing <= 2x the cached steady round that
    follows — also gated in deterministic sim ticks, not wall-clock.

    Threshold note: the v1 bench's headline "cached = 3x fused" came from
    single timing windows, which on host-CPU collectives jitter by 50%+ and
    systematically flattered the second-measured driver; under the v2
    best-of-3 methodology the honest steady-state ratio at (S=4, M=4, F=3)
    is ~1.3x — structurally capped near 1.6x, since the cached round still
    pays all of Phase B's forward AND backward ticks and the round-fixed
    optimizer/dispatch cost.  The floor is set below the measured ratio with
    margin; the packed gate (a same-executable A/B) is the tight one.
    """
    with open(path) as f:
        bench = json.load(f)
    ok = True

    def gate(cond, msg):
        nonlocal ok
        log(f"  [{'PASS' if cond else 'FAIL'}] {msg}")
        ok = ok and cond

    sp = bench.get("speedup_cached_vs_fused") or 0.0
    gate(sp >= CACHED_SPEEDUP_FLOOR,
         f"speedup_cached_vs_fused {sp:.2f} >= {CACHED_SPEEDUP_FLOOR}")
    frozen = bench.get("frozen_stages", 0)
    ratio = bench.get("packed_scan_ratio")
    if frozen >= 2 and ratio is not None:
        gate(ratio < 1.0,
             f"packed/scan round-ms ratio {ratio:.3f} < 1.0 at F={frozen}")
    else:
        log(f"  [skip] packed/scan ratio gate (F={frozen} < 2: no "
            f"cross-owner bubbles to pack away)")
    dts = bench.get("cache_dtypes", {})
    if "f32" in dts and "bf16" in dts:
        f32d, bf = dts["f32"], dts["bf16"]
        gate(bf["bytes_per_entry"] * 2 == f32d["bytes_per_entry"],
             f"bf16 entry bytes {bf['bytes_per_entry']} == half of f32's "
             f"{f32d['bytes_per_entry']}")
        gate(bf["cache_hit_rate"] == f32d["cache_hit_rate"],
             f"bf16 hit rate {bf['cache_hit_rate']:.0%} == f32's at half "
             f"the bytes")
        drift = bf.get("loss_drift_vs_f32", 1.0)
        gate(drift < 1e-3, f"bf16 loss drift vs f32 {drift:.2e} < 1e-3")
    ten = bench.get("tenants")
    if ten:
        tr = ten["per_tenant_round_ratio"]
        gate(tr < 2.0,
             f"T=4 per-tenant packed round is {tr:.2f}x the T=1 round "
             f"(< 2.0: the tenant conveyor amortizes the fill/drain "
             f"bubble instead of re-paying it per tenant)")
    check_hetero(bench, gate)
    el = bench.get("elastic")
    if el and el.get("recovery_round_ticks") is not None:
        gate(el["recovery_round_ticks"] <= 2 * el["steady_round_ticks"],
             f"checkpoint-free recovery round {el['recovery_round_ticks']} "
             f"ticks <= 2x the post-shrink cached steady round "
             f"{el['steady_round_ticks']} (boundary {el['boundary']}, "
             f"refill {el['rounds_to_cache_refill']} round(s))")
    return ok


def run(log=print, out_path: str = DEFAULT_OUT, devices: int = 4) -> Dict:
    out = {}
    S, M, lps = devices, 8, 12 // devices      # 12 blocks over the mesh
    from repro.core.partition import DeviceProfile
    from repro.core.pipeline import pipeline_tick_counts
    from repro.core.simulator import LayerProfile, SimConfig, simulate_round

    ticks = {}
    for frozen_stages in range(S):
        t = pipeline_tick_counts(S, M, boundary=frozen_stages * lps, lps=lps)
        tc = pipeline_tick_counts(S, M, boundary=frozen_stages * lps, lps=lps,
                                  cached=True)
        t["fwd_ticks_cached"] = tc["fwd_ticks"]
        t.pop("phase_a_round_ticks")
        t.pop("phase_a_saved_ticks")
        # closed forms + discrete-event cross-check (unit-cost frozen
        # blocks, free hot blocks and links: engine time == tick count)
        t.update(_tick_ledger(S, M, frozen_stages))
        if 0 < frozen_stages < S:
            assert t["sim_round_scan"] == t["phase_a_round_ticks_scan"]
            assert t["sim_round_packed"] == t["phase_a_round_ticks_packed"]
        ticks[f"frozen_{frozen_stages}"] = t
        log(f"  frozen_stages={frozen_stages}: fwd={t['fwd_ticks']} "
            f"(cached {tc['fwd_ticks']}) bwd={t['bwd_ticks']} ticks; "
            f"phase A/round scan={t['phase_a_round_ticks_scan']} "
            f"packed={t['phase_a_round_ticks_packed']} "
            f"(saves {t['phase_a_saved_ticks']})")
    out["tick_counts"] = ticks

    layers = [LayerProfile(0.01, 0.02, 20.0, 30.0, 0.6, 2.0)] * 12
    sim_devices = [DeviceProfile(1.0, 4096)] * S
    sim = SimConfig(n_layers=12, n_devices=S, n_microbatches=M)

    # heterogeneous mesh: the paper's speed-weighted assignment
    # (assign_layers) vs the uniform split, on a skewed simulated mesh.
    # Deterministic discrete-event model, so CI gates on it (--check):
    # the speed-weighted partition must beat uniform.
    from repro.core.partition import (parse_device_profiles, span_sizes,
                                      spans_from_profiles)
    skew = ([1.0, 0.5, 2.0, 1.0] * ((S + 3) // 4))[:S]
    het_devices = [DeviceProfile(compute_speed=sp, memory_mb=4096)
                   for sp in skew]
    costs = [l.fwd_s + l.bwd_s for l in layers]
    w_spans = spans_from_profiles(12, parse_device_profiles(skew),
                                  layer_costs=costs)
    r_uni = simulate_round("ringada", sim, layers, het_devices,
                           unfreeze_depth=6)
    r_wtd = simulate_round("ringada", sim, layers, het_devices,
                           unfreeze_depth=6, spans=list(w_spans))
    out["hetero"] = {
        "device_speeds": skew,
        "weighted_spans": [list(sp) for sp in w_spans],
        "uniform_round_s": r_uni.time_per_round_s,
        "weighted_round_s": r_wtd.time_per_round_s,
        "speedup": r_uni.time_per_round_s / r_wtd.time_per_round_s,
        "uniform_peak_mb": r_uni.max_memory_mb,
        "weighted_peak_mb": r_wtd.max_memory_mb,
    }
    log(f"  hetero mesh (speeds {skew}): weighted spans "
        f"{list(span_sizes(w_spans))} round={r_wtd.time_per_round_s:.3f}s "
        f"vs uniform {r_uni.time_per_round_s:.3f}s "
        f"({out['hetero']['speedup']:.2f}x)")

    # elastic: price the checkpoint-free crash recovery in sim ticks on the
    # same 12-block mesh at the section-2 depth-6 operating point.  A crash
    # costs one full re-capture round at the survivor geometry (the cache
    # was rebound), then cached rounds resume — deterministic, so --check
    # gates recovery <= 2x the post-shrink steady round.
    from repro.core.simulator import predict_recovery
    survivors = [DeviceProfile(1.0, 4096)] * max(S - 1, 1)
    rec = predict_recovery(12, survivors, M, boundary=6, packed=True,
                           slots_per_epoch=1)
    out["elastic"] = {
        "survivor_spans": [list(sp) for sp in rec["spans"]],
        "boundary": rec["boundary"],
        "frozen_stages": rec["frozen_stages"],
        "recovery_round_ticks": rec["recovery_round_ticks"],
        "steady_round_ticks": rec["steady_round_ticks"],
        "rounds_to_cache_refill": rec["rounds_to_cache_refill"],
    }
    log(f"  elastic crash {S} -> {len(rec['spans'])} units: recovery round "
        f"{rec['recovery_round_ticks']} ticks vs cached steady "
        f"{rec['steady_round_ticks']} (boundary 6 -> {rec['boundary']}, "
        f"refill in {rec['rounds_to_cache_refill']} round(s))")

    util = {}
    for depth in (1, 3, 6, 12):
        r = simulate_round("ringada", sim, layers, sim_devices,
                           unfreeze_depth=depth)
        rc = simulate_round("ringada_cached", sim, layers, sim_devices,
                            unfreeze_depth=depth)
        busy = sum(r.device_busy_s.values())
        util[f"depth_{depth}"] = {
            "round_s": r.time_per_round_s,
            "round_s_cached": rc.time_per_round_s,
            "utilization": busy / (r.time_per_round_s * S),
        }
        log(f"  depth={depth:2d}: round={r.time_per_round_s:.3f}s "
            f"(cached {rc.time_per_round_s:.3f}s) "
            f"util={busy / (r.time_per_round_s * S):.2%}")
    out["simulated_rounds"] = util

    log(f"fused RingExecutor vs reference RingTrainer vs packed vs actcache "
        f"({devices} host devices):")
    out["fused_vs_reference"] = bench_fused_vs_reference(log, devices)
    log(f"chaos recovery drill ({devices} -> {devices - 1} host devices):")
    out["elastic"]["measured"] = bench_elastic(log, devices)
    if out_path:
        out["bench_ring"] = write_bench_ring(out, out_path, log)
    return out


if __name__ == "__main__":
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=DEFAULT_OUT,
                    help="where to write BENCH_ring.json ('' to skip)")
    ap.add_argument("--devices", type=int, default=4,
                    help="virtual CPU devices for the measured section "
                         "(CI runs 2 and 4)")
    ap.add_argument("--check", default=None, metavar="BENCH_JSON",
                    help="gate mode: validate a written BENCH_ring.json "
                         "against the regression thresholds and exit "
                         "nonzero on failure (no benchmarks are run)")
    args = ap.parse_args()
    if args.check:
        sys.exit(0 if check_bench_ring(args.check) else 1)
    print(json.dumps(run(out_path=args.out, devices=args.devices), indent=1))
