"""Elastic ring under churn: the chaos-mode differential harness.

Extends the PR-5 methodology (closed forms == discrete-event simulator ==
measured executor ledgers, exactly) to fleet churn:

  (a) churn replay     — ``ChurnEvent`` validation, ``apply_churn``,
      ``simulate_training(churn=...)`` re-pricing recovery rounds;
  (b) detection        — ``StragglerDetector`` EWMA re-fit + hysteresis:
      a stable skewed mesh triggers at most ONE repartition (no flapping);
  (c) recovery         — ``RingExecutor.shrink``: post-shrink measured tick
      ledgers equal ``spmd_tick_round`` / ``predict_recovery`` EXACTLY, and
      post-shrink training matches a from-scratch S-1 ring (same transplanted
      params + Adam moments) at the established 1e-5 / 1e-3 pins — the
      checkpoint-free recovery claim, as a differential;
  (d) the chaos gate   — ``ChaosBackend`` through ``RingSession``: a
      mid-schedule kill completes training with no checkpoint restore,
      save -> resume across a shrink is bit-reproducible, a non-elastic
      crash raises, a rejoin grows the ring back.

Subprocess tests need 4 CPU devices (XLA_FLAGS host platform override).
"""
import json
import math
import os
import subprocess
import sys

import pytest

from repro.core.elastic import StragglerDetector, parse_chaos_events
from repro.core.partition import (DeviceProfile, normalize_spans,
                                  parse_device_profiles, span_sizes)
from repro.core.simulator import (ChurnEvent, LayerProfile, SimConfig,
                                  apply_churn, full_round_ticks,
                                  predict_recovery, simulate_training)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_sub(code: str) -> dict:
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


# ---------------------------------------------------------------------------
# (a) churn events: validation, parsing, fleet replay
# ---------------------------------------------------------------------------


def test_churn_event_validation():
    ChurnEvent(round=0, kind="crash", device=0)        # ok
    with pytest.raises(ValueError, match="unknown churn kind"):
        ChurnEvent(round=0, kind="explode", device=0)
    with pytest.raises(ValueError, match=">= 0"):
        ChurnEvent(round=-1, kind="crash", device=0)
    with pytest.raises(ValueError, match=">= 0"):
        ChurnEvent(round=0, kind="crash", device=-2)
    with pytest.raises(ValueError, match="factor"):
        ChurnEvent(round=0, kind="slowdown", device=0, factor=0.0)


def test_parse_chaos_events():
    evs = parse_chaos_events(["5:slowdown:1:4.0", "3:crash:2", "7:JOIN:2"])
    assert [e.round for e in evs] == [3, 5, 7]         # sorted by round
    assert evs[0] == ChurnEvent(round=3, kind="crash", device=2)
    assert evs[1].factor == 4.0
    assert evs[2].kind == "join"                        # case-insensitive
    for bad in ("3:crash", "a:crash:2", "3:crash:x", "3:crash:2:z",
                "3:explode:2", "1:2:3:4:5"):
        with pytest.raises(ValueError, match="chaos spec"):
            parse_chaos_events([bad])


def test_apply_churn_fleet_replay():
    fleet = parse_device_profiles([1.0, 1.25, 0.5, 0.75])
    f2 = apply_churn(fleet, ChurnEvent(round=0, kind="crash", device=2))
    assert [p.compute_speed for p in f2] == [1.0, 1.25, 0.75]
    assert len(fleet) == 4                              # input untouched
    f3 = apply_churn(f2, ChurnEvent(round=1, kind="slowdown", device=0,
                                    factor=2.0))
    assert f3[0].compute_speed == 0.5
    f4 = apply_churn(f3, ChurnEvent(round=2, kind="join", device=2,
                                    profile=DeviceProfile(0.5, 100.0)))
    assert len(f4) == 4 and f4[2].compute_speed == 0.5
    with pytest.raises(ValueError, match="fleet has"):
        apply_churn(f2, ChurnEvent(round=0, kind="crash", device=7))
    one = [DeviceProfile(1.0, float("inf"))]
    with pytest.raises(ValueError, match="last device"):
        apply_churn(one, ChurnEvent(round=0, kind="leave", device=0))


def _unit_layers(n):
    return [LayerProfile(fwd_s=1.0, bwd_s=1.0, act_mb=1.0, weight_mb=1.0,
                         adapter_mb=0.1, boundary_mb=0.0) for _ in range(n)]


def test_simulate_training_replays_churn():
    """A crash mid-run shrinks the simulated fleet (later rounds run on the
    survivors' speed-weighted spans) and resets the cached scheme's capture
    counter: the first post-crash round is priced as a full capture round."""
    sim = SimConfig(n_layers=12, n_devices=4, n_microbatches=4)
    devs = parse_device_profiles([1.0, 1.0, 1.0, 1.0])
    kw = dict(rounds=6, unfreeze_interval=10**6, initial_depth=3,
              slots_per_epoch=1)
    tot_plain, _, times_plain = simulate_training("ringada_cached", sim,
                                                  _unit_layers(12), devs, **kw)
    churn = [ChurnEvent(round=3, kind="crash", device=1)]
    tot_churn, _, times = simulate_training("ringada_cached", sim,
                                            _unit_layers(12), devs,
                                            churn=churn, **kw)
    assert len(times) == 6
    per_round = [t - p for t, p in zip(times, [0.0] + times[:-1])]
    # rounds 0-2 identical to the no-churn run; round 3 re-pays capture
    per_plain = [t - p for t, p in zip(times_plain, [0.0] + times_plain[:-1])]
    assert per_round[:3] == pytest.approx(per_plain[:3])
    assert per_round[3] > per_round[2]                 # recovery > steady
    assert per_round[4] < per_round[3]                 # cache refilled
    with pytest.raises(TypeError, match="ChurnEvent"):
        simulate_training("ringada", sim, _unit_layers(12), devs,
                          churn=["3:crash:1"], **kw)


def test_predict_recovery_closed_forms():
    """S=4, M=2, F=2 packed: recovery = (S*M + F - 1) + S*2*(M + hot - 1)
    = 9 + 24 = 33 ticks; steady cached = 24 — recovery <= 2x steady, the
    invariant the elastic bench gates."""
    survivors = parse_device_profiles([1.0, 1.0, 1.0, 1.0])
    pred = predict_recovery(8, survivors, 2, boundary=4, slots_per_epoch=3)
    assert span_sizes(pred["spans"]) == (2, 2, 2, 2)
    assert pred["boundary"] == 4 and pred["frozen_stages"] == 2
    assert pred["recovery_round_ticks"] == 4 * 2 + 2 - 1 + 4 * 2 * (2 + 2 - 1)
    assert pred["steady_round_ticks"] == 4 * 2 * (2 + 2 - 1)
    assert pred["rounds_to_cache_refill"] == 3
    assert pred["recovery_round_ticks"] <= 2 * pred["steady_round_ticks"]
    # un-alignable boundary aligns DOWN to a survivor span edge
    surv3 = parse_device_profiles([1.0, 1.25, 0.75])
    pred3 = predict_recovery(14, surv3, 2, boundary=11)
    assert pred3["boundary"] in [b for b, _ in pred3["spans"]] + [14]
    assert pred3["boundary"] <= 11
    # consistency with full_round_ticks at the predicted geometry
    F = pred3["frozen_stages"]
    want = full_round_ticks(pred3["spans"], 2, pred3["boundary"],
                            packed=F >= 2)
    assert pred3["recovery_round_ticks"] == want["round_ticks"]


# ---------------------------------------------------------------------------
# (b) straggler detection: EWMA re-fit + hysteresis, fires-at-most-once
# ---------------------------------------------------------------------------

SPEEDS = [1.0, 1.25, 0.5, 0.75]


def _stage_times(spans, speeds):
    return [sz / s for sz, s in zip(span_sizes(normalize_spans(spans)),
                                    speeds)]


def test_detector_fires_exactly_once_on_stable_skew():
    """Spans 4:4:3:3 over the true speeds 1.0:1.25:0.5:0.75 bottleneck at
    6.0 ticks vs 4.0 for the optimal 4:5:2:3 (ratio 1.5 >= 1.2): the
    detector fires after ``patience`` rounds, repartitions ONCE, and never
    proposes again on the equalized layout — the no-flapping pin."""
    det = StragglerDetector(parse_device_profiles(SPEEDS), 14,
                            threshold=1.2, patience=2)
    spans = normalize_spans([4, 4, 3, 3])
    props = []
    for _ in range(6):
        det.observe(spans, _stage_times(spans, SPEEDS))
        prop = det.propose(spans)
        props.append(prop)
        if prop is not None:
            spans = prop                               # apply the repartition
    fired = [p for p in props if p is not None]
    assert len(fired) == 1 and det.repartitions == 1
    assert span_sizes(fired[0]) == (4, 5, 2, 3)
    assert props[0] is None and props[1] is not None   # patience=2
    assert all(p is None for p in props[2:])           # equalized: no flap
    assert det.bottleneck(spans) == pytest.approx(4.0)


def test_detector_ewma_discovers_slowdown():
    """Seeded with unit profiles, a genuinely 4x-slower device 2 is
    discovered from measured stage times alone: the EWMA speed converges
    toward 0.25 and the proposal shrinks its span."""
    det = StragglerDetector(parse_device_profiles([1.0] * 4), 12, alpha=0.5,
                            threshold=1.2, patience=2)
    spans = normalize_spans([3, 3, 3, 3])
    true = [1.0, 1.0, 0.25, 1.0]
    prop = None
    for _ in range(8):
        det.observe(spans, _stage_times(spans, true))
        prop = det.propose(spans) or prop
    assert abs(det.speeds[2] - 0.25) < 0.05            # EWMA converged
    assert prop is not None
    assert span_sizes(prop)[2] < 3                     # straggler's span shrank
    # one transient slow round never triggers (patience + EWMA smoothing)
    det2 = StragglerDetector(parse_device_profiles([1.0] * 4), 12,
                             patience=2)
    det2.observe(spans, [3.0, 3.0, 12.0, 3.0])         # single GC-pause round
    assert det2.propose(spans) is None


def test_detector_membership_and_validation():
    det = StragglerDetector(parse_device_profiles(SPEEDS), 14)
    det.remove(2)
    assert [p.compute_speed for p in det.fleet] == [1.0, 1.25, 0.75]
    det.insert(2, DeviceProfile(0.5, float("inf")))
    assert [p.compute_speed for p in det.fleet] == SPEEDS
    with pytest.raises(ValueError, match="alpha"):
        StragglerDetector(det.fleet, 14, alpha=0.0)
    with pytest.raises(ValueError, match="threshold"):
        StragglerDetector(det.fleet, 14, threshold=0.9)
    with pytest.raises(ValueError, match="shape mismatch"):
        det.observe([4, 4, 3, 3], [1.0, 1.0, 1.0])


def test_device_profile_validation():
    """The bugfix satellite: NaN / non-positive speeds used to flow straight
    into Algorithm 1's span arithmetic (NaN poisons the binary search into
    returning degenerate spans); they now fail at construction."""
    for bad in (float("nan"), 0.0, -1.0, float("-inf")):
        with pytest.raises(ValueError, match="compute_speed"):
            DeviceProfile(compute_speed=bad, memory_mb=1.0)
        with pytest.raises(ValueError):
            parse_device_profiles([1.0, bad])
    with pytest.raises(ValueError, match="memory_mb"):
        DeviceProfile(compute_speed=1.0, memory_mb=float("nan"))
    with pytest.raises(ValueError, match="link_mbps"):
        DeviceProfile(compute_speed=1.0, memory_mb=1.0, link_mbps=0.0)
    assert DeviceProfile(2.0, 8.0).slowed(4.0).compute_speed == 0.5
    with pytest.raises(ValueError):
        DeviceProfile(2.0, 8.0).slowed(0.0)


# ---------------------------------------------------------------------------
# (c) + (d): executor/session differential — 4-device subprocess
# ---------------------------------------------------------------------------

PRELUDE = """
import json
import numpy as np
import jax, jax.numpy as jnp
from repro.core.pipeline import make_ring_mesh
from repro.configs import TrainConfig, get_config
from repro.models import params as P
from repro.core import pipeline as pl
from repro.core.executor import RingExecutor
from repro.core.partition import parse_device_profiles
from repro.core.simulator import predict_recovery, spmd_tick_round

cfg = get_config("stablelm-3b").reduced(n_layers=14, repeats=14,
                                        d_model=64, d_ff=128, vocab_size=128)
S, M, mb, seq = 4, 2, 1, 16
SPEEDS = [1.0, 1.25, 0.5, 0.75]

def fresh_params():
    params = P.materialize(P.param_defs(cfg), jax.random.key(0))
    ad = params["blocks"][0]["adapter"]
    ad["w_up"] = 0.02 * jax.random.normal(jax.random.key(9), ad["w_up"].shape,
                                          jnp.float32).astype(ad["w_up"].dtype)
    return params

mesh = make_ring_mesh(S)

def batch(k=0):
    t = jax.random.randint(jax.random.key(10 + k), (S, M, mb, seq), 0,
                           cfg.vocab_size)
    l = jax.random.randint(jax.random.key(20 + k), (S, M, mb, seq), 0,
                           cfg.vocab_size)
    return t, l

f32 = lambda x: x.astype(jnp.float32)
maxerr = lambda a, b: max(jax.tree.leaves(jax.tree.map(
    lambda x, y: float(jnp.abs(f32(x) - f32(y)).max()), a, b)))
host = lambda t: jax.tree.map(np.asarray, t)
"""


def test_shrink_differential_ticks_and_numerics():
    """The tentpole acceptance test, three crash scenarios on the 4-device
    mesh (uneven 4:5:2:3 layouts included, one case down-realigns the
    boundary, one lands on F=1 where packing is a no-op):

      * geometry — the executor's post-shrink spans/boundary equal
        ``predict_recovery``'s, the measured recovery (capture) and steady
        (cached) tick ledgers equal the simulator EXACTLY (integer equality);
      * numerics — post-shrink training is loss/param-equivalent (1e-5 /
        1e-3) to a FROM-SCRATCH S-1 executor built at the same spans with
        the same transplanted params + Adam moments + step counter: nothing
        was lost to the crash, no checkpoint was read;
      * the rebound activation cache re-captures: hit pattern
        [miss, miss, hit, hit] after the shrink on both rings.
    """
    code = PRELUDE + """
tc = TrainConfig(learning_rate=1e-3, unfreeze_interval=10**6,
                 initial_unfreeze_depth=3, n_microbatches=M,
                 batch_size=mb, seq_len=seq)
batches = [batch(0), batch(1)]
cases = [("4:5:2:3/kill2", [4, 5, 2, 3], 2),
         ("4:4:3:3/kill0", [4, 4, 3, 3], 0),
         ("4:5:2:3/kill3", [4, 5, 2, 3], 3)]
out = {}
for name, layout, dead in cases:
    profs = parse_device_profiles(SPEEDS)
    drv = RingExecutor(cfg, tc, mesh, fresh_params(), S, M, spans=layout,
                       cache_capacity=2)
    with jax.set_mesh(mesh):
        for r in range(4):
            t, l = batches[r % 2]
            RingExecutor.materialize_metrics(drv.round(t, l, slot=r % 2))
    b_pre = drv.boundary_at(drv.step)
    surv = [p for i, p in enumerate(profs) if i != dead]
    drv.shrink(dead, profiles=surv)
    pred = predict_recovery(cfg.repeats, surv, M, b_pre, slots_per_epoch=2)
    b = drv.boundary_at(drv.step)

    # from-scratch S-1 twin: same spans, transplanted params+moments+step
    pc = host(drv.export_params())
    m_ad = host(pl.unstack_entry(drv.opt_state["m"]["adapter"], drv.spans))
    v_ad = host(pl.unstack_entry(drv.opt_state["v"]["adapter"], drv.spans))
    m_hd, v_hd = host(drv.opt_state["m"]["head"]), host(drv.opt_state["v"]["head"])
    count = int(drv.opt_state["count"])
    twin = RingExecutor(cfg, tc, drv.mesh, pc, S - 1, M,
                        spans=drv.spans, cache_capacity=2)
    twin.opt_state = {
        "m": {"adapter": pl.stack_entry(m_ad, twin.spans), "head": m_hd},
        "v": {"adapter": pl.stack_entry(v_ad, twin.spans), "head": v_hd},
        "count": jnp.asarray(count)}
    twin.step = drv.step

    rows = np.asarray([i for i in range(S) if i != dead])
    losses, hits = [], []
    with jax.set_mesh(drv.mesh):
        for r in range(4):
            t, l = batches[r % 2]
            ma = RingExecutor.materialize_metrics(
                drv.round(t[rows], l[rows], slot=r % 2))
            mt = RingExecutor.materialize_metrics(
                twin.round(t[rows], l[rows], slot=r % 2))
            losses.append((ma["loss"], mt["loss"]))
            hits.append((ma["cache_hit"], mt["cache_hit"]))

    led_r = drv.measured_tick_ledger(b, "capture")
    led_s = drv.measured_tick_ledger(b, "cached")
    S1 = S - 1
    sim_r = spmd_tick_round(drv.spans, M, b,
                            packed=led_r["frozen_stages"] >= 2)
    sim_s = spmd_tick_round(drv.spans, M, b, cached=True)
    out[name] = {
        "spans": [list(sp) for sp in drv.spans],
        "pred_spans": [list(sp) for sp in pred["spans"]],
        "b": b, "pred_b": pred["boundary"], "b_pre": b_pre,
        "losses": losses, "hits": hits,
        "param_err": maxerr(drv.export_params(), twin.export_params()),
        "frozen": led_r["frozen_stages"],
        "measured_recovery": led_r["phase_a_round_ticks"]
                             + S1 * 2 * led_r["bwd_ticks"],
        "measured_steady": led_s["phase_a_round_ticks"]
                           + S1 * 2 * led_s["bwd_ticks"],
        "pred_recovery": pred["recovery_round_ticks"],
        "pred_steady": pred["steady_round_ticks"],
        "sim_recovery_a": sim_r["phase_a_round_ticks"],
        "led_recovery_a": led_r["phase_a_round_ticks"],
        "sim_steady_a": sim_s["phase_a_round_ticks"],
        "led_steady_a": led_s["phase_a_round_ticks"],
    }
print(json.dumps(out))
"""
    res = _run_sub(code)
    saw_realign = saw_unpacked = False
    for name, rec in res.items():
        # geometry: executor == predict_recovery
        assert rec["spans"] == rec["pred_spans"], (name, rec)
        assert rec["b"] == rec["pred_b"], (name, rec)
        assert rec["b"] <= rec["b_pre"]                # aligns DOWN only
        saw_realign |= rec["b"] < rec["b_pre"]
        saw_unpacked |= rec["frozen"] < 2
        # tick differential: measured ledgers == simulator, exactly
        assert rec["led_recovery_a"] == rec["sim_recovery_a"], (name, rec)
        assert rec["led_steady_a"] == rec["sim_steady_a"] == 0, (name, rec)
        assert rec["measured_recovery"] == rec["pred_recovery"], (name, rec)
        assert rec["measured_steady"] == rec["pred_steady"], (name, rec)
        # numerics: post-shrink ring == from-scratch S-1 twin
        for a, t in rec["losses"]:
            assert math.isfinite(a) and abs(a - t) < 1e-5, (name, rec)
        assert rec["param_err"] < 1e-3, (name, rec)
        # checkpoint-free cache re-capture on both rings
        assert rec["hits"] == [[False, False], [False, False],
                               [True, True], [True, True]], (name, rec)
    assert saw_realign, "no case exercised boundary down-realignment"
    assert saw_unpacked, "no case exercised the F<2 unpacked recovery"


def test_chaos_session_kill_completes_and_resumes():
    """(d) end to end through RingSession: kill device 2 before round 3 of
    8 — training completes on the survivors with NO checkpoint restore,
    exactly one round is flagged ``layout_changed``, save -> restore across
    the shrink is bit-reproducible, and the same crash without ``elastic``
    raises instead of limping."""
    code = PRELUDE + """
import os, tempfile
from repro.api import RingSession
tc = TrainConfig(learning_rate=1e-3, unfreeze_interval=10**6,
                 initial_unfreeze_depth=3, n_microbatches=M,
                 batch_size=mb, seq_len=seq)
err = None
try:
    s0 = RingSession.create(cfg, tc, backend="fused", n_stages=S,
                            chaos="1:crash:2", log=lambda *a: None)
    s0.run(3)
except RuntimeError as e:
    err = str(e)
sess = RingSession.create(cfg, tc, backend="fused", n_stages=S,
                          chaos="3:crash:2", elastic=True,
                          log=lambda *a: None)
hist = sess.run(8)
path = os.path.join(tempfile.mkdtemp(), "chaos_ck")
sess.save(path)
cont = [h["loss"] for h in sess.run(3)]
restored = RingSession.restore(path, cfg, tc, log=lambda *a: None)
again = [h["loss"] for h in restored.run(3)]
with open(path + ".json") as f:
    ex = json.load(f)["extra"]
print(json.dumps({
    "err": err,
    "marks": [bool(h.get("layout_changed")) for h in hist],
    "losses": [h["loss"] for h in hist],
    "survivors": hist[-1]["survivors"],
    "shrinks": sess.backend.shrinks,
    "spans": [list(sp) for sp in sess.backend.spans],
    "r_spans": [list(sp) for sp in restored.backend.spans],
    "r_survivors": list(restored.backend.survivors),
    "ck_survivors": ex.get("survivors"), "ck_stages": ex.get("n_stages"),
    "cont": cont, "again": again}))
"""
    res = _run_sub(code)
    assert res["err"] and "elastic" in res["err"], res["err"]
    assert res["marks"] == [False] * 3 + [True] + [False] * 4
    assert all(math.isfinite(l) for l in res["losses"])
    assert res["survivors"] == [0, 1, 3] and res["shrinks"] == 1
    # the checkpoint records the membership; restore replays it exactly
    assert res["ck_survivors"] == [0, 1, 3] and res["ck_stages"] == 4
    assert res["r_survivors"] == [0, 1, 3]
    assert res["r_spans"] == res["spans"]
    assert res["cont"] == res["again"], res            # bit-reproducible


def test_straggler_session_repartitions_once():
    """(b) through the live session: explicit 4:4:3:3 spans over the true
    speeds 1.0:1.25:0.5:0.75 — the detector's synthetic stage timings drive
    an EWMA re-fit that fires ONE hysteresis-gated repartition to the
    Algorithm-1 4:5:2:3 layout (round ``patience``), then stays quiet for
    the rest of the run."""
    code = PRELUDE + """
from repro.api import RingSession
tc = TrainConfig(learning_rate=1e-3, unfreeze_interval=10**6,
                 initial_unfreeze_depth=3, n_microbatches=M,
                 batch_size=mb, seq_len=seq)
sess = RingSession.create(cfg, tc, backend="fused", n_stages=S,
                          spans=[4, 4, 3, 3], device_profiles=SPEEDS,
                          elastic=True, log=lambda *a: None)
hist = sess.run(8)
print(json.dumps({
    "marks": [bool(h.get("layout_changed")) for h in hist],
    "losses": [h["loss"] for h in hist],
    "repartitions": sess.backend.repartitions,
    "shrinks": sess.backend.shrinks,
    "spans": [list(sp) for sp in sess.backend.spans],
    "stage_times": hist[-1]["stage_times"]}))
"""
    res = _run_sub(code)
    assert res["repartitions"] == 1 and res["shrinks"] == 0
    assert res["spans"] == [[0, 4], [4, 9], [9, 11], [11, 14]]
    assert res["marks"].count(True) == 1               # fired exactly once
    assert res["marks"][1]                             # at round patience=2
    assert all(math.isfinite(l) for l in res["losses"])
    # post-repartition the synthetic stage times are equalized (4.0 ticks)
    assert res["stage_times"] == pytest.approx([4.0] * 4)


def test_chaos_session_crash_then_rejoin_grows_back():
    """A crash at round 2 shrinks 4 -> 3; the same device rejoining at
    round 5 grows the ring back to 4 (``RingExecutor.grow``), training runs
    to completion throughout."""
    code = PRELUDE + """
from repro.api import RingSession
tc = TrainConfig(learning_rate=1e-3, unfreeze_interval=10**6,
                 initial_unfreeze_depth=3, n_microbatches=M,
                 batch_size=mb, seq_len=seq)
sess = RingSession.create(cfg, tc, backend="fused", n_stages=S,
                          chaos=["2:crash:1", "5:join:1"], elastic=True,
                          log=lambda *a: None)
hist = sess.run(8)
bad = None
try:
    RingSession.create(cfg, tc, backend="fused", n_stages=S,
                       chaos="1:join:7", elastic=True,
                       log=lambda *a: None).run(3)
except ValueError as e:
    bad = str(e)
print(json.dumps({
    "marks": [bool(h.get("layout_changed")) for h in hist],
    "losses": [h["loss"] for h in hist],
    "sizes": [len(h["survivors"]) for h in hist],
    "survivors": hist[-1]["survivors"],
    "spans": [list(sp) for sp in sess.backend.spans],
    "bad": bad}))
"""
    res = _run_sub(code)
    assert res["sizes"] == [4, 4, 3, 3, 3, 4, 4, 4]
    assert res["marks"] == [False, False, True, False, False,
                            True, False, False]
    assert res["survivors"] == [0, 1, 2, 3]
    assert len(res["spans"]) == 4
    assert all(math.isfinite(l) for l in res["losses"])
    # a device that never was in the fleet cannot join (the data source
    # owns exactly the original S rows)
    assert res["bad"] and "original fleet" in res["bad"], res["bad"]


def test_elastic_restore_remediation_repartitions_stale_layout():
    """The bugfix satellite: restoring a checkpoint whose span layout is
    stale for the CURRENT fleet used to leave the ring limping on the old
    spans (or force a fresh run).  With ``elastic=True`` +
    ``device_profiles``, restore loads the saved layout first (the moments
    are laid out per span) and then repartitions live to the fleet's
    Algorithm-1 layout, logging old -> new."""
    code = PRELUDE + """
import os, tempfile
from repro.api import RingSession
tc = TrainConfig(learning_rate=1e-3, unfreeze_interval=10**6,
                 initial_unfreeze_depth=3, n_microbatches=M,
                 batch_size=mb, seq_len=seq)
path = os.path.join(tempfile.mkdtemp(), "stale_ck")
sess = RingSession.create(cfg, tc, backend="fused", n_stages=S,
                          log=lambda *a: None)
saved_spans = [list(sp) for sp in sess.backend.spans]
sess.run(2)
sess.save(path)
logs = []
res = RingSession.restore(path, cfg, tc, elastic=True,
                          device_profiles=SPEEDS, log=logs.append)
spans_after = [list(sp) for sp in res.backend.spans]
losses = [h["loss"] for h in res.run(2)]
# without elastic the stale layout is kept verbatim (back-compat)
res2 = RingSession.restore(path, cfg, tc, log=lambda *a: None)
print(json.dumps({
    "saved": saved_spans, "after": spans_after, "losses": losses,
    "kept": [list(sp) for sp in res2.backend.spans],
    "log": "\\n".join(str(l) for l in logs)}))
"""
    res = _run_sub(code)
    assert res["saved"] == [[0, 4], [4, 8], [8, 11], [11, 14]]
    assert res["after"] == [[0, 4], [4, 9], [9, 11], [11, 14]]  # 4:5:2:3
    assert res["kept"] == res["saved"]                 # non-elastic: verbatim
    assert "stale" in res["log"] and "repartition" in res["log"]
    assert all(math.isfinite(l) for l in res["losses"])
