"""RingSession facade (repro/api): the pluggable-API contracts.

Pins:

  (a) session-vs-oracle equivalence — every backend reproduces the driver it
      wraps: PjitBackend matches the staged-recompile loop the seed's
      train_pjit ran (exact same ops, tight tolerance); Reference/Fused
      backends match RingTrainer/RingExecutor driven directly (and track each
      other within the cross-driver tolerances test_executor.py pins); the
      Cached backend matches the cache-disabled fused session across a
      boundary drop within test_actcache.py's tolerances,
  (b) policy protocol — every UnfreezePolicy (incl. LossPlateauPolicy under
      adversarial loss curves: rising, oscillating, NaN/inf) emits a
      monotone depth/boundary sequence; the session's runtime check rejects a
      policy that violates the contract,
  (c) checkpointing — ``checkpoint.save(..., opt_state=...)`` round-trips the
      Adam moments even with adapters_only=True, and a restored session
      continues with IDENTICAL losses for 5 steps (pjit inline; ring in a
      4-device subprocess).
"""
import json
import math
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.api import (ExplicitPolicy, IntervalPolicy, LossPlateauPolicy,
                       RingSession, resolve_policy)
from repro.configs import TrainConfig, get_config
from repro.core.unfreeze import depth_to_boundary

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_sub(code: str) -> dict:
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


# ---------------------------------------------------------------------------
# (b) policy protocol: monotone boundary under ANY loss sequence
# ---------------------------------------------------------------------------

N_BLOCKS = 8


def _adversarial_curves():
    rng = np.random.default_rng(0)
    curves = {
        "decreasing": [5.0 / (1 + 0.1 * i) for i in range(120)],
        "increasing": [1.0 + 0.1 * i for i in range(120)],
        "oscillating": [3.0 + 2.0 * math.sin(i) for i in range(120)],
        "constant": [2.0] * 120,
        "cliff_then_flat": [5.0] * 10 + [0.5] * 110,
        "nan_inf_mix": [float("nan"), float("inf"), 1.0, float("-inf"),
                        2.0, float("nan")] * 20,
    }
    for s in range(3):
        curves[f"random_{s}"] = list(rng.normal(3.0, 2.0, size=120))
    return curves


def _policies():
    return {
        "interval": IntervalPolicy(initial_depth=1, interval=7),
        "explicit": ExplicitPolicy((1, 2, 2, 5, 8), interval=9),
        "plateau_p1": LossPlateauPolicy(initial_depth=1, patience=1,
                                        min_rel_improve=1e-2),
        "plateau_p3": LossPlateauPolicy(initial_depth=2, patience=3,
                                        min_rel_improve=1e-3, smoothing=0.9),
    }


@pytest.mark.parametrize("curve_name", sorted(_adversarial_curves()))
@pytest.mark.parametrize("policy_name", sorted(_policies()))
def test_policy_monotone_boundary_property(policy_name, curve_name):
    """Depth never shrinks / boundary never rises, for every policy under
    every loss curve — the activation cache's invalidation contract."""
    cfg = get_config("stablelm-3b").reduced(n_layers=N_BLOCKS,
                                            repeats=N_BLOCKS)
    policy = _policies()[policy_name]
    losses = _adversarial_curves()[curve_name]
    prev_depth, prev_boundary = 0, cfg.repeats
    for step, loss in enumerate(losses):
        d = policy.depth_at(step, N_BLOCKS)
        b = depth_to_boundary(cfg, d)
        assert 1 <= d <= N_BLOCKS, (step, d)
        assert d >= prev_depth, f"depth shrank {prev_depth}->{d} at {step}"
        assert b <= prev_boundary, f"boundary rose {prev_boundary}->{b}"
        prev_depth, prev_boundary = d, b
        policy.observe(step, loss)


def test_plateau_policy_unfreezes_on_plateau_only():
    """Improving loss holds depth; a plateau bumps it by exactly one."""
    p = LossPlateauPolicy(initial_depth=1, patience=2, min_rel_improve=1e-2,
                          smoothing=0.0)
    for step, loss in enumerate([5.0, 4.0, 3.0, 2.0]):  # steady improvement
        p.observe(step, loss)
    assert p.depth_at(4, N_BLOCKS) == 1
    for step in range(4, 8):                            # flatline: plateau
        p.observe(step, 2.0)
    assert p.depth_at(8, N_BLOCKS) > 1


def test_explicit_policy_rejects_non_monotone():
    with pytest.raises(ValueError, match="non-monotone"):
        ExplicitPolicy((1, 3, 2))


def test_resolve_policy_names():
    tc = TrainConfig(unfreeze_interval=13)
    p = resolve_policy(None, tc)
    assert isinstance(p, IntervalPolicy) and p._sched.interval == 13
    assert isinstance(resolve_policy("plateau", tc), LossPlateauPolicy)
    with pytest.raises(ValueError, match="unknown policy"):
        resolve_policy("nope", tc)


def test_session_rejects_rising_boundary_at_runtime():
    """Defense-in-depth: a policy that breaks the contract mid-run (not at
    construction) is caught by the session's per-step check."""
    class Malicious:
        wants_loss = False

        def depth_at(self, step, n_blocks):
            return 3 if step < 2 else 1          # depth shrinks: boundary rises

        def observe(self, step, loss):
            pass

        def state(self):
            return {}

        def load_state(self, state):
            pass

    cfg = get_config("stablelm-3b").reduced(n_layers=4, repeats=4,
                                            d_model=64, d_ff=128,
                                            vocab_size=128)
    tc = TrainConfig(batch_size=2, seq_len=16)
    sess = RingSession.create(cfg, tc, backend="pjit", policy=Malicious())
    sess.step()
    sess.step()
    with pytest.raises(RuntimeError, match="monotone"):
        sess.step()


# ---------------------------------------------------------------------------
# (c) checkpoint: opt-state round-trip + identical-loss resume (pjit, inline)
# ---------------------------------------------------------------------------


def test_checkpoint_opt_state_roundtrip(tmp_path):
    """adapters_only=True used to DROP the optimizer state entirely; now it
    rides along in the opt:: namespace and restores bit-exactly."""
    import jax
    from repro.checkpoint import checkpoint as ckpt
    from repro.core import training
    from repro.models import params as prm
    from repro.optim import adamw

    cfg = get_config("stablelm-3b").reduced(n_layers=2, repeats=2,
                                            d_model=64, d_ff=128,
                                            vocab_size=128)
    params = prm.materialize(prm.param_defs(cfg), jax.random.key(0), cfg.dtype)
    opt = adamw.init(training.full_trainable(params))
    # make the moments non-trivial so the round-trip is meaningful
    opt = jax.tree.map(lambda x: x + 0.25 if x.dtype == np.float32 else x, opt)
    path = os.path.join(tmp_path, "ck")
    ckpt.save(path, params, step=3, opt_state=opt, adapters_only=True)
    back = ckpt.restore_opt(path, jax.tree.map(np.zeros_like, opt))
    for a, b in zip(jax.tree.leaves(opt), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # a checkpoint without opt state refuses to pretend it can resume
    ckpt.save(os.path.join(tmp_path, "noopt"), params, adapters_only=True)
    with pytest.raises(ValueError, match="no optimizer state"):
        ckpt.restore_opt(os.path.join(tmp_path, "noopt"), opt)


def _tiny_pjit_setup():
    cfg = get_config("stablelm-3b").reduced(n_layers=4, repeats=4,
                                            d_model=128, d_ff=256)
    tc = TrainConfig(learning_rate=1e-3, batch_size=2, seq_len=32,
                     unfreeze_interval=3)
    return cfg, tc


def test_pjit_session_resumes_with_identical_losses(tmp_path):
    """Save mid-run; the restored session's next 5 losses are IDENTICAL to
    the uninterrupted run's (params + Adam moments + policy step + data
    cursor all round-trip)."""
    cfg, tc = _tiny_pjit_setup()
    path = os.path.join(tmp_path, "ck")
    sess = RingSession.create(cfg, tc, backend="pjit")
    sess.run(4)
    sess.save(path)
    cont = [h["loss"] for h in sess.run(5)]
    restored = RingSession.restore(path, cfg, tc)
    again = [h["loss"] for h in restored.run(5)]
    assert cont == again, (cont, again)
    assert restored.step_count == sess.step_count


def test_restore_policy_mismatch_raises(tmp_path):
    cfg, tc = _tiny_pjit_setup()
    path = os.path.join(tmp_path, "ck")
    sess = RingSession.create(cfg, tc, backend="pjit")
    sess.run(1)
    sess.save(path)
    with pytest.raises(ValueError, match="policy"):
        RingSession.restore(path, cfg, tc, policy=LossPlateauPolicy())


# ---------------------------------------------------------------------------
# (a) session vs oracle: pjit (inline, 1 device)
# ---------------------------------------------------------------------------


def test_pjit_session_matches_staged_recompile_oracle():
    """The session's pjit backend reruns EXACTLY the loop the seed's
    train_pjit hand-wired: same Batcher draws, same boundary segments, same
    jitted+donated step fns — losses and params must agree to float noise."""
    import jax
    import jax.numpy as jnp
    from repro.core import training
    from repro.core.unfreeze import UnfreezeSchedule, boundary_schedule
    from repro.data.pipeline import Batcher, make_client_datasets, merged
    from repro.models import params as prm
    from repro.optim import adamw

    cfg, tc = _tiny_pjit_setup()
    steps = 8

    # --- oracle: the pre-session train_pjit loop, verbatim ---
    params = prm.materialize(prm.param_defs(cfg), jax.random.key(tc.seed),
                             cfg.dtype)
    opt_state = adamw.init(training.full_trainable(params))
    ds = merged(make_client_datasets(4, vocab=cfg.vocab_size, n_per_client=256,
                                     seq=tc.seq_len, seed=tc.seed, kind="lm"))
    batcher = Batcher(ds, tc.batch_size, seed=tc.seed)
    segs = boundary_schedule(cfg, UnfreezeSchedule.from_train_config(tc), steps)
    oracle_losses, step_fns = [], {}
    for (s0, s1, boundary) in segs:
        if boundary not in step_fns:
            step_fns[boundary] = jax.jit(
                training.make_train_step(cfg, tc, boundary),
                donate_argnums=(0, 1))
        for _ in range(s0, s1):
            params, opt_state, metrics = step_fns[boundary](
                params, opt_state, batcher.next())
            oracle_losses.append(float(metrics["loss"]))

    # --- session ---
    sess = RingSession.create(cfg, tc, backend="pjit")
    hist = sess.run(steps)
    sess_losses = [h["loss"] for h in hist]

    for ol, sl in zip(oracle_losses, sess_losses):
        assert abs(ol - sl) < 1e-6, (oracle_losses, sess_losses)
    f32 = lambda x: np.asarray(x, np.float32)
    err = max(float(np.abs(f32(a) - f32(b)).max()) for a, b in
              zip(jax.tree.leaves(params),
                  jax.tree.leaves(sess.backend.export_params())))
    assert err < 1e-5, err
    assert hist[-1]["compile_count"] == len(step_fns)


# ---------------------------------------------------------------------------
# (a) session vs oracle: ring backends (4-device subprocess)
# ---------------------------------------------------------------------------

PRELUDE = """
import json
import jax, jax.numpy as jnp
from repro.core.pipeline import make_ring_mesh
from repro.api import RingSession
from repro.configs import TrainConfig, get_config
from repro.models import params as P

cfg = get_config("stablelm-3b").reduced(n_layers=4, repeats=4,
                                        d_model=128, d_ff=256)
S, M, mb, seq = 4, 3, 1, 32

def fresh_params():
    params = P.materialize(P.param_defs(cfg), jax.random.key(0))
    ad = params["blocks"][0]["adapter"]
    ad["w_up"] = 0.02 * jax.random.normal(jax.random.key(9), ad["w_up"].shape,
                                          jnp.float32).astype(ad["w_up"].dtype)
    return params

def slot_batch(k, seq_=seq):
    t = jax.random.randint(jax.random.key(10 + k), (S, M, mb, seq_), 0,
                           cfg.vocab_size)
    l = jax.random.randint(jax.random.key(20 + k), (S, M, mb, seq_), 0,
                           cfg.vocab_size)
    return t, l

f32 = lambda x: x.astype(jnp.float32)
maxerr = lambda a, b: max(jax.tree.leaves(jax.tree.map(
    lambda x, y: float(jnp.abs(f32(x) - f32(y)).max()), a, b)))
"""


def test_ring_backends_match_direct_drivers():
    """ReferenceBackend == RingTrainer and FusedBackend == RingExecutor when
    driven on identical batches across a boundary bump; the two backends
    track each other within the cross-driver tolerances test_executor pins."""
    code = PRELUDE + """
from repro.core.ring import RingTrainer
from repro.core.executor import RingExecutor

mesh = make_ring_mesh(4)
tc = TrainConfig(learning_rate=1e-3, unfreeze_interval=S, n_microbatches=M,
                 batch_size=mb, seq_len=seq)
tokens, labels = slot_batch(0)
out = {k: [] for k in ("drv_ref", "ses_ref", "drv_fused", "ses_fused", "b")}
with jax.set_mesh(mesh):
    drv_ref = RingTrainer(cfg, tc, mesh, fresh_params(), S, M)
    drv_fused = RingExecutor(cfg, tc, mesh, fresh_params(), S, M)
    ses_ref = RingSession.create(cfg, tc, backend="reference", n_stages=S,
                                 params=fresh_params())
    ses_fused = RingSession.create(cfg, tc, backend="fused", n_stages=S,
                                   params=fresh_params())
    for r in range(3):
        mr = drv_ref.round(tokens, labels)
        mf = RingExecutor.materialize_metrics(drv_fused.round(tokens, labels))
        sr = ses_ref.step((tokens, labels)).materialize()
        sf = ses_fused.step((tokens, labels)).materialize()
        out["drv_ref"].append(mr["loss"]); out["ses_ref"].append(sr.loss)
        out["drv_fused"].append(mf["loss"]); out["ses_fused"].append(sf.loss)
        assert mr["boundary"] == sr.boundary == mf["boundary"] == sf.boundary
        out["b"].append(sr.boundary)
    out["ref_param_err"] = maxerr(drv_ref.export_params(),
                                  ses_ref.backend.export_params())
    out["fused_param_err"] = maxerr(drv_fused.export_params(),
                                    ses_fused.backend.export_params())
    out["cross_param_err"] = maxerr(ses_ref.backend.export_params(),
                                    ses_fused.backend.export_params())
    out["ses_fused_compiles"] = ses_fused.backend.compile_count
    out["ses_ref_compiles"] = ses_ref.backend.compile_count
print(json.dumps(out))
"""
    res = _run_sub(code)
    assert res["b"] == [3, 2, 1]
    # same driver under the session facade: agreement to float noise
    for dr, sr in zip(res["drv_ref"], res["ses_ref"]):
        assert abs(dr - sr) < 1e-6, (res["drv_ref"], res["ses_ref"])
    for df, sf in zip(res["drv_fused"], res["ses_fused"]):
        assert abs(df - sf) < 1e-6, (res["drv_fused"], res["ses_fused"])
    assert res["ref_param_err"] < 1e-5
    assert res["fused_param_err"] < 1e-5
    # cross-driver: the tolerances test_executor.py pins (bf16 params,
    # different reduce orders)
    for sr, sf in zip(res["ses_ref"], res["ses_fused"]):
        assert abs(sr - sf) < 2e-2
    assert res["cross_param_err"] < 5e-2
    # compile counts surface through the facade: 1 per boundary fused,
    # S per boundary reference
    assert res["ses_fused_compiles"] == 3
    assert res["ses_ref_compiles"] == 3 * 4


def test_cached_session_matches_fused_across_boundary_drop():
    """CachedBackend == FusedBackend on identical slotted data, INCLUDING
    across boundary drops (invalidate + re-capture, never stale activations)
    — test_actcache.py's tolerances, through the facade."""
    code = PRELUDE + """
tc = TrainConfig(learning_rate=1e-3, unfreeze_interval=4 * S, n_microbatches=M,
                 batch_size=mb, seq_len=seq)
batches = [slot_batch(0), slot_batch(1)]
out = {"plain": [], "cached": [], "hit": [], "b": []}
plain = RingSession.create(cfg, tc, backend="fused", n_stages=S,
                           params=fresh_params())
drv = RingSession.create(cfg, tc, backend="cached", n_stages=S,
                         slots_per_epoch=2, params=fresh_params())
for r in range(12):
    slot = r % 2
    t, l = batches[slot]
    mp = plain.step((slot, t, l)).materialize()
    mc = drv.step((slot, t, l)).materialize()
    out["plain"].append(mp.loss)
    out["cached"].append(mc.loss)
    out["hit"].append(mc.cache_hit)
    out["b"].append(mc.boundary)
    assert mp.boundary == mc.boundary
out["param_err"] = maxerr(plain.export_params(), drv.export_params())
out["stats"] = drv.backend.driver.cache.stats()
print(json.dumps(out))
"""
    res = _run_sub(code)
    assert res["b"] == [3] * 4 + [2] * 4 + [1] * 4
    assert res["hit"] == [False, False, True, True] * 3
    for pl, cl in zip(res["plain"], res["cached"]):
        assert abs(pl - cl) < 1e-5, (res["plain"], res["cached"])
    assert res["param_err"] < 1e-3
    st = res["stats"]
    assert st["cache_hits"] == 6 and st["cache_misses"] == 6
    assert st["cache_invalidations"] == 2


def test_ring_session_resumes_with_identical_losses(tmp_path):
    """The --save/--resume bugfix, pinned end-to-end: a fused ring session
    saved mid-run and restored continues with IDENTICAL losses (params +
    stage-stacked Adam moments + policy step + data cursor round-trip)."""
    code = PRELUDE + f"""
import os
tc = TrainConfig(learning_rate=1e-3, unfreeze_interval=2 * S, n_microbatches=M,
                 batch_size=mb, seq_len=seq)
path = os.path.join({str(tmp_path)!r}, "ring_ck")
sess = RingSession.create(cfg, tc, backend="fused", n_stages=S)
sess.run(2)
sess.save(path)
cont = [h["loss"] for h in sess.run(5)]
restored = RingSession.restore(path, cfg, tc)
again = [h["loss"] for h in restored.run(5)]
bad_restore = None
try:
    RingSession.restore(path, cfg, tc, backend="pjit")
except ValueError as e:
    bad_restore = str(e)
print(json.dumps({{"cont": cont, "again": again, "bad": bad_restore,
                   "step": restored.step_count}}))
"""
    res = _run_sub(code)
    assert res["cont"] == res["again"], (res["cont"], res["again"])
    assert res["step"] == 7 * 4
    assert res["bad"] and "format" in res["bad"]
