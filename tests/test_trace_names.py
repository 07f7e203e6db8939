"""The program names its work in a profiler trace (``repro/scopes.py``).

Device scopes reach the compiled executables' ``op_name`` metadata: the pjit
step and the fused round (S=1 here, S=2 in a host-device subprocess) carry
every phase scope, the hot blocks' backward reads
``transpose(jvp(ringada.hot))``, no matrix product is left outside a
phase, and the scopes change nothing but metadata.  Host spans land in the profiler's trace: ``ringada.round`` holds
``ringada.data`` then ``ringada.dispatch``, and the host sync of
``RoundMetrics`` is ``ringada.sync``.
"""
import contextlib
import json
import os
import re
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro import scopes
from repro.api import RingSession
from repro.configs import TrainConfig, get_config
from repro.core import training
from repro.core.executor import make_fused_round, ring_opt_init
from repro.core.pipeline import make_ring_mesh, stage_stack
from repro.models import params as prm
from repro.optim import adamw

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PHASE = re.compile("|".join(re.escape(p) for p in scopes.PHASES))


def _cfg(n_blocks: int = 4):
    return get_config("stablelm-3b").reduced(n_layers=n_blocks,
                                             repeats=n_blocks, d_model=128,
                                             d_ff=256)


def op_names(hlo_text: str):
    return re.findall(r'op_name="([^"]*)"', hlo_text)


def check_scopes(names, *, trunk_blocks: bool):
    """Every phase and the attention tag appear, the hot backward keeps its
    phase, and every matrix product lies inside some phase."""
    joined = "\n".join(names)
    for scope in scopes.PHASES + (scopes.ATTENTION,):
        assert scope in joined, scope
    assert f"transpose(jvp({scopes.HOT}))" in joined
    dots = [n for n in names if n.endswith("dot_general")]
    assert dots and all(PHASE.search(n) for n in dots), [
        n for n in dots if not PHASE.search(n)]
    attn = [n for n in names if scopes.ATTENTION in n]
    trunk_attn = [n for n in attn if scopes.TRUNK in n]
    assert bool(trunk_attn) == trunk_blocks, trunk_attn


def canonical_hlo(text: str):
    """Optimized HLO without its metadata and stack-frame tables, with
    instruction and computation names renumbered by first appearance."""
    text = re.sub(r", metadata=\{[^}]*\}", "", text)
    lines = text.splitlines()
    start = next(i for i, ln in enumerate(lines)
                 if ln.startswith(("%", "ENTRY")))
    names = {}
    body = re.sub(r"%[A-Za-z_][\w.\-]*",
                  lambda m: names.setdefault(m.group(0), f"%n{len(names)}"),
                  "\n".join(lines[start:]))
    return body.splitlines()


class _NoScope(contextlib.ContextDecorator):
    def __init__(self, name):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def _pjit_hlo(boundary: int = 2):
    cfg = _cfg()
    tc = TrainConfig(batch_size=2, seq_len=32)
    params = prm.materialize(prm.param_defs(cfg), jax.random.key(0))
    opt = adamw.init(training.full_trainable(params))
    batch = {k: jnp.zeros((2, 32), jnp.int32) for k in ("tokens", "labels")}
    step = jax.jit(training.make_train_step(cfg, tc, boundary))
    return step.lower(params, opt, batch).compile().as_text()


def _fused_hlo():
    cfg = _cfg()
    mesh = make_ring_mesh(1)
    params = prm.materialize(prm.param_defs(cfg), jax.random.key(0))
    blocks, shared = stage_stack(params, cfg, 1)
    tokens = jnp.zeros((1, 2, 1, 32), jnp.int32)
    tc = TrainConfig(n_microbatches=2, batch_size=1, seq_len=32)
    fn = make_fused_round(cfg, tc, mesh, n_stages=1, boundary=0, n_micro=2)
    with jax.set_mesh(mesh):
        return jax.jit(fn).lower(blocks, shared,
                                 ring_opt_init(blocks, shared), tokens,
                                 tokens).compile().as_text()


@pytest.mark.parametrize("boundary", [0, 2])
def test_pjit_step_carries_phase_scopes(boundary):
    check_scopes(op_names(_pjit_hlo(boundary)), trunk_blocks=boundary > 0)


def test_fused_round_one_stage_carries_phase_scopes():
    names = op_names(_fused_hlo())
    check_scopes(names, trunk_blocks=False)
    # the embedding gather is the trunk at F = 0
    assert any(scopes.TRUNK in n for n in names)


@pytest.mark.parametrize("build", [_pjit_hlo, _fused_hlo],
                         ids=["pjit", "fused"])
def test_scopes_change_only_metadata(build, monkeypatch):
    scoped = build()
    monkeypatch.setattr(jax, "named_scope", _NoScope)
    plain = build()
    assert scopes.HOT in scoped and scopes.HOT not in plain
    assert canonical_hlo(scoped) == canonical_hlo(plain)


def test_fused_round_two_stages_carries_phase_scopes():
    """S=2 at boundary 2 of 4 blocks: stage 0 frozen (F=1), so the trunk runs
    blocks on the ring while stage 1 trains."""
    code = f"""
import json, re, sys
sys.path.insert(0, {os.path.join(ROOT, "tests")!r})
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding
from test_trace_names import _cfg, op_names, check_scopes
from repro.configs import TrainConfig
from repro.core.executor import make_fused_round, ring_opt_init, ring_opt_specs
from repro.core.pipeline import make_ring_mesh, stage_stack, stage_shardings
from repro.models import params as prm

cfg = _cfg()
S, M, mb, seq = 2, 2, 1, 32
tc = TrainConfig(n_microbatches=M, batch_size=mb, seq_len=seq)
mesh = make_ring_mesh(S)
stage, rep = stage_shardings(mesh)
params = prm.materialize(prm.param_defs(cfg), jax.random.key(0))
blocks, shared = stage_stack(params, cfg, S)
blocks, shared = jax.device_put(blocks, stage), jax.device_put(shared, rep)
opt = jax.device_put(ring_opt_init(blocks, shared), jax.tree.map(
    lambda s: NamedSharding(mesh, s), ring_opt_specs()))
tokens = jax.device_put(jnp.zeros((S, M, mb, seq), jnp.int32), stage)
fn = make_fused_round(cfg, tc, mesh, n_stages=S, boundary=2, n_micro=M)
with jax.set_mesh(mesh):
    text = jax.jit(fn).lower(blocks, shared, opt, tokens,
                             tokens).compile().as_text()
check_scopes(op_names(text), trunk_blocks=True)
print(json.dumps({{"ok": True}}))
"""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert json.loads(out.stdout.strip().splitlines()[-1]) == {"ok": True}


def _host_spans(trace_dir):
    from jax.profiler import ProfileData

    path = next(Path(trace_dir).rglob("*.xplane.pb"))
    spans = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(ev.name, ev.start_ns, ev.end_ns)
                          for ev in line.events
                          if ev.name.startswith("ringada.")]
    return sorted(spans, key=lambda s: s[1])


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_session_spans_nest_on_the_trace_clock(tmp_path):
    cfg = _cfg(2)
    tc = TrainConfig(batch_size=2, seq_len=16)
    sess = RingSession.create(cfg, tc, backend="pjit")
    sess.step().materialize()                   # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(3):
            sess.step().materialize()
        sess.run(2)
    spans = _host_spans(tmp_path)
    by = {n: [s for s in spans if s[0] == n]
          for n in (scopes.ROUND, scopes.DATA, scopes.DISPATCH, scopes.SYNC)}
    assert [len(v) for v in by.values()] == [5, 5, 5, 5]
    for rnd, data, disp in zip(by[scopes.ROUND], by[scopes.DATA],
                               by[scopes.DISPATCH]):
        assert _inside(data, rnd) and _inside(disp, rnd)
        assert data[2] <= disp[1]
    # each round's metrics are synced after its round span, before the next
    rounds = by[scopes.ROUND]
    for k, sync in enumerate(by[scopes.SYNC][:3]):
        assert rounds[k][2] <= sync[1]
        assert k + 1 == len(rounds) or sync[2] <= rounds[k + 1][1]
