"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, interpret=True."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels import adapter_fused as afk
from repro.kernels import flash_attention as fak
from repro.kernels import rwkv_scan as rsk


@pytest.mark.parametrize("T,D,m", [(128, 128, 32), (256, 512, 64),
                                   (300, 256, 48), (64, 1024, 16)])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("act", ["gelu", "relu", "silu"])
def test_adapter_fused_sweep(T, D, m, dtype, act):
    if act != "gelu" and (T, D, m) != (256, 512, 64):
        pytest.skip("activation sweep on one shape only")
    key = jax.random.key(0)
    h = jax.random.normal(key, (T, D), dtype)
    wd = 0.05 * jax.random.normal(jax.random.key(1), (D, m), jnp.float32)
    wu = 0.05 * jax.random.normal(jax.random.key(2), (m, D), jnp.float32)
    got = afk.adapter_fused(h, wd, wu, activation=act, interpret=True)
    want = ref.adapter_fused(h, wd, wu, activation=act)
    atol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=atol)


@pytest.mark.parametrize("N,S,hd,chunk", [(2, 32, 16, 8), (4, 64, 32, 32),
                                          (1, 96, 64, 32), (3, 40, 8, 16)])
def test_rwkv_scan_sweep(N, S, hd, chunk):
    keys = jax.random.split(jax.random.key(0), 6)
    r, k, v = (jax.random.normal(keys[i], (N, S, hd), jnp.float32)
               for i in range(3))
    lw = -jnp.exp(0.5 * jax.random.normal(keys[3], (N, S, hd)) - 1.0)
    u = 0.5 * jax.random.normal(keys[4], (N, 1, hd))
    s0 = 0.1 * jax.random.normal(keys[5], (N, hd, hd))
    got, gT = rsk.rwkv_scan(r, k, v, lw, u, s0, chunk=chunk, interpret=True)
    want, wT = ref.rwkv_scan(r, k, v, lw, u, s0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-3, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(gT), np.asarray(wT),
                               atol=1e-3, rtol=1e-3)


def test_rwkv_scan_state_chaining():
    """Two half-sequences with state carry == one full sequence."""
    N, S, hd = 2, 64, 16
    keys = jax.random.split(jax.random.key(1), 6)
    r, k, v = (jax.random.normal(keys[i], (N, S, hd), jnp.float32)
               for i in range(3))
    lw = -jnp.exp(0.5 * jax.random.normal(keys[3], (N, S, hd)) - 1.0)
    u = 0.5 * jax.random.normal(keys[4], (N, 1, hd))
    s0 = jnp.zeros((N, hd, hd))
    full, sT = rsk.rwkv_scan(r, k, v, lw, u, s0, chunk=16, interpret=True)
    h1, s1 = rsk.rwkv_scan(r[:, :32], k[:, :32], v[:, :32], lw[:, :32], u, s0,
                           chunk=16, interpret=True)
    h2, s2 = rsk.rwkv_scan(r[:, 32:], k[:, 32:], v[:, 32:], lw[:, 32:], u, s1,
                           chunk=16, interpret=True)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([h1, h2], 1)),
                               np.asarray(full), atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(sT),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("Sq,Sk,hd,group,window", [
    (128, 128, 64, 1, None),
    (128, 128, 64, 4, None),
    (256, 256, 32, 2, 64),
    (128, 256, 64, 1, None),          # decode-ish: fewer queries than keys
    (128, 128, 128, 8, 32),
])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_flash_attention_sweep(Sq, Sk, hd, group, window, dtype):
    B, Nk = 2, 2
    Nq = Nk * group
    q = jax.random.normal(jax.random.key(0), (B, Nq, Sq, hd), dtype)
    k = jax.random.normal(jax.random.key(1), (B, Nk, Sk, hd), dtype)
    v = jax.random.normal(jax.random.key(2), (B, Nk, Sk, hd), dtype)
    got = fak.flash_attention(q, k, v, window=window, block_q=64, block_k=64,
                              interpret=True)
    want = ref.flash_attention(q, k, v, window=window)
    atol = 3e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=atol)


@pytest.mark.parametrize("S,hd,group,window,block", [
    (256, 80, 1, None, 64),       # causal: 4 key blocks a row, 6 skipped
    (256, 128, 1, None, 64),      # the MXU's width next to the model's 80
    (256, 80, 2, None, 64),       # GQA: two query heads per K/V head
    (256, 80, 1, 96, 64),         # a window: blocks below it skipped too
    (512, 80, 1, None, 128),
])
def test_flash_attention_matches_attend(S, hd, group, window, block):
    """The kernel against the model's own ``_attend`` on bf16 inputs, each
    side against float32 attention on the same inputs.

    Each side rounds twice to bf16 (8 significant bits, so at most 2^-8
    relative each): the probabilities that meet v, which moves an output by
    at most 2^-8 max|v|, and the output itself, at most 2^-8 max|v| again.
    So each lies within 2^-7 max|v| of float32, and the two within 2^-6."""
    from repro.models.blocks import _attend
    B, K = 2, 2
    H = K * group
    keys = jax.random.split(jax.random.key(3), 3)
    q = jax.random.normal(keys[0], (B, S, H, hd), jnp.bfloat16)
    k = jax.random.normal(keys[1], (B, S, K, hd), jnp.bfloat16)
    v = jax.random.normal(keys[2], (B, S, K, hd), jnp.bfloat16)
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    heads = lambda t: t.transpose(0, 2, 1, 3)
    got = heads(fak.flash_attention(heads(q), heads(k), heads(v),
                                    window=window, block_q=block,
                                    block_k=block, interpret=True))
    plain = _attend(q, k, v, pos, pos, causal=True, window=window, n_sink=0,
                    q_chunk=S // 2)
    f32 = _attend(*(t.astype(jnp.float32) for t in (q, k, v)), pos, pos,
                  causal=True, window=window, n_sink=0, q_chunk=S // 2)
    vmax = float(jnp.max(jnp.abs(v.astype(jnp.float32))))
    got, plain, f32 = (np.asarray(t, np.float32) for t in (got, plain, f32))
    assert np.abs(got - f32).max() <= 2.0 ** -7 * vmax
    assert np.abs(plain - f32).max() <= 2.0 ** -7 * vmax
    assert np.abs(got - plain).max() <= 2.0 ** -6 * vmax


def test_ops_wrappers_jit():
    h = jax.random.normal(jax.random.key(0), (4, 8, 64), jnp.bfloat16)
    wd = 0.1 * jax.random.normal(jax.random.key(1), (64, 16), jnp.float32)
    wu = 0.1 * jax.random.normal(jax.random.key(2), (16, 64), jnp.float32)
    out = ops.adapter_fused(h, wd, wu)         # leading dims flattened inside
    assert out.shape == h.shape
    want = ref.adapter_fused(h.reshape(-1, 64), wd, wu).reshape(h.shape)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=2e-2)


def test_model_uses_pallas_adapter_consistently():
    """impl='pallas' must match impl='jnp' end-to-end on a block stack."""
    from repro.configs import get_config
    from repro.models import params as prm
    from repro.models import transformer as tfm
    cfg = get_config("rwkv6-7b").reduced()
    params = prm.materialize(prm.param_defs(cfg), jax.random.key(0), cfg.dtype)
    tokens = jax.random.randint(jax.random.key(1), (1, 32), 0, cfg.vocab_size)
    a, _ = tfm.forward(params, tokens, cfg, impl="jnp")
    b, _ = tfm.forward(params, tokens, cfg, impl="pallas")
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), atol=5e-2)


@pytest.mark.parametrize("B,S,D,N,chunk", [(2, 32, 8, 4, 8), (1, 64, 16, 8, 16),
                                           (3, 48, 4, 16, 16)])
def test_mamba_scan_sweep(B, S, D, N, chunk):
    from repro.kernels import mamba_scan as msk
    keys = jax.random.split(jax.random.key(0), 3)
    log_a = -jnp.exp(0.5 * jax.random.normal(keys[0], (B, S, D, N)) - 1.0)
    b = jax.random.normal(keys[1], (B, S, D, N), jnp.float32) * 0.5
    c = jax.random.normal(keys[2], (B, S, N), jnp.float32)
    got_y, got_s = msk.mamba_scan(log_a, b, c, chunk=chunk, interpret=True)
    want_y, want_s = ref.mamba_scan(log_a, b, c)
    np.testing.assert_allclose(np.asarray(got_y), np.asarray(want_y),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s),
                               atol=1e-4, rtol=1e-4)
