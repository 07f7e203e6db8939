"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jax.Array


def _act(name: str):
    return {"gelu": jax.nn.gelu, "relu": jax.nn.relu, "silu": jax.nn.silu}[name]


def adapter_fused(h: Array, w_down: Array, w_up: Array, *,
                  activation: str = "gelu") -> Array:
    """h [..., D]; eq. (1): h + act(h @ Wd) @ Wu, fp32 internals."""
    hf = h.astype(jnp.float32)
    mid = _act(activation)(hf @ w_down.astype(jnp.float32))
    return h + (mid @ w_up.astype(jnp.float32)).astype(h.dtype)


def rwkv_scan(r: Array, k: Array, v: Array, lw: Array, u: Array,
              state0: Array):
    """Sequential RWKV-6 wkv recurrence (the definitional oracle).

    r,k,v,lw: [N, S, hd] fp32 (lw = log decay <= 0); u: [N, 1, hd];
    state0: [N, hd, hd]. Returns (out [N, S, hd], state [N, hd, hd]).

        out_t = r_t (S_{t-1} + u o k_t v_t^T);  S_t = w_t o S_{t-1} + k_t v_t^T
    """
    def step(s, xs):
        rt, kt, vt, lwt = xs
        kv = jnp.einsum("nk,nv->nkv", kt, vt)
        out = jnp.einsum("nk,nkv->nv", rt, s + u[:, 0, :, None] * kv)
        s2 = jnp.exp(lwt)[:, :, None] * s + kv
        return s2, out

    state, outs = jax.lax.scan(
        step, state0,
        (r.swapaxes(0, 1), k.swapaxes(0, 1), v.swapaxes(0, 1),
         lw.swapaxes(0, 1)))
    return outs.swapaxes(0, 1), state


def flash_attention(q: Array, k: Array, v: Array, *, causal: bool = True,
                    window: int | None = None) -> Array:
    """q [B, H, Sq, hd]; k,v [B, K, Sk, hd], H a multiple of K. fp32 softmax."""
    Sq, Sk = q.shape[2], k.shape[2]
    group = q.shape[1] // k.shape[1]
    k, v = (jnp.repeat(t, group, axis=1) for t in (k, v))
    s = jnp.einsum("bnqh,bnkh->bnqk", q, k,
                   preferred_element_type=jnp.float32)
    s = s / jnp.sqrt(jnp.float32(q.shape[-1]))
    qi = jnp.arange(Sq)[:, None] + (Sk - Sq)    # align last query with last key
    ki = jnp.arange(Sk)[None, :]
    m = jnp.ones((Sq, Sk), bool)
    if causal:
        m &= ki <= qi
    if window is not None:
        m &= (qi - ki) < window
    s = jnp.where(m, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(m, p, 0.0)
    return jnp.einsum("bnqk,bnkh->bnqh", p.astype(v.dtype), v)


def mamba_scan(log_a: Array, b: Array, c: Array):
    """Sequential selective-SSM oracle. log_a, b: [B,S,D,N]; c: [B,S,N].

        s_t = exp(log_a_t) * s_{t-1} + b_t ;  y_t = sum_N s_t * c_t
    """
    B, S, D, N = log_a.shape

    def step(s, xs):
        la_t, b_t, c_t = xs
        s2 = jnp.exp(la_t) * s + b_t
        y = jnp.einsum("bdn,bn->bd", s2, c_t)
        return s2, y

    s0 = jnp.zeros((B, D, N), jnp.float32)
    sT, ys = jax.lax.scan(step, s0, (log_a.swapaxes(0, 1), b.swapaxes(0, 1),
                                     c.swapaxes(0, 1)))
    return ys.swapaxes(0, 1), sT
