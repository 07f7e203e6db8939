"""jit'd public wrappers for the Pallas kernels.

On CPU (this container) the kernels run with ``interpret=True`` — the kernel body
executes in Python for correctness validation; on TPU they lower to Mosaic.
"""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels import adapter_fused as _af
from repro.kernels import flash_attention as _fa
from repro.kernels import rwkv_scan as _rs


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


@partial(jax.jit, static_argnames=("activation",))
def adapter_fused(h: jax.Array, w_down: jax.Array, w_up: jax.Array, *,
                  activation: str = "gelu") -> jax.Array:
    """h [..., D] — leading dims flattened for the kernel and restored."""
    shape = h.shape
    h2 = h.reshape(-1, shape[-1])
    out = _af.adapter_fused(h2, w_down, w_up, activation=activation,
                            interpret=_interpret())
    return out.reshape(shape)


@jax.jit
def rwkv_scan(r, k, v, lw, u, state0):
    return _rs.rwkv_scan(r, k, v, lw, u, state0, interpret=_interpret())


@partial(jax.jit, static_argnames=("causal", "window"))
def flash_attention(q, k, v, *, causal: bool = True, window=None):
    """q [B, H, Sq, hd]; k, v [B, K, Sk, hd] (GQA when K < H)."""
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               interpret=_interpret())


@jax.jit
def mamba_scan(log_a, b, c):
    from repro.kernels import mamba_scan as _ms

    return _ms.mamba_scan(log_a, b, c, interpret=_interpret())
