"""Batched serving driver: prefill + decode with KV caches.

Implements a simple synchronous continuous-batching server loop: requests are
padded into fixed batch slots, prefilled once, then decoded step-by-step; finished
slots are refilled from the queue. Serves any registered arch (reduced variants on
CPU).

Multi-tenant adapter hot-swap (S-LoRA style): with ``--adapter-store DIR``
pointing at an :class:`repro.api.tenants.AdapterStore`, each request may carry
a tenant id (a store entry name).  ONE shared trunk stays resident; the
:class:`AdapterRegistry` grafts each tenant's trained adapter+head bundle into
the base tree (same shapes, so the jitted prefill/decode executables are
reused across tenants — zero recompiles on swap), the batcher groups each
batch by tenant, and the registry re-checks store mtimes between batches: a
bundle a training session just ``save_to``'d is servable on the very next
batch, no restart.

Usage:
    PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b --reduced \
        --requests 8 --max-new 16 [--adapter-store ckpt/adapters]
"""
from __future__ import annotations

import argparse
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.launch.compile_cache import use_compile_cache
from repro.models import params as prm
from repro.models import transformer as tfm


@dataclass
class Request:
    rid: int
    prompt: np.ndarray                 # [L] int32
    max_new: int
    tenant: Optional[str] = None       # AdapterStore entry name; None = trunk
    out: List[int] = field(default_factory=list)
    done: bool = False


class AdapterRegistry:
    """Per-tenant merged param trees over one shared trunk.

    ``params_for(tenant)`` grafts the tenant's ``{"adapter", "head"}`` bundle
    from the store into the base canonical tree — the graft only swaps
    leaves, never shapes, so every tenant runs through the SAME jitted
    executables.  ``refresh()`` reloads any entry whose payload mtime moved
    (the hot-swap hook: a freshly trained bundle is picked up between
    batches) and returns the names it swapped in.
    """

    def __init__(self, base_params: Dict[str, Any], store):
        self.base = base_params
        self.store = store
        self._like = {"adapter": base_params["blocks"][0]["adapter"],
                      "head": base_params["head"]}
        self._merged: Dict[str, Dict[str, Any]] = {}
        self._mtimes: Dict[str, float] = {}

    def refresh(self) -> List[str]:
        swapped = []
        for name in self.store.names():
            mt = self.store.mtime(name)
            if self._mtimes.get(name) == mt:
                continue
            bundle, _ = self.store.get(name, self._like)
            entry = {**self.base["blocks"][0], "adapter": bundle["adapter"]}
            self._merged[name] = {**self.base, "head": bundle["head"],
                                  "blocks": (entry,)}
            self._mtimes[name] = mt
            swapped.append(name)
        return swapped

    def tenants(self) -> List[str]:
        return sorted(self._merged)

    def params_for(self, tenant: Optional[str]) -> Dict[str, Any]:
        if tenant is None:
            return self.base
        if tenant not in self._merged:
            self.refresh()
        if tenant not in self._merged:
            raise KeyError(
                f"unknown tenant {tenant!r}: store has {self.tenants()}")
        return self._merged[tenant]


class BatchServer:
    """Fixed-slot synchronous batcher (one shared KV cache, per-slot positions).

    With a ``registry`` each batch is tenant-homogeneous: the queue is
    consumed in arrival order, but one batch only packs requests that share
    the head request's tenant (the trunk counts as a tenant of its own), and
    the registry's mtime watch runs between batches so hot-swapped adapters
    take effect on the next batch.
    """

    def __init__(self, cfg, params, *, slots: int, horizon: int,
                 impl: str = "jnp", registry: Optional[AdapterRegistry] = None):
        self.cfg, self.params = cfg, params
        self.registry = registry
        self.slots, self.horizon = slots, horizon
        mem = None
        if cfg.frontend or cfg.enc_dec:
            mem = jnp.zeros((1, cfg.n_frontend_tokens or 16, cfg.d_model),
                            jnp.bfloat16)
        self._memory = mem
        self.prefill = jax.jit(
            lambda p, t, m=None: tfm.prefill(p, t, cfg, memory=m,
                                             seq_len=horizon, impl=impl))
        self.decode = jax.jit(
            lambda p, t, c: tfm.decode_step(p, t, c, cfg, impl=impl),
            donate_argnums=(2,))

    def run(self, requests: List[Request], log=print) -> Dict[int, List[int]]:
        queue = list(requests)
        t0 = time.time()
        decoded_tokens = 0
        results: Dict[int, List[int]] = {}
        while queue:
            if self.registry is not None:
                for name in self.registry.refresh():    # hot-swap point
                    log(f"adapter hot-swap: reloaded {name!r}")
                tenant = queue[0].tenant
                batch = [r for r in queue
                         if r.tenant == tenant][: self.slots]
                taken = {id(r) for r in batch}
                queue = [r for r in queue if id(r) not in taken]
                params = self.registry.params_for(tenant)
            else:
                batch = queue[: self.slots]
                queue = queue[self.slots:]
                params = self.params
            L = max(len(r.prompt) for r in batch)
            toks = np.zeros((len(batch), L), np.int32)
            for i, r in enumerate(batch):
                toks[i, L - len(r.prompt):] = r.prompt     # left-pad
            mem = (jnp.broadcast_to(self._memory,
                                    (len(batch),) + self._memory.shape[1:])
                   if self._memory is not None else None)
            args = (params, jnp.asarray(toks)) + (
                (mem,) if mem is not None else ())
            logits, cache = self.prefill(*args)
            cur = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
            max_new = max(r.max_new for r in batch)
            outs = [cur]
            for _ in range(max_new - 1):
                logits, cache = self.decode(params, cur, cache)
                cur = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
                outs.append(cur)
                decoded_tokens += len(batch)
            gen = np.concatenate([np.asarray(o) for o in outs], axis=1)
            for i, r in enumerate(batch):
                results[r.rid] = gen[i, : r.max_new].tolist()
        dt = time.time() - t0
        log(f"served {len(requests)} requests, "
            f"{decoded_tokens} decode steps in {dt:.2f}s "
            f"({decoded_tokens / max(dt, 1e-9):.1f} tok/s)")
        return results


def main() -> None:
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--layers", type=int, default=None,
                    help="override the block count (applied after --reduced; "
                         "match the training run when serving its adapters)")
    ap.add_argument("--adapter-store", default=None,
                    help="AdapterStore directory of trained per-tenant "
                         "bundles; requests round-robin over the entries "
                         "(plus the bare trunk) and each batch serves its "
                         "tenant's grafted params — hot-swapped on mtime "
                         "change, no restart")
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.layers:
        import dataclasses
        cfg = dataclasses.replace(cfg, n_layers=args.layers,
                                  repeats=args.layers // cfg.layers_per_repeat)
    params = prm.materialize(prm.param_defs(cfg), jax.random.key(0), cfg.dtype)
    registry = None
    tenant_cycle: List[Optional[str]] = [None]
    if args.adapter_store:
        from repro.api.tenants import AdapterStore

        registry = AdapterRegistry(params, AdapterStore(args.adapter_store))
        names = registry.refresh()
        print(f"adapter store: serving trunk + {len(names)} tenants {names}")
        tenant_cycle = [None] + list(names)
    rng = np.random.default_rng(0)
    reqs = [Request(i, rng.integers(0, cfg.vocab_size,
                                    size=rng.integers(4, args.prompt_len + 1)
                                    ).astype(np.int32), args.max_new,
                    tenant=tenant_cycle[i % len(tenant_cycle)])
            for i in range(args.requests)]
    server = BatchServer(cfg, params, slots=args.slots,
                         horizon=args.prompt_len + args.max_new + 8,
                         registry=registry)
    results = server.run(reqs)
    print({k: v[:8] for k, v in list(results.items())[:4]})


if __name__ == "__main__":
    main()
