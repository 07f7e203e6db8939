import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x input-shape x mesh) combo.

Proves the distribution config is coherent without hardware: ShapeDtypeStruct
inputs, pjit with explicit in/out shardings, ``.lower().compile()`` must succeed;
``memory_analysis()`` proves per-chip fit, ``cost_analysis()`` + the optimized HLO
feed the roofline (EXPERIMENTS.md §Dry-run / §Roofline).

The two lines above MUST stay the first statements in this file: jax locks the
device count at first init, and the production meshes need 512 host devices.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch starcoder2-7b \
        --shape train_4k [--multi-pod] [--boundary N] [--out DIR]
    PYTHONPATH=src python -m repro.launch.dryrun --all [--out DIR]
"""
import argparse
import json
import time
import traceback
from typing import Any, Dict

import jax

from repro import roofline as rl
from repro.configs import INPUT_SHAPES, ASSIGNED, TrainConfig, get_config, shape_runnable
from repro.core import training
from repro.launch import inputs as inp
from repro.launch.mesh import make_production_mesh


def lower_combo(arch: str, shape_name: str, *, multi_pod: bool,
                boundary: int = 0, remat: bool = True,
                keep_hlo: bool = False) -> Dict[str, Any]:
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    rec: Dict[str, Any] = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                           "boundary": boundary, "status": "ok"}

    ok, reason = shape_runnable(cfg, shape)
    if not ok:
        rec.update(status="skip", reason=reason)
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.size
    moe_groups = 32 if multi_pod else 16          # = data-parallel shards
    pspecs = inp.param_specs(cfg, mesh)
    aparams = inp.abstract_params(cfg)
    aspec = inp.act_spec(cfg, shape, mesh)
    tc = TrainConfig()

    t0 = time.time()
    with jax.set_mesh(mesh):
        if shape.kind == "train":
            batch, bspecs = inp.train_inputs(cfg, shape, mesh)
            ospecs = inp.opt_state_specs(cfg, mesh)
            ostate = inp.abstract_opt_state(cfg)
            step = training.make_train_step(cfg, tc, boundary, remat=remat,
                                            act_spec=aspec,
                                            moe_groups=moe_groups)
            jitted = jax.jit(step,
                             in_shardings=(pspecs, ospecs, bspecs),
                             out_shardings=(pspecs, ospecs, None),
                             donate_argnums=(0, 1))
            lowered = jitted.lower(aparams, ostate, batch)
        elif shape.kind == "prefill":
            inputs, ispecs = inp.prefill_inputs(cfg, shape, mesh)
            step = training.make_prefill_step(cfg, shape.seq_len, act_spec=aspec,
                                              moe_groups=moe_groups)
            args = [aparams, inputs["tokens"]]
            shards = [pspecs, ispecs["tokens"]]
            if "memory" in inputs:
                args.append(inputs["memory"])
                shards.append(ispecs["memory"])
            jitted = jax.jit(step, in_shardings=tuple(shards),
                             out_shardings=inp.prefill_out_specs(
                                 cfg, shape, mesh))
            lowered = jitted.lower(*args)
        else:  # decode
            inputs, ispecs = inp.decode_inputs(cfg, shape, mesh)
            step = training.make_serve_step(cfg, act_spec=aspec)
            jitted = jax.jit(step,
                             in_shardings=(pspecs, ispecs["token"],
                                           ispecs["cache"]),
                             out_shardings=(None, None, ispecs["cache"]),
                             donate_argnums=(2,))
            lowered = jitted.lower(aparams, inputs["token"], inputs["cache"])
        t_lower = time.time() - t0

        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    ma = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll = rl.collective_bytes(hlo)
    mf = rl.model_flops(cfg, shape)
    analytic = rl.analytic_flops(cfg, shape)
    roof = rl.build(arch, shape, mesh_name, chips, cost, coll, mf, analytic)

    rec.update(
        chips=chips,
        lower_s=round(t_lower, 2), compile_s=round(t_compile, 2),
        memory=dict(
            argument_bytes=ma.argument_size_in_bytes,
            output_bytes=ma.output_size_in_bytes,
            temp_bytes=ma.temp_size_in_bytes,
            alias_bytes=ma.alias_size_in_bytes,
            peak_bytes=(ma.argument_size_in_bytes + ma.output_size_in_bytes
                        + ma.temp_size_in_bytes - ma.alias_size_in_bytes),
        ),
        cost={k: cost.get(k) for k in ("flops", "bytes accessed")},
        collectives=coll,
        roofline=roof.to_dict(),
        hlo_bytes=len(hlo),
    )
    if keep_hlo:
        rec["hlo"] = hlo
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--boundary", type=int, default=0)
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    combos = []
    archs = ASSIGNED if (args.all or args.arch is None) else [args.arch]
    shapes = list(INPUT_SHAPES) if (args.all or args.shape is None) \
        else [args.shape]
    meshes = [False, True] if (args.all or args.both_meshes) else \
        [args.multi_pod]
    for a in archs:
        for s in shapes:
            for mp in meshes:
                combos.append((a, s, mp))

    os.makedirs(args.out, exist_ok=True)
    n_ok = n_skip = n_fail = 0
    for a, s, mp in combos:
        mesh_name = "pod2x16x16" if mp else "pod16x16"
        tag = f"{a}__{s}__{mesh_name}" + (
            f"__b{args.boundary}" if args.boundary else "")
        path = os.path.join(args.out, tag + ".json")
        if os.path.exists(path) and not args.force:
            print(f"[cached] {tag}")
            rec = json.load(open(path))
            n_ok += rec["status"] == "ok"
            n_skip += rec["status"] == "skip"
            n_fail += rec["status"] == "fail"
            continue
        print(f"[run]    {tag} ...", flush=True)
        try:
            rec = lower_combo(a, s, multi_pod=mp, boundary=args.boundary,
                              remat=not args.no_remat)
        except Exception as e:  # a failure here is a sharding bug — record it
            rec = {"arch": a, "shape": s, "mesh": mesh_name, "status": "fail",
                   "error": f"{type(e).__name__}: {e}",
                   "traceback": traceback.format_exc()[-4000:]}
        with open(path, "w") as f:
            json.dump(rec, f, indent=1)
        if rec["status"] == "ok":
            n_ok += 1
            r = rec["roofline"]
            print(f"         ok: compile={rec['compile_s']}s "
                  f"peak={rec['memory']['peak_bytes']/2**30:.2f}GiB/chip "
                  f"dominant={r['dominant']} "
                  f"terms=({r['compute_s']:.2e},{r['memory_s']:.2e},"
                  f"{r['collective_s']:.2e})s", flush=True)
        elif rec["status"] == "skip":
            n_skip += 1
            print(f"         skip: {rec['reason']}")
        else:
            n_fail += 1
            print(f"         FAIL: {rec['error']}")
    print(f"done: ok={n_ok} skip={n_skip} fail={n_fail}")
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
