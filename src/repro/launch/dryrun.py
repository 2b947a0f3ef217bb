"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

MUST set XLA_FLAGS before any other import (jax locks the device count
on first init)."""
import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))

import argparse      # noqa: E402
import dataclasses   # noqa: E402
import json          # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402

import jax           # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs.base import get, registry          # noqa: E402
from repro.configs.shapes import SHAPES, applicable, skip_reason  # noqa: E402
from repro.distribution import sharding as shd        # noqa: E402
from repro.launch import hlo_analysis as HA           # noqa: E402
from repro.launch.mesh import make_production_mesh    # noqa: E402
from repro.models.model import Model                  # noqa: E402
from repro.models.options import RunOptions           # noqa: E402
from repro.runtime.steps import (abstract_train_state,  # noqa: E402
                                 make_decode_step, make_prefill_step,
                                 make_train_step, train_state_shardings)


def lower_cell(arch_name: str, shape_name: str, mesh, opts: RunOptions,
               *, want_text: bool = False):
    cfg = get(arch_name)
    shape = SHAPES[shape_name]
    model = Model(cfg, opts)
    n_dev = mesh.devices.size
    rules = opts.rules()
    out = {"arch": arch_name, "shape": shape_name,
           "mesh": "x".join(str(s) for s in mesh.devices.shape),
           "n_devices": int(n_dev),
           "opts": {k: v for k, v in dataclasses.asdict(opts).items()
                    if k in ("remat", "layer_loop", "microbatches",
                             "moe_sharding", "fsdp", "param_dtype",
                             "fsdp_pods", "capacity_factor", "q_chunk")}}

    t0 = time.time()
    with shd.use_mesh(mesh, rules):
        if shape.kind == "train":
            step = make_train_step(model)
            state = abstract_train_state(model)
            state_sh = train_state_shardings(model, mesh)
            batch_sh = model.batch_shardings(shape, mesh)
            batch = model.input_specs(shape)["batch"]
            rep = shd.named(mesh, shd.spec_for((), (), mesh))
            lowered = jax.jit(
                step, in_shardings=(state_sh, batch_sh),
                out_shardings=(state_sh,
                               {"loss": rep, "gnorm": rep, "lr": rep}),
            ).lower(state, batch)
        elif shape.kind == "prefill":
            step = make_prefill_step(model)
            p_sh = model.param_shardings(mesh)
            batch_sh = model.batch_shardings(shape, mesh)
            batch = model.input_specs(shape)["batch"]
            lowered = jax.jit(step, in_shardings=(p_sh, batch_sh)).lower(
                model.abstract_params(), batch)
        else:  # decode
            step = make_decode_step(model)
            p_sh = model.param_shardings(mesh)
            spec = model.input_specs(shape)
            bsh = model.batch_shardings(shape, mesh)
            lowered = jax.jit(
                step, in_shardings=(p_sh, bsh["cache"], bsh["token"]),
            ).lower(model.abstract_params(), spec["cache"], spec["token"])
        out["lower_s"] = round(time.time() - t0, 2)

        t0 = time.time()
        compiled = lowered.compile()
        out["compile_s"] = round(time.time() - t0, 2)

    ca = compiled.cost_analysis() or {}
    ma = compiled.memory_analysis()
    out["cost_analysis"] = {"flops": ca.get("flops", 0.0),
                            "bytes": ca.get("bytes accessed", 0.0)}
    out["memory"] = {
        "argument_bytes": getattr(ma, "argument_size_in_bytes", 0),
        "output_bytes": getattr(ma, "output_size_in_bytes", 0),
        "temp_bytes": getattr(ma, "temp_size_in_bytes", 0),
        "code_bytes": getattr(ma, "generated_code_size_in_bytes", 0),
    }
    txt = compiled.as_text()
    out["hlo"] = HA.analyze(txt)
    if want_text:
        out["hlo_text"] = txt

    # roofline (per device)
    flops_dev = out["hlo"]["dot_flops"]
    bytes_dev = out["hlo"]["bytes_touched"]
    coll_dev = out["hlo"]["collective_bytes_total"]
    out["roofline"] = HA.roofline_terms(flops_dev, bytes_dev, coll_dev)

    # analytic model flops (global, fp-counted the 6ND/2ND way)
    N = cfg.param_count()
    Na = cfg.param_count(active_only=True)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        mf = 6.0 * Na * tokens
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        mf = 2.0 * Na * tokens
    else:
        mf = 2.0 * Na * shape.global_batch
    out["model_flops_global"] = mf
    out["model_flops_per_device"] = mf / n_dev
    out["useful_ratio"] = (mf / n_dev) / max(flops_dev, 1.0)
    out["params_b"] = round(N / 1e9, 3)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--layer-loop", default="scan",
                    choices=["scan", "unroll"])
    ap.add_argument("--remat", default="full",
                    choices=["none", "full", "dots"])
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--moe-shard", default="tp", choices=["tp", "cap", "ep"])
    ap.add_argument("--moe-group", type=int, default=0)
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--param-dtype", default="float32",
                    choices=["float32", "bfloat16"])
    ap.add_argument("--kv-dtype", default="",
                    choices=["", "bfloat16", "float8_e4m3fn"])
    ap.add_argument("--fsdp-pods", action="store_true")
    ap.add_argument("--seq-shard", action="store_true")
    ap.add_argument("--q-chunk", type=int, default=512)
    ap.add_argument("--capacity-factor", type=float, default=1.25)
    ap.add_argument("--out", default="benchmarks/results/dryrun.json")
    ap.add_argument("--tag", default="baseline")
    args = ap.parse_args()

    opts = RunOptions(remat=args.remat, layer_loop=args.layer_loop,
                      microbatches=args.microbatches,
                      moe_sharding=args.moe_shard,
                      moe_group=args.moe_group,
                      fsdp=not args.no_fsdp,
                      param_dtype=args.param_dtype,
                      kv_cache_dtype=args.kv_dtype,
                      fsdp_pods=args.fsdp_pods,
                      seq_shard_activations=args.seq_shard,
                      q_chunk=args.q_chunk,
                      capacity_factor=args.capacity_factor)

    archs = sorted(registry()) if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    results = []
    if os.path.exists(args.out):
        with open(args.out) as f:
            results = json.load(f)
    done = {(r.get("arch"), r.get("shape"), r.get("mesh"), r.get("tag"))
            for r in results}

    for multi in meshes:
        mesh = make_production_mesh(multi_pod=multi)
        mesh_name = "x".join(str(s) for s in mesh.devices.shape)
        for a in archs:
            cfg = get(a)
            for s in shapes:
                key = (a, s, mesh_name, args.tag)
                if key in done:
                    continue
                if not applicable(cfg, SHAPES[s]):
                    rec = {"arch": a, "shape": s, "mesh": mesh_name,
                           "tag": args.tag, "skipped": skip_reason(cfg, SHAPES[s])}
                    print(f"[skip] {a} x {s} x {mesh_name}: {rec['skipped']}")
                else:
                    print(f"[lower] {a} x {s} x {mesh_name} ...", flush=True)
                    try:
                        rec = lower_cell(a, s, mesh, opts)
                        rec["tag"] = args.tag
                        rl = rec["roofline"]
                        print(f"  ok compile={rec['compile_s']}s "
                              f"dom={rl['dominant']} "
                              f"comp={rl['compute_s']:.4f}s "
                              f"mem={rl['memory_s']:.4f}s "
                              f"coll={rl['collective_s']:.4f}s "
                              f"useful={rec['useful_ratio']:.2f}", flush=True)
                    except Exception as e:   # noqa: BLE001
                        rec = {"arch": a, "shape": s, "mesh": mesh_name,
                               "tag": args.tag, "error": str(e)[:500],
                               "trace": traceback.format_exc()[-2000:]}
                        print(f"  ERROR: {str(e)[:200]}", flush=True)
                results.append(rec)
                with open(args.out, "w") as f:
                    json.dump(results, f, indent=1)
    n_err = sum(1 for r in results if "error" in r)
    print(f"done: {len(results)} records, {n_err} errors -> {args.out}")


if __name__ == "__main__":
    main()
