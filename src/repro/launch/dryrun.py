import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any other import (jax locks the device
# count at first init). Everything below is the multi-pod dry-run driver:
# lower + compile every (arch x input-shape) cell on the production meshes,
# print memory/cost analysis, and derive the roofline terms.

import argparse          # noqa: E402
import json              # noqa: E402
import sys               # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402

import jax               # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import (ARCHS, SHAPES, RunConfig, get_arch,  # noqa: E402
                           get_shape)
from repro.distributed import sharding as shard_rules          # noqa: E402
from repro.distributed.sharding import use_batch_axes           # noqa: E402
from repro.launch import hlo_cost                              # noqa: E402
from repro.launch import roofline as rl                        # noqa: E402
from repro.launch.mesh import (make_production_mesh,  # noqa: E402
                               make_replica_split_mesh)
from repro.launch.step_fns import (make_decode_step, make_prefill_step,      # noqa: E402
                                   make_train_step)
from repro.models import api as model_api                      # noqa: E402
from repro.optim import adamw                                  # noqa: E402


def lower_cell(arch_name: str, shape_name: str, *, multi_pod: bool,
               replication: str = "none", remat: str = "full",
               seq_chunk: int = 2048, kv_block: int = 512,
               donate: bool = True):
    """Lower + compile one (arch x shape x mesh) cell; return stats dict."""
    cfg = get_arch(arch_name)
    shape = get_shape(shape_name)
    run = RunConfig(model=cfg, shape=shape, remat=remat,
                    seq_chunk=seq_chunk, kv_block=kv_block,
                    replication_axis=replication)
    if replication == "split":
        mesh = make_replica_split_mesh()
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    mesh_name = ("replica-split" if replication == "split" else
                 ("2x16x16" if multi_pod else "16x16"))

    abstract_params = model_api.abstract_state(cfg)
    p_sh = shard_rules.param_shardings(abstract_params, mesh)
    in_specs = model_api.input_specs(cfg, shape)
    in_sh = shard_rules.input_shardings(in_specs, mesh, replication)

    # repro: allow[wallclock] -- genuine wall measurement
    t0 = time.perf_counter()
    if shape.kind == "train":
        step, model = make_train_step(run)
        opt_abstract = adamw.init_abstract(abstract_params)
        opt_sh = adamw.AdamWState(
            step=NamedSharding(mesh, P()),
            m=jax.tree.map(lambda s: s, p_sh),
            v=jax.tree.map(lambda s: s, p_sh))
        jitted = jax.jit(
            step,
            in_shardings=(p_sh, opt_sh, in_sh),
            out_shardings=(p_sh, opt_sh, NamedSharding(mesh, P())),
            donate_argnums=(0, 1) if donate else ())
        with jax.set_mesh(mesh), use_batch_axes(
                shard_rules.batch_axes(mesh, replication)):
            lowered = jitted.lower(abstract_params, opt_abstract, in_specs)
    elif shape.kind == "prefill":
        step, model = make_prefill_step(run)
        cache_abs = jax.eval_shape(
            lambda: model.init_cache(shape.global_batch, shape.seq_len))
        cache_sh = shard_rules.cache_shardings(cache_abs, mesh,
                                               shape.global_batch,
                                               replication)
        logits_sh = NamedSharding(mesh, shard_rules.input_pspec(
            (shape.global_batch, 1, cfg.vocab_size), mesh, replication))
        jitted = jax.jit(step, in_shardings=(p_sh, in_sh),
                         out_shardings=(logits_sh, cache_sh))
        with jax.set_mesh(mesh), use_batch_axes(
                shard_rules.batch_axes(mesh, replication)):
            lowered = jitted.lower(abstract_params, in_specs)
    else:  # decode
        step, model = make_decode_step(run)
        cache_abs = jax.eval_shape(
            lambda: model.init_cache(shape.global_batch, shape.seq_len))
        cache_sh = shard_rules.cache_shardings(cache_abs, mesh,
                                               shape.global_batch,
                                               replication)
        logits_sh = NamedSharding(mesh, shard_rules.input_pspec(
            (shape.global_batch, 1, cfg.vocab_size), mesh, replication))
        jitted = jax.jit(
            step,
            in_shardings=(p_sh, cache_sh, in_sh["tokens"], in_sh["pos"]),
            out_shardings=(logits_sh, cache_sh),
            donate_argnums=(1,) if donate else ())
        with jax.set_mesh(mesh), use_batch_axes(
                shard_rules.batch_axes(mesh, replication)):
            lowered = jitted.lower(abstract_params, cache_abs,
                                   in_specs["tokens"], in_specs["pos"])
    # repro: allow[wallclock] -- genuine wall measurement
    t_lower = time.perf_counter() - t0

    # repro: allow[wallclock] -- genuine wall measurement
    t0 = time.perf_counter()
    compiled = lowered.compile()
    # repro: allow[wallclock] -- genuine wall measurement
    t_compile = time.perf_counter() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    # trip-count-aware analysis (XLA's cost_analysis counts loop bodies once;
    # see launch/hlo_cost.py) — flops/bytes/collectives are all per-device
    rep = hlo_cost.analyze(hlo)

    n_active = model_api.param_count(cfg, active_only=True)
    mf = rl.model_flops(n_active, shape.tokens_per_step,
                        "train" if shape.kind == "train" else "serve")
    terms = rl.RooflineTerms(
        arch=arch_name, shape=shape_name, mesh=mesh_name, chips=chips,
        flops_per_device=rep.flops,
        bytes_per_device=rep.bytes_lb,
        bytes_per_device_ub=rep.bytes,
        bytes_by_op={k: v for k, v in sorted(
            rep.bytes_by_op.items(), key=lambda kv: -kv[1])[:12]},
        collective_bytes_per_device=rep.collective_bytes,
        collective_breakdown=rep.collective_breakdown,
        model_flops_global=mf,
        memory_per_device=None if mem is None else {
            "argument": mem.argument_size_in_bytes,
            "output": mem.output_size_in_bytes,
            "temp": mem.temp_size_in_bytes,
            "alias": mem.alias_size_in_bytes,
            "generated_code": mem.generated_code_size_in_bytes,
        }).finish()

    return {"ok": True, "cell": f"{arch_name}:{shape_name}:{mesh_name}",
            "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
            "xla_cost_analysis": {k: float(v) for k, v in cost.items()
                                  if k in ("flops", "bytes accessed")},
            "terms": terms.as_dict()}


def run_cells(cells, *, multi_pod: bool, replication: str = "none",
              remat: str = "full", out_path: str = None, verbose: bool = True):
    results = []
    for arch_name, shape_name in cells:
        tag = f"{arch_name}:{shape_name}:{'multi' if multi_pod else 'single'}"
        try:
            res = lower_cell(arch_name, shape_name, multi_pod=multi_pod,
                             replication=replication, remat=remat)
            t = res["terms"]
            if verbose:
                mem = t["memory_per_device"] or {}
                per_dev_gb = (mem.get("argument", 0) + mem.get("temp", 0)) / 2**30
                print(f"[ok] {tag:48s} compile={res['compile_s']:7.1f}s "
                      f"comp={t['compute_s']:.3e}s mem={t['memory_s']:.3e}s "
                      f"coll={t['collective_s']:.3e}s dom={t['dominant']:10s} "
                      f"bytes/dev={per_dev_gb:6.2f}GiB "
                      f"useful={t['useful_ratio']:.2f}", flush=True)
        except Exception as e:  # noqa: BLE001 - report, keep going
            res = {"ok": False, "cell": tag, "error": f"{type(e).__name__}: {e}",
                   "traceback": traceback.format_exc()}
            print(f"[FAIL] {tag}: {res['error']}", flush=True)
        results.append(res)
        if out_path:
            with open(out_path, "w") as f:
                json.dump(results, f, indent=1)
    return results


def applicable_cells(include_long_for_all: bool = False):
    cells = []
    for arch in ARCHS.values():
        for shape in SHAPES.values():
            if shape.name == "long_500k" and not arch.is_subquadratic \
                    and not include_long_for_all:
                continue
            cells.append((arch.name, shape.name))
    return cells


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None, help="arch id (default: all)")
    ap.add_argument("--shape", default=None, help="shape id (default: all)")
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--replication", default="none",
                    choices=["none", "pod", "split"])
    ap.add_argument("--remat", default="full",
                    choices=["none", "full", "dots"])
    ap.add_argument("--out", default=None, help="write JSON results here")
    args = ap.parse_args(argv)

    if args.arch and args.shape:
        cells = [(args.arch, args.shape)]
    elif args.arch:
        cells = [(a, s) for a, s in applicable_cells() if a == args.arch]
    elif args.shape:
        cells = [(a, s) for a, s in applicable_cells() if s == args.shape]
    else:
        cells = applicable_cells()

    all_results = []
    meshes = {"single": [False], "multi": [True], "both": [False, True]}
    for mp in meshes[args.mesh]:
        out = None
        if args.out:
            stem, ext = os.path.splitext(args.out)
            out = f"{stem}_{'multi' if mp else 'single'}{ext}" \
                if args.mesh == "both" else args.out
        all_results += run_cells(cells, multi_pod=mp,
                                 replication=args.replication,
                                 remat=args.remat, out_path=out)
    n_fail = sum(1 for r in all_results if not r["ok"])
    print(f"\n{len(all_results) - n_fail}/{len(all_results)} cells OK")
    return 1 if n_fail else 0


if __name__ == "__main__":
    sys.exit(main())
