"""Multi-pod dry-run: prove every (arch x shape x mesh) cell lowers,
SPMD-partitions, and compiles, and extract its roofline inputs.

MUST be run as a script/module: the XLA_FLAGS line below executes before
any other jax import (jax locks the device count on first init).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --all
  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-8b \
      --shape train_4k --multi-pod
  PYTHONPATH=src python -m repro.launch.dryrun --all --out results/
"""
import os

os.environ["XLA_FLAGS"] = (os.environ.get("_DRYRUN_EXTRA_XLA", "") +
                           " --xla_force_host_platform_device_count=512")

# ruff: noqa: E402
import argparse
import dataclasses
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.configs import (ASSIGNED_ARCHS, SHAPE_BY_NAME, SHAPES,
                           get_config, shape_applicability)
from repro.launch import specs as S
from repro.launch.hlo_cost import analyze as hlo_analyze
from repro.launch.mesh import make_production_mesh
from repro.launch.modelflops import model_flops
from repro.runtime.steps import (make_prefill_step, make_serve_step,
                                 make_train_step)
from repro.sharding import rules


def _mem_dict(ma) -> dict:
    if ma is None:
        return {}
    fields = ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "alias_size_in_bytes",
              "generated_code_size_in_bytes")
    return {f: getattr(ma, f, None) for f in fields}


def lower_cell(arch: str, shape_name: str, *, multi_pod: bool,
               seq_shard_cache: bool = False, tcfg_override=None,
               shard_hints: bool = False, compile_only: bool = False):
    """Build + lower + compile one cell; returns (record, compiled)."""
    cfg = get_config(arch)
    if shard_hints:
        cfg = cfg.replace(shard_hints=True)
    shape = SHAPE_BY_NAME[shape_name]
    skip = shape_applicability(cfg, shape)
    rec = {"arch": arch, "shape": shape_name,
           "mesh": "multi_pod_2x16x16" if multi_pod else "single_pod_16x16",
           "seq_shard_cache": seq_shard_cache, "shard_hints": shard_hints}
    if skip:
        rec.update(status="skip", reason=skip)
        return rec, None

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.devices.size
    rec["n_devices"] = int(n_dev)
    t0 = time.time()

    if shape.kind == "train":
        tcfg = tcfg_override or S.default_train_config(cfg, shape)
        # per-microbatch batch must stay shardable over the data axes
        dp_size = rules._axis_size(mesh, rules.data_axes(mesh))
        max_mb = max(1, shape.global_batch // dp_size)
        if tcfg.microbatches > max_mb:
            tcfg = dataclasses.replace(tcfg, microbatches=max_mb)
        rec["tcfg"] = {"microbatches": tcfg.microbatches,
                       "remat": tcfg.remat,
                       "grad_compress": tcfg.grad_compress}
        state_shape = S.train_state_shape(cfg, tcfg)
        p_sh = rules.param_shardings(state_shape["params"], mesh, cfg)
        state_sh = {"params": p_sh,
                    "opt": rules.opt_shardings(state_shape["opt"],
                                               state_shape["params"],
                                               mesh, cfg)}
        if "ef" in state_shape:
            state_sh["ef"] = rules.param_shardings(state_shape["ef"],
                                                   mesh, cfg)
        batch_shape = S.batch_specs(cfg, shape)
        b_sh = rules.batch_shardings(batch_shape, mesh)
        step = make_train_step(cfg, tcfg)
        jitted = jax.jit(step, in_shardings=(state_sh, b_sh),
                         out_shardings=(state_sh, None),
                         donate_argnums=(0,))
        with jax.set_mesh(mesh):
            lowered = jitted.lower(state_shape, batch_shape)
    elif shape.kind == "prefill":
        params_shape = S.params_shape(cfg)
        p_sh = rules.param_shardings(params_shape, mesh, cfg)
        batch_shape = S.batch_specs(cfg, shape)
        b_sh = rules.batch_shardings(batch_shape, mesh)
        cache_sh_shape = S.cache_shape(cfg, shape.global_batch,
                                       shape.seq_len) \
            if cfg.has_kv_cache or cfg.sub_quadratic else None
        step = make_prefill_step(cfg)
        out_cache_sh = None
        if cache_sh_shape is not None:
            out_cache_sh = rules.cache_shardings(cache_sh_shape, mesh, cfg,
                                                 seq_shard_cache)
        jitted = jax.jit(step, in_shardings=(p_sh, b_sh),
                         out_shardings=(None, out_cache_sh))
        with jax.set_mesh(mesh):
            lowered = jitted.lower(params_shape, batch_shape)
    else:  # decode
        params_shape = S.params_shape(cfg)
        # serving layout: TP-only weights (no FSDP gathers) whenever the
        # model-sharded params fit HBM (see rules.param_spec)
        import math
        p_bytes = sum(math.prod(l.shape) * l.dtype.itemsize
                      for l in jax.tree.leaves(params_shape))
        tp_only = shard_hints and p_bytes / 16 <= 12e9
        rec["tp_only"] = tp_only
        p_sh = rules.param_shardings(params_shape, mesh, cfg,
                                     tp_only=tp_only)
        cache_shape, tok_s, pos_s = S.decode_specs(cfg, shape)
        c_sh = rules.cache_shardings(cache_shape, mesh, cfg,
                                     seq_shard_cache)
        dp = rules.data_axes(mesh)
        tok_sh = rules.batch_shardings({"t": tok_s}, mesh)["t"]
        step = make_serve_step(cfg)
        jitted = jax.jit(step,
                         in_shardings=(p_sh, c_sh, tok_sh,
                                       rules.replicated(mesh)),
                         out_shardings=(c_sh, tok_sh,
                                        rules.replicated(mesh)),
                         donate_argnums=(1,))
        with jax.set_mesh(mesh):
            lowered = jitted.lower(params_shape, cache_shape, tok_s, pos_s)

    rec["lower_s"] = round(time.time() - t0, 2)
    t0 = time.time()
    compiled = lowered.compile()
    rec["compile_s"] = round(time.time() - t0, 2)
    rec["memory"] = _mem_dict(compiled.memory_analysis())
    ca = compiled.cost_analysis() or {}
    rec["xla_cost"] = {"flops": ca.get("flops"),
                       "bytes_accessed": ca.get("bytes accessed")}
    txt = compiled.as_text()
    cost = hlo_analyze(txt)
    rec["hlo"] = cost.to_dict()
    rec["model_flops_global"] = model_flops(cfg, SHAPE_BY_NAME[shape_name])
    from repro.launch.modelbytes import analytic_bytes
    tc = None
    if shape.kind == "train":
        tc = tcfg_override or S.default_train_config(cfg, shape)
    rec["analytic_bytes_per_device"] = analytic_bytes(
        cfg, SHAPE_BY_NAME[shape_name], n_dev, tc)
    rec["status"] = "ok"
    if compile_only:
        return rec, compiled
    return rec, compiled


def run_cells(cells, out_path: Path, *, force=False, seq_shard=False,
              shard_hints=False, print_analysis=True):
    results = {}
    if out_path.exists():
        results = json.loads(out_path.read_text())
    for arch, shape_name, multi_pod in cells:
        key = f"{arch}|{shape_name}|{'multi' if multi_pod else 'single'}"
        if seq_shard:
            key += "|seqshard"
        if shard_hints:
            key += "|hints"
        if key in results and results[key].get("status") in ("ok", "skip") \
                and not force:
            print(f"[cached] {key}: {results[key]['status']}")
            continue
        print(f"[dryrun] {key} ...", flush=True)
        try:
            rec, compiled = lower_cell(arch, shape_name,
                                       multi_pod=multi_pod,
                                       seq_shard_cache=seq_shard,
                                       shard_hints=shard_hints)
            if print_analysis and compiled is not None:
                print(f"  memory_analysis: {rec['memory']}")
                print(f"  cost_analysis: {rec['xla_cost']}")
            if rec["status"] == "ok":
                print(f"  OK lower={rec['lower_s']}s "
                      f"compile={rec['compile_s']}s "
                      f"flops/dev={rec['hlo']['flops']:.3e} "
                      f"coll_link={rec['hlo']['total_coll_link_bytes']:.3e}")
            else:
                print(f"  SKIP: {rec['reason']}")
        except Exception as e:  # noqa: BLE001 — record and continue
            rec = {"arch": arch, "shape": shape_name,
                   "mesh": "multi" if multi_pod else "single",
                   "status": "error", "error": f"{type(e).__name__}: {e}",
                   "trace": traceback.format_exc()[-2000:]}
            print(f"  ERROR {type(e).__name__}: {e}")
        results[key] = rec
        out_path.parent.mkdir(parents=True, exist_ok=True)
        out_path.write_text(json.dumps(results, indent=1, default=float))
    return results


def all_cells(meshes=("single", "multi")):
    cells = []
    for arch in ASSIGNED_ARCHS:
        for shape in SHAPES:
            for m in meshes:
                cells.append((arch, shape.name, m == "multi"))
    return cells


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--single-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--seq-shard-cache", action="store_true")
    ap.add_argument("--shard-hints", action="store_true",
                    help="lower the optimized (activation-constrained) "
                         "variant; recorded under a separate |hints key")
    ap.add_argument("--out", default="results/dryrun.json")
    args = ap.parse_args()

    if args.all:
        meshes = []
        if args.single_pod or not args.multi_pod:
            meshes.append("single")
        if args.multi_pod or not args.single_pod:
            meshes.append("multi")
        cells = all_cells(tuple(meshes))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape, args.multi_pod)]
    run_cells(cells, Path(args.out), force=args.force,
              seq_shard=args.seq_shard_cache, shard_hints=args.shard_hints)


if __name__ == "__main__":
    main()
