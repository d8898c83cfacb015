"""Multi-pod dry-run: lower + compile every (arch x shape) on the production
mesh, with ShapeDtypeStruct stand-ins (no allocation), and record
memory/cost/collective analysis for the roofline.

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-72b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--fast]

Run as a script, it forces 512 host devices through ``XLA_FLAGS`` before
the first backend initialization (jax locks the device count there, not at
import).  Importing this module sets no flags: smoke tests / benches import
repro.* directly and keep seeing their own devices.
"""
import argparse
import json
import os
import time
import traceback
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import roofline as RL
from repro import sharding as shd
from repro.configs import (ASSIGNED_ARCHS, ASSIGNED_SHAPES, get_config,
                           get_shape)
from repro.launch.mesh import make_production_mesh
from repro.models import build_model
from repro.training.loop import make_train_step
from repro.training.optimizer import AdamWConfig, adamw_init

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")


def per_chip_bytes(shapes_tree, shardings_tree) -> float:
    """Actual bytes resident per chip given resolved shardings."""
    import numpy as np
    total = 0.0
    for sds, sh in zip(jax.tree.leaves(shapes_tree),
                       jax.tree.leaves(shardings_tree)):
        shard = sh.shard_shape(sds.shape)
        total += float(np.prod(shard)) * sds.dtype.itemsize
    return total


def abstract_init(bundle) -> Tuple[Dict, Dict]:
    """Parameter ShapeDtypeStructs + logical specs WITHOUT allocating."""
    box = {}

    def f(key):
        params, specs = bundle.init(key)
        box["specs"] = specs
        return params

    shapes = jax.eval_shape(f, jax.random.key(0))
    return shapes, box["specs"]


def abstract_caches(bundle, batch: int, max_len: int,
                    quant: bool = False) -> Tuple[Dict, Dict]:
    box = {}

    def f():
        caches, specs = bundle.cache_init(batch, max_len, quant=quant)
        box["specs"] = specs
        return caches

    shapes = jax.eval_shape(f)
    return shapes, box["specs"]


def _input_shardings(bundle, shape, mesh, rules):
    specs = bundle.input_specs(shape)
    logical = bundle.input_logical(shape)
    return {k: shd.logical_to_sharding(logical.get(k, (None,) * len(v.shape)),
                                       v.shape, mesh, rules)
            for k, v in specs.items()}, specs


def _lower_and_compile(cfg, shape, mesh, rules, attention_impl: str,
                       kv_quant: bool = False):
    """Build + AOT-compile the step function for one workload."""
    t0 = time.perf_counter()
    bundle = build_model(cfg)
    param_shapes, param_specs = abstract_init(bundle)
    param_sh = shd.tree_shardings(param_specs, param_shapes, mesh, rules)
    in_sh, in_specs = _input_shardings(bundle, shape, mesh, rules)

    with shd.mesh_rules(mesh, rules):
        if shape.kind == "train":
            opt_shapes = jax.eval_shape(adamw_init, param_shapes)
            opt_specs = {"mu": param_specs, "nu": param_specs, "step": ()}
            opt_sh = shd.tree_shardings(opt_specs, opt_shapes, mesh, rules)
            step_fn = make_train_step(bundle, AdamWConfig(),
                                      impl=attention_impl)
            jitted = jax.jit(step_fn,
                             in_shardings=(param_sh, opt_sh, in_sh),
                             out_shardings=(param_sh, opt_sh, None),
                             donate_argnums=(0, 1))
            lowered = jitted.lower(param_shapes, opt_shapes, in_specs)
        elif shape.kind == "prefill":
            def prefill_fn(params, batch):
                return bundle.prefill(params, batch, impl=attention_impl)
            jitted = jax.jit(prefill_fn, in_shardings=(param_sh, in_sh))
            lowered = jitted.lower(param_shapes, in_specs)
        else:  # decode: serve_step = ONE token against a seq_len KV cache
            cache_shapes, cache_specs = abstract_caches(
                bundle, shape.global_batch, shape.seq_len, quant=kv_quant)
            cache_sh = shd.tree_shardings(cache_specs, cache_shapes, mesh,
                                          rules)

            def serve_step(params, caches, batch):
                return bundle.decode_step(params, caches, batch,
                                          impl="reference")
            jitted = jax.jit(serve_step,
                             in_shardings=(param_sh, cache_sh, in_sh),
                             out_shardings=(None, cache_sh),
                             donate_argnums=(1,))
            lowered = jitted.lower(param_shapes, cache_shapes, in_specs)

        t_lower = time.perf_counter() - t0
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0 - t_lower
    return compiled, t_lower, t_compile


def _extrapolated_cost(cfg, shape, mesh, rules, attention_impl: str,
                       n_groups: int, kv_quant: bool = False) -> Dict:
    """Per-partition flops/bytes/collective-bytes, scan-trip-count corrected:
    lower 1-group and 2-group variants, total = c1 + (n_groups-1)*(c2-c1)."""
    from repro import flags
    vals = {}
    for k in (1, 2):
        ck = _with_layers(cfg, k)
        with flags.cost_transparent():
            compiled, _, _ = _lower_and_compile(ck, shape, mesh, rules,
                                                attention_impl, kv_quant)
        cost = compiled.cost_analysis() or {}
        coll = RL.collective_bytes_from_hlo(compiled.as_text())
        vals[k] = {"flops": float(cost.get("flops", 0.0)),
                   "bytes": float(cost.get("bytes accessed", 0.0)),
                   "coll": coll}
    out = {}
    for key in ("flops", "bytes"):
        delta = max(vals[2][key] - vals[1][key], 0.0)
        out[key] = vals[1][key] + (n_groups - 1) * delta
    detail = {}
    for k in vals[1]["coll"]:
        if k == "counts":
            continue
        delta = max(vals[2]["coll"][k] - vals[1]["coll"][k], 0.0)
        detail[k] = vals[1]["coll"][k] + (n_groups - 1) * delta
    out["collective_bytes"] = detail["total"]
    out["collective_detail"] = detail
    return out


def _with_layers(cfg, k_groups: int):
    """cfg with k layer-pattern groups (enc-dec: k enc + k dec layers)."""
    import dataclasses
    period = len(cfg.layer_pattern)
    rep = {"n_layers": k_groups * period}
    if cfg.enc_dec:
        rep["n_enc_layers"] = k_groups * period
    if cfg.climber is not None:
        rep["n_layers"] = k_groups
        rep["climber"] = dataclasses.replace(cfg.climber,
                                             layers_per_block=k_groups)
    return dataclasses.replace(cfg, **rep)


def should_skip(cfg, shape) -> Optional[str]:
    if shape.name == "long_500k" and not cfg.sub_quadratic:
        return ("pure full-attention arch: long_500k requires sub-quadratic "
                "attention (DESIGN.md §4)")
    return None


def dryrun_one(arch: str, shape_name: str, *, multi_pod: bool = False,
               save: bool = True, verbose: bool = True,
               fsdp: bool = True, extra_tag: str = "",
               attention_impl: str = "chunked",
               rules_override: Optional[Dict] = None,
               moe_dispatch: str = "gspmd", kv_quant: bool = False) -> Dict:
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    tag = f"{mesh_name}_{arch}_{shape_name}{extra_tag}"
    skip = should_skip(cfg, shape)
    if skip:
        rec = {"tag": tag, "arch": arch, "shape": shape_name,
               "mesh": mesh_name, "status": "skipped", "reason": skip}
        if save:
            _save(tag, rec)
        if verbose:
            print(f"[dryrun] SKIP {tag}: {skip}")
        return rec

    t0 = time.perf_counter()
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.size
    rules = shd.rules_for_shape(mesh, shape.global_batch, fsdp=fsdp)
    if rules_override:
        names = set(mesh.axis_names)
        rules.update({k: tuple(a for a in v if a in names)
                      for k, v in rules_override.items()})

    # ---- 1. full-config compile: proves the (arch x shape x mesh) lowers;
    #         source of memory_analysis ----
    from repro import flags as _flags
    _moe_tok = _flags.MOE_DISPATCH.set(moe_dispatch)
    try:
        compiled, t_lower, t_compile = _lower_and_compile(
            cfg, shape, mesh, rules, attention_impl, kv_quant)
        cost = compiled.cost_analysis() or {}
        try:
            mem = compiled.memory_analysis()
            mem_d = {k: getattr(mem, k) for k in
                     ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "generated_code_size_in_bytes")
                     if hasattr(mem, k)}
        except Exception as e:  # noqa: BLE001
            mem, mem_d = None, {"error": str(e)}
        hlo = compiled.as_text()

        # ---- 2. roofline terms: XLA cost analysis counts a scan body ONCE,
        #         so the layer scan under-counts flops/bytes/collectives by
        #         the trip count.  Lower 1-group and 2-group variants and
        #         extrapolate total = base + n_groups * delta. ----
        n_groups = cfg.n_groups if cfg.climber is None else \
            cfg.climber.layers_per_block
        ext = _extrapolated_cost(cfg, shape, mesh, rules, attention_impl,
                                 n_groups, kv_quant)
        # actual per-chip weight/cache residency for the memory estimate
        _bundle = build_model(cfg)
        _pshapes, _pspecs = abstract_init(_bundle)
        params_bytes_chip = per_chip_bytes(
            _pshapes, shd.tree_shardings(_pspecs, _pshapes, mesh, rules))
        cache_bytes_chip = None
        if shape.kind == "decode":
            _cshapes, _cspecs = abstract_caches(_bundle, shape.global_batch,
                                                shape.seq_len, quant=kv_quant)
            cache_bytes_chip = per_chip_bytes(
                _cshapes, shd.tree_shardings(_cspecs, _cshapes, mesh, rules))
    finally:
        _flags.MOE_DISPATCH.reset(_moe_tok)

    report = RL.analyse(arch, shape_name, mesh_name, chips,
                        {"flops": ext["flops"],
                         "bytes accessed": ext["bytes"]},
                        "", cfg, shape,
                        per_device_peak_memory=mem_d.get("temp_size_in_bytes"),
                        params_bytes_chip=params_bytes_chip,
                        cache_bytes_chip=cache_bytes_chip)
    # collective bytes were extrapolated per-partition already
    report.collective_bytes = ext["collective_bytes"] * chips
    report.collective_s = report.collective_bytes / (chips * RL.TPU_V5E.ici_bw)
    report.collective_detail = ext["collective_detail"]
    rec = {
        "tag": tag, "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "status": "ok", "chips": chips,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory_analysis": mem_d,
        "cost_analysis": {k: cost.get(k) for k in
                          ("flops", "bytes accessed", "utilization operand 0")
                          if k in cost},
        "roofline": report.to_dict(),
        "hlo_bytes_len": len(hlo),
    }
    if save:
        _save(tag, rec)
    if verbose:
        print(f"[dryrun] OK {tag}: chips={chips} "
              f"compile={t_compile:.1f}s "
              f"mem={mem_d} "
              f"flops={report.hlo_flops:.3e} "
              f"compute={report.compute_s*1e3:.2f}ms "
              f"memory_xla={report.memory_s*1e3:.2f}ms "
              f"memory_est={report.memory_s_est*1e3:.2f}ms "
              f"collective={report.collective_s*1e3:.2f}ms "
              f"dominant={report.dominant} useful={report.useful_ratio:.2f}")
    return rec


def _save(tag: str, rec: Dict):
    os.makedirs(RESULTS_DIR, exist_ok=True)
    with open(os.path.join(RESULTS_DIR, tag + ".json"), "w") as f:
        json.dump(rec, f, indent=2, default=str)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--impl", default="chunked")
    ap.add_argument("--missing", action="store_true",
                    help="skip combinations that already have a result file")
    ap.add_argument("--moe-dispatch", default="gspmd",
                    choices=["gspmd", "a2a"])
    ap.add_argument("--tag", default="")
    ap.add_argument("--rules", default="",
                    help='logical-rule overrides, e.g. "experts=data;seq=model"')
    ap.add_argument("--kv-quant", action="store_true",
                    help="int8 KV cache (decode shapes)")
    ap.add_argument("--profile", default=None, choices=[None, "serving"],
                    help="apply the §Perf-optimized sharding profile")
    args = ap.parse_args()
    overrides = None
    if args.rules:
        overrides = {}
        for kv in args.rules.split(";"):
            k, v = kv.split("=")
            overrides[k.strip()] = tuple(a for a in v.split(",") if a)
    if args.profile == "serving":
        # hillclimb-2 outcome: TP-resident weights, sequence-sharded KV cache
        args.no_fsdp = True
        overrides = dict(overrides or {})
        overrides.setdefault("cache_seq", ("model",))

    jobs = []
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    if args.all:
        for a in ASSIGNED_ARCHS:
            for s in ASSIGNED_SHAPES:
                for mp in meshes:
                    jobs.append((a, s.name, mp))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        for mp in meshes:
            jobs.append((args.arch, args.shape, mp))

    if args.missing:
        def _exists(a, s, mp):
            mesh_name = "pod2x16x16" if mp else "pod16x16"
            return os.path.exists(os.path.join(
                RESULTS_DIR, f"{mesh_name}_{a}_{s}.json"))
        jobs = [j for j in jobs if not _exists(*j)]
        print(f"[dryrun] {len(jobs)} missing jobs to run")

    failures = []
    for a, s, mp in jobs:
        try:
            dryrun_one(a, s, multi_pod=mp, fsdp=not args.no_fsdp,
                       attention_impl=args.impl,
                       moe_dispatch=args.moe_dispatch, extra_tag=args.tag,
                       rules_override=overrides, kv_quant=args.kv_quant)
        except Exception as e:  # noqa: BLE001
            failures.append((a, s, mp, repr(e)))
            print(f"[dryrun] FAIL {a} {s} multi_pod={mp}: {e}")
            traceback.print_exc()
    print(f"[dryrun] done: {len(jobs) - len(failures)}/{len(jobs)} ok")
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    main()
