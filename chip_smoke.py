"""Chip smoke test: Climber at its published size served by FlameEngine.

    python chip_smoke.py                # one TPU chip, the serving path
    python chip_smoke.py --four-chips   # four TPU chips, mesh serving only
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse [--four-chips]

One process drives the chip(s) end to end through the entry points a user
calls: ``create_engine("flame", ...)`` and ``run_workload_async``.

One chip: Climber as published (2 x 12 layers, d_model 256, 4 heads of 64,
a 2M-item catalog; random weights from ``--seed``) in the paper's base
scenario (512 history items, slates of up to 128 candidates).  The engine
runs the fused impl over an int8 history-KV pool with incremental history
and segment-packed tails.  Repeat-user sessions produce pool misses, hits
and incremental extensions; a few top-k generation requests follow on the
same engine.  Served scores are checked against the model's ``reference``
impl, jit-wrapped on the same chip (a native-pool engine, within
``TOL_REFERENCE``), and the int8 pool against the native one (within the
documented drift bound ``TOL_INT8``).  The compiled ``cached`` and
``decode`` executors must contain the Pallas kernel (``tpu_custom_call``).

Four chips: the same published model served by a single-device engine, a
(4,1) data-parallel engine and a (2,2) data x model engine (the mesh-tested
chunked impl, int8 pool); scores agree within ``TOL_MESH``, every device
holds the pool bytes its ``pool_bytes_used_shard{i}`` gauge reports, and
the scoring executors split the request batch over every chip.

``--rehearse`` runs the same phases on the CPU at the reduced size (use
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` with
``--four-chips``); it skips the checks only a TPU can pass and prints no
result line.  Without a TPU (and without ``--rehearse``) the script exits
non-zero and prints no result.  The last line of a passing chip run is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

#: native-pool fused serving vs the jit-wrapped reference impl (sigmoid
#: task probabilities; both run bf16 weights with f32 attention math)
TOL_REFERENCE = 2e-2
#: int8 pool vs native pool — the documented drift bound
#: (tests/test_pda_v2.py::INT8_SCORE_DRIFT_BOUND)
TOL_INT8 = 2e-2
#: (4,1) and (2,2) mesh engines vs the single-device engine
TOL_MESH = 2e-2

N_USERS = 12          # repeat users of the session traffic
N_ADVANCED = 4        # users whose history grows past the window in wave 3
GEN_STEPS = 4
GEN_K = 2
GEN_UNIVERSE = 64
BUCKETS = (128, 32)


class SmokeFailure(Exception):
    pass


def check(cond, msg: str):
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str):
    print(f"[smoke] {msg}", flush=True)


def session_waves(rng, n_history: int, max_slate: int, n_items: int):
    """Three waves of repeat-user scoring traffic.  Wave 1 meets an empty
    pool (misses: encode); wave 2 re-ranks fresh slates for the same users
    (hits); in wave 3 the first ``N_ADVANCED`` users' histories grew by one
    item beyond the model window (stale entries whose window is unchanged:
    incremental extension), the rest hit again.  Every third slate is a
    full ``max_slate``; the others are ragged."""
    hists = {u: rng.integers(0, n_items, n_history + 8).astype(np.int32)
             for u in range(N_USERS)}

    def slate(i):
        m = max_slate if i % 3 == 0 else int(rng.integers(8, max_slate))
        return rng.integers(0, n_items, m).astype(np.int32)

    def wave():
        return [{"history": hists[u], "user_id": u, "candidates": slate(u)}
                for u in range(N_USERS)]

    w1, w2 = wave(), wave()
    for u in range(N_ADVANCED):
        hists[u] = np.append(hists[u], np.int32(rng.integers(n_items)))
    return [w1, w2, wave()], hists


def check_scores(out, m: int, n_tasks: int, what: str):
    out = np.asarray(out, np.float32)
    check(out.shape == (m, n_tasks),
          f"{what}: scores of shape {out.shape}, expected {(m, n_tasks)}")
    check(bool(np.isfinite(out).all()) and out.min() >= 0 and out.max() <= 1,
          f"{what}: scores not finite probabilities")


def peak_bytes(dev):
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def build_model(args):
    import jax

    from repro.configs import climber
    from repro.launch.serve import print_memory_estimate, random_params
    from repro.models import build_model as build

    cfg = climber.config("reduced" if args.rehearse else "published",
                         d_model=64)
    n_history = 64 if args.rehearse else 512
    max_slate = 32 if args.rehearse else 128
    bundle = build(cfg)
    print_memory_estimate(cfg, bundle, n_history)
    t0 = time.perf_counter()
    params = jax.block_until_ready(random_params(bundle, args.seed))
    log(f"random weights from seed {args.seed} in "
        f"{time.perf_counter() - t0:.1f}s")
    return cfg, bundle, params, n_history, max_slate


def one_chip(args, cfg, bundle, params, n_history, max_slate):
    import jax

    from repro.serving import create_engine
    from repro.serving.api import TopKConfig
    from repro.serving.scheduler import run_workload_async

    on_tpu = not args.rehearse
    buckets = BUCKETS if on_tpu else (32, 8)
    n_tasks = cfg.climber.num_tasks
    rng = np.random.default_rng(args.seed)
    waves, hists = session_waves(rng, n_history, max_slate, cfg.vocab_size)
    engines = []
    try:
        t0 = time.perf_counter()
        eng = create_engine(
            "flame", bundle, params, n_history=n_history, buckets=buckets,
            impl="fused", history_cache=True, pool_dtype="int8",
            incremental_history=True, extend_buckets=(n_history,),
            pack_tails=True, generate=GEN_STEPS)
        engines.append(eng)
        fams = ", ".join(f"{k}:{v}" for k, v in eng.dso.families.items())
        log(f"int8 engine: {len(eng.dso.compiled)} executors ({fams}) "
            f"built in {eng.dso.build_time_s:.1f}s "
            f"(construction {time.perf_counter() - t0:.1f}s)")

        served = []
        for i, wave in enumerate(waves, 1):
            t0 = time.perf_counter()
            res = run_workload_async(eng, wave)
            check(res["resolved"] == len(wave),
                  f"wave {i}: {res['resolved']}/{len(wave)} resolved")
            for r, out in zip(wave, res["outputs"]):
                check_scores(out, len(r["candidates"]), n_tasks,
                             f"wave {i} user {r['user_id']}")
            served.append(res["outputs"])
            log(f"wave {i}: {len(wave)} requests, "
                f"{sum(len(r['candidates']) for r in wave)} candidates "
                f"in {time.perf_counter() - t0:.2f}s")

        universe = rng.choice(cfg.vocab_size, GEN_UNIVERSE,
                              replace=False).astype(np.int32)
        gen = [{"history": hists[u], "user_id": u, "candidates": universe,
                "generate": TopKConfig(k=GEN_K, steps=GEN_STEPS)}
               for u in range(N_ADVANCED, N_ADVANCED + 3)]
        t0 = time.perf_counter()
        res = run_workload_async(eng, gen)
        check(res["resolved"] == len(gen), "generation requests unresolved")
        for out in res["outputs"]:
            out = np.asarray(out)
            check(out.shape == (GEN_K, GEN_STEPS),
                  f"generation output of shape {out.shape}")
            check(bool(np.isin(out, universe).all()),
                  "generated ids outside the request's universe")
        log(f"generation: {len(gen)} top-{GEN_K} x {GEN_STEPS}-step "
            f"requests in {time.perf_counter() - t0:.2f}s, first: "
            f"{np.asarray(res['outputs'][0])[0].tolist()}")

        m = eng.metrics()
        n_req = sum(len(w) for w in waves) + len(gen)
        log(f"requests served: {int(m['requests'])} (expected {n_req}); "
            f"pool hits {m['pool_hits']}, misses {m['pool_misses']} "
            f"(stale {m['pool_stale']}), extensions {m['pool_extensions']}; "
            f"gen_tokens {m.get('gen_tokens', 0)}; "
            f"dso_packed_segments {m.get('dso_packed_segments', 0)}")
        check(int(m["requests"]) == n_req, "served request count")
        check(m["pool_misses"] >= N_USERS, "session traffic missed no entry")
        check(m["pool_hits"] >= N_USERS, "session traffic hit no entry")
        check(m["pool_extensions"] >= 1, "no incremental extension ran")
        for kind in ("cached", "decode"):
            hlo = eng.dso.compiled[(kind, buckets[0])].as_text()
            has = "tpu_custom_call" in hlo
            log(f"{kind} b{buckets[0]} executor: tpu_custom_call "
                f"{'present' if has else 'absent'}")
            check(has or not on_tpu,
                  f"the compiled {kind} executor runs no Pallas kernel")

        # correctness: a native-pool engine against the reference impl,
        # and the int8 pool against the native one, on wave-2 requests
        t0 = time.perf_counter()
        native = create_engine(
            "flame", bundle, params, n_history=n_history, buckets=buckets,
            impl="fused", history_cache=True, pool_dtype="native")
        engines.append(native)
        log(f"native-pool engine: {len(native.dso.compiled)} executors "
            f"built in {native.dso.build_time_s:.1f}s")
        ref = jax.jit(lambda p, h, c, s: bundle.prefill(
            p, {"history": h, "candidates": c, "side": s},
            impl="reference"))
        d_ref = d_int8 = 0.0
        spread = []
        for r, out8 in list(zip(waves[1], served[1]))[:3]:
            got = np.asarray(native.serve(r["history"], r["candidates"],
                                          user_id=r["user_id"]), np.float32)
            want = np.asarray(ref(
                params, r["history"][None, :n_history],
                r["candidates"][None],
                native._side_features(r["history"])), np.float32)[0]
            d_ref = max(d_ref, float(np.abs(got - want).max()))
            d_int8 = max(d_int8, float(np.abs(
                np.asarray(out8, np.float32) - got).max()))
            spread.append(float(want.std()))
        log(f"reference check ({time.perf_counter() - t0:.1f}s incl. "
            f"build): max|native - reference| {d_ref:.3e} "
            f"(tol {TOL_REFERENCE:g}), max|int8 - native| {d_int8:.3e} "
            f"(tol {TOL_INT8:g}); reference score std "
            f"{min(spread):.3e}..{max(spread):.3e}")
        check(d_ref <= TOL_REFERENCE, "native-pool scores off the reference")
        check(d_int8 <= TOL_INT8, "int8-pool drift past its bound")
        dev = jax.devices()[0]
        log(f"{dev}: peak_bytes_in_use {peak_bytes(dev)}")
    finally:
        for e in engines:
            e.shutdown()


def four_chips(args, cfg, bundle, params, n_history, max_slate):
    import jax

    from repro.launch.mesh import make_serving_mesh
    from repro.serving import create_engine
    from repro.serving.scheduler import run_workload_async

    devs = jax.devices()
    check(len(devs) == 4, f"--four-chips needs 4 devices, JAX has "
                          f"{len(devs)}")
    bucket = max_slate
    rng = np.random.default_rng(args.seed)
    waves, _ = session_waves(rng, n_history, max_slate, cfg.vocab_size)
    reqs = waves[0][:6] + waves[1][:6]           # misses, then hits
    results = {}
    for name, spec in (("single", ""), ("(4,1)", "4,1"), ("(2,2)", "2,2")):
        mesh = make_serving_mesh(spec) if spec else None
        t0 = time.perf_counter()
        eng = create_engine(
            "flame", bundle, params, n_history=n_history, buckets=(bucket,),
            impl="chunked", history_cache=True, pool_dtype="int8",
            mesh=mesh)
        try:
            build_s = eng.dso.build_time_s
            res = run_workload_async(eng, reqs)
            check(res["resolved"] == len(reqs), f"{name}: unresolved")
            outs = np.concatenate([np.asarray(o, np.float32).ravel()
                                   for o in res["outputs"]])
            results[name] = outs
            m = eng.metrics()
            line = (f"{name}: built in {build_s:.1f}s, served "
                    f"{len(reqs)} in {time.perf_counter() - t0:.1f}s, "
                    f"pool bytes {m['pool_bytes']}")
            if mesh is not None:
                model_ways = int(mesh.shape["model"])
                shard = [m[f"pool_bytes_used_shard{i}"]
                         for i in range(model_ways)]
                placed = eng.history_pool.device_bytes()
                line += (f", pool_bytes_used_shard{{i}} {shard}, placed "
                         f"per device {[placed.get(d, 0) for d in devs]}")
                check(m["pool_bytes"] > 0, f"{name}: nothing pooled")
                check(all(s * model_ways == m["pool_bytes"] for s in shard),
                      f"{name}: shard gauges do not split the pool bytes")
                if not args.rehearse:
                    check(set(placed) == set(devs),
                          f"{name}: pool bytes not on every device")
                    check(all(placed[d] == shard[0] for d in devs),
                          f"{name}: placed bytes differ from the gauges")
                ex = eng.dso.compiled[("cached", bucket)]
                out_sh = ex.output_shardings
                out_shape = ex.out_info.shape
                local = out_sh.shard_shape(out_shape)
                line += (f"; cached output {tuple(out_shape)} split "
                         f"{tuple(local)} over {len(out_sh.device_set)} "
                         f"devices")
                check(len(out_sh.device_set) == 4,
                      f"{name}: executor output not on every device")
                check(local[0] * mesh.shape["data"] == out_shape[0],
                      f"{name}: request batch not split over data ways")
            log(line)
        finally:
            eng.shutdown()
    base = results["single"]
    for name in ("(4,1)", "(2,2)"):
        d = float(np.abs(results[name] - base).max())
        log(f"{name} vs single device: max abs diff {d:.3e} "
            f"(tol {TOL_MESH:g}, bitwise {bool(d == 0.0)})")
        check(d <= TOL_MESH, f"{name} scores off the single-device engine")
    for d in devs:
        log(f"{d}: peak_bytes_in_use {peak_bytes(d)}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the mesh path on four chips")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at the reduced size; no result line")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    t_start = time.perf_counter()

    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"[smoke] FAILED: no repro package under {src}; run this "
              f"script from a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        print(f"[smoke] FAILED: JAX found no usable backend: {e}",
              file=sys.stderr)
        return 3
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    dev = devs[0]
    log(f"device: platform {dev.platform}, device_kind {dev.device_kind}, "
        f"count {len(devs)}; jax {jax.__version__}; compile cache "
        f"{cache_dir or 'off'}")
    if dev.platform != "tpu" and not args.rehearse:
        print(f"[smoke] FAILED: no TPU — JAX's devices are "
              f"{dev.platform} (run on a TPU host, or pass --rehearse for "
              f"the CPU rehearsal)", file=sys.stderr)
        return 3

    try:
        model = build_model(args)
        if args.four_chips:
            four_chips(args, *model)
        else:
            one_chip(args, *model)
    except SmokeFailure as e:
        print(f"[smoke] FAILED: {e}", file=sys.stderr)
        return 1
    log(f"passed in {time.perf_counter() - t_start:.1f}s")
    if args.rehearse:
        log("rehearsal: no result line")
        return 0
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
