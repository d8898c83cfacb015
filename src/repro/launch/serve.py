"""Serving launcher: any registered engine under synthetic traffic.

    PYTHONPATH=src python -m repro.launch.serve --engine flame \
        --requests 32 --buckets 64,32,16 --distribution jittered
    PYTHONPATH=src python -m repro.launch.serve --engine flame \
        --size published --history 512 --buckets 128,32 --impl fused \
        --history-cache --pool-dtype int8    # paper-size Climber (TPU)
    PYTHONPATH=src python -m repro.launch.serve --engine flame \
        --history-cache --pool-slots 128 --users 8 --requests 64
    PYTHONPATH=src python -m repro.launch.serve --engine flame \
        --generate topk --gen-steps 8     # generative candidate decode
    PYTHONPATH=src python -m repro.launch.serve --engine flame \
        --generate beam --beam-width 4
    PYTHONPATH=src python -m repro.launch.serve --engine flame \
        --generate topk --impl fused --pool-dtype int8   # FKE v2 decode
    PYTHONPATH=src python -m repro.launch.serve --engine text --arch gemma3-12b

Engines are selected by name through the API v2 registry
(repro.serving.api); requests are driven through ``submit`` so cross-request
chunk coalescing is exercised for the flame engine.
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.configs import climber as climber_configs
from repro.configs import reduced_config
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_serving_mesh
from repro.models import build_model
from repro.serving import ServeRequest, available_engines, create_engine
from repro.serving.api import BeamConfig, DegradationPolicy, TopKConfig
from repro.serving.faults import FaultInjector
from repro.serving.kv_cache import POOL_DTYPES, quantized_nbytes
from repro.serving.scheduler import (TrafficConfig, generate_traffic,
                                     run_workload_async)
from repro.training import checkpoint


def _print_metrics(tag: str, m: dict):
    print(f"[serve] {tag}: " + ", ".join(
        f"{k}={v:.2f}" if isinstance(v, float) else f"{k}={v}"
        for k, v in sorted(m.items())))


def _parse_kv_floats(spec: str, what: str) -> dict:
    """Parse ``name=value,name=value`` CLI maps (tier deadlines, mixes)."""
    out = {}
    for part in filter(None, (p.strip() for p in spec.split(","))):
        if "=" not in part:
            raise SystemExit(f"[serve] bad {what} entry {part!r} "
                             f"(want name=value)")
        k, v = part.split("=", 1)
        out[k.strip()] = float(v)
    return out


def random_params(bundle, seed: int):
    """Random weights from ``seed``, initialized in one compiled program
    (op-by-op init of a 2M-row embedding costs a compile per op)."""
    return jax.jit(lambda k: bundle.init(k)[0])(jax.random.key(seed))


def print_memory_estimate(cfg, bundle, n_history: int):
    """Print the bytes the rec serving path holds, from shapes alone
    (nothing is allocated): the params, the item embedding among them,
    and one user's history-KV pool entry per stored precision."""
    shapes = jax.eval_shape(lambda k: bundle.init(k)[0], jax.random.key(0))
    nbytes = lambda a: a.size * a.dtype.itemsize  # noqa: E731
    kv = bundle.history_kv_specs(shapes, n_history, batch=1)
    est = {"params": sum(nbytes(a) for a in jax.tree.leaves(shapes)),
           "embedding": nbytes(shapes["embed"]["embedding"]),
           **{f"pool_entry_{d}": quantized_nbytes(kv, d)
              for d in POOL_DTYPES}}
    c = cfg.climber
    print(f"[serve] {cfg.name}: {c.num_blocks}x{c.layers_per_block} layers, "
          f"d_model {cfg.d_model}, {cfg.n_heads} heads of {cfg.head_dim}, "
          f"vocab {cfg.vocab_size:,}, history {n_history}")
    print("[serve] estimated bytes: " + ", ".join(
        f"{k} {v / 1e6:.1f} MB" for k, v in est.items()))


def serve_text(args):
    cfg = reduced_config(args.arch)
    print(f"[serve] text engine on reduced {cfg.name}: {cfg.n_layers}L "
          f"d={cfg.d_model} pattern={cfg.layer_pattern}")
    bundle = build_model(cfg)
    params, _ = bundle.init(jax.random.key(0))
    eng = create_engine("text", bundle, params, batch=2, max_len=128)
    rng = np.random.default_rng(0)
    futs = [eng.submit(ServeRequest(
        history=rng.integers(0, cfg.vocab_size, 16).astype(np.int32),
        n_tokens=args.tokens)) for _ in range(args.requests)]
    for f in futs:
        r = f.result()
        print(f"[serve] req {r.request_id}: generated {r.output.tolist()} "
              f"in {r.latency_s * 1e3:.0f} ms")
    _print_metrics("metrics", eng.metrics())
    eng.shutdown()


def serve_rec(args):
    cfg = climber_configs.config(args.size, d_model=args.d_model)
    bundle = build_model(cfg)
    print_memory_estimate(cfg, bundle, args.history)
    params = random_params(bundle, args.seed)
    if args.ckpt:
        params, step = checkpoint.restore(args.ckpt, params)
        print(f"[serve] restored checkpoint @ step {step}")

    gen_mode = getattr(args, "generate", "none")
    if gen_mode != "none":
        if not args.history_cache:
            print("[serve] --generate implies --history-cache (beams live "
                  "in the pooled-KV plane); enabling it")
            args.history_cache = True

    kw = dict(n_history=args.history, feature_mode=args.feature_mode,
              max_pending=args.max_pending, impl=args.impl,
              mesh=make_serving_mesh(args.mesh, args.model_parallel),
              buckets=tuple(int(b) for b in args.buckets.split(",")),
              n_streams=args.streams, coalesce=not args.no_coalesce,
              max_batch=args.max_batch,
              window_s=args.window_ms * 1e-3,
              n_workers=args.concurrency,
              history_cache=args.history_cache,
              pool_slots=args.pool_slots,
              pool_budget_bytes=(int(args.pool_budget_mb * 2**20)
                                 if args.pool_budget_mb else None),
              pool_dtype=args.pool_dtype,
              pool_placement=args.pool_placement,
              pool_spill_bytes=int(args.pool_spill_mb * 2**20),
              incremental_history=args.incremental_history,
              extend_buckets=(tuple(int(b) for b in
                                    args.extend_buckets.split(",")
                                    if b.strip())
                              if args.extend_buckets.strip() else None),
              extend_refresh_limit=args.extend_refresh_limit,
              pack_tails=args.pack_tails,
              pack_rows=args.pack_rows if args.pack_rows > 0 else None,
              deadline_s=args.deadline_ms * 1e-3)
    # ---- overload discipline / fault tolerance (ISSUE 9) ----
    tier_defaults = None
    if args.slo_tier_defaults.strip():
        tier_defaults = {k: v * 1e-3 for k, v in _parse_kv_floats(
            args.slo_tier_defaults, "--slo-tier-defaults").items()}
    degradation = None
    if args.degrade > 0:
        degradation = DegradationPolicy(threshold_s=args.degrade * 1e-3)
    faults = None
    if args.fault_spec.strip():
        faults = FaultInjector.parse(args.fault_spec,
                                     seed=args.fault_seed)
    kw.update(admission=args.admission, shed_policy=args.shed_policy,
              slo_tier_defaults=tier_defaults,
              watchdog_grace_s=args.watchdog_grace_ms * 1e-3,
              degradation=degradation, faults=faults)
    if gen_mode != "none":
        kw.update(generate=args.gen_steps, gen_vocab=args.gen_vocab)
    eng = create_engine(args.engine, bundle, params, **kw)
    fams = ", ".join(f"{k}:{v}" for k, v in eng.dso.families.items())
    print(f"[serve] executor pool built in {eng.dso.build_time_s:.2f}s "
          f"(families {fams}, impl {args.impl}, "
          f"batch axis {eng.dso.policy.batch}, "
          f"coalesce={'on' if eng.dso.policy.enabled else 'off'}, "
          f"pack_tails={'on' if args.pack_tails else 'off'}, "
          f"deadline={args.deadline_ms:g}ms)")
    if eng.mesh is not None:
        print(f"[serve] mesh: data={eng.mesh.shape['data']} x "
              f"model={eng.mesh.shape['model']} over "
              f"{len(jax.devices())} {jax.default_backend()} device(s)")
    if args.history_cache:
        budget = (f"{args.pool_budget_mb:g} MB budget"
                  if args.pool_budget_mb else "no byte budget")
        print(f"[serve] history-KV pool: {args.pool_slots} slots, "
              f"{budget}, dtype {args.pool_dtype}, "
              f"placement {args.pool_placement}, incremental="
              f"{'on' if args.incremental_history else 'off'}")

    tier_mix = _parse_kv_floats(args.slo_mix, "--slo-mix") \
        if args.slo_mix.strip() else None
    tc = TrafficConfig(
        candidate_counts=tuple(int(c) for c in args.counts.split(",")),
        distribution=args.distribution, n_requests=args.requests,
        n_history=args.history, seed=0, n_users=args.users,
        tier_mix=tier_mix)
    reqs = generate_traffic(tc, n_items=cfg.vocab_size)
    if gen_mode != "none":
        # generative decode: the traffic's ragged candidate slates become
        # per-request token universes (zipf/jittered slate sizes -> ragged
        # decode dispatches), and each request asks for top-k or beam
        # generation instead of scoring
        gen_eos = args.gen_eos if args.gen_eos >= 0 else None
        gen_cfg = (TopKConfig(k=args.beam_width, steps=args.gen_steps,
                              eos=gen_eos)
                   if gen_mode == "topk" else
                   BeamConfig(width=args.beam_width, steps=args.gen_steps,
                              eos=gen_eos))
        for r in reqs:
            r["generate"] = gen_cfg
        print(f"[serve] generative decode: {gen_mode} width "
              f"{args.beam_width} x {args.gen_steps} steps, per-request "
              f"token universes from the candidate slates")
    # chaos / overload runs tolerate rejections and injected failures —
    # the liveness contract they DO assert is zero hung futures: every
    # submitted request resolves, errors included, inside the timeout
    chaos = bool(args.fault_spec.strip()) or args.shed_policy != "none"
    res = run_workload_async(eng, reqs,
                             arrival_gap_s=args.arrival_gap_ms * 1e-3,
                             tolerate_errors=chaos)
    unit = "gen tokens/s" if gen_mode != "none" else "items/s"
    print(f"[serve] {res['requests']} requests | "
          f"{res['throughput_items_per_s']:.0f} {unit} | "
          f"p50 {res['p50_latency_ms']:.1f} ms | "
          f"p99 {res['p99_latency_ms']:.1f} ms")
    if chaos:
        hint = (f" retry_after~{res['retry_after_mean_ms']:.0f}ms "
                f"(x{res['retry_after_hinted']})"
                if res.get("retry_after_hinted") else "")
        print(f"[serve] overload/chaos accounting: "
              f"resolved={res['resolved']} rejected={res['rejected']} "
              f"failed={res['failed']} hung={res['hung']}{hint}")
        if res["hung"]:
            _print_metrics("engine metrics", eng.metrics())
            raise SystemExit(f"[serve] LIVENESS VIOLATION: {res['hung']} "
                             f"future(s) never resolved")
    if gen_mode != "none":
        for i, out in enumerate(res["outputs"][:3]):
            best = [t for t in out[0].tolist() if t >= 0]
            print(f"[serve] req {i}: best sequence {best}")
    _print_metrics("engine metrics", eng.metrics())
    eng.shutdown()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--engine", default="flame",
                    choices=list(available_engines()))
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--history", type=int, default=128)
    ap.add_argument("--buckets", default="64,32,16")
    ap.add_argument("--counts", default="16,32,64")
    ap.add_argument("--distribution", default="uniform",
                    choices=["uniform", "zipf", "jittered", "lognormal"])
    ap.add_argument("--feature-mode", default="sync",
                    choices=["off", "sync", "async"])
    ap.add_argument("--impl", default="chunked",
                    choices=["reference", "chunked", "pallas", "fused"],
                    help="attention impl for the model forward (chunked "
                         "avoids O(S^2) score materialization on CPU; "
                         "fused is the FKE candidate-scoring engine — "
                         "cached scoring reads quantized pool KV and the "
                         "dedup row index in-kernel)")
    ap.add_argument("--history-cache", action="store_true",
                    help="split the SUMI forward: pool per-user history KV, "
                         "serve candidate-only executors on pool hits")
    ap.add_argument("--pool-slots", type=int, default=256,
                    help="history-KV pool capacity (entries, LRU-evicted)")
    ap.add_argument("--pool-budget-mb", type=float, default=0.0,
                    help="history-KV pool byte budget in MB (0 = entry "
                         "bound only); LRU-evicts by bytes_used")
    ap.add_argument("--pool-dtype", default="native",
                    choices=["native", "bf16", "int8"],
                    help="stored precision of pool entries (int8 uses "
                         "per-head scales; ~2x users per byte budget vs "
                         "the bf16-native entries, ~4x vs f32)")
    ap.add_argument("--pool-placement", default="device",
                    choices=["device", "host"],
                    help="device keeps entries as JAX device arrays (no "
                         "host round-trip per dispatch); host is the "
                         "legacy PR 2 behavior")
    ap.add_argument("--pool-spill-mb", type=float, default=0.0,
                    help="host-RAM second-tier budget in MB absorbing "
                         "pool evictions (0 = no spill tier)")
    ap.add_argument("--incremental-history", action="store_true",
                    help="on stale pool hits sharing a window prefix with "
                         "the cached entry, re-encode only the suffix + "
                         "side token against the cached prefix K/V")
    ap.add_argument("--extend-buckets", default="",
                    help="comma list of trusted-prefix lengths for the "
                         "extend executor family (empty = the default "
                         "ladder n,3n/4,n/2; prefixes below n/2 re-encode "
                         "— the crossover policy)")
    ap.add_argument("--extend-refresh-limit", type=int, default=0,
                    help="force a full re-encode after this many "
                         "incremental extensions of one pool entry (bounds "
                         "requantization drift under --pool-dtype int8; "
                         "0 = uncapped)")
    ap.add_argument("--pack-tails", action="store_true",
                    help="DSO v2 segment packing (needs --history-cache): "
                         "partial tail chunks from different requests pack "
                         "into shared (1, bucket) rows, each candidate "
                         "segment steered to its own user's pooled history "
                         "KV — reclaims the padding the greedy bucket "
                         "split dispatches on non-uniform traffic")
    ap.add_argument("--pack-rows", type=int, default=0,
                    help="row capacity of the packed executors (packed "
                         "rows are dense, so fewer rows carry the same "
                         "candidate throughput at less executor cost; "
                         "--max-batch still sizes how many distinct users "
                         "one packed dispatch can steer to; 0 = auto "
                         "max_batch/4)")
    ap.add_argument("--deadline-ms", type=float, default=0.0,
                    help="default per-request deadline budget: pending "
                         "chunks flush earliest-deadline-first and the "
                         "DSO stops collecting co-riders once its cost "
                         "model says waiting longer would miss the "
                         "earliest deadline (0 = no deadlines; misses "
                         "surface as the deadline_misses metric)")
    ap.add_argument("--admission", default="edf", choices=["edf", "fifo"],
                    help="admission queue order: edf serves earliest "
                         "absolute deadline first (ties: better SLO tier, "
                         "then arrival); fifo is the arrival-order baseline")
    ap.add_argument("--slo-tier-defaults", default="",
                    help="per-tier default deadline budgets in ms, e.g. "
                         "'interactive=50,standard=250,bulk=2000'; applied "
                         "when a request carries no explicit deadline "
                         "(empty = only --deadline-ms applies)")
    ap.add_argument("--shed-policy", default="none",
                    choices=["none", "tiered"],
                    help="tiered: when the queue is at depth or the "
                         "EWMA-predicted wait blows an arrival's budget, "
                         "fail the worst lower-priority queued request "
                         "(ShedError, shed_{tier} counters) instead of "
                         "blocking everyone")
    ap.add_argument("--degrade", type=float, default=0.0,
                    help="graceful-degradation queue-delay threshold in ms "
                         "(0 = off): a sustained delay EWMA above it steps "
                         "the service level down — 1: flush coalescing "
                         "windows immediately, 2: + bulk generation at "
                         "half width/steps, 3: + bulk encodes become "
                         "cached-hit-or-shed; recovery reverses the steps")
    ap.add_argument("--slo-mix", default="",
                    help="traffic tier mix as weights, e.g. "
                         "'interactive=0.2,standard=0.5,bulk=0.3' "
                         "(empty = all standard)")
    ap.add_argument("--watchdog-grace-ms", type=float, default=0.0,
                    help="fail any future still unresolved this long past "
                         "its deadline with WatchdogTimeout (0 = no "
                         "watchdog); the liveness backstop under faults")
    ap.add_argument("--fault-spec", default="",
                    help="chaos injection arms, e.g. "
                         "'dispatch:0.2,stall:0.1:0.02,evict:0.1' "
                         "(see repro.serving.faults); deterministic per "
                         "--fault-seed.  The launcher then tolerates "
                         "failures but exits non-zero if any future hangs")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="PRNG seed for --fault-spec arms")
    ap.add_argument("--generate", default="none",
                    choices=["none", "topk", "beam"],
                    help="generative candidate decode (needs "
                         "--history-cache, auto-enabled): serve "
                         "autoregressive top-k / beam generation over the "
                         "item vocabulary from pooled history KV instead "
                         "of scoring candidate slates; the traffic's "
                         "candidate ids become per-request token universes")
    ap.add_argument("--gen-steps", type=int, default=8,
                    help="generated sequence length (also sizes the "
                         "decode executors' KV headroom)")
    ap.add_argument("--beam-width", type=int, default=4,
                    help="hypotheses kept per step (beam width for "
                         "--generate beam, k for --generate topk)")
    ap.add_argument("--gen-eos", type=int, default=-1,
                    help="EOS item id: a hypothesis emitting it finishes "
                         "early, and once every hypothesis has finished "
                         "the remaining decode rounds are skipped "
                         "(gen_early_exits metric; -1 = no EOS)")
    ap.add_argument("--gen-vocab", type=int, default=512,
                    help="fallback token-universe size when a generative "
                         "request carries no candidate restriction")
    ap.add_argument("--mesh", default="",
                    help="serve the flame executors over a 'data,model' "
                         "device mesh, e.g. --mesh 2,2: the request batch "
                         "axis is sharded over data ways and attention "
                         "heads over model ways, with pooled history KV "
                         "committed to the same layout (empty = no mesh; "
                         "on CPU hosts set XLA_FLAGS="
                         "--xla_force_host_platform_device_count=K first)")
    ap.add_argument("--model-parallel", type=int, default=0,
                    help="shortcut for --mesh: shard KV heads over N model "
                         "ways, data ways = devices // N")
    ap.add_argument("--users", type=int, default=0,
                    help="repeat-user traffic: draw requests from this many "
                         "users with stable histories (0 = unique users)")
    ap.add_argument("--streams", type=int, default=2)
    ap.add_argument("--concurrency", type=int, default=4,
                    help="pipeline worker threads")
    ap.add_argument("--no-coalesce", action="store_true",
                    help="disable cross-request chunk coalescing")
    ap.add_argument("--max-batch", type=int, default=4,
                    help="coalescing fill target / executor batch axis")
    ap.add_argument("--window-ms", type=float, default=2.0,
                    help="coalescing time window")
    ap.add_argument("--max-pending", type=int, default=64,
                    help="admission queue bound (backpressure)")
    ap.add_argument("--arrival-gap-ms", type=float, default=0.0,
                    help="max random gap between request arrivals")
    ap.add_argument("--size", default="reduced",
                    choices=climber_configs.SIZES,
                    help="rec engines: Climber as published (2x12 layers, "
                         "d_model 256, 2M items) or the CPU-sized reduced "
                         "variant (2x2 layers, --d-model, 50k items)")
    ap.add_argument("--d-model", type=int, default=128,
                    help="d_model of --size reduced")
    ap.add_argument("--seed", type=int, default=0,
                    help="PRNG seed of the random weights")
    ap.add_argument("--ckpt", default=None, help="restore params from here")
    ap.add_argument("--arch", default="gemma3-12b",
                    help="text engine: reduced config name")
    ap.add_argument("--tokens", type=int, default=12,
                    help="text engine: tokens per request")
    args = ap.parse_args()
    enable_compile_cache()

    if args.engine == "text":
        serve_text(args)
    else:
        serve_rec(args)


if __name__ == "__main__":
    main()
