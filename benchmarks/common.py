"""Shared benchmark utilities."""
import time

import jax
import numpy as np

from repro.configs import climber as climber_configs
from repro.models import build_model


def make_climber(d_model=128, layers=2, blocks=2, seed=0):
    """CPU-feasible Climber with the paper's structure (blocks/SUMI/head)."""
    cfg = climber_configs.config("reduced", d_model=d_model,
                                 layers_per_block=layers, num_blocks=blocks)
    bundle = build_model(cfg)
    params, _ = bundle.init(jax.random.key(seed))
    return cfg, bundle, params


def timeit(fn, *args, warmup=2, iters=8):
    """Median wall time (s) with block_until_ready."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def csv_row(name, us_per_call, derived=""):
    print(f"{name},{us_per_call:.1f},{derived}")
