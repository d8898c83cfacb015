"""All-to-all MoE dispatch (the §Perf optimized path) == GSPMD path.

The multi-shard case runs in a subprocess with 8 forced host devices so the
main pytest process keeps seeing 1 device."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.mesh import make_mesh
from repro.models.layers import split_params
from repro.models.moe import moe_apply, moe_apply_a2a, moe_init
from tests.test_moe import make_cfg

SUBPROCESS_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, "src")
sys.path.insert(0, ".")
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_mesh
from repro.models.layers import split_params
from repro.models.moe import moe_apply, moe_apply_a2a, moe_init
from tests.test_moe import make_cfg

cfg = make_cfg(e=8, k=2, cf=8.0)
params, _ = split_params(moe_init(jax.random.key(0), cfg))
x = jax.random.normal(jax.random.key(1), (8, 16, 64), jnp.float32)
mesh = make_mesh((8,), ("data",))
ref, aux_ref = moe_apply(params, x, cfg)
out, aux = jax.jit(lambda p, xx: moe_apply_a2a(p, xx, cfg, mesh=mesh,
                                               axis="data"))(params, x)
err = float(np.abs(np.asarray(out) - np.asarray(ref)).max())
scale = float(np.abs(np.asarray(ref)).max())
assert err / scale < 2e-2, (err, scale)
d_ref = float(aux_ref["dropped_fraction"])
d_a2a = float(aux["dropped_fraction"])
assert d_a2a <= 0.05, d_a2a
print("OK", err, scale)
"""


def test_a2a_single_shard_matches_gspmd():
    """On a 1-device mesh the a2a path must equal the scatter path exactly
    (all_to_all over a size-1 axis is the identity)."""
    cfg = make_cfg(e=4, k=2, cf=8.0)
    params, _ = split_params(moe_init(jax.random.key(0), cfg))
    x = jax.random.normal(jax.random.key(1), (2, 32, 64), jnp.float32)
    mesh = make_mesh((1,), ("data",))
    ref, _ = moe_apply(params, x, cfg)
    out, aux = moe_apply_a2a(params, x, cfg, mesh=mesh, axis="data")
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=2e-2, rtol=2e-2)
    assert float(aux["dropped_fraction"]) < 0.05


def test_a2a_multi_shard_matches_gspmd_subprocess():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", SUBPROCESS_SCRIPT],
                         cwd=os.path.join(os.path.dirname(__file__), ".."),
                         env=env, capture_output=True, text=True, timeout=300)
    assert res.returncode == 0, res.stderr[-2000:]
    assert "OK" in res.stdout
