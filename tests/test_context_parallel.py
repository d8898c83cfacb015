"""Context-parallel attention (§Perf hillclimb 3): exactness vs reference.

Multi-shard case runs in a subprocess with 8 forced host devices (2x4 mesh)
so the main pytest process keeps 1 device."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.mesh import make_mesh
from repro.models.attention import (context_parallel_attention,
                                    reference_attention)

SUBPROCESS_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, "src")
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_mesh
from repro.models.attention import context_parallel_attention, reference_attention

mesh = make_mesh((2, 4), ("data", "model"))
ks = jax.random.split(jax.random.key(0), 3)
q = jax.random.normal(ks[0], (2, 256, 4, 32))
k = jax.random.normal(ks[1], (2, 256, 2, 32))
v = jax.random.normal(ks[2], (2, 256, 2, 32))
for mode, w in [("sliding", 64), ("causal", 0), ("full", 0)]:
    out = jax.jit(lambda a, b, c: context_parallel_attention(
        a, b, c, mode, window=w, mesh=mesh))(q, k, v)
    ref = reference_attention(q, k, v, mode, window=w)
    err = float(np.abs(np.asarray(out) - np.asarray(ref)).max())
    assert err < 1e-5, (mode, err)
print("OK")
"""


@pytest.mark.parametrize("mode,window", [("sliding", 64), ("causal", 0),
                                         ("full", 0)])
def test_cp_attention_single_device(mode, window):
    mesh = make_mesh((1, 1), ("data", "model"))
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (2, 256, 4, 32))
    k = jax.random.normal(ks[1], (2, 256, 2, 32))
    v = jax.random.normal(ks[2], (2, 256, 2, 32))
    out = context_parallel_attention(q, k, v, mode, window=window, mesh=mesh)
    ref = reference_attention(q, k, v, mode, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-6, rtol=2e-6)


def test_cp_attention_multi_shard_subprocess():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", SUBPROCESS_SCRIPT],
                         cwd=os.path.join(os.path.dirname(__file__), ".."),
                         env=env, capture_output=True, text=True, timeout=300)
    assert res.returncode == 0, res.stderr[-2000:]
    assert "OK" in res.stdout


def test_cp_halo_masks_wraparound():
    """Shard 0's halo comes from the LAST shard (ring ppermute) and must be
    fully masked: changing the tail of the sequence must not affect the
    first window of outputs under sliding attention."""
    mesh = make_mesh((1, 1), ("data", "model"))
    ks = jax.random.split(jax.random.key(1), 3)
    q = jax.random.normal(ks[0], (1, 128, 2, 16))
    k = jax.random.normal(ks[1], (1, 128, 2, 16))
    v = jax.random.normal(ks[2], (1, 128, 2, 16))
    out1 = context_parallel_attention(q, k, v, "sliding", window=32, mesh=mesh)
    k2 = k.at[:, -16:].set(99.0)
    v2 = v.at[:, -16:].set(99.0)
    out2 = context_parallel_attention(q, k2, v2, "sliding", window=32,
                                      mesh=mesh)
    np.testing.assert_array_equal(np.asarray(out1[:, :32]),
                                  np.asarray(out2[:, :32]))
