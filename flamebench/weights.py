"""Climber weights made by the benchmark from ``--seed``.

One jitted call draws every leaf on the device, in the type it is served
in (bfloat16; the per-layer temperatures in float32).  The program under
test and the plain reference both read this tree, so the reference never
takes weights the program made.  :func:`check_layout` holds the tree to the
program's own parameter layout, so a program whose layout moved fails the
run instead of serving a tree it does not read.
"""
from __future__ import annotations

import zlib

import numpy as np

N_SIDE_FEATURES = 12
POS_TABLE = 8192


def layout(model: dict) -> dict:
    """Leaf path -> (shape, dtype name, init rule) for a Climber config."""
    d, f, h = model["d_model"], model["d_ff"], model["n_heads"]
    hkv, hd = model["n_kv_heads"], model["head_dim"]
    c = model["climber"]
    nl, nb, e, t = c["layers_per_block"], c["num_blocks"], \
        c["num_experts_head"], c["num_tasks"]
    out = {
        "embed/embedding": ((model["vocab_size"], d), "bfloat16", 0.02),
        "pos_embed": ((POS_TABLE, d), "bfloat16", 0.02),
        "side_proj": ((N_SIDE_FEATURES, d), "bfloat16", "fan_in"),
        "gate_w": ((nb, d), "bfloat16", 0.02),
        "gate_b": ((nb, d), "bfloat16", "bias"),
        "out_norm/scale": ((d,), "bfloat16", "scale"),
        "out_norm/bias": ((d,), "bfloat16", "bias"),
        "experts_w1": ((e, d, d), "bfloat16", 1 / np.sqrt(d)),
        "experts_w2": ((e, d, d), "bfloat16", 1 / np.sqrt(d)),
        "task_gates": ((t, d, e), "bfloat16", 1 / np.sqrt(d)),
        "task_towers": ((t, d), "bfloat16", 1 / np.sqrt(d)),
    }
    for i in range(nb):
        b = f"blocks/b{i}"
        out.update({
            f"{b}/norm1/scale": ((nl, d), "bfloat16", "scale"),
            f"{b}/norm1/bias": ((nl, d), "bfloat16", "bias"),
            f"{b}/norm2/scale": ((nl, d), "bfloat16", "scale"),
            f"{b}/norm2/bias": ((nl, d), "bfloat16", "bias"),
            f"{b}/attn/wq": ((nl, d, h, hd), "bfloat16", 1 / np.sqrt(d)),
            f"{b}/attn/wk": ((nl, d, hkv, hd), "bfloat16", 1 / np.sqrt(d)),
            f"{b}/attn/wv": ((nl, d, hkv, hd), "bfloat16", 1 / np.sqrt(d)),
            f"{b}/attn/wo": ((nl, h, hd, d), "bfloat16",
                             1 / np.sqrt(h * hd)),
            f"{b}/ffn/w_up": ((nl, d, f), "bfloat16", 1 / np.sqrt(d)),
            f"{b}/ffn/w_down": ((nl, f, d), "bfloat16", 1 / np.sqrt(f)),
            f"{b}/temp": ((nl, 1), "float32", "temp"),
        })
    return out


def _nest(flat: dict) -> dict:
    tree: dict = {}
    for path, v in flat.items():
        node = tree
        *parents, leaf = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = v
    return tree


def make_params(model: dict, seed: int):
    """The weight tree on the default device, from ``seed`` (any integer)."""
    import jax
    import jax.numpy as jnp

    lay = layout(model)
    s = int(seed) % 2**64

    def draw(lo, hi):
        key = jax.random.fold_in(jax.random.key(lo), hi)
        flat = {}
        for path, (shape, dtype, rule) in lay.items():
            k = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
            dt = jnp.dtype(dtype)
            z = jax.random.normal(k, shape, dt)
            if rule == "scale":
                v = 1.0 + 0.1 * z
            elif rule == "bias":
                v = 0.1 * z
            elif rule == "temp":
                v = 0.55 + 0.1 * z
            elif rule == "fan_in":
                v = z / np.sqrt(shape[0])
            else:
                v = z * rule
            flat[path] = v.astype(dt)
        return _nest(flat)

    return jax.jit(draw)(np.uint32(s & 0xFFFFFFFF), np.uint32(s >> 32))


def check_layout(params, program_shapes) -> None:
    """Raise unless ``params`` has the program's leaves, shapes and dtypes."""
    import jax

    ours = {jax.tree_util.keystr(p): (tuple(a.shape), str(a.dtype))
            for p, a in jax.tree_util.tree_leaves_with_path(params)}
    theirs = {jax.tree_util.keystr(p): (tuple(a.shape), str(a.dtype))
              for p, a in
              jax.tree_util.tree_leaves_with_path(program_shapes)}
    if ours != theirs:
        diff = sorted(set(ours.items()) ^ set(theirs.items()))[:8]
        raise RuntimeError(f"weight layout differs from the program's: "
                           f"{diff}")
