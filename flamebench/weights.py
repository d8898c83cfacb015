"""Weights made by the benchmark from ``--seed``.

One jitted call draws every leaf of a family's layout (``layout(model)`` of
``flamebench/families/<family>.py``) on the device, in the type it is
served in.  The program under test and the plain reference both read this
tree, so the reference never takes weights the program made.
:func:`check_layout` holds the tree to the program's own parameter layout,
so a program whose layout moved fails the run instead of serving a tree it
does not read.
"""
from __future__ import annotations

import zlib

import numpy as np


def _nest(flat: dict) -> dict:
    tree: dict = {}
    for path, v in flat.items():
        node = tree
        *parents, leaf = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = v
    return tree


def make_params(lay: dict, seed: int):
    """The weight tree of layout ``lay`` (leaf path -> (shape, dtype name,
    init rule)) on the default device, from ``seed`` (any integer).  A rule
    is a standard deviation, or ``"scale"`` (1 + 0.1 z), ``"bias"``
    (0.1 z), ``"temp"`` (0.55 + 0.1 z) or ``"fan_in"`` (z / sqrt of the
    leading dimension)."""
    import jax
    import jax.numpy as jnp

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
