"""Sort-based fixed-capacity top-k mixture of experts.

Dispatch is the standard sort/scatter formulation (no [T,E,C] one-hot
blow-up, which would be ~100TB at kimi-k2 scale):

  1. router logits -> top_k expert ids + gates per token
  2. flatten (token, k) assignments, sort by expert id
  3. position-within-expert via running counts; drop past capacity
  4. scatter rows into a [E, C, d] buffer, batched expert GEMMs
  5. gather back, gate-weight, sum over k

The [E, C, d] buffer carries logical axes ("experts", None, None) so experts
shard over the data/pod axes (expert parallelism); the scatter/gather lower to
all-to-all style collectives under GSPMD — visible in the roofline's
collective term and targeted by the §Perf hillclimb.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import sharding as shd
from repro.models import layers as L
from repro.models.ffn import ffn_init, ffn_apply


def moe_init(key, cfg, stacked: int = 0):
    m = cfg.moe
    d, f, e = cfg.d_model, m.d_ff_expert, m.num_experts
    ks = jax.random.split(key, 5)
    n_gate = cfg.activation == "swiglu"
    shape_up = (e, d, f)
    logical_up = ("experts", "embed", "expert_mlp")
    p = {
        "router": L.dense_init(ks[0], (d, e), ("embed", None),
                               stacked=stacked, dtype=jnp.float32),
        "w_up": L.dense_init(ks[1], shape_up, logical_up, stacked=stacked,
                             fan_in_axes=(1,)),
        "w_down": L.dense_init(ks[2], (e, f, d), ("experts", "expert_mlp", "embed"),
                               stacked=stacked, fan_in_axes=(1,)),
    }
    if n_gate:
        p["w_gate"] = L.dense_init(ks[3], shape_up, logical_up, stacked=stacked,
                                   fan_in_axes=(1,))
    if m.num_shared_experts:
        p["shared"] = ffn_init(ks[4], cfg, d_ff=f * m.num_shared_experts,
                               stacked=stacked)
    return p


def _capacity(n_tokens: int, m) -> int:
    c = int(np.ceil(n_tokens * m.top_k * m.capacity_factor / m.num_experts))
    return max(8, -(-c // 8) * 8)  # pad to multiple of 8


def moe_dispatch(params, x, cfg, impl: str = "xla") -> Tuple[jnp.ndarray, dict]:
    """Dispatch-mode switch: GSPMD scatter/gather vs explicit all-to-all
    (flags.MOE_DISPATCH, requires an active mesh context)."""
    from repro import flags
    from repro import sharding as shd
    active = shd._ACTIVE.get()
    if flags.MOE_DISPATCH.get() == "a2a" and active is not None:
        mesh, _rules = active
        # a2a shards tokens over EVERY mesh axis; fall back when the token
        # count doesn't divide (e.g. single-token decode steps)
        if cfg.moe.num_experts % mesh.shape["data"] == 0 and \
                int(np.prod(x.shape[:2])) % mesh.size == 0:
            return moe_apply_a2a(params, x, cfg, mesh=mesh, axis="data",
                                 impl=impl)
    return moe_apply(params, x, cfg, impl=impl)


def moe_apply(params, x, cfg, impl: str = "xla") -> Tuple[jnp.ndarray, dict]:
    """x [B,S,d] -> (out [B,S,d], aux {load_balance_loss, router_z_loss, ...})."""
    m = cfg.moe
    b, s, d = x.shape
    t = b * s
    e, k = m.num_experts, m.top_k
    cap = _capacity(t, m)
    xt = x.reshape(t, d)

    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32),
                        params["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gates, expert_idx = jax.lax.top_k(probs, k)            # [t,k]
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

    # ---- aux losses (Switch-style) ----
    me = probs.mean(axis=0)                                 # mean router prob
    ce = jnp.zeros((e,), jnp.float32).at[expert_idx.reshape(-1)].add(1.0) / (t * k)
    load_balance = e * jnp.sum(me * ce)
    z_loss = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)

    # ---- sort-based dispatch ----
    flat_expert = expert_idx.reshape(-1)                    # [t*k]
    order = jnp.argsort(flat_expert)
    sorted_expert = flat_expert[order]
    token_of = order // k                                   # source token row
    counts = jnp.zeros((e,), jnp.int32).at[sorted_expert].add(1)
    starts = jnp.cumsum(counts) - counts
    pos = jnp.arange(t * k) - starts[sorted_expert]
    keep = pos < cap
    dest = jnp.where(keep, sorted_expert * cap + pos, e * cap)  # overflow -> scratch row

    buf = jnp.zeros((e * cap + 1, d), x.dtype).at[dest].set(xt[token_of])
    buf = buf[:-1].reshape(e, cap, d)
    buf = shd.constrain_ctx(buf, "experts", None, None)

    # ---- expert GEMMs ----
    up = jnp.einsum("ecd,edf->ecf", buf, params["w_up"])
    if "w_gate" in params:
        g = jnp.einsum("ecd,edf->ecf", buf, params["w_gate"])
        h = jax.nn.silu(g.astype(jnp.float32)) * up.astype(jnp.float32)
    else:
        h = jax.nn.gelu(up.astype(jnp.float32))
    h = h.astype(x.dtype)
    out_buf = jnp.einsum("ecf,efd->ecd", h, params["w_down"])
    out_buf = shd.constrain_ctx(out_buf, "experts", None, None).reshape(e * cap, d)

    # ---- combine ----
    gathered = jnp.where(keep[:, None], out_buf[jnp.clip(dest, 0, e * cap - 1)], 0.0)
    combined = jnp.zeros((t, d), x.dtype).at[token_of].add(
        gathered * gates.reshape(-1)[order][:, None].astype(x.dtype))

    if "shared" in params:
        combined = combined + ffn_apply(params["shared"], xt, cfg, impl=impl).reshape(t, d)

    aux = {"load_balance_loss": load_balance * m.load_balance_loss,
           "router_z_loss": z_loss * m.router_z_loss,
           "dropped_fraction": 1.0 - keep.mean()}
    return combined.reshape(b, s, d), aux


# ---------------------------------------------------------------------------
# all-to-all expert-parallel dispatch (§Perf hillclimb: the GSPMD scatter
# formulation above lowers to full-dispatch-buffer all-reduces; this
# shard_map path exchanges only the routed tokens over the ICI).
# ---------------------------------------------------------------------------

def moe_apply_a2a(params, x, cfg, *, mesh, axis: str = "data",
                  impl: str = "xla") -> Tuple[jnp.ndarray, dict]:
    """Expert-parallel MoE with explicit all_to_all dispatch.

    Experts are sharded over ``axis`` (E % n_shards == 0).  Each shard
    routes its local tokens, builds a [n_shards, E_local, C, d] send buffer
    (capacity per (shard, expert)), exchanges it with all_to_all, runs its
    local experts, and reverses the exchange.  ICI traffic per layer is
    2 * tokens * top_k * d * capacity_factor bytes — independent of E.
    """
    from jax.sharding import PartitionSpec as P

    m = cfg.moe
    b, s, d = x.shape
    e, k = m.num_experts, m.top_k
    n_shards = mesh.shape[axis]
    assert e % n_shards == 0, (e, n_shards)
    e_local = e // n_shards
    # tokens are sharded over every mesh axis (data x model x pod): each
    # device runs its own token slice against its data-shard's experts, so
    # expert GEMM FLOPs stay 1/devices each — no model-axis replication.
    token_axes = tuple(a for a in mesh.axis_names)
    t_local = (b * s) // mesh.size
    # per (shard, global expert) capacity
    cap = int(np.ceil(t_local * k * m.capacity_factor / e))
    cap = max(4, -(-cap // 4) * 4)

    router = params["router"]
    w_up, w_down = params["w_up"], params["w_down"]
    w_gate = params.get("w_gate")
    has_gate = w_gate is not None
    if not has_gate:
        w_gate = w_up  # placeholder with identical sharding

    def local_fn(xt, router, w_up, w_gate, w_down):
        # xt [t_local, d]; expert weights [e_local, d, f] (this shard's)
        tl = xt.shape[0]
        logits = jnp.einsum("td,de->te", xt.astype(jnp.float32),
                            router.astype(jnp.float32))
        probs = jax.nn.softmax(logits, axis=-1)
        gates, expert_idx = jax.lax.top_k(probs, k)
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

        flat_expert = expert_idx.reshape(-1)
        order = jnp.argsort(flat_expert)
        sorted_expert = flat_expert[order]
        token_of = order // k
        counts = jnp.zeros((e,), jnp.int32).at[sorted_expert].add(1)
        starts = jnp.cumsum(counts) - counts
        pos = jnp.arange(tl * k) - starts[sorted_expert]
        keep = pos < cap
        dest = jnp.where(keep, sorted_expert * cap + pos, e * cap)

        send = jnp.zeros((e * cap + 1, d), xt.dtype).at[dest].set(xt[token_of])
        send = send[:-1].reshape(n_shards, e_local * cap, d)
        # exchange: shard i sends its tokens for shard j's experts to shard j
        recv = jax.lax.all_to_all(send, axis, 0, 0, tiled=False)
        # recv [n_shards, e_local*cap, d] -> [e_local, n_shards*cap, d]
        buf = recv.reshape(n_shards, e_local, cap, d).transpose(1, 0, 2, 3) \
            .reshape(e_local, n_shards * cap, d)

        up = jnp.einsum("ecd,edf->ecf", buf, w_up)
        if has_gate:
            g = jnp.einsum("ecd,edf->ecf", buf, w_gate)
            h = jax.nn.silu(g.astype(jnp.float32)) * up.astype(jnp.float32)
        else:
            h = jax.nn.gelu(up.astype(jnp.float32))
        out = jnp.einsum("ecf,efd->ecd", h.astype(xt.dtype), w_down)

        # reverse exchange
        back = out.reshape(e_local, n_shards, cap, d).transpose(1, 0, 2, 3) \
            .reshape(n_shards, e_local * cap, d)
        got = jax.lax.all_to_all(back, axis, 0, 0, tiled=False)
        got = got.reshape(e * cap, d)
        gathered = jnp.where(keep[:, None],
                             got[jnp.clip(dest, 0, e * cap - 1)], 0.0)
        combined = jnp.zeros((tl, d), xt.dtype).at[token_of].add(
            gathered * gates.reshape(-1)[order][:, None].astype(xt.dtype))

        me = probs.mean(axis=0)
        ce = jnp.zeros((e,), jnp.float32).at[flat_expert].add(1.0) / (tl * k)
        lb = jax.lax.pmean(e * jnp.sum(me * ce), token_axes)
        zl = jax.lax.pmean(jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2),
                           token_axes)
        dropped = jax.lax.pmean(1.0 - keep.mean(), token_axes)
        return combined, lb, zl, dropped

    xt = x.reshape(b * s, d)
    combined, lb, zl, dropped = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(token_axes, None), P(None, None), P(axis, None, None),
                  P(axis, None, None), P(axis, None, None)),
        out_specs=(P(token_axes, None), P(), P(), P()),
        check_vma=False,
    )(xt, router, w_up, w_gate, w_down)

    if "shared" in params:
        combined = combined + ffn_apply(params["shared"], xt, cfg,
                                        impl=impl).reshape(b * s, d)
    aux = {"load_balance_loss": lb * m.load_balance_loss,
           "router_z_loss": zl * m.router_z_loss,
           "dropped_fraction": dropped}
    return combined.reshape(b, s, d), aux
