"""ModelBundle: a uniform functional API over every assigned architecture.

    bundle = build_model(cfg)
    params, specs = bundle.init(key)
    loss, metrics = bundle.loss_fn(params, batch)            # train shapes
    logits = bundle.prefill(params, batch)                   # prefill shapes
    logits, caches = bundle.decode_step(params, caches, batch)  # decode shapes
    bundle.input_specs(shape_cfg) / bundle.cache_init(...)   # dry-run stand-ins

Families: text decoders (dense/moe/hybrid/ssm/vlm) share one implementation
(vlm prepends stub patch embeddings); audio is encoder-decoder; the GR
models climber (the paper's) and hstu are provided by repro.core.climber
and repro.core.hstu and dispatched here.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import sharding as shd
from repro.models import encdec as E
from repro.models import layers as L
from repro.models import transformer as T
from repro.types import ModelConfig, ShapeConfig


@dataclasses.dataclass(frozen=True)
class HostPlane:
    """One host-side input of the split serving surface, one row per
    request: the batch key the surface reads it under, where the serving
    engine takes it from, and its row's width (0: one value per history
    window position) and dtype.

    Sources: ``window`` (the request's history window ids),
    ``side_features`` (the PDA side-feature mean over the history) and
    ``action_times`` (the per-action timestamps: the request's own, else
    the PDA user-history service's)."""

    name: str
    source: str
    width: int = 0
    dtype: str = "int32"


@dataclasses.dataclass
class ModelBundle:
    cfg: ModelConfig
    init: Callable
    loss_fn: Callable                    # (params, batch) -> (loss, metrics)
    prefill: Callable                    # (params, batch) -> logits
    decode_step: Callable                # (params, caches, batch) -> (logits, caches)
    input_specs: Callable                # (ShapeConfig) -> {name: ShapeDtypeStruct}
    input_logical: Callable              # (ShapeConfig) -> {name: logical tuple}
    cache_init: Callable                 # (batch, max_len) -> (caches, specs)
    # split-forward serving surface (GR models; None for decode families):
    # prefill == score_candidates(params, encode_history(params, hist), cand)
    encode_history: Optional[Callable] = None   # (params, batch) -> HistoryKV
    score_candidates: Optional[Callable] = None  # (params, kv, cand) -> scores
    history_kv_specs: Optional[Callable] = None  # (params, n_hist, b) -> specs
    # incremental suffix extension: re-encode only the changed window suffix
    # + side token against a cached HistoryKV (PDA v2 stale-hit path)
    extend_history: Optional[Callable] = None   # (params, kv, batch, *, prefix_len) -> HistoryKV
    # generative decode surface (ISSUE 8): one vocab-scoring step against
    # padded beam caches + the KV append that grows them — a decode step is
    # score_candidates(M=V) at the beam's current length plus append_token
    decode_logits: Optional[Callable] = None    # (params, kv, cand, lengths) -> probs [B,M,T]
    append_token: Optional[Callable] = None     # (params, kv, tok, lengths) -> HistoryKV
    # the host planes the split surface's batches carry, in executor
    # argument order (encode/extend/full executors take one array each)
    host_planes: Tuple[HostPlane, ...] = ()


def cross_entropy(logits, targets, mask):
    """Mean CE over masked positions; computed in f32."""
    lf = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(lf, axis=-1)
    gold = jnp.take_along_axis(lf, targets[..., None], axis=-1)[..., 0]
    nll = (logz - gold) * mask
    return nll.sum() / jnp.maximum(mask.sum(), 1.0)


# ---------------------------------------------------------------------------
# text decoder family (dense / moe / hybrid / ssm / vlm)
# ---------------------------------------------------------------------------

def _build_text(cfg: ModelConfig) -> ModelBundle:
    is_vlm = cfg.modality == "vision"

    def init(key):
        k1, k2 = jax.random.split(key)
        params = {"embed": L.embed_init(k1, cfg),
                  "stack": T.stack_init(k2, cfg)}
        if is_vlm:
            k3 = jax.random.fold_in(key, 3)
            params["projector"] = L.dense_init(k3, (cfg.d_model, cfg.d_model),
                                               ("embed", "act_model"))
        return L.split_params(params)

    def embed_inputs(params, batch):
        x = L.embed(params["embed"], batch["tokens"], cfg)
        if is_vlm and "patch_embeds" in batch:
            pe = jnp.einsum("bpd,de->bpe", batch["patch_embeds"].astype(x.dtype),
                            params["projector"])
            x = jnp.concatenate([pe, x], axis=1)
        return x

    def forward(params, batch, *, mode, impl="chunked", remat=False,
                caches=None, cur_len=None):
        x = embed_inputs(params, batch)
        x = shd.constrain_ctx(x, "batch", None, None)
        b, s = x.shape[:2]
        if mode == "decode":
            positions = jnp.broadcast_to(batch["cur_index"][None, None], (b, 1))
        else:
            positions = jnp.broadcast_to(jnp.arange(s), (b, s))
        x, new_caches, aux = T.stack_apply(
            params["stack"], x, cfg, mode=mode, positions=positions,
            caches=caches, cur_len=cur_len, impl=impl, remat=remat)
        logits = L.unembed(params["embed"], x, cfg)
        logits = shd.constrain_ctx(logits, "batch", None, "vocab")
        return logits, new_caches, aux

    def loss_fn(params, batch, impl="chunked"):
        logits, _, aux = forward(params, batch, mode="train", impl=impl,
                                 remat=True)
        n_front = batch["patch_embeds"].shape[1] if (is_vlm and "patch_embeds"
                                                     in batch) else 0
        lg = logits[:, n_front:]
        targets = batch["tokens"][:, 1:]
        mask = jnp.ones_like(targets, jnp.float32)
        loss = cross_entropy(lg[:, :-1], targets, mask)
        total = loss + aux["load_balance_loss"] + aux["router_z_loss"]
        return total, {"ce_loss": loss, **aux}

    def prefill(params, batch, impl="chunked", caches=None):
        logits, new_caches, _ = forward(params, batch, mode="prefill",
                                        impl=impl, caches=caches,
                                        cur_len=batch["tokens"].shape[1])
        if caches is not None:
            return logits, new_caches
        return logits

    def decode_step(params, caches, batch, impl="reference"):
        cur_len = batch["cur_index"] + 1
        logits, new_caches, _ = forward(params, batch, mode="decode",
                                        impl=impl, caches=caches,
                                        cur_len=cur_len)
        return logits, new_caches

    def cache_init(batch, max_len, dtype=jnp.bfloat16, quant=False):
        return T.init_caches(cfg, batch, max_len, dtype=dtype, quant=quant)

    def input_specs(shape: ShapeConfig):
        b = shape.global_batch
        if shape.kind == "decode":
            specs = {"tokens": jax.ShapeDtypeStruct((b, 1), jnp.int32),
                     "cur_index": jax.ShapeDtypeStruct((), jnp.int32)}
            return specs
        s = shape.seq_len
        specs = {}
        if is_vlm:
            p = min(cfg.frontend_tokens, s // 2)
            specs["patch_embeds"] = jax.ShapeDtypeStruct((b, p, cfg.d_model),
                                                         jnp.bfloat16)
            s = s - p
        specs["tokens"] = jax.ShapeDtypeStruct((b, s), jnp.int32)
        return specs

    def input_logical(shape: ShapeConfig):
        lg = {"tokens": ("batch", None)}
        if shape.kind == "decode":
            lg["cur_index"] = ()
        elif is_vlm:
            lg["patch_embeds"] = ("batch", None, None)
        return lg

    return ModelBundle(cfg, init, loss_fn, prefill, decode_step,
                       input_specs, input_logical, cache_init)


# ---------------------------------------------------------------------------
# audio encoder-decoder family
# ---------------------------------------------------------------------------

def _frames_for(cfg: ModelConfig, seq_len: int) -> int:
    return max(8, seq_len // 4)      # stub conv frontend downsamples 4x


def _build_audio(cfg: ModelConfig) -> ModelBundle:

    def init(key):
        k1, k2 = jax.random.split(key)
        params = {"embed": L.embed_init(k1, cfg), **E.encdec_init(k2, cfg)}
        return L.split_params(params)

    def loss_fn(params, batch, impl="chunked"):
        enc_out = E.encode(params, batch["frames"], cfg, impl=impl)
        x = L.embed(params["embed"], batch["tokens"], cfg)
        b, s = x.shape[:2]
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
        x, _ = E.decode_stack(params, x, enc_out, cfg, mode="train",
                              positions=positions, impl=impl, remat=True)
        logits = L.unembed(params["embed"], x, cfg)
        targets = batch["tokens"][:, 1:]
        loss = cross_entropy(logits[:, :-1], targets,
                             jnp.ones_like(targets, jnp.float32))
        return loss, {"ce_loss": loss}

    def prefill(params, batch, impl="chunked", caches=None):
        enc_out = E.encode(params, batch["frames"], cfg, impl=impl)
        x = L.embed(params["embed"], batch["tokens"], cfg)
        b, s = x.shape[:2]
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
        x, new_caches = E.decode_stack(params, x, enc_out, cfg, mode="prefill",
                                       positions=positions, caches=caches,
                                       cur_len=s, impl=impl)
        logits = L.unembed(params["embed"], x, cfg)
        if caches is not None:
            xk, xv = E.cross_kv(params, enc_out, cfg)
            new_caches = {**new_caches, "xk": xk, "xv": xv}
            return logits, new_caches
        return logits

    def decode_step(params, caches, batch, impl="reference"):
        x = L.embed(params["embed"], batch["tokens"], cfg)
        b = x.shape[0]
        positions = jnp.broadcast_to(batch["cur_index"][None, None], (b, 1))
        x, new_caches = E.decode_stack(params, x, None, cfg, mode="decode",
                                       positions=positions, caches=caches,
                                       cur_len=batch["cur_index"] + 1, impl=impl)
        logits = L.unembed(params["embed"], x, cfg)
        return logits, new_caches

    def cache_init(batch, max_len, dtype=jnp.bfloat16, n_frames=None,
                   quant=False):
        del quant  # enc-dec caches stay bf16 (cross-attn K/V reused per step)
        n_frames = n_frames or _frames_for(cfg, 4096)
        return E.init_dec_caches(cfg, batch, max_len, n_frames, dtype)

    def input_specs(shape: ShapeConfig):
        b = shape.global_batch
        if shape.kind == "decode":
            return {"tokens": jax.ShapeDtypeStruct((b, 1), jnp.int32),
                    "cur_index": jax.ShapeDtypeStruct((), jnp.int32)}
        f = _frames_for(cfg, shape.seq_len)
        return {"frames": jax.ShapeDtypeStruct((b, f, cfg.d_model), jnp.bfloat16),
                "tokens": jax.ShapeDtypeStruct((b, shape.seq_len), jnp.int32)}

    def input_logical(shape: ShapeConfig):
        lg = {"tokens": ("batch", None)}
        if shape.kind == "decode":
            lg["cur_index"] = ()
        else:
            lg["frames"] = ("batch", None, None)
        return lg

    return ModelBundle(cfg, init, loss_fn, prefill, decode_step,
                       input_specs, input_logical, cache_init)


def build_model(cfg: ModelConfig) -> ModelBundle:
    if cfg.family == "climber":
        from repro.core.climber import build_climber
        return build_climber(cfg)
    if cfg.family == "hstu":
        from repro.core.hstu import build_hstu
        return build_hstu(cfg)
    if cfg.enc_dec:
        return _build_audio(cfg)
    return _build_text(cfg)
