"""Compile the serving hot path for a described TPU v5e chip (no chip needed).

The TPU compiler is installed with JAX; it compiles for a chip that is
described and not attached.  Interpret-mode tests cannot show what it
refuses (tile alignment, VMEM limits, programs that do not fit), so these
tests lower at Climber's published widths and assert the compiled program
holds the Pallas kernel (``tpu_custom_call``).

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and the suite runs
under several workers.  Keep every such compile in this one file.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import climber as climber_configs
from repro.kernels.fused_score import ops as fs_ops
from repro.models import build_model
from repro.serving.kv_cache import raw_kv_specs

# Climber base widths: batch rows, slate bucket, heads, head dim, and the
# pooled history length the kernel tests use
B, M, H, D, S = 4, 128, 4, 64, 513
HBM_BYTES = 16 * 2**30          # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip are written to the persistent cache but
    # cannot be read back without one: keep the cache out of these tests
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_on)
    # traces made here took the TPU branches; drop them before CPU tests
    # of this worker retrace the same functions
    jax.clear_caches()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_args(one_chip, store_dtype, *, cand_len=M, rows=B,
                 index_shape=None, lengths=False):
    """q/k/v candidate operands [B,M,H,D], pooled history [U,S,Hkv,D] in
    the pool's stored dtype (+ int8 scales), optional row index and
    per-row lengths."""
    bf16 = jnp.bfloat16
    args = [_spec((B, cand_len, H, D), bf16, one_chip),
            _spec((rows, S, H, D), store_dtype, one_chip),
            _spec((rows, S, H, D), store_dtype, one_chip),
            _spec((B, cand_len, H, D), bf16, one_chip),
            _spec((B, cand_len, H, D), bf16, one_chip)]
    kw = {}
    if store_dtype == jnp.int8:
        kw["k_scale"] = _spec((rows, 1, H, 1), jnp.float32, one_chip)
        kw["v_scale"] = _spec((rows, 1, H, 1), jnp.float32, one_chip)
    if index_shape is not None:
        kw["row_index"] = _spec(index_shape, jnp.int32, one_chip)
    if lengths:
        args.append(_spec((rows,), jnp.int32, one_chip))
    return args, kw


def _assert_kernel_compiles(fn, args, kw):
    compiled = jax.jit(fn).lower(*args, **kw).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("store", ["int8", "bf16"])
@pytest.mark.parametrize("mode", ["cached", "extend", "decode"])
def test_fused_score_kernel_compiles(one_chip, mode, store):
    dtype = jnp.int8 if store == "int8" else jnp.bfloat16
    fn = {"cached": fs_ops.fused_cached_attention,
          "extend": fs_ops.fused_extend_attention,
          "decode": fs_ops.fused_decode_attention}[mode]
    fn = functools.partial(fn, path="kernel", interpret=False)
    # dedup row index over U = B unique pool rows; extend re-encodes a
    # short suffix against the pooled prefix
    args, kw = _kernel_args(one_chip, dtype, index_shape=(B,),
                            cand_len=8 if mode == "extend" else M,
                            lengths=mode == "decode")
    _assert_kernel_compiles(fn, args, kw)


@pytest.mark.parametrize("store", ["int8", "bf16"])
def test_fused_score_packed_aligned_kernel_compiles(one_chip, store):
    """Segment-packed 2-D row index with 8-aligned segments: q blocks of 8
    rows, each steered to its own pool row."""
    dtype = jnp.int8 if store == "int8" else jnp.bfloat16
    fn = functools.partial(fs_ops.fused_cached_attention, path="kernel",
                           interpret=False)
    args, kw = _kernel_args(one_chip, dtype, index_shape=(B, M))
    _assert_kernel_compiles(fn, args, kw)


def test_full_depth_fused_score_step_fits_one_chip(one_chip, monkeypatch):
    """One cached-scoring step of published Climber (2 x 12 layers, 2M-item
    embedding) with the fused impl over an int8 pool, as the engine's
    ``cached`` executor runs it: the kernel is in the program and the
    program fits one chip's HBM."""
    from repro.kernels.fused_score import kernel as fs_kernel
    monkeypatch.setattr(fs_ops, "_auto_path", lambda: "kernel")
    monkeypatch.setattr(fs_kernel, "default_interpret", lambda: False)

    cfg = climber_configs.config("published")
    bundle = build_model(cfg)
    params = jax.eval_shape(lambda k: bundle.init(k)[0], jax.random.key(0))
    kv = raw_kv_specs(bundle.history_kv_specs(params, 512, batch=B), "int8")
    on_chip = lambda t: jax.tree.map(  # noqa: E731
        lambda s: _spec(s.shape, s.dtype, one_chip), t)

    def step(params, kv, idx, cands):
        return bundle.score_candidates(params, kv, cands, impl="fused",
                                       row_index=idx)

    compiled = jax.jit(step).lower(
        on_chip(params), on_chip(kv), _spec((B,), jnp.int32, one_chip),
        _spec((B, M), jnp.int32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total < HBM_BYTES, mem


# ---------------------------------------------------------------------------
# the pointwise (HSTU) variant
# ---------------------------------------------------------------------------

HSTU_S = 4096                   # hstu-large-4k's history window


@pytest.mark.parametrize("mode", ["cached", "extend"])
def test_hstu_kernel_compiles_under_its_own_op_name(one_chip, mode):
    """The pointwise variant compiles to a Pallas call whose HLO op is
    named ``%_hstu_kernel_call`` (the trace tells its calls apart by it):
    cached over an int8 pool with a packed, 8-aligned seg index; extend
    of a 1,024-action suffix over a 3,072-action prefix."""
    import re

    bf16, f32 = jnp.bfloat16, jnp.float32
    if mode == "cached":
        fn = functools.partial(fs_ops.hstu_cached_attention, n_norm=4096.0,
                               path="kernel", interpret=False)
        args = [_spec((1, M, H, D), bf16, one_chip),
                _spec((B, HSTU_S, H, D), jnp.int8, one_chip),
                _spec((B, HSTU_S, H, D), jnp.int8, one_chip),
                _spec((1, M, H, D), bf16, one_chip),
                _spec((1, M, H, D), bf16, one_chip),
                _spec((B, 1, HSTU_S), f32, one_chip),
                _spec((), f32, one_chip)]
        kw = {"k_scale": _spec((B, 1, H, 1), f32, one_chip),
              "v_scale": _spec((B, 1, H, 1), f32, one_chip),
              "row_index": _spec((1, M), jnp.int32, one_chip)}
    else:
        fn = functools.partial(fs_ops.hstu_extend_attention, n_norm=4096.0,
                               path="kernel", interpret=False)
        suf, pre = 1024, HSTU_S - 1024
        args = [_spec((B, suf, H, D), bf16, one_chip),
                _spec((B, pre, H, D), bf16, one_chip),
                _spec((B, pre, H, D), bf16, one_chip),
                _spec((B, suf, H, D), bf16, one_chip),
                _spec((B, suf, H, D), bf16, one_chip),
                _spec((B, suf, pre), f32, one_chip),
                _spec((B, suf, suf), f32, one_chip)]
        kw = {}
    text = jax.jit(fn).lower(*args, **kw).compile().as_text()
    calls = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert calls and all(re.match(r"\s*%_hstu_kernel_call\b", ln)
                         for ln in calls), calls


def test_hstu_packed_cached_is_one_call_reading_k_hist_sixth(one_chip):
    """A packed 512-candidate cached call over 4,096 int8 positions (wide q
    blocks, history copied by the kernel) is one ``%_hstu_kernel_call``
    whose operands keep their order: the benchmark's reader takes the
    pooled K from the sixth."""
    import re

    from flamebench.families.hstu import kernel_call

    bf16, f32 = jnp.bfloat16, jnp.float32
    fn = functools.partial(fs_ops.hstu_cached_attention, n_norm=4096.0,
                           path="kernel", bk=512, interpret=False)
    cand = _spec((1, 512, H, D), bf16, one_chip)
    hist = _spec((B, HSTU_S, H, D), jnp.int8, one_chip)
    text = jax.jit(fn).lower(
        cand, hist, hist, cand, cand, _spec((B, 1, HSTU_S), f32, one_chip),
        _spec((), f32, one_chip),
        k_scale=_spec((B, 1, H, 1), f32, one_chip),
        v_scale=_spec((B, 1, H, 1), f32, one_chip),
        row_index=_spec((1, 512), jnp.int32, one_chip)
    ).compile().as_text()
    calls = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert len(calls) == 1 and re.match(r"\s*%_hstu_kernel_call\b",
                                        calls[0]), calls
    assert kernel_call(calls[0]) == {
        "rows": 1, "heads": H, "q_rows": 512, "pool_rows": B,
        "s_pad": HSTU_S, "kv_bytes": 1}


def test_full_depth_hstu_steps_fit_one_chip(one_chip, monkeypatch):
    """hstu-large at a 4,096-action window (8 layers, d 256, 4 x 64 heads,
    2M-item catalog): the engine's int8-epilogue encode of four rows and a
    packed 512-candidate cached step over four int8 pool rows compile, the
    scoring step holds the pointwise kernel, and each fits one chip."""
    from repro.kernels.fused_score import bias_kernel
    from repro.kernels.fused_score import kernel as fs_kernel
    from repro.serving.kv_cache import quantize_kv_graph
    from repro.types import HSTUConfig, ModelConfig
    monkeypatch.setattr(fs_ops, "_auto_path", lambda: "kernel")
    monkeypatch.setattr(fs_kernel, "default_interpret", lambda: False)
    monkeypatch.setattr(bias_kernel, "default_interpret", lambda: False)

    cfg = ModelConfig(name="hstu-large-4k", family="hstu", n_layers=8,
                      d_model=256, n_heads=4, n_kv_heads=4, head_dim=64,
                      d_ff=0, vocab_size=2_000_000, norm="layernorm",
                      hstu=HSTUConfig(max_seq_len=HSTU_S))
    bundle = build_model(cfg)
    params = jax.eval_shape(lambda k: bundle.init(k)[0], jax.random.key(0))
    kv = raw_kv_specs(bundle.history_kv_specs(params, HSTU_S, batch=B),
                      "int8")
    on_chip = lambda t: jax.tree.map(  # noqa: E731
        lambda s: _spec(s.shape, s.dtype, one_chip), t)
    plane = _spec((B, HSTU_S), jnp.int32, one_chip)

    def encode(params, history, times):
        return quantize_kv_graph(bundle.encode_history(
            params, {"history": history, "times": times}, impl="fused"),
            "int8")

    def score(params, kv, seg, cands):
        return bundle.score_candidates(params, kv, cands, impl="fused",
                                       row_index=seg)

    compiled = [
        jax.jit(encode).lower(on_chip(params), plane, plane).compile(),
        jax.jit(score).lower(
            on_chip(params), on_chip(kv),
            _spec((1, 512), jnp.int32, one_chip),
            _spec((1, 512), jnp.int32, one_chip)).compile()]
    assert "%_hstu_kernel_call" in compiled[1].as_text()
    # the bias tables are read with no XLA gather (one index at a time on
    # the TPU): only embedding rows and the packed row index (64 entries)
    import math
    import re
    for c in compiled:
        for dims in re.findall(r"= \w+\[([\d,]+)\]\S* gather\(",
                               c.as_text()):
            shape = [int(x) for x in dims.split(",")]
            assert shape[-1] == cfg.d_model or math.prod(shape) <= 64, dims
    for c in compiled:
        mem = c.memory_analysis()
        total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                 + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
        assert total < HBM_BYTES, mem


@pytest.mark.parametrize("n_q,n_k,offset", [(1, HSTU_S, HSTU_S),
                                            (512, HSTU_S, 3584)])
def test_hstu_relative_bias_kernel_compiles(one_chip, n_q, n_k, offset):
    """The lane-gather bias kernel at the cached path's shape (one row per
    pool row) and at an encode block's (512 queries over 4,096 keys)."""
    import functools as ft
    fn = ft.partial(fs_ops.hstu_relative_bias, offset=offset, path="kernel",
                    interpret=False)
    text = jax.jit(fn).lower(
        _spec((HSTU_S + 1,), jnp.bfloat16, one_chip),
        _spec((129,), jnp.bfloat16, one_chip),
        _spec((B, n_q), jnp.int32, one_chip),
        _spec((B, n_k), jnp.int32, one_chip)).compile().as_text()
    assert "tpu_custom_call" in text
