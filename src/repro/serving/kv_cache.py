"""KV state managers for serving.

Two families live here:

``KVCacheManager``   batched decode-cache slot manager for the text
                     architectures (continuous-batching-lite): one pooled
                     cache pytree, per-slot lengths, prefill-insert/release.

``HistoryKVPool``    byte-budgeted, optionally quantized, two-tier LRU pool
                     of cached *history-side* SUMI K/V for GR serving — the
                     PDA v2 realization of the MTServe / "One Pool, Two
                     Caches" hierarchical-cache idea.  The SUMI mask makes
                     the history prefix self-contained, so its per-layer K/V
                     depend only on the user history; FlameEngine encodes it
                     once, parks it here, and repeat/session-re-rank traffic
                     runs candidate-only executors against the pooled entry.

Pool contract (PDA v2)
----------------------
*Keys and staleness.*  Entries are keyed by a stable user identity (or a
content hash of the history) and carry a **fingerprint** — a hash of the
full upstream history array.  A key hit whose fingerprint differs means the
user's history advanced since the encode: the entry is *stale* and must not
be scored against.  ``lookup`` drops it but can hand the dropped entry back
as an **extension basis** (K/V + the history window it encoded) so the
engine can re-encode only the changed suffix instead of the whole window.

*Capacity.*  ``slots`` bounds the entry count, ``budget_bytes`` bounds the
primary tier's stored bytes (entries vary in size with ``n_history``; the
paper-scale entry is ~6.5 MB/user, so bytes — not counts — are the real HBM
constraint).  Eviction is strictly LRU; both limits may be combined.  An
entry that alone exceeds ``budget_bytes`` is *rejected* (counted in
``rejects``) rather than admitted, so ``bytes_used <= budget_bytes`` is a
hard invariant.

*Placement.*  ``placement="device"`` keeps stored leaves as JAX device
arrays (HBM-resident next to the weights — dispatches consume them without
a host round-trip); ``placement="host"`` stores host numpy (the PR 2
behavior, kept for A/B benchmarking).  ``spill_bytes > 0`` enables a
host-RAM second tier: primary-tier evictions demote there instead of
dropping, and a later hit promotes back (counted as ``spill_hits``) —
"One Pool, Two Caches" within one process.

*Quantization.*  ``dtype`` selects the stored precision: ``"native"``
(compute dtype), ``"bf16"``, or ``"int8"`` with a per-(layer, head)
absmax scale.  Only floating leaves (K/V) are quantized: integer leaves
(an entry's action times) are stored as they are.  Dequantization happens at lookup (on device under device
placement), so executor input signatures never change; int8 roughly
quadruples users-per-budget vs f32 at a bounded score drift (asserted in
tests/test_pda_v2.py).
"""
from __future__ import annotations

import collections
import dataclasses
import math
import threading
from typing import Dict, Hashable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.dso import stage


@dataclasses.dataclass
class Slot:
    active: bool = False
    length: int = 0
    request_id: int = -1
    tokens: Optional[list] = None


class KVCacheManager:
    def __init__(self, bundle, batch: int, max_len: int, **kw):
        self.bundle = bundle
        self.batch = batch
        self.max_len = max_len
        self.caches, self.cache_specs = bundle.cache_init(batch, max_len, **kw)
        self.slots = [Slot() for _ in range(batch)]

    def free_slots(self) -> List[int]:
        return [i for i, s in enumerate(self.slots) if not s.active]

    def assign(self, request_id: int, prompt_len: int) -> int:
        free = self.free_slots()
        if not free:
            raise RuntimeError("no free KV-cache slots")
        i = free[0]
        self.slots[i] = Slot(True, prompt_len, request_id, [])
        return i

    def release(self, slot: int):
        self.slots[slot] = Slot()

    def write_prefill(self, slot: int, caches_one):
        """Insert a single-sequence cache (batch=1, stacked-layer axis 0) into
        batch position ``slot`` of the pooled cache."""
        self.caches = jax.tree.map(
            lambda f, o: jax.lax.dynamic_update_slice_in_dim(
                f, o.astype(f.dtype), slot, axis=1),
            self.caches, caches_one)

    def lengths(self) -> np.ndarray:
        return np.array([s.length for s in self.slots], np.int32)


# ---------------------------------------------------------------------------
# quantization hooks (shared by pool entries; per-(layer, head) scaling)
# ---------------------------------------------------------------------------

POOL_DTYPES = ("native", "bf16", "int8")


@dataclasses.dataclass
class _QuantLeaf:
    """One quantized KV leaf: values + (for int8) per-(layer, head) scale.

    KV leaves are [B, L, S, Hkv, D]; the int8 scale reduces over the
    position and feature axes (S, D) and keeps (B, L, 1, Hkv, 1), so every
    attention head of every layer owns its own dynamic range.  ``scale is
    None`` marks a plain bf16 cast.  ``dtype`` is the original compute
    dtype to dequantize back to (executor input signatures are fixed, so a
    natively-f32 leaf must come back f32 — a natively-bf16 leaf stored
    under ``dtype="bf16"`` round-trips losslessly)."""

    q: object          # int8 (or bf16) values, original shape
    scale: object      # f32 absmax scale, reduced shape; None for bf16
    dtype: object      # original jnp dtype to dequantize back to


def _scale_axes(ndim: int) -> Tuple[int, ...]:
    if ndim >= 4:
        return (ndim - 3, ndim - 1)          # (S, D) of [..., S, Hkv, D]
    return tuple(range(ndim))                # fallback: one global scale


def _stored_as_is(a, dtype: str) -> bool:
    """Whether a leaf keeps its own dtype in the pool: every leaf of a
    ``native`` pool, and integer leaves (action times) of any pool."""
    return dtype == "native" or not jnp.issubdtype(a.dtype, jnp.floating)


def quantize_leaf(a, dtype: str):
    """Quantize one KV leaf to the pool's stored precision.

    Returns the stored representation: the array itself for ``native`` (or
    an integer leaf), a bf16 cast for ``bf16``, or a :class:`_QuantLeaf`
    for ``int8``."""
    if _stored_as_is(a, dtype):
        return a
    a = jnp.asarray(a)
    if dtype == "bf16":
        return _QuantLeaf(a.astype(jnp.bfloat16), None, a.dtype)
    if dtype == "int8":
        af = a.astype(jnp.float32)
        scale = jnp.maximum(
            jnp.max(jnp.abs(af), axis=_scale_axes(a.ndim), keepdims=True),
            1e-8)
        q = jnp.clip(jnp.round(af / scale * 127.0), -127, 127).astype(jnp.int8)
        return _QuantLeaf(q, scale, a.dtype)
    raise ValueError(f"pool dtype must be one of {POOL_DTYPES}, got {dtype!r}")


def dequantize_leaf(stored):
    """Invert :func:`quantize_leaf` back to the original dtype.  Native
    (unwrapped) leaves pass through untouched (no host/device migration);
    host-resident quantized leaves dequantize in numpy (cheap elementwise,
    no JAX dispatch), device-resident ones on device."""
    if isinstance(stored, _QuantLeaf):
        xp = np if isinstance(stored.q, np.ndarray) else jnp
        if stored.scale is None:               # bf16 cast
            return xp.asarray(stored.q).astype(stored.dtype)
        return (xp.asarray(stored.q, np.float32)
                * (xp.asarray(stored.scale) / 127.0)).astype(stored.dtype)
    return stored


def quantize_kv(kv, dtype: str):
    """Quantize a KV pytree; returns (payload pytree, stored nbytes)."""
    payload = jax.tree.map(lambda a: quantize_leaf(a, dtype), kv)
    return payload, payload_bytes(payload)


def quantize_kv_graph(kv, dtype: str):
    """In-graph pool quantization for fused encode/append epilogues
    (FKE v2): emits the :func:`raw_kv_view` structure directly —
    ``(int8 values, f32 scale)`` tuples, ``(bf16 values, None)`` casts,
    or plain native leaves — so a jitted executor's OUTPUT already *is*
    the pool's stored representation and ``put(prequantized=True)`` can
    admit it without a separate quantize pass (and without ever
    materializing the fp KV on the host).  Op-for-op the same jnp
    computation as :func:`quantize_leaf`, so the emitted codes/scales are
    bitwise identical to a post-hoc :func:`quantize_kv` of the same
    values (asserted in tests/test_decode_serving.py)."""
    if dtype == "native":
        return kv

    def one(a):
        a = jnp.asarray(a)
        if _stored_as_is(a, dtype):
            return a
        if dtype == "bf16":
            return (a.astype(jnp.bfloat16), None)
        if dtype == "int8":
            af = a.astype(jnp.float32)
            scale = jnp.maximum(
                jnp.max(jnp.abs(af), axis=_scale_axes(a.ndim),
                        keepdims=True), 1e-8)
            q = jnp.clip(jnp.round(af / scale * 127.0),
                         -127, 127).astype(jnp.int8)
            return (q, scale)
        raise ValueError(
            f"pool dtype must be one of {POOL_DTYPES}, got {dtype!r}")
    return jax.tree.map(one, kv)


def _shard_elems(shape, shard_spec) -> int:
    """Element count ONE shard holds of an array with this global shape.
    ``shard_spec`` maps a shape to a NamedSharding (or None = replicated);
    the per-shard shape comes from the sharding itself, so the accounting
    follows whatever layout (head-split, sequence-split, replicated) the
    divisibility fallback actually resolved — analytically, which keeps it
    true on CPU hosts where forced host "devices" share one allocator."""
    shape = tuple(int(s) for s in shape)
    if shard_spec is not None:
        sh = shard_spec(shape)
        if sh is not None:
            return math.prod(sh.shard_shape(shape))
    return math.prod(shape)


def quantized_nbytes(
        kv, dtype: str, shard_spec=None
) -> int:
    """Stored bytes :func:`quantize_kv` would produce, WITHOUT quantizing —
    shape/dtype arithmetic only, so admission prechecks are free.  With
    ``shard_spec`` (shape -> NamedSharding), the bytes one shard holds."""
    total = 0
    for a in jax.tree.leaves(kv):
        n = _shard_elems(a.shape, shard_spec)
        if dtype in POOL_DTYPES and _stored_as_is(a, dtype):
            total += n * jnp.dtype(a.dtype).itemsize
        elif dtype == "bf16":
            total += n * 2
        elif dtype == "int8":
            scale_shape = tuple(1 if i in _scale_axes(a.ndim) else s
                                for i, s in enumerate(a.shape))
            total += n + _shard_elems(scale_shape, shard_spec) * 4
        else:
            raise ValueError(
                f"pool dtype must be one of {POOL_DTYPES}, got {dtype!r}")
    return total


def dequantize_kv(payload):
    """Dequantize a payload pytree back to original-dtype leaves."""
    return jax.tree.map(
        dequantize_leaf, payload,
        is_leaf=lambda x: isinstance(x, _QuantLeaf))


def raw_kv_view(payload):
    """Zero-copy *raw* view of a stored payload for quantization-aware
    executors (the FKE path): every quantized leaf becomes a ``(values,
    scale)`` tuple over THE stored arrays (scale ``None`` for a plain bf16
    cast — dropped by ``jax.tree.flatten``), native leaves pass through.
    The executor dequantizes tiles in-kernel, so a lookup never
    materializes the dequantized entry on the host.  Callers must treat
    the arrays as immutable — they alias pool storage."""
    return jax.tree.map(
        lambda s: (s.q, s.scale) if isinstance(s, _QuantLeaf) else s,
        payload, is_leaf=lambda x: isinstance(x, _QuantLeaf))


def raw_kv_specs(kv_specs, dtype: str):
    """ShapeDtypeStruct pytree matching :func:`raw_kv_view` output for a
    pool storing ``dtype`` — what a quantization-aware AOT executor is
    compiled against (shape/dtype arithmetic only)."""
    def one(spec):
        if dtype in POOL_DTYPES and _stored_as_is(spec, dtype):
            return spec
        if dtype == "bf16":
            return (jax.ShapeDtypeStruct(spec.shape, jnp.bfloat16), None)
        if dtype == "int8":
            scale_shape = tuple(1 if i in _scale_axes(len(spec.shape)) else s
                                for i, s in enumerate(spec.shape))
            return (jax.ShapeDtypeStruct(spec.shape, jnp.int8),
                    jax.ShapeDtypeStruct(scale_shape, jnp.float32))
        raise ValueError(f"pool dtype must be one of {POOL_DTYPES}, "
                         f"got {dtype!r}")
    return jax.tree.map(one, kv_specs)


def _stored_arrays(payload):
    out = []
    for leaf in jax.tree.leaves(
            payload, is_leaf=lambda x: isinstance(x, _QuantLeaf)):
        if isinstance(leaf, _QuantLeaf):
            out.append(leaf.q)
            if leaf.scale is not None:
                out.append(leaf.scale)
        else:
            out.append(leaf)
    return out

def payload_bytes(
        payload
) -> int:  # flamecheck: host-sync-ok(shape arithmetic over .shape tuples and Python ints; no device data is read)
    """Stored bytes of a (possibly quantized) payload pytree."""
    return sum(int(np.prod(a.shape)) * jnp.dtype(a.dtype).itemsize
               for a in _stored_arrays(payload))


def _device_move(a):
    """Pin one array in the serving accelerator's memory.  On the CPU
    backend host and device memory coincide, so plain numpy is the faster
    representation of the same placement (no per-op dispatch overhead);
    with a real accelerator attached this is the HBM residency that spares
    the per-dispatch H2D copy."""
    if jax.default_backend() == "cpu":
        return np.asarray(a)  # flamecheck: host-sync-ok(CPU tier: source is already host-resident, asarray is a no-op view — host and device memory coincide)
    return jnp.asarray(a)


def _place(payload, placement: str):
    """Move every stored array to the tier's memory space."""
    move = _device_move if placement == "device" else np.asarray
    return jax.tree.map(
        lambda s: _QuantLeaf(
            move(s.q), None if s.scale is None else move(s.scale), s.dtype)
        if isinstance(s, _QuantLeaf) else move(s),
        payload, is_leaf=lambda x: isinstance(x, _QuantLeaf))


# ---------------------------------------------------------------------------
# history-KV pool (GR serving)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(eq=False)           # identity semantics: tier members
class _PoolEntry:
    fingerprint: Hashable          # content hash of the full history array
    payload: object                # stored (possibly quantized) KV pytree
    nbytes: int                    # stored bytes (quantized size)
    hist_window: Optional[np.ndarray]   # model-window ids at encode time
    refreshes: int = 0             # incremental extensions since full encode
    shard_nbytes: int = 0          # bytes ONE model shard holds (== nbytes
                                   # for mesh-less pools / replicated leaves)


@dataclasses.dataclass
class StaleBasis:
    """What ``lookup`` hands back for a dropped stale entry so the engine
    can extend the cached prefix instead of re-encoding from scratch."""

    kv: object                     # K/V extension basis (dequantized, or a
                                   # raw stored view under ``raw_basis``)
    hist_window: Optional[np.ndarray]  # window the basis encoded
    refreshes: int = 0             # extensions already layered on this basis


class HistoryKVPool:
    """Byte-budgeted two-tier LRU pool of encoded history K/V (PDA v2).

    See the module docstring for the full contract.  Quick API tour:

    ``lookup(key, fingerprint, want_basis=..., raw=...)``
        one counted probe: returns ``(kv, status, basis)`` with status
        ``"hit"`` (kv is the dequantized entry, recency refreshed),
        ``"stale"`` (entry dropped; ``basis`` carries its K/V + encoded
        window + extension refresh count when ``want_basis``) or
        ``"miss"``.  Stale and miss both count as misses, so hit-rate
        math is unchanged from v1.  ``raw=True`` (the FKE executors)
        skips dequantization: hits return :func:`raw_kv_view` of the
        stored payload — (values, scale) over the stored arrays, no copy.
    ``get(key, fingerprint)``
        v1 sugar over ``lookup``: the kv on hit, else None.
    ``peek(key, fingerprint)``
        uncounted re-check for single-flight leader election.
    ``put(key, fingerprint, kv, hist_window=None, refreshes=0)``
        quantize + admit, then evict LRU-first until both the ``slots`` and
        ``budget_bytes`` limits hold (evictions demote to the spill tier
        when enabled); oversized entries are rejected, never admitted.
        ``refreshes`` counts incremental extensions layered on the entry
        since its last full encode (the engine's drift cap).
    ``count_extension()`` / ``count_refresh_reencode()``
        engine callbacks: one stale hit was served by incremental suffix
        extension (``extensions`` stat) / the extension-drift cap forced a
        full re-encode instead (``refresh_reencodes`` stat).

    All methods are thread-safe — pipeline workers hit the pool
    concurrently."""

    def __init__(self, slots: Optional[int] = 256, *,
                 budget_bytes: Optional[int] = None,
                 dtype: str = "native", placement: str = "device",
                 spill_bytes: int = 0, mesh=None, shard_spec=None):
        if slots is None and budget_bytes is None:
            raise ValueError("pool needs slots and/or budget_bytes")
        if slots is not None and slots < 1:
            raise ValueError(f"pool needs >= 1 slot, got {slots}")
        if budget_bytes is not None and budget_bytes < 1:
            raise ValueError(f"budget_bytes must be >= 1, got {budget_bytes}")
        if dtype not in POOL_DTYPES:
            raise ValueError(f"dtype must be one of {POOL_DTYPES}, got {dtype!r}")
        if placement not in ("device", "host"):
            raise ValueError(f"placement must be device|host, got {placement!r}")
        self.slots = slots
        self.budget_bytes = budget_bytes
        self.dtype = dtype
        self.placement = placement
        self.spill_budget = int(spill_bytes)
        # mesh-sharded serving: ``shard_spec`` (shape -> NamedSharding, or
        # None for replicated) commits device-placed leaves to the layout
        # the sharded executors consume — pooled KV lives where its heads
        # live — and drives the analytic per-shard byte accounting.  The
        # byte budget is the pool's TOTAL across shards; each model shard
        # gets an even share of it.
        self.mesh = mesh
        self._shard_spec = shard_spec
        self._model_ways = 1
        if mesh is not None and "model" in mesh.axis_names:
            self._model_ways = int(mesh.shape["model"])
        self._shard_budget = None
        if budget_bytes is not None and self._model_ways > 1:
            self._shard_budget = budget_bytes // self._model_ways
        self._entries: "collections.OrderedDict[Hashable, _PoolEntry]" = \
            collections.OrderedDict()
        self._spill: "collections.OrderedDict[Hashable, _PoolEntry]" = \
            collections.OrderedDict()
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0
        self.stale = 0
        self.evictions = 0
        self.rejects = 0
        self.extensions = 0
        self.refresh_reencodes = 0
        self.spill_hits = 0
        # host seconds and calls of lookup / put (spans flame.pool.lookup
        # and flame.pool.put)
        self.time_s: Dict[str, float] = {"lookup": 0.0, "put": 0.0}
        self.calls: Dict[str, int] = {"lookup": 0, "put": 0}
        self.bytes_used = 0
        self.spill_bytes_used = 0
        self.shard_bytes_used = 0

    @staticmethod
    def entry_bytes(kv) -> int:
        """Unquantized (compute-dtype) bytes of a KV pytree."""
        return payload_bytes(kv)

    # ---- placement (mesh-aware) ----
    def _move(self, a):
        """Shard-aware device placement of one stored array: with a mesh,
        commit it to the executor-facing NamedSharding layout (heads on the
        model axis, pooled-user rows replicated) so the hot path never
        reshards it.  On the CPU backend forced host "devices" share one
        allocator and AOT executables auto-place uncommitted host arrays,
        so plain numpy stays the faster representation of the same
        placement (and keeps the bitwise single- vs multi-device parity
        path committed-array free)."""
        if self._shard_spec is not None and jax.default_backend() != "cpu":
            sh = self._shard_spec(np.shape(a))
            if sh is not None:
                return jax.device_put(a, sh)  # flamecheck: host-sync-ok(async H2D publish committing pool KV to the executors' NamedSharding layout, not a device->host sync)
        return _device_move(a)

    def _place_stored(self, payload, placement: str):
        """Tier placement honoring the pool's mesh layout for the device
        tier; host-tier moves fall through to the plain numpy path."""
        if placement == "device" and self._shard_spec is not None:
            return jax.tree.map(
                lambda s: _QuantLeaf(
                    self._move(s.q),
                    None if s.scale is None else self._move(s.scale),
                    s.dtype)
                if isinstance(s, _QuantLeaf) else self._move(s),
                payload, is_leaf=lambda x: isinstance(x, _QuantLeaf))
        return _place(payload, placement)

    # ---- lookup side ----
    def _load(self, e: _PoolEntry, raw: bool = False):
        if raw:
            # quantization-aware executor path: hand back the stored
            # arrays themselves ((values, scale) tuples for quantized
            # leaves) — no dequantization, no copy
            return raw_kv_view(e.payload)
        kv = dequantize_kv(e.payload)
        if self.placement == "host":
            kv = jax.tree.map(
                np.asarray, kv)  # flamecheck: host-sync-ok(host-placement pools hand out host arrays by contract)
        return kv

    def _add_time(self, name: str, seconds: float):
        """One timed ``lookup`` or ``put``: ``<name>_s`` / ``<name>_n``."""
        with self._lock:
            self.time_s[name] += seconds
            self.calls[name] += 1

    def lookup(self, key: Hashable, fingerprint: Hashable, *,
               want_basis: bool = False, raw: bool = False,
               raw_basis: bool = False):
        """One counted probe (:meth:`_lookup`) in the span
        ``flame.pool.lookup``, timed into ``lookup_s`` / ``lookup_n``."""
        with stage("pool.lookup") as st:
            out = self._lookup(key, fingerprint, want_basis=want_basis,
                               raw=raw, raw_basis=raw_basis)
        self._add_time("lookup", st.s)
        return out

    def _lookup(self, key: Hashable, fingerprint: Hashable, *,
                want_basis: bool = False, raw: bool = False,
                raw_basis: bool = False):
        """One counted probe; see the class docstring.  Checks the primary
        tier, then the spill tier (promoting on a spill hit).  Counter
        bookkeeping happens under the lock; dequantization runs after
        releasing it (payloads are immutable once stored), so concurrent
        workers never serialize on the dequant math.  ``raw_basis=True``
        hands a dropped stale entry back as its :func:`raw_kv_view` —
        the quantized-extend-basis path: extend executors compiled
        against raw pool specs dequantize in-graph, so the host never
        pays the dequant (or ships the dequantized bytes)."""
        with self._lock:
            e = self._entries.get(key)
            if e is not None:
                if e.fingerprint == fingerprint:
                    self._entries.move_to_end(key)
                    self.hits += 1
                    status = "hit"
                else:
                    del self._entries[key]      # stale: history advanced
                    self.bytes_used -= e.nbytes
                    self.shard_bytes_used -= e.shard_nbytes
                    self.stale += 1
                    self.misses += 1
                    status = "stale"
            else:
                e = self._spill.pop(key, None)
                if e is not None:
                    self.spill_bytes_used -= e.nbytes
                    if e.fingerprint == fingerprint:
                        self.hits += 1
                        self.spill_hits += 1
                        status = "promote"
                    else:
                        self.stale += 1
                        self.misses += 1
                        status = "stale"
                else:
                    self.misses += 1
                    return None, "miss", None
        if status == "promote":
            # re-place toward the primary tier OUTSIDE the lock (a
            # paper-scale promotion is a multi-MB H2D copy), then admit.
            # While in flight the entry sits in neither tier; a concurrent
            # same-key miss may encode and put() meanwhile (promotions are
            # not single-flighted), so only admit if the key is still
            # absent — the racing entry is at least as fresh, and this
            # request is still correctly served from the promoted copy.
            e.payload = self._place_stored(e.payload, self.placement)
            demoted: List[_PoolEntry] = []
            with self._lock:
                if key not in self._entries:
                    demoted = self._admit(key, e)
            self._finish_demotions(demoted)
            return self._load(e, raw), "hit", None
        if status == "hit":
            return self._load(e, raw), "hit", None
        basis = StaleBasis(self._load(e, raw_basis), e.hist_window,
                           e.refreshes) if want_basis else None
        return None, "stale", basis

    def get(self, key: Hashable, fingerprint: Hashable):
        """v1 surface: the cached pytree on a fresh hit, else None."""
        kv, _, _ = self.lookup(key, fingerprint)
        return kv

    def contains(self, key: Hashable, fingerprint: Hashable) -> bool:
        """Uncounted O(1) existence probe (either tier, no recency touch,
        no dequantization) — the engine's admit-time prefetch short-circuit
        only needs to know whether a fresh entry exists."""
        with self._lock:
            e = self._entries.get(key) or self._spill.get(key)
            return e is not None and e.fingerprint == fingerprint

    def peek(self, key: Hashable, fingerprint: Hashable, *,
             raw: bool = False):
        """Like ``get`` but without touching hit/miss/stale counters (and
        without dropping stale entries) — used by the engine's single-flight
        leader election to re-check the pool after the initial counted miss,
        so each request still counts exactly one lookup."""
        with self._lock:
            e = self._entries.get(key)
            if e is not None and e.fingerprint == fingerprint:
                self._entries.move_to_end(key)
            else:
                e = self._spill.get(key)
                if e is None or e.fingerprint != fingerprint:
                    return None
        return self._load(e, raw)

    # ---- admission side ----
    def _admit(self, key: Hashable, entry: _PoolEntry
               ) -> List[_PoolEntry]:  # flamecheck: locked-by-caller(self._lock)
        """Insert into the primary tier and evict until limits hold.
        Caller holds the lock.  Returns the entries demoted to the spill
        tier — their payloads still sit in the primary tier's memory space;
        the caller moves them host-side AFTER releasing the lock (a
        paper-scale demotion is a multi-MB D2H copy, and lookups must not
        serialize behind it) via :meth:`_finish_demotions`."""
        demoted: List[_PoolEntry] = []
        old = self._entries.pop(key, None)
        if old is not None:                 # replace, don't leak its bytes
            self.bytes_used -= old.nbytes
            self.shard_bytes_used -= old.shard_nbytes
        self._entries[key] = entry
        self.bytes_used += entry.nbytes
        self.shard_bytes_used += entry.shard_nbytes
        while (self.slots is not None and len(self._entries) > self.slots) \
                or (self.budget_bytes is not None
                    and self.bytes_used > self.budget_bytes) \
                or (self._shard_budget is not None
                    and self.shard_bytes_used > self._shard_budget):
            k, ev = self._entries.popitem(last=False)   # LRU end
            self.bytes_used -= ev.nbytes
            self.shard_bytes_used -= ev.shard_nbytes
            self.evictions += 1
            if self.spill_budget > 0:
                stale_sp = self._spill.pop(k, None)   # defensive: keep the
                if stale_sp is not None:              # byte accounting true
                    self.spill_bytes_used -= stale_sp.nbytes
                self._spill[k] = ev
                self.spill_bytes_used += ev.nbytes
                demoted.append(ev)
        while self.spill_bytes_used > self.spill_budget and self._spill:
            _, ev = self._spill.popitem(last=False)
            self.spill_bytes_used -= ev.nbytes
            if ev in demoted:
                demoted.remove(ev)          # evicted again before placement
        return demoted

    def _finish_demotions(self, demoted: List[_PoolEntry]):
        """Host-place payloads of freshly demoted entries, outside the lock.
        The conversion is only committed if the entry still sits in the
        spill tier — a concurrent promotion (which re-places the payload
        toward the primary tier) wins the race either way, since dispatch
        consumes host and device arrays alike."""
        for ev in demoted:
            host_payload = _place(ev.payload, "host")
            with self._lock:
                if any(e is ev for e in self._spill.values()):
                    ev.payload = host_payload

    def put(self, key: Hashable, fingerprint: Hashable, kv,
            hist_window: Optional[np.ndarray] = None,
            refreshes: int = 0, *, prequantized: bool = False,
            compute_dtype=None) -> bool:
        """:meth:`_put` in the span ``flame.pool.put``, timed into
        ``put_s`` / ``put_n``."""
        with stage("pool.put") as st:
            out = self._put(key, fingerprint, kv, hist_window, refreshes,
                            prequantized=prequantized,
                            compute_dtype=compute_dtype)
        self._add_time("put", st.s)
        return out

    def _put(self, key: Hashable, fingerprint: Hashable, kv,
             hist_window: Optional[np.ndarray] = None,
             refreshes: int = 0, *, prequantized: bool = False,
             compute_dtype=None) -> bool:
        """Quantize + admit; returns False when the entry was rejected for
        exceeding ``budget_bytes`` on its own.  ``refreshes`` records how
        many incremental extensions are layered on this entry since its
        last full encode (the engine's extension-drift cap reads it back
        through :class:`StaleBasis`).

        ``prequantized=True`` (FKE v2 in-epilogue quantization): ``kv``
        already IS the stored representation — the :func:`raw_kv_view`
        structure a fused encode/append epilogue emits
        (:func:`quantize_kv_graph`), with ``(values, scale)`` tuples as
        quantized leaves — and is wrapped into pool entries with no
        quantize pass.  ``compute_dtype`` (default f32) is what
        dequantizing lookups hand back."""
        payload = None
        if prequantized:
            cdt = jnp.dtype(compute_dtype or jnp.float32)
            payload = jax.tree.map(
                lambda x: _QuantLeaf(x[0], x[1], cdt)
                if isinstance(x, tuple) else x,
                kv, is_leaf=lambda x: isinstance(x, tuple))
            nbytes = payload_bytes(payload)
            shard_nbytes = nbytes if self._shard_spec is None else sum(
                _shard_elems(a.shape, self._shard_spec)
                * jnp.dtype(a.dtype).itemsize
                for a in _stored_arrays(payload))
        else:
            # size precheck BEFORE quantizing/placing: a rejected entry
            # must not pay the (multi-MB at paper scale) quantize +
            # transfer cost.  The per-shard share is prechecked too — an
            # entry whose replicated leaves alone exceed one shard's
            # budget slice can never be held.  (Prequantized payloads
            # above skip the quantize pass entirely, so their precheck is
            # plain shape arithmetic over the stored arrays.)
            nbytes = quantized_nbytes(kv, self.dtype)
            shard_nbytes = nbytes if self._shard_spec is None else \
                quantized_nbytes(kv, self.dtype, shard_spec=self._shard_spec)
        if (self.budget_bytes is not None and nbytes > self.budget_bytes) \
                or (self._shard_budget is not None
                    and shard_nbytes > self._shard_budget):
            with self._lock:
                self.rejects += 1
            return False
        if payload is None:
            payload, nbytes = quantize_kv(kv, self.dtype)
        payload = self._place_stored(payload, self.placement)
        if hist_window is not None:
            hist_window = np.array(
                hist_window)  # flamecheck: host-sync-ok(defensive copy of the caller-owned host id window)
        with self._lock:
            old = self._entries.pop(key, None)
            if old is not None:
                self.bytes_used -= old.nbytes
                self.shard_bytes_used -= old.shard_nbytes
            sp = self._spill.pop(key, None)
            if sp is not None:
                self.spill_bytes_used -= sp.nbytes
            demoted = self._admit(key, _PoolEntry(fingerprint, payload,
                                                  nbytes, hist_window,
                                                  refreshes, shard_nbytes))
        self._finish_demotions(demoted)
        return True

    def count_extension(self):
        with self._lock:
            self.extensions += 1

    def count_refresh_reencode(self):
        """Engine callback: a stale hit had an extendable basis, but the
        extension-drift cap (``--extend-refresh-limit``) forced a full
        re-encode instead."""
        with self._lock:
            self.refresh_reencodes += 1

    # ---- introspection / lifecycle ----
    def keys(self) -> List[Hashable]:
        """Primary-tier keys, LRU -> MRU order (for tests/introspection)."""
        with self._lock:
            return list(self._entries)

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def drop(self, key: Hashable) -> bool:
        """Force-evict one key from BOTH tiers (fault injection / admin
        invalidation — ``serving.faults`` eviction storms drive this).
        Returns True when an entry was actually dropped; counted in
        ``evictions`` so storm pressure shows up in the pool stats."""
        with self._lock:
            e = self._entries.pop(key, None)
            if e is not None:
                self.bytes_used -= e.nbytes
                self.shard_bytes_used -= e.shard_nbytes
            sp = self._spill.pop(key, None)
            if sp is not None:
                self.spill_bytes_used -= sp.nbytes
            if e is None and sp is None:
                return False
            self.evictions += 1
            return True

    def release(self) -> None:
        """Drop every entry (engine shutdown); counters survive for metrics."""
        with self._lock:
            self._entries.clear()
            self._spill.clear()
            self.bytes_used = 0
            self.spill_bytes_used = 0
            self.shard_bytes_used = 0

    def device_bytes(self) -> Dict[object, int]:
        """Primary-tier stored bytes per device as actually placed: the
        sum of every device-resident stored array's addressable shards on
        each device (host-resident leaves count nowhere).  The measured
        counterpart of :meth:`shard_bytes`' analytic accounting."""
        with self._lock:
            payloads = [e.payload for e in self._entries.values()]
        out: Dict[object, int] = collections.Counter()
        for payload in payloads:
            for a in _stored_arrays(payload):
                if isinstance(a, jax.Array):
                    for s in a.addressable_shards:
                        out[s.device] += s.data.nbytes
        return dict(out)

    def shard_bytes(self) -> List[int]:
        """Primary-tier stored bytes per model shard (one gauge per shard;
        [] for mesh-less pools).  The serving layout is symmetric by
        construction — every stored leaf is either split evenly over the
        model axis or replicated on all of its shards — so the shards hold
        identical byte counts."""
        with self._lock:
            if self.mesh is None:
                return []
            return [self.shard_bytes_used] * self._model_ways

    def stats(self) -> Dict[str, float]:
        with self._lock:
            total = self.hits + self.misses
            shard = {}
            if self.mesh is not None:
                shard["shard_ways"] = self._model_ways
                for i in range(self._model_ways):
                    shard[f"bytes_shard{i}"] = self.shard_bytes_used
            return {
                **shard,
                "entries": len(self._entries),
                "slots": self.slots if self.slots is not None else -1,
                "budget_bytes": (self.budget_bytes
                                 if self.budget_bytes is not None else -1),
                "hits": self.hits,
                "misses": self.misses,
                "stale": self.stale,
                "evictions": self.evictions,
                "rejects": self.rejects,
                "extensions": self.extensions,
                "refresh_reencodes": self.refresh_reencodes,
                "hit_rate": self.hits / total if total else 0.0,
                "bytes": self.bytes_used,
                "spill_entries": len(self._spill),
                "spill_bytes": self.spill_bytes_used,
                "spill_hits": self.spill_hits,
                **{f"{k}_s": v for k, v in self.time_s.items()},
                **{f"{k}_n": v for k, v in self.calls.items()},
            }
