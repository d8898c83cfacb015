"""Dynamic Stream Orchestrator (DSO) — explicit-shape executors + routing.

TPU/JAX mapping of the paper's §3.3 (see DESIGN.md):

  TensorRT profile w/ fixed batch shape  ->  AOT-compiled XLA executable
                                             (jit(f).lower(shapes).compile())
  preallocated I/O buffers               ->  persistent padded input buffers
  CUDA-graph capture                     ->  the AOT executable itself (one
                                             dispatch, no retrace)
  CUDA streams / executor index queue    ->  executor checkout queue +
                                             JAX async dispatch; worker
                                             threads interleave host work

Routing: an upstream request with M candidates is split greedily into bucket
chunks in descending bucket order; the final partial chunk is padded up to
the smallest covering bucket (the paper's "split by batch size in descending
order").

DSO v2 (segment packing + deadline-aware flushing)
--------------------------------------------------
Under non-uniform candidate traffic the greedy split leaves every request's
tail chunk partially filled, and the v1 dispatcher paid that padding on
every dispatch (``padded_fraction`` routinely 20-40% on zipf traffic).  Two
mechanisms reclaim it:

* **Segment packing** (:class:`SegmentPacker`): partial tail chunks from
  *different requests* are packed into one ``(1, bucket)`` row as
  independent segments.  Candidates never attend to each other under the
  SUMI mask, so a row may carry candidates of several users as long as each
  candidate scores against its own user's history KV — the executor
  receives a per-candidate ``[B, bucket]`` KV slot index (the per-q-block
  generalization of the per-row dedup ``row_index``) steering every segment
  to its user's pooled rows.  Packing is bitwise-clean by construction and
  subsumes KV-row dedup: same-user segments share one stacked KV slot.
* **Deadline-aware flushing**: pending chunks are ordered earliest-deadline
  -first (deadline-less chunks sort last; ties break on the request's
  remaining work, then FIFO), and the collect loop sizes its wait against a
  per-(kind, bucket) EWMA cost model — it flushes as soon as waiting any
  longer would make the earliest collected deadline unmeetable, instead of
  always sleeping the full flat window.

Generative decode families (ISSUE 8)
------------------------------------
The flame engine registers two more executor families when built with
``generate > 0``; the DSO needs no new machinery for either:

* ``decode`` — one vocab-scoring step for an in-flight beam: lead args are
  the beam's padded KV leaves plus its ``lengths`` row, the candidate axis
  carries the step's token universe, and the usual bucket ladder chunks
  ragged universes.  Under ``pack_tails`` the SegmentPacker packs tail
  chunks of *different beams'* decode steps into shared rows exactly like
  cached scoring — the per-candidate segment index steers each universe
  segment to its own beam's stacked KV slot, so per-step ragged decode
  batching falls out of the PR 5 contract unchanged.
* ``append`` — the single-token KV append growing a chosen hypothesis;
  rides the plain (unpacked) path at bucket 1 and returns device KV leaves
  (an engine-output kind, like ``encode``/``extend``).

Chunks from concurrent generative requests coalesce per step, so the
decode families inherit cross-request batching, deadline flushing, and the
fill/padding metrics (``dso_dispatches_decode`` etc.) for free.
"""
from __future__ import annotations

import contextlib
import dataclasses
import heapq
import itertools
import math
import queue
import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Callable, Dict, Hashable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.fused_score.ops import qblock_counts


# ---------------------------------------------------------------------------
# stage spans
# ---------------------------------------------------------------------------

class stage:
    """``with stage("dso.stack", kind=k) as st:`` opens the profiler span
    ``flame.dso.stack`` around the body and leaves its elapsed
    ``time.perf_counter`` seconds in ``st.s`` for the caller to add to the
    stage's counters (``<prefix>_<stage>_s`` / ``_n``).  Keyword arguments
    become the span's metadata, which the profiler encodes only while it
    records.  Spans mark work, never a wait: a span open across a blocking
    wait would cover the device's idle gaps it is meant to explain."""

    __slots__ = ("_ann", "_t0", "s")

    def __init__(self, name: str, **meta):
        self._ann = jax.profiler.TraceAnnotation("flame." + name, **meta)
        self.s = 0.0

    def __enter__(self) -> "stage":
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.s = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)


# ---------------------------------------------------------------------------
# bucket routing
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Chunk:
    bucket: int       # executor shape this chunk runs on
    start: int        # offset into the request's candidate list
    valid: int        # number of real candidates (<= bucket; rest is padding)


def split_request(m: int, buckets: Sequence[int]) -> List[Chunk]:
    """Greedy descending-bucket split of M candidates."""
    bs = sorted(set(buckets), reverse=True)
    assert m >= 1 and bs, (m, buckets)
    plan: List[Chunk] = []
    off, rem = 0, m
    for b in bs:
        while rem >= b:
            plan.append(Chunk(b, off, b))
            off += b
            rem -= b
    if rem > 0:
        cover = min(x for x in bs if x >= rem)  # smallest covering bucket
        plan.append(Chunk(cover, off, rem))
    return plan


def padded_fraction(m: int, buckets: Sequence[int]) -> float:
    plan = split_request(m, buckets)
    padded = sum(c.bucket for c in plan)
    return 1.0 - m / padded


# ---------------------------------------------------------------------------
# executors
# ---------------------------------------------------------------------------

class Executor:
    """One AOT-compiled executable for a fixed candidate bucket."""

    def __init__(self, bucket: int, compiled, eid: int):
        self.bucket = bucket
        self.compiled = compiled
        self.eid = eid
        self.calls = 0

    def __call__(self, *args):
        self.calls += 1
        return self.compiled(*args)


def per_row_signature(fn: Callable, shapes: Sequence, n_kv: int,
                      split: bool, layout: Optional[Callable] = None
                      ) -> Tuple[Callable, tuple]:
    """Executor half of the orchestrator's per-row contract.

    ``fn(params, *args)`` is written against stacked operands ``shapes``
    whose first ``n_kv`` are history-KV rows with leading axis ``B``.  The
    returned function instead takes those rows one per slot — ``B`` groups
    of ``n_kv`` ``[1, ...]`` arrays, slot-major — and concatenates them as
    its first op, so the copy runs inside the executable's one launch
    rather than as eager device ops on the dispatch thread.  With ``split``
    the output comes back as a tuple of ``B`` per-row pytrees (leading axis
    1 each), so the dispatcher hands rows out by Python index.  ``layout``
    (shape -> sharding or None, mesh executors) pins each stacked output
    leaf before the split, so every shard slices its rows locally instead
    of exchanging them.  Returns the function and its AOT shapes;
    ``n_kv == 0`` and no ``split`` is ``fn`` unchanged."""
    shapes = tuple(shapes)
    if n_kv:
        slots = shapes[0].shape[0]
        row = tuple(jax.ShapeDtypeStruct((1,) + s.shape[1:], s.dtype)
                    for s in shapes[:n_kv])
        shapes = row * slots + shapes[n_kv:]
    if not n_kv and not split:
        return fn, shapes

    def per_row(params, *args):
        if n_kv:
            rows, rest = args[:slots * n_kv], args[slots * n_kv:]
            args = tuple(jnp.concatenate(rows[j::n_kv], axis=0)
                         for j in range(n_kv)) + rest
        out = fn(params, *args)
        if split:
            if layout is not None:
                out = jax.tree.map(
                    lambda a: a if layout(a.shape) is None else
                    jax.lax.with_sharding_constraint(a, layout(a.shape)),
                    out)
            b = jax.tree.leaves(out)[0].shape[0]
            out = tuple(jax.tree.map(lambda a: a[i:i + 1], out)
                        for i in range(b))
        return out
    return per_row, shapes


class ExecutorPool:
    """Per-bucket executor index queues (paper Fig 10).

    ``build_fn(bucket)`` must return an AOT-compiled callable for that
    bucket's shapes.  ``n_streams`` executors are built per bucket — the
    CUDA-stream analogue: that many chunks of the same bucket can be in
    flight concurrently (JAX async dispatch overlaps their host work).
    """

    def __init__(self, build_fn: Callable[[int], Callable],
                 buckets: Sequence[int], n_streams: int = 2):
        self.buckets = sorted(set(buckets), reverse=True)
        self.queues: Dict[int, "queue.Queue[Executor]"] = {}
        self.build_time_s = 0.0
        eid = 0
        t0 = time.perf_counter()
        for b in self.buckets:
            q: "queue.Queue[Executor]" = queue.Queue()
            compiled = build_fn(b)
            for _ in range(n_streams):
                q.put(Executor(b, compiled, eid))
                eid += 1
            self.queues[b] = q
        self.build_time_s = time.perf_counter() - t0

    def acquire(self, bucket: int) -> Executor:
        return self.queues[bucket].get()

    def release(self, ex: Executor):
        self.queues[ex.bucket].put(ex)


# ---------------------------------------------------------------------------
# the orchestrator
# ---------------------------------------------------------------------------

class DynamicStreamOrchestrator:
    """Routes requests with arbitrary candidate counts onto the executor pool.

    ``pad_slice_fn(request, chunk)`` -> executor args for one chunk (padded
    to ``chunk.bucket``); ``gather_fn(results, chunks, m)`` -> final output.
    """

    def __init__(self, pool: ExecutorPool,
                 pad_slice_fn: Callable, gather_fn: Callable,
                 max_workers: int = 8):
        self.pool = pool
        self.pad_slice = pad_slice_fn
        self.gather = gather_fn
        self._tp = ThreadPoolExecutor(max_workers=max_workers)
        self.chunk_count = 0
        self._lock = threading.Lock()

    def _run_chunk(self, request, chunk: Chunk):
        ex = self.pool.acquire(chunk.bucket)
        try:
            args = self.pad_slice(request, chunk)
            out = ex(*args)
            jax.block_until_ready(out)
            return out
        finally:
            self.pool.release(ex)

    def submit(self, request, m: int):
        """Non-blocking: returns a future resolving to the gathered output."""
        plan = split_request(m, self.pool.buckets)
        with self._lock:
            self.chunk_count += len(plan)
        futs = [self._tp.submit(self._run_chunk, request, c) for c in plan]

        def resolve():
            results = [f.result() for f in futs]
            return self.gather(results, plan, m)

        return _Lazy(resolve)

    def score(self, request, m: int):
        """Blocking convenience wrapper."""
        return self.submit(request, m).result()

    def shutdown(self):
        self._tp.shutdown(wait=True)


class _Lazy:
    def __init__(self, fn):
        self._fn = fn

    def result(self):
        return self._fn()


# ---------------------------------------------------------------------------
# cross-request chunk coalescing (API v2) + segment packing (DSO v2)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class CoalescePolicy:
    """When/how same-bucket chunks from different requests share a dispatch.

    ``max_batch`` is both the fill target and the executors' compiled batch
    axis; ``window_s`` bounds how long the first chunk of a batch waits for
    co-riders before dispatching partially filled.  Chunks that carry a
    deadline (DSO v2) may flush *earlier* than the window: the collect loop
    stops waiting once ``now + estimated_dispatch_cost`` would overrun the
    earliest collected deadline (per-(kind, bucket) EWMA cost model).

    ``pack_rows`` sizes the PACKED executors' row axis independently of
    ``max_batch`` (which still sizes the stacked unique-KV axis, i.e. how
    many distinct users one packed dispatch can steer to): packed rows are
    dense, so a fraction of the unpacked row capacity carries the same
    candidate throughput at a fraction of the executor cost.  ``None``
    defaults to ``max_batch``.

    ``data_ways`` (mesh-sharded serving) is the data-parallel width of the
    engine's device mesh.  ``max_batch`` / ``pack_rows`` are PER-DEVICE
    capacities: the compiled global batch/row axes scale by ``data_ways``
    so one coalesced flush feeds every data shard a full per-device batch
    without resharding — throughput scales with the mesh instead of each
    device serving a 1/ways sliver of a fixed batch.  Preserving the
    per-device (local) shape is also what makes sharded serving bitwise
    against a single-device engine on CPU CI: XLA's kernel selection (and
    hence FP reduction order) depends on the local batch shape, so equal
    local shapes mean identical per-row arithmetic.

    ``tier_windows`` (SLO-tiered serving, ISSUE 9) maps an SLO tier name to
    a multiplier on ``window_s`` — the per-tier pack/flush policy: an
    interactive chunk should flush almost immediately (scale ~0) while bulk
    work may wait longer than the default window for better packing.  The
    collect loop uses the MINIMUM scale across the chunks it has collected,
    so one interactive co-rider flushes the whole dispatch.  ``None``
    (and unknown tiers / tier-less chunks) means scale 1.0.

    ``pack_align`` rounds every packed segment's start offset up to a
    multiple of this many candidate slots (FKE v2): the fused kernel
    steers pooled-KV reads per ``bq``-sized q BLOCK through a scalar-
    prefetched index sampled at each block's first candidate, so packed
    rows feed ``path="kernel"`` only when no segment crosses a block
    boundary.  1 (the default) packs densely — the jnp formulation does
    not care; the engine raises it to ``fused_score.ops.PACK_ALIGN`` (the
    kernel's packed ``bq``) under ``impl="fused"``.  Alignment holes are
    dead slots: seg index 0 / candidate -1, exactly like row-tail
    padding."""

    enabled: bool = True
    max_batch: int = 4
    window_s: float = 0.002
    pack_rows: Optional[int] = None
    data_ways: int = 1
    tier_windows: Optional[Dict[str, float]] = None
    pack_align: int = 1

    def __post_init__(self):
        if self.max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {self.max_batch}")
        if self.window_s < 0:
            raise ValueError(f"window_s must be >= 0, got {self.window_s}")
        if self.pack_rows is not None and self.pack_rows < 1:
            raise ValueError(f"pack_rows must be >= 1, got {self.pack_rows}")
        if self.data_ways < 1:
            raise ValueError(f"data_ways must be >= 1, got {self.data_ways}")
        if self.pack_align < 1:
            raise ValueError(
                f"pack_align must be >= 1, got {self.pack_align}")

    @property
    def batch(self) -> int:
        """Compiled (global) batch-axis size: coalescing off degrades to
        (1, bucket); mesh-sharded engines compile ``max_batch`` rows PER
        data shard."""
        return self.max_batch * self.data_ways if self.enabled else 1

    @property
    def rows(self) -> int:
        """Compiled (global) row-axis size of PACKED executors — scales by
        the data ways like ``batch`` does."""
        if not self.enabled:
            return 1
        per_dev = self.pack_rows if self.pack_rows is not None else \
            self.max_batch
        return per_dev * self.data_ways

    def tier_scale(self, tier: Optional[str]) -> float:
        """Flush-window multiplier for one chunk's SLO tier."""
        if self.tier_windows is None or tier is None:
            return 1.0
        return self.tier_windows.get(tier, 1.0)


_SEQ = itertools.count()


@dataclasses.dataclass
class _PendingChunk:
    args: Tuple[np.ndarray, ...]      # host arrays, each with leading axis 1
    future: "Future"                  # concurrent.futures.Future per chunk
    dedup_token: Optional[Hashable] = None   # stable identity of lead args
    valid: int = 0                    # real candidates in this chunk
    deadline: Optional[float] = None  # absolute perf_counter deadline
    remaining: int = 0                # request work left incl. this chunk
    tier: Optional[str] = None        # owning request's SLO tier (flush policy)
    seq: int = dataclasses.field(default_factory=lambda: next(_SEQ))
    enqueue_t: float = dataclasses.field(default_factory=time.perf_counter)

    def _key(self):
        # EDF first; deadline-less chunks sort last.  Ties break on the
        # owning request's remaining work (shortest-remaining-work), then
        # FIFO sequence for determinism.
        return (self.deadline if self.deadline is not None else math.inf,
                self.remaining, self.seq)

    def __lt__(self, other: "_PendingChunk") -> bool:
        return self._key() < other._key()


class SegmentPacker:
    """First-fit packer of tail-chunk segments into shared executor rows.

    One packer instance plans ONE packed dispatch: up to ``max_rows`` rows
    of ``bucket`` candidate slots, fed at most ``max_kv`` distinct KV
    identities (the compiled leading axis of the stacked unique-KV
    operands).  ``try_add(valid, ident)`` places a segment of ``valid``
    candidates belonging to KV identity ``ident`` into the first row with
    room (never splitting a segment across rows — a segment IS one
    request's chunk, so no segment ever crosses a request boundary by
    construction) and returns its ``(row, offset, kv_slot)`` placement, or
    ``None`` when the segment doesn't fit this dispatch.

    ``align`` > 1 (FKE v2 kernel-path packing) rounds every segment's
    start offset up to an ``align`` multiple before the fit check, so a
    segment occupies ``[off, off + valid)`` with ``off % align == 0`` —
    every ``align``-sized block a segment touches starts either at or
    inside that segment, which is exactly the fused kernel's per-q-block
    index-sampling contract (``bq == align``).  Alignment holes stay dead
    slots (seg 0 / candidate -1 planes in ``_dispatch_packed``), same as
    row-tail padding."""

    def __init__(self, bucket: int, max_rows: int, max_kv: int,
                 align: int = 1):
        assert bucket >= 1 and max_rows >= 1 and max_kv >= 1 and align >= 1
        self.bucket = bucket
        self.max_rows = max_rows
        self.max_kv = max_kv
        self.align = align
        self.fills: List[int] = []            # candidate slots used per row
        self.placements: List[Tuple[int, int, int]] = []  # (row, off, slot)
        self.slot_of: Dict[Hashable, int] = {}
        self.n_slots = 0

    def _aligned(self, fill: int) -> int:
        return -(-fill // self.align) * self.align

    def try_add(self, valid: int, ident: Hashable
                ) -> Optional[Tuple[int, int, int]]:
        if not 1 <= valid <= self.bucket:
            raise ValueError(f"segment of {valid} candidates does not fit a "
                             f"{self.bucket}-slot row")
        slot = self.slot_of.get(ident)
        if slot is None and self.n_slots >= self.max_kv:
            return None
        row = next((i for i, f in enumerate(self.fills)
                    if self._aligned(f) + valid <= self.bucket), None)
        if row is None:
            if len(self.fills) >= self.max_rows:
                return None
            row = len(self.fills)
            self.fills.append(0)
        if slot is None:
            slot = self.n_slots
            self.slot_of[ident] = slot
            self.n_slots += 1
        off = self._aligned(self.fills[row])
        self.fills[row] = off + valid
        place = (row, off, slot)
        self.placements.append(place)
        return place

    @property
    def n_rows(self) -> int:
        return len(self.fills)

    def is_full(self) -> bool:
        """No further segment (not even a 1-candidate one) can be placed."""
        return (len(self.fills) == self.max_rows
                and all(self._aligned(f) >= self.bucket
                        for f in self.fills))


class CoalescingOrchestrator:
    """DSO whose executors carry a real batch axis ``(B, bucket)`` and whose
    dispatcher merges same-bucket chunks *from different in-flight requests*
    into one executor call.

    ``build_fn(bucket, batch)`` -> AOT-compiled callable over arrays whose
    leading axis is ``batch``; ``pad_slice_fn(request, chunk)`` -> host numpy
    args for one chunk (each shaped ``(1, ...)``, candidate axis padded to
    ``chunk.bucket``); ``gather_fn(rows, chunks, m)`` -> final output.

    ``families`` generalizes the executor key from ``bucket`` to
    ``(kind, bucket)`` — the history-cache serving path registers separate
    executor families for full-pass, candidate-only (pool hit) and
    history-encode (pool miss) dispatches, each with its own bucket list and
    coalescing queues.  With families, ``build_fn(kind, bucket, batch,
    signature)`` builds each executor, ``submit(..., kind=...)`` routes, and
    ``pad_slice_fn(request, chunk, kind)`` / ``gather_fn(rows, chunks, m,
    kind)`` slice and reassemble.  ``signature(fn, shapes, layout=None)``
    is the orchestrator's own per-row wrapper for that kind
    (:func:`per_row_signature`, bound to the kind's declared KV count and
    output split, below): ``build_fn`` writes ``fn`` against stacked
    ``[batch, ...]`` operands ``shapes``, applies ``signature`` and compiles
    what it returns, so the dispatch and the executor read one contract.
    Executor outputs may be arbitrary pytrees (the encode family returns a
    HistoryKV dict); host outputs are scattered back leaf-wise, and a
    ``device_output_kinds`` executor returns its rows already split.

    Per (kind, bucket) there are ``n_streams`` worker threads, each owning
    one executor (the CUDA-stream analogue).  A worker that pops the first
    pending chunk keeps collecting until the dispatch is full, the
    ``window_s`` flush window elapses, or — when collected chunks carry
    deadlines — waiting longer would overrun the earliest deadline given
    the (kind, bucket) EWMA dispatch-cost estimate.  Pending chunks pop in
    EDF order (ties: shortest remaining work, then FIFO).  The collected
    host args are stacked along the batch axis (ONE device transfer per
    argument per dispatch — the PDA packed-transfer insight applied at
    dispatch granularity), KV rows are handed over per slot, the executor
    runs once, and result rows scatter back to the per-chunk futures.
    Rows are independent under XLA, so coalesced scores are
    bitwise-identical to solo dispatches (asserted in tests).

    Per-row KV contract (one launch per dispatch, no eager device ops):

    * **In-graph stacking** — ``kv_kinds`` maps a kind to ``(n_kv,
      mode)``: its number of leading history-KV args and how they fill the
      dispatch's KV slots (``mode`` below).  The dispatcher never stacks
      those args: it hands the executor one group of ``[1, ...]`` row
      arrays per KV slot, slot-major, ``B`` slots (the compiled batch,
      ``policy.batch``), and fills unused slots with slot 0's arrays (the
      same objects again — nothing is allocated, and no row index points
      at a padding slot).  The executor concatenates them as its first op,
      so a pool entry reaches the launch as the stored array itself.  The
      remaining host numpy args keep the v1 single ``np.concatenate`` and
      one transfer per argument; host-numpy KV rows (a host-placed pool)
      are one transfer per row.
    * **In-graph splitting** — kinds listed in ``device_output_kinds``
      (the history encode/extend/append families) have executors that
      return a tuple of ``B`` per-row output pytrees; the dispatcher hands
      chunk ``i`` element ``i``, so an encoded entry flows dispatcher ->
      pool -> next dispatch as its own device buffers, with no slicing on
      the host.  Other kinds read their stacked output back once and
      slice it in numpy.
    * ``mode="row"`` — one KV slot per chunk.
    * ``mode="dedup"`` — KV-row dedup: chunks whose leading args are the
      *same objects* (the chunks of one multi-chunk request) or that carry
      the same ``dedup_token`` through ``submit`` (co-batched requests
      hitting one pool entry — quantized pools dequantize to fresh arrays
      per lookup, so object identity alone would miss them) take **one**
      KV slot, and the executor receives an extra ``[B] int32`` row-index
      argument (after the per-slot rows) to gather each row's view.  How
      the executor consumes the index is its business — the framework
      executors materialize ``kv[idx]`` inside the jit, while the FKE
      (``impl="fused"``) executors forward the index into the fused
      kernel's KV block reads, making the gather free.  Saved slots are
      reported as ``dedup_rows_saved``.
    * ``mode="packed"`` — DSO v2 segment packing: dedup, and the
      dispatcher additionally packs partial chunks from different requests
      into shared rows: ``pad_slice_fn`` must return the chunk's candidate
      slice UNPADDED (``(1, valid)``, last arg), and the executor signature
      becomes ``(*kv_rows (per slot), seg_index [rows, bucket] int32,
      candidates [rows, bucket] int32)`` where ``seg_index`` maps every
      candidate slot to its KV row (padding slots point at row 0 and carry
      the ``-1`` candidate sentinel).  Each chunk's future resolves to the
      exact ``[1, valid, ...]`` slice of its segment.  Same-identity
      chunks share a KV slot; savings count into ``dedup_rows_saved``."""

    _DEFAULT_KIND = "default"
    KV_MODES = ("row", "dedup", "packed")   # how KV args fill slots
    _COST_EWMA = 0.3          # per-(kind, bucket) dispatch-cost smoothing

    def __init__(self, build_fn: Callable,
                 buckets: Optional[Sequence[int]] = None,
                 pad_slice_fn: Callable = None, gather_fn: Callable = None,
                 policy: CoalescePolicy = CoalescePolicy(),
                 n_streams: int = 2,
                 families: Optional[Dict[str, Sequence[int]]] = None,
                 kv_kinds: Optional[Dict[str, Tuple[int, str]]] = None,
                 device_output_kinds: Sequence[str] = (),
                 serialize_dispatch: bool = False,
                 fault_hook: Optional[Callable[[str, int], None]] = None,
                 dispatch_retries: int = 2,
                 retry_backoff_s: float = 0.001):
        self._legacy = families is None
        if families is None:
            # adapt the single-family callbacks to the kinds signatures once
            # so the dispatch paths below stay uniform
            if buckets is None:
                raise ValueError("pass either buckets (legacy single-family)"
                                 " or families")
            families = {self._DEFAULT_KIND: buckets}
            _build, _pad, _gather = build_fn, pad_slice_fn, gather_fn
            build_fn = lambda kind, b, batch, sig: _build(b, batch)  # noqa: E731
            pad_slice_fn = lambda req, c, kind: _pad(req, c)    # noqa: E731
            gather_fn = lambda rows, cs, m, kind: _gather(rows, cs, m)  # noqa: E731
        self.families: Dict[str, List[int]] = {
            kind: sorted(set(bs), reverse=True)
            for kind, bs in families.items()}
        # primary (first-registered) family drives the legacy .buckets view
        self.buckets = next(iter(self.families.values()))
        self.policy = policy
        self.pad_slice = pad_slice_fn
        self.gather = gather_fn

        kv_kinds = dict(kv_kinds or {})
        bad = sorted(k for k, (_, mode) in kv_kinds.items()
                     if mode not in self.KV_MODES)
        if bad:
            raise ValueError(f"kinds {bad}: KV slot mode not one of "
                             f"{self.KV_MODES}")
        # leading history-KV args per kind, handed to the executor per slot
        self._kv_rows: Dict[str, int] = {k: n for k, (n, _) in
                                         kv_kinds.items()}
        self._dedup = frozenset(k for k, (_, mode) in kv_kinds.items()
                                if mode == "dedup")
        self._packed = frozenset(k for k, (_, mode) in kv_kinds.items()
                                 if mode == "packed")
        self._device_output = frozenset(device_output_kinds)
        self.chunk_count = 0
        self.dispatch_count = 0
        self.rows_dispatched = 0       # real (non-padding) rows
        self.dedup_rows_saved = 0      # restacks avoided by dedup/packing
        self.packed_rows = 0           # rows carrying >= 1 packed segment
        self.packed_segments = 0       # segments dispatched via packing
        # q blocks of packed rows in use whose sub-blocks read one pool row
        # (the pointwise kernel scores them whole) or several
        self.qblocks_uniform = 0
        self.qblocks_mixed = 0
        self.ingraph_dispatches = 0    # KV rows handed over per slot
        self.queue_delay_total_s = 0.0
        self.queue_delay_count = 0
        self.kind_chunks: Dict[str, int] = {k: 0 for k in self.families}
        self.kind_dispatches: Dict[str, int] = {k: 0 for k in self.families}
        # host-side stage seconds summed over dispatches (the spans
        # flame.dso.stack / .launch / .readback / .scatter, and the
        # unspanned device wait), and launch + wait per family
        self.stage_s: Dict[str, float] = {
            k: 0.0 for k in ("stack", "launch", "wait", "readback",
                             "scatter")}
        self.kind_run_s: Dict[str, float] = {k: 0.0 for k in self.families}
        # fault tolerance (ISSUE 9): ``fault_hook(kind, bucket)`` runs just
        # before every executor launch (the chaos injection point); a raised
        # exception with a truthy ``.transient`` retries with exponential
        # backoff up to ``dispatch_retries`` times before failing the batch.
        self._fault_hook = fault_hook
        self._dispatch_retries = max(0, int(dispatch_retries))
        self._retry_backoff_s = float(retry_backoff_s)
        self.dispatch_retry_count = 0      # transient failures retried
        self.dispatch_failure_count = 0    # batches failed into futures
        # per-family deadline misses: chunks whose dispatch completed past
        # their absolute deadline (degradation decisions read these)
        self.deadline_miss_chunks: Dict[str, int] = {
            k: 0 for k in self.families}
        # graceful degradation: a non-None override caps the effective
        # flush window (level >= 1 sets 0.0 — flush immediately)
        self._window_override: Optional[float] = None
        # per-(kind, bucket) candidate-slot occupancy: slots dispatched vs
        # real candidates in them — 1 - valid/slots is the padded fraction
        self.slot_count: Dict[Tuple[str, int], int] = {}
        self.valid_count: Dict[Tuple[str, int], int] = {}
        self._cost: Dict[Tuple[str, int], float] = {}   # EWMA dispatch cost
        self._stat_lock = threading.Lock()
        # Mesh-sharded executables run one computation across EVERY device:
        # XLA's in-process collectives rendezvous per-computation with no
        # cross-computation ordering, so two dispatch threads whose
        # executions overlap on shared devices can interleave their
        # collectives and deadlock (observed on forced-host CPU meshes).
        # Engines serving a multi-device mesh set serialize_dispatch so the
        # launch+wait region runs under one process-wide lock; single-device
        # executables keep fully concurrent streams.
        self._dispatch_lock = threading.Lock() if serialize_dispatch \
            else None
        self._stop = False

        self._pending: Dict[Tuple[str, int], List[_PendingChunk]] = {}
        self._cond: Dict[Tuple[str, int], threading.Condition] = {}
        self._threads: List[threading.Thread] = []
        self.build_time_s = 0.0
        #: (kind, bucket) -> the AOT executable all streams share; exposed
        #: so tests/benches can inspect compiled HLO (e.g. assert the
        #: steady-state hot path carries no cross-shard reshard collectives)
        self.compiled: Dict[Tuple[str, int], object] = {}

        t0 = time.perf_counter()
        for kind, bs in self.families.items():
            for b in bs:
                self._pending[(kind, b)] = []
                self._cond[(kind, b)] = threading.Condition()
                self.slot_count[(kind, b)] = 0
                self.valid_count[(kind, b)] = 0
                compiled = build_fn(kind, b, policy.batch,
                                    self._signature(kind))
                self.compiled[(kind, b)] = compiled
                for s in range(n_streams):
                    ex = Executor(b, compiled, eid=len(self._threads))
                    th = threading.Thread(
                        target=self._worker, args=(kind, b, ex),
                        name=f"dso-{kind}-b{b}-s{s}", daemon=True)
                    self._threads.append(th)
        self.build_time_s = time.perf_counter() - t0
        for th in self._threads:
            th.start()

    def _signature(self, kind: str) -> Callable:
        """The per-row wrapper ``build_fn`` applies for ``kind``: the
        kind's declared KV count and whether its rows come back split."""
        n_kv = self._kv_rows.get(kind, 0)
        split = kind in self._device_output

        def signature(fn, shapes, layout=None):
            return per_row_signature(fn, shapes, n_kv, split, layout)
        return signature

    # ---- submission ----
    def submit(self, request, m: int, kind: Optional[str] = None,
               dedup_token: Optional[Hashable] = None,
               deadline: Optional[float] = None,
               tier: Optional[str] = None):
        """Non-blocking: split into chunks, enqueue each onto its
        (kind, bucket) coalescing queue; returns a lazy future gathering the
        chunk rows.  ``dedup_token``, when given, is a stable identity for
        the chunk's dedupable/packable leading args (see the class
        docstring); ``deadline`` is an absolute ``time.perf_counter``
        instant the request's dispatch should start by — chunks carrying
        one pop earliest-deadline-first and flush early when the cost model
        says waiting longer would miss it.  ``tier`` (SLO tier name) scales
        the flush window per ``CoalescePolicy.tier_windows``."""
        if kind is None:
            kind = next(iter(self.families))
        plan = split_request(m, self.families[kind])
        with self._stat_lock:
            self.chunk_count += len(plan)
            self.kind_chunks[kind] += len(plan)
        futs = []
        for c in plan:
            args = self.pad_slice(request, c, kind)
            f = Future()
            futs.append(f)
            cond = self._cond[(kind, c.bucket)]
            with cond:
                heapq.heappush(
                    self._pending[(kind, c.bucket)],
                    _PendingChunk(args, f, dedup_token, valid=c.valid,
                                  deadline=deadline, remaining=m - c.start,
                                  tier=tier))
                cond.notify()

        def resolve():
            rows = [f.result() for f in futs]
            return self.gather(rows, plan, m, kind)

        return _Lazy(resolve)

    def score(self, request, m: int, kind: Optional[str] = None,
              dedup_token: Optional[Hashable] = None,
              deadline: Optional[float] = None,
              tier: Optional[str] = None):
        return self.submit(request, m, kind, dedup_token, deadline,
                           tier).result()

    def set_window_override(self, window_s: Optional[float]):
        """Degradation hook: cap the effective flush window at ``window_s``
        (0.0 == flush immediately); ``None`` restores the policy window."""
        with self._stat_lock:
            self._window_override = window_s

    # ---- dispatcher ----
    @staticmethod
    def _ident(c: _PendingChunk, n_lead: int) -> Hashable:
        return c.dedup_token if c.dedup_token is not None \
            else tuple(id(a) for a in c.args[:n_lead])

    def _collect(self, kind: str, bucket: int,
                 pending: List[_PendingChunk], cond: threading.Condition,
                 batch: List[_PendingChunk]) -> Optional[SegmentPacker]:
        """Pop the first chunk and keep collecting co-riders into the
        CALLER-OWNED ``batch`` list (caller holds ``cond``; filling the
        caller's list means a mid-collect exception can never strand the
        already-popped chunks — the worker fails exactly what was taken).
        The flush decision is deadline/cost-aware: with no deadlines in the
        collected set this is the v1 window policy (the window opens when
        collection starts, not at enqueue — a chunk that already sat in the
        queue past ``window_s`` would otherwise always dispatch solo); once
        any collected chunk carries a deadline, the wait is additionally
        capped at ``earliest_deadline - est_cost``.  The window itself is
        scaled by the minimum SLO-tier scale of the collected chunks
        (``CoalescePolicy.tier_windows``) and capped by the degradation
        override (``set_window_override``)."""
        pol = self.policy
        n_lead = self._kv_rows.get(kind, 0)
        packer = SegmentPacker(bucket, pol.rows, pol.batch,
                               align=pol.pack_align) \
            if kind in self._packed else None

        def take() -> bool:
            """Place the earliest-deadline pending chunk that FITS this
            dispatch.  For packed kinds a large head segment may not fit
            the remaining row space while smaller later chunks still do —
            skipping it costs the head nothing (it couldn't ride this
            dispatch anyway and leads the next one), and packing the
            smaller co-riders is exactly what reclaims the padding."""
            if packer is None:
                if len(batch) >= pol.batch or not pending:
                    return False
                batch.append(heapq.heappop(pending))
                return True
            skipped: List[_PendingChunk] = []
            got = False
            while pending:
                c = heapq.heappop(pending)
                if packer.try_add(c.valid, self._ident(c, n_lead)) \
                        is not None:
                    batch.append(c)
                    got = True
                    break
                skipped.append(c)
            for c in skipped:
                heapq.heappush(pending, c)
            return got

        took = take()
        assert took, "first chunk must always fit an empty dispatch"
        if pol.enabled and (pol.max_batch > 1 or packer is not None):
            with self._stat_lock:
                override = self._window_override
            base_window = pol.window_s if override is None \
                else min(pol.window_s, override)
            t_open = time.perf_counter()
            while not self._stop:
                full = packer.is_full() if packer is not None \
                    else len(batch) >= pol.max_batch
                if full:
                    break
                if pending:
                    if take():
                        continue
                    break        # nothing pending fits: flush what we have
                if packer is not None and len(batch) >= pol.max_batch:
                    # the dispatch already carries the v1 fill target's
                    # worth of chunks in fewer (denser) rows — waiting for
                    # MORE co-riders would trade latency (and, by Little's
                    # law, throughput at fixed concurrency) for slot
                    # capacity the in-flight population can't fill anyway.
                    # Deeper queues still pack up to the slot capacity
                    # through the take() loop above without ever waiting.
                    break
                now = time.perf_counter()
                scale = min(pol.tier_scale(c.tier) for c in batch)
                target = t_open + base_window * scale
                dls = [c.deadline for c in batch if c.deadline is not None]
                if dls:
                    with self._stat_lock:
                        est = self._cost.get((kind, bucket), 0.0)
                    target = min(target, min(dls) - est)
                left = target - now
                if left <= 0:
                    break
                cond.wait(timeout=left)
        now = time.perf_counter()
        delay = sum(now - c.enqueue_t for c in batch)
        with self._stat_lock:
            self.queue_delay_total_s += delay
            self.queue_delay_count += len(batch)
        return packer

    def _worker(self, kind: str, bucket: int, ex: Executor):
        key = (kind, bucket)
        cond, pending = (self._cond[key], self._pending[key]
                         )  # flamecheck: unguarded-ok(dicts frozen after __init__; the heap is only touched under cond)
        while True:
            batch: List[_PendingChunk] = []
            with cond:
                while not pending and not self._stop:
                    cond.wait()
                if not pending and self._stop:
                    return
                try:
                    packer = self._collect(kind, bucket, pending, cond,
                                           batch)
                except BaseException as e:  # noqa: BLE001 — never strand
                    # a mid-collect failure (e.g. a poisoned packer state)
                    # must fail exactly the chunks already popped off the
                    # heap and keep the stream thread alive; anything still
                    # pending stays queued for the next round
                    for c in batch:
                        if not c.future.done():
                            c.future.set_exception(e)
                    continue
            if packer is not None:
                self._dispatch_packed(kind, bucket, ex, batch, packer)
            else:
                self._dispatch(kind, bucket, ex, batch)

    @staticmethod
    def _stack_rows(rows: List, batch: int) -> np.ndarray:
        """Stack per-chunk host rows (leading axis 1) along the batch axis,
        padded with zero rows to the compiled batch size: one array, so
        one transfer per argument."""
        if len(rows) < batch:
            rows = list(rows) + [np.zeros_like(rows[0])] * (batch - len(rows))
        return np.concatenate(rows, axis=0)

    @staticmethod
    def _slot_args(slots: List[tuple], batch: int) -> list:
        """Per-slot KV args, slot-major, for a :func:`per_row_signature`
        executor: ``batch`` slots, the unused ones filled with slot 0's
        arrays (the same objects — nothing is allocated or copied)."""
        slots = list(slots) + [slots[0]] * (batch - len(slots))
        return [a for slot in slots for a in slot]

    def _note_dispatch(self, kind: str, bucket: int, n_chunks: int,
                       rows_used: int, valid: int, saved: int,
                       packed: bool, missed: int,
                       stages: Dict[str, float],
                       qblocks: Tuple[int, int] = (0, 0)):
        """Count one dispatch; ``stages`` holds its stage seconds (keys of
        ``stage_s``), whose launch + wait is the cost model's sample;
        ``qblocks`` its packed rows' (uniform, mixed) q blocks."""
        key = (kind, bucket)
        cost_s = stages["launch"] + stages["wait"]
        with self._stat_lock:
            self.dispatch_count += 1
            self.kind_dispatches[kind] += 1
            if kind in self._kv_rows:
                self.ingraph_dispatches += 1
            self.kind_run_s[kind] += cost_s
            for k, v in stages.items():
                self.stage_s[k] += v
            self.rows_dispatched += n_chunks
            self.dedup_rows_saved += saved
            self.slot_count[key] += rows_used * bucket
            self.valid_count[key] += valid
            self.deadline_miss_chunks[kind] += missed
            if packed:
                self.packed_rows += rows_used
                self.packed_segments += n_chunks
                self.qblocks_uniform += qblocks[0]
                self.qblocks_mixed += qblocks[1]
            old = self._cost.get(key)
            self._cost[key] = cost_s if old is None else \
                (1 - self._COST_EWMA) * old + self._COST_EWMA * cost_s

    @staticmethod
    def _count_missed(batch: List[_PendingChunk]) -> int:
        """Chunks whose dispatch completed past their absolute deadline."""
        now = time.perf_counter()
        return sum(1 for c in batch
                   if c.deadline is not None and now > c.deadline)

    def _run_executor(self, ex: Executor, stacked, meta: dict
                      ) -> Tuple[object, float, float]:  # flamecheck: host-sync-ok(dispatch boundary: the wait must happen inside the timed region — and inside the dispatch lock when executables are multi-device)
        """Launch (span ``flame.dso.launch``) + wait (timed, no span);
        returns (out, launch_s, wait_s).  Serialized under the dispatch
        lock when the executables are multi-device (see
        ``serialize_dispatch``)."""
        with self.serialized():
            with stage("dso.launch", **meta) as launch:
                out = ex(*stacked)
            t0 = time.perf_counter()
            jax.block_until_ready(out)
            return out, launch.s, time.perf_counter() - t0

    def serialized(self):
        """Context under which device work runs in one process-wide order
        with the executor launches: the dispatch lock when the executables
        are multi-device (``serialize_dispatch``), else a no-op.  Callers
        that issue their own eager multi-device ops on executor outputs
        (the pool quantizing and publishing a fresh entry) hold it too —
        an eager op racing a launch deadlocks a forced-host CPU mesh just
        as two launches do."""
        return self._dispatch_lock or contextlib.nullcontext()

    def _run_attempts(self, kind: str, bucket: int, ex: Executor, stacked,
                      meta: dict) -> Tuple[object, float, float]:
        """Fault-tolerant executor run: fire the chaos hook, then the
        executor; an exception with a truthy ``.transient`` attribute (the
        :class:`serving.faults.FaultInjected` contract — real transient
        infra errors can adopt it) retries with exponential backoff up to
        ``dispatch_retries`` times.  Anything else — or an exhausted
        budget — propagates to the caller, which fails every rider's
        future with the ORIGINAL traceback."""
        attempt = 0
        while True:
            try:
                if self._fault_hook is not None:
                    self._fault_hook(kind, bucket)
                return self._run_executor(ex, stacked, meta)
            except BaseException as e:  # noqa: BLE001 — classified below
                if not getattr(e, "transient", False) \
                        or attempt >= self._dispatch_retries:
                    raise
                attempt += 1
                with self._stat_lock:
                    self.dispatch_retry_count += 1
                time.sleep(self._retry_backoff_s * (2 ** (attempt - 1)))

    def _dispatch(self, kind: str, bucket: int, ex: Executor,
                  batch: List[_PendingChunk]
                  ):  # flamecheck: host-sync-ok(dispatch boundary: results must land on host to fan back out to per-chunk futures)
        n = len(batch)
        meta = {"kind": kind, "bucket": bucket, "rows": n}
        try:
            B = self.policy.batch
            with stage("dso.stack", **meta) as st_stack:
                n_kv = self._kv_rows.get(kind, 0)
                n_uniq = n
                if kind in self._dedup:
                    # identity-dedup the leading args: chunks carrying the
                    # SAME arg objects (one request split across chunks, or
                    # requests sharing a pool entry) take one KV slot; the
                    # executor gathers per-row views through the idx
                    # argument
                    slot_of: Dict[tuple, int] = {}
                    uniq: List[tuple] = []
                    idx = np.zeros(B, np.int32)
                    for i, c in enumerate(batch):
                        ident = self._ident(c, n_kv)
                        slot = slot_of.get(ident)
                        if slot is None:
                            slot = len(uniq)
                            slot_of[ident] = slot
                            uniq.append(c.args[:n_kv])
                        idx[i] = slot
                    n_uniq = len(uniq)
                    stacked = self._slot_args(uniq, B) + [idx]
                elif n_kv:
                    stacked = self._slot_args([c.args[:n_kv] for c in batch],
                                              B)
                else:
                    stacked = []
                rests = [c.args[n_kv:] for c in batch]
                for j in range(len(rests[0])):
                    stacked.append(self._stack_rows([r[j] for r in rests], B))
            out, launch_s, wait_s = self._run_attempts(kind, bucket, ex,
                                                       stacked, meta)
            readback_s = 0.0
            if kind in self._device_output:
                # the executor returned its rows split (per_row_signature):
                # device-resident pool entries, handed out by index
                with stage("dso.scatter", **meta) as st_scatter:
                    parts = list(out[:n])
            else:
                with stage("dso.readback", **meta) as st_read:
                    host = jax.tree.map(np.asarray, out)  # pytree outputs OK
                readback_s = st_read.s
                with stage("dso.scatter", **meta) as st_scatter:
                    parts = [jax.tree.map(lambda a: a[i:i + 1], host)
                             for i in range(n)]
            self._note_dispatch(kind, bucket, n, rows_used=n,
                                valid=sum(c.valid for c in batch),
                                saved=n - n_uniq, packed=False,
                                missed=self._count_missed(batch),
                                stages={"stack": st_stack.s,
                                        "launch": launch_s, "wait": wait_s,
                                        "readback": readback_s,
                                        "scatter": st_scatter.s})
            # riders wake only once their dispatch is counted
            for c, part in zip(batch, parts):
                c.future.set_result(part)
        except BaseException as e:  # noqa: BLE001 — fail every rider
            with self._stat_lock:
                self.dispatch_failure_count += 1
            for c in batch:
                if not c.future.done():
                    c.future.set_exception(e)

    def _dispatch_packed(self, kind: str, bucket: int, ex: Executor,
                         batch: List[_PendingChunk], packer: SegmentPacker
                         ):  # flamecheck: host-sync-ok(dispatch boundary: seg-index planes are built host-side and results fan back out to futures)
        """One packed dispatch: hand each unique KV identity over once, in
        its slot, build the ``[rows, bucket]`` seg-index and candidate
        planes from the packer's placements, run the executor, and scatter
        each segment's exact ``[1, valid, ...]`` output slice back to its
        chunk future."""
        n = len(batch)
        meta = {"kind": kind, "bucket": bucket, "rows": packer.n_rows}
        try:
            B = self.policy.batch
            n_lead = self._kv_rows[kind]
            with stage("dso.stack", **meta) as st_stack:
                # each unique KV identity's args once, in slot order
                uniq_args: List[Optional[tuple]] = [None] * packer.n_slots
                for c in batch:
                    slot = packer.slot_of[self._ident(c, n_lead)]
                    if uniq_args[slot] is None:
                        uniq_args[slot] = c.args[:n_lead]
                stacked = self._slot_args(uniq_args, B)
                rows = self.policy.rows
                seg_idx = np.zeros((rows, bucket), np.int32)
                cands = np.full((rows, bucket), -1, np.int32)
                for c, (row, off, slot) in zip(batch, packer.placements):
                    cands[row, off:off + c.valid] = np.asarray(
                        c.args[n_lead])[0]
                    seg_idx[row, off:off + c.valid] = slot
                stacked += [seg_idx, cands]
                qblocks = qblock_counts(seg_idx[:packer.n_rows],
                                        self.policy.pack_align)
            out, launch_s, wait_s = self._run_attempts(kind, bucket, ex,
                                                       stacked, meta)
            with stage("dso.readback", **meta) as st_read:
                host = jax.tree.map(np.asarray, out)
            with stage("dso.scatter", **meta) as st_scatter:
                parts = [jax.tree.map(
                    lambda a: a[row:row + 1, off:off + c.valid], host)
                    for c, (row, off, _) in zip(batch, packer.placements)]
            self._note_dispatch(kind, bucket, n, rows_used=packer.n_rows,
                                valid=sum(c.valid for c in batch),
                                saved=n - packer.n_slots, packed=True,
                                missed=self._count_missed(batch),
                                qblocks=qblocks,
                                stages={"stack": st_stack.s,
                                        "launch": launch_s, "wait": wait_s,
                                        "readback": st_read.s,
                                        "scatter": st_scatter.s})
            # riders wake only once their dispatch is counted
            for c, part in zip(batch, parts):
                c.future.set_result(part)
        except BaseException as e:  # noqa: BLE001 — fail every rider
            with self._stat_lock:
                self.dispatch_failure_count += 1
            for c in batch:
                if not c.future.done():
                    c.future.set_exception(e)

    # ---- introspection / lifecycle ----
    def stats(self) -> Dict[str, float]:
        with self._stat_lock:
            d = max(self.dispatch_count, 1)
            slots = sum(self.slot_count.values())
            valid = sum(self.valid_count.values())
            out = {
                "chunks": self.chunk_count,
                "dispatches": self.dispatch_count,
                "rows_dispatched": self.rows_dispatched,
                "avg_fill": self.rows_dispatched / d,
                "batch_axis": self.policy.batch,
                "dedup_rows_saved": self.dedup_rows_saved,
                "packed_rows": self.packed_rows,
                "packed_segments": self.packed_segments,
                "qblocks_uniform": self.qblocks_uniform,
                "qblocks_mixed": self.qblocks_mixed,
                "ingraph_dispatches": self.ingraph_dispatches,
                "cand_slots": slots,
                "cand_valid": valid,
                "padded_fraction": 1.0 - valid / slots if slots else 0.0,
                "queue_delay_ms": (1e3 * self.queue_delay_total_s
                                   / max(self.queue_delay_count, 1)),
                "queue_delay_s": self.queue_delay_total_s,
                "queue_delay_n": self.queue_delay_count,
                **{f"{k}_s": v for k, v in self.stage_s.items()},
                "dispatch_retries": self.dispatch_retry_count,
                "dispatch_failures": self.dispatch_failure_count,
                "deadline_miss_chunks": sum(
                    self.deadline_miss_chunks.values()),
            }
            if not self._legacy:
                for kind in self.families:
                    out[f"chunks_{kind}"] = self.kind_chunks[kind]
                    out[f"dispatches_{kind}"] = self.kind_dispatches[kind]
                    out[f"run_s_{kind}"] = self.kind_run_s[kind]
                    out[f"deadline_miss_chunks_{kind}"] = \
                        self.deadline_miss_chunks[kind]
                    out[f"cand_slots_{kind}"] = sum(
                        s for (k, _), s in self.slot_count.items()
                        if k == kind)
                    out[f"cand_valid_{kind}"] = sum(
                        v for (k, _), v in self.valid_count.items()
                        if k == kind)
                for (kind, b), s in self.slot_count.items():
                    if s:
                        out[f"fill_{kind}_b{b}"] = \
                            self.valid_count[(kind, b)] / s
            return out

    def shutdown(self):
        self._stop = True
        for cond in self._cond.values():
            with cond:
                cond.notify_all()
        for th in self._threads:
            th.join(timeout=5.0)
