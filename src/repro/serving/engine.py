"""Serving engines behind the API v2 surface (repro.serving.api).

Every engine shares the same staged pipeline scaffolding:

  submit() --> bounded admission queue (backpressure)
           --> PDA feature prefetch (fire-and-forget cache warm)
           --> worker threads: feature query -> execute -> ResponseFuture

and differs only in the execute stage:

  FlameEngine                the paper's system end to end — PDA feature
                             query, coalescing DSO over batch-axis AOT
                             executors (chunks from *different* in-flight
                             requests share one dispatch), SUMI-masked
                             forward of the bundle's model (Climber,
                             HSTU), per-candidate task scores;
  TextServingEngine          prefill+decode serving for the decode-based
                             assigned architectures.

Engines self-register ("flame" / "text"); construct them via
``repro.serving.api.create_engine``.  See DESIGN.md for the request
lifecycle diagram and docs/ARCHITECTURE.md for the end-to-end narrative.

Executor-family contract (FlameEngine <-> CoalescingOrchestrator)
-----------------------------------------------------------------
Executors are AOT-compiled per ``(kind, bucket)``:

  ("full",   M-bucket)   monolithic SUMI pass (pool off)
  ("cached", M-bucket)   candidate-only scoring against pooled history K/V;
                         with KV-row dedup the signature carries unique KV
                         rows + a [B] gather index; under ``impl="fused"``
                         the rows are the pool's RAW (quantized) leaves and
                         both dequant and gather happen in-kernel
                         (kernels/fused_score)
  ("encode", n_history)  history encode repopulating the pool on a miss
  ("extend", prefix_len) PDA v2 incremental path: re-encode only the window
                         suffix + side token against a stale entry's cached
                         prefix K/V (bucket = trusted prefix length)

Executors take the host planes the bundle declares
(``ModelBundle.host_planes``), in order: Climber's ``(history, side)``,
HSTU's ``(history, times)``; the engine fills each from the request, PDA's
side-feature query or PDA's user-history service.

``_pad_slice(request, chunk, kind)`` produces one chunk's host/device args
(leading axis 1); ``_gather(rows, chunks, m, kind)`` reassembles per-request
outputs.  Families that carry history-KV rows (cached, extend, decode,
append) declare their leading KV arg count to the DSO and are compiled for
its per-row signature (``core/dso.py::per_row_signature``): one ``[1, ...]``
array per KV slot, concatenated in graph; encode/extend/append return their
output rows already split, so a dispatch is one launch.

Pool fingerprint/staleness semantics live in ``serving/kv_cache.py``; the
history window is fingerprinted over the FULL upstream array (side features
average all of it), and stale entries become extension bases instead of pure
losses when ``incremental_history`` is on.
"""
from __future__ import annotations

import dataclasses
import hashlib
import heapq
import itertools
import math
import queue
import threading
import time
from concurrent.futures import Future
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import sharding as shd
from repro.core import dso as DSO
from repro.core import pda as PDA
from repro.models.model import ModelBundle
from repro.serving.api import (SLO_TIERS, TIER_RANK, AdmissionQueueFull,
                               DeadlineExceeded, DegradedError,
                               ResponseFuture, ServeMetrics, ServeRequest,
                               ServeResponse, ShedError, WatchdogTimeout,
                               register_engine)
from repro.kernels.fused_score.ops import PACK_ALIGN
from repro.serving.kv_cache import (HistoryKVPool, KVCacheManager,
                                    quantize_kv_graph, raw_kv_specs)

_STOP = object()

#: per-tier flush-window multipliers handed to ``CoalescePolicy``: an
#: interactive chunk flushes almost immediately, bulk may wait past the
#: default window for better packing.  Tier-less chunks (and "standard")
#: keep scale 1.0, so tier-agnostic callers see the v1 window exactly.
_TIER_WINDOW_SCALE = {"interactive": 0.25, "standard": 1.0, "bulk": 2.0}

#: re-encode-vs-extend crossover: an extension re-encodes the (window -
#: bucket) suffix, so once the trusted prefix drops below this share of the
#: window the extension does most of a full re-encode's work anyway (while
#: layering another requantization)
_EXTEND_CROSSOVER = 0.5

#: service-time EWMA smoothing for admission-time wait prediction
_SERVICE_EWMA = 0.3


def _try_fail(fut: ResponseFuture, exc: BaseException) -> bool:
    """Best-effort set_exception: the future may have been resolved by a
    worker in the same race window.  Returns True when the exception was
    actually delivered (callers count sheds/timeouts only on delivery)."""
    try:
        fut.set_exception(exc)
        return True
    except Exception:  # InvalidStateError — already resolved, fine
        return False


class _AdmissionRecord:
    """One queued submission: the priority key, the request's future, its
    submit timestamp, and the SLO/deadline facts shedding decisions read."""

    __slots__ = ("key", "fut", "t_submit", "tier", "deadline_abs", "shed")

    def __init__(self, key: tuple, fut: ResponseFuture, t_submit: float,
                 tier: str, deadline_abs: Optional[float]):
        self.key = key
        self.fut = fut
        self.t_submit = t_submit
        self.tier = tier
        self.deadline_abs = deadline_abs
        self.shed = False              # lazy-deletion marker (see shed_victim)


class _AdmissionQueue:
    """Bounded deadline-ordered (EDF) admission queue with tiered shedding.

    Replaces the FIFO ``queue.Queue`` of PR 1: records pop in priority-key
    order — ``(absolute deadline | inf, tier rank, seq)`` under ``edf``
    (deadline-less work sorts last, ties break best-tier-first then FIFO),
    or pure arrival order under ``fifo`` (the A/B baseline the overload
    bench gates against).

    One mutex guards the heap, with two condition variables over it
    (``not_empty`` for workers, ``not_full`` for blocked submitters) so a
    completed get wakes exactly a submitter and a put wakes exactly a
    worker.  Shedding removes a queued victim *lazily*: ``shed_victim``
    marks the worst strictly-lower-priority record and frees its capacity
    slot; ``get`` skips marked records when they surface at the heap root.

    ``close()`` is the stop signal: getters return ``None`` immediately
    (they do NOT drain — shutdown must not wait out a deep queue) and
    blocked putters raise; ``drain()`` then hands shutdown the leftovers
    to fail."""

    def __init__(self, maxsize: int, mode: str = "edf"):
        if mode not in ("edf", "fifo"):
            raise ValueError(f"admission mode must be edf|fifo, got {mode!r}")
        self.maxsize = maxsize
        self.mode = mode
        self._lock = threading.Lock()
        self._not_empty = threading.Condition(self._lock)
        self._not_full = threading.Condition(self._lock)
        self._heap: List[Tuple[tuple, _AdmissionRecord]] = []
        self._seq = itertools.count()
        self._live = 0                 # unshed records (capacity accounting)
        self._closed = False

    def key_for(self, deadline_abs: Optional[float], tier: str) -> tuple:
        """Priority key for one submission (smaller = served sooner)."""
        if self.mode == "fifo":
            return (next(self._seq),)
        return (deadline_abs if deadline_abs is not None else math.inf,
                TIER_RANK.get(tier, 1), next(self._seq))

    def put(self, rec: _AdmissionRecord, timeout: Optional[float] = None):
        """Enqueue; blocks while at capacity (``timeout=0`` = non-blocking).
        Raises ``queue.Full`` past the timeout and ``RuntimeError`` when
        closed."""
        with self._not_full:
            if timeout == 0:
                if self._live >= self.maxsize and not self._closed:
                    raise queue.Full
            else:
                end = None if timeout is None \
                    else time.perf_counter() + timeout
                while self._live >= self.maxsize and not self._closed:
                    left = None if end is None else end - time.perf_counter()
                    if left is not None and left <= 0:
                        raise queue.Full
                    self._not_full.wait(timeout=left)
            if self._closed:
                raise RuntimeError("admission queue closed")
            heapq.heappush(self._heap, (rec.key, rec))
            self._live += 1
            self._not_empty.notify()

    def get(self) -> Optional[_AdmissionRecord]:
        """Pop the best live record (blocking); ``None`` once closed — the
        worker stop signal (leftovers are failed by ``drain``, not served)."""
        with self._not_empty:
            while True:
                while self._heap and self._heap[0][1].shed:
                    heapq.heappop(self._heap)      # lazy-deleted victims
                if self._closed:
                    return None
                if self._heap:
                    _, rec = heapq.heappop(self._heap)
                    self._live -= 1
                    self._not_full.notify()
                    return rec
                self._not_empty.wait()

    def shed_victim(self, key: tuple
                    ) -> Optional[_AdmissionRecord]:
        """Remove and return the WORST queued record strictly lower-priority
        than ``key`` (latest deadline, lowest tier), or ``None`` when
        everything queued outranks the caller.  O(n) scan — the queue is
        admission-bounded, and shedding only runs under overload."""
        with self._lock:
            worst: Optional[_AdmissionRecord] = None
            for _, rec in self._heap:
                if not rec.shed and rec.key > key \
                        and (worst is None or rec.key > worst.key):
                    worst = rec
            if worst is None:
                return None
            worst.shed = True
            self._live -= 1
            self._not_full.notify()
            return worst

    def qsize(self) -> int:
        with self._lock:
            return self._live

    def close(self):
        with self._lock:
            self._closed = True
            self._not_empty.notify_all()
            self._not_full.notify_all()

    def drain(self) -> List[_AdmissionRecord]:
        """Pop every remaining live record (shutdown fails them)."""
        with self._lock:
            out = [rec for _, rec in self._heap if not rec.shed]
            self._heap.clear()
            self._live = 0
            return out


class _PipelinedEngine:
    """API v2 pipeline scaffolding shared by all engines.

    ``submit`` admits into a bounded deadline-ordered queue (blocking when
    full is the backpressure signal; a timeout raises
    :class:`AdmissionQueueFull`); ``n_workers`` threads drain it in EDF
    order and run the engine-specific ``_execute``.  Subclasses must finish
    their own setup *before* calling ``__init__`` here — workers start
    immediately.

    Overload discipline (all off by default — v1 semantics preserved):

    * ``admission="fifo"`` reverts to arrival-order service (A/B baseline).
    * ``slo_tier_defaults`` maps tier → default deadline seconds, used when
      a request carries no explicit ``deadline_s`` (falls back to the
      engine-wide default for unlisted tiers).
    * ``shed_policy="tiered"`` enables admission-time load shedding: when
      the queue is at depth or the EWMA-predicted wait blows the incoming
      request's budget, the worst strictly-lower-priority queued victim is
      failed with :class:`ShedError` (or the incoming request itself when
      nothing queued ranks below it).
    * ``watchdog_grace_s > 0`` starts a watchdog thread that fails any
      future still unresolved ``grace`` past its deadline with
      :class:`WatchdogTimeout` — under fault injection no request ever
      hangs.
    * ``degradation`` (a :class:`DegradationPolicy`) observes queue delay
      from the workers; level transitions invoke the ``_on_degrade`` hook.
    * ``faults`` (a :class:`FaultInjector`) arms the worker-stall hook here
      (subclasses wire its dispatch/pool arms)."""

    def __init__(self, *, max_pending: int = 64, n_workers: int = 4,
                 name: str = "engine", admission: str = "edf",
                 shed_policy: str = "none",
                 slo_tier_defaults: Optional[Dict[str, float]] = None,
                 watchdog_grace_s: float = 0.0,
                 degradation=None, faults=None):
        # engine-default deadline budget (seconds; 0 = none): subclasses
        # that support deadlines set it BEFORE calling __init__ here
        self._deadline_s = getattr(self, "_deadline_s", 0.0)
        if shed_policy not in ("none", "tiered"):
            raise ValueError(
                f"shed_policy must be none|tiered, got {shed_policy!r}")
        if slo_tier_defaults is not None:
            bad = set(slo_tier_defaults) - set(SLO_TIERS)
            if bad:
                raise ValueError(f"unknown SLO tiers in defaults: {bad}")
        self._metrics = ServeMetrics(
            stages=("admit", "features", "times", "service"))
        self._admission = _AdmissionQueue(max_pending, mode=admission)
        self._shed = shed_policy == "tiered"
        self._tier_defaults = dict(slo_tier_defaults) \
            if slo_tier_defaults else None
        self._degradation = degradation
        self._degrade_applied = 0
        self._faults = faults
        self._ewma_lock = threading.Lock()
        self._service_ewma_s: Optional[float] = None
        self._n_workers = max(int(n_workers), 1)
        self._open = True
        self._workers: List[threading.Thread] = []
        for i in range(n_workers):
            th = threading.Thread(target=self._worker_loop,
                                  name=f"{name}-worker-{i}", daemon=True)
            th.start()
            self._workers.append(th)
        self._watchdog_grace_s = float(watchdog_grace_s)
        self._watchdog_stop = threading.Event()
        self._watchdog_lock = threading.Lock()
        self._watchdog_futs: Dict[int, Tuple[ResponseFuture, float]] = {}
        self._watchdog_th: Optional[threading.Thread] = None
        if self._watchdog_grace_s > 0:
            th = threading.Thread(target=self._watchdog_loop,
                                  name=f"{name}-watchdog", daemon=True)
            th.start()
            self._watchdog_th = th

    # ---- engine-specific hooks ----
    def _execute(self, request: ServeRequest
                 ) -> Tuple[np.ndarray, Dict[str, float]]:
        """Run one request; returns (output, stage timings)."""
        raise NotImplementedError

    def _admit_hook(self, request: ServeRequest):
        """Called on the caller's thread at submit time (e.g. PDA prefetch)."""

    def _extra_metrics(self) -> Dict[str, float]:
        return {}

    def _close(self):
        """Engine-specific teardown after the workers have drained."""

    # ---- ServingEngine protocol ----
    def _effective_deadline(self, req: ServeRequest) -> float:
        """Deadline budget (seconds, 0 = none): explicit ``deadline_s``
        wins, then the engine's per-tier default, then the global one."""
        if req.deadline_s is not None:
            return req.deadline_s
        tier = getattr(req, "slo_tier", "standard")
        if self._tier_defaults is not None and tier in self._tier_defaults:
            return self._tier_defaults[tier]
        return self._deadline_s

    def _predicted_wait_s(self, depth: int) -> float:
        """EWMA service-time estimate of queue wait at the given depth."""
        with self._ewma_lock:
            s = self._service_ewma_s
        return 0.0 if s is None else depth * s / self._n_workers

    def _shed_for(self, rec: _AdmissionRecord):
        """Tiered admission-time shedding: under overload (queue at depth,
        or predicted wait past the incoming budget) drop the lowest-value
        work in sight — a strictly worse queued victim if one exists, else
        the incoming request itself (raises :class:`ShedError`)."""
        depth = self._admission.qsize()
        overloaded = depth >= self._admission.maxsize
        if not overloaded and rec.deadline_abs is not None:
            wait = self._predicted_wait_s(depth)
            overloaded = time.perf_counter() + wait > rec.deadline_abs
        if not overloaded:
            return
        # admission-control feedback: a shed caller should back off for
        # about one queue-drain interval instead of hammering — the same
        # queue-delay EWMA that detected the overload prices the hint
        retry_after_s = self._predicted_wait_s(depth)
        victim = self._admission.shed_victim(rec.key)
        if victim is not None:
            err = ShedError(
                f"request {victim.fut.request.request_id} "
                f"({victim.tier}) shed: displaced by a higher-priority "
                f"arrival under overload")
            err.retry_after_s = retry_after_s
            if _try_fail(victim.fut, err):
                self._metrics.incr(f"shed_{victim.tier}")
                self._metrics.incr("shed_total")
            return
        # nothing queued ranks below the incoming request: it IS the
        # lowest-value work — shed it before it burns a queue slot
        self._metrics.incr(f"shed_{rec.tier}")
        self._metrics.incr("shed_total")
        err = ShedError(
            f"request {rec.fut.request.request_id} ({rec.tier}) shed at "
            f"admission: queue overloaded and no lower-priority victim")
        err.retry_after_s = retry_after_s
        raise err

    def submit(self, request: ServeRequest, *,
               timeout: Optional[float] = None) -> ResponseFuture:
        if not self._open:
            raise RuntimeError("engine is shut down")
        tier = getattr(request, "slo_tier", "standard")
        if tier not in TIER_RANK:
            raise ValueError(
                f"request {request.request_id}: unknown slo_tier {tier!r}; "
                f"expected one of {SLO_TIERS}")
        dl = self._effective_deadline(request)
        if dl and time.perf_counter() > request.arrival_t + dl:
            # admission-time shedding: the latency budget is already blown,
            # so executing would burn an executor slot on a guaranteed miss
            # and delay co-pending requests that can still make theirs —
            # reject here, before the prefetch hook or a queue slot
            self._metrics.incr("deadline_shed")
            raise DeadlineExceeded(
                f"request {request.request_id}: deadline budget "
                f"{dl * 1e3:.3g} ms already exhausted at admission")
        deadline_abs = (request.arrival_t + dl) if dl else None
        fut = ResponseFuture(request)
        with DSO.stage("admit", request_id=request.request_id) as st:
            self._admit_hook(request)
        self._metrics.add_time("admit", st.s)
        t_submit = time.perf_counter()
        rec = _AdmissionRecord(self._admission.key_for(deadline_abs, tier),
                               fut, t_submit, tier, deadline_abs)
        if self._shed:
            self._shed_for(rec)        # may raise ShedError for `rec` itself
        try:
            self._admission.put(rec, timeout=timeout)
        except queue.Full:
            err = AdmissionQueueFull(
                f"admission queue full ({self._admission.maxsize} pending)")
            err.retry_after_s = self._predicted_wait_s(
                self._admission.qsize())
            raise err from None
        except RuntimeError:
            # queue closed mid-put: shutdown raced us
            _try_fail(fut, RuntimeError("engine shut down during submit"))
            return fut
        self._watchdog_register(fut, deadline_abs)
        if not self._open:
            # lost the race with shutdown(): the workers may already have
            # observed the close signal, so nobody will resolve this
            # future — fail it rather than hang the caller
            _try_fail(fut, RuntimeError("engine shut down during submit"))
        return fut

    def serve(self, history: np.ndarray,
              candidates: Optional[np.ndarray] = None, **kw) -> np.ndarray:
        """Blocking sugar around submit()."""
        req = ServeRequest(
            history=np.asarray(history),
            candidates=None if candidates is None else np.asarray(candidates),
            **kw)
        return self.submit(req).result().output

    def metrics(self) -> Dict[str, float]:
        # engine internals first: _extra_metrics may refresh ServeMetrics
        # gauges (padded_fraction) that summary() reports
        extra = self._extra_metrics()
        out = self._metrics.summary()
        out["pending"] = self._admission.qsize()
        out.update(extra)
        return out

    def shutdown(self):
        if not self._open:
            return
        self._open = False
        self._admission.close()        # workers see None and exit
        for th in self._workers:
            th.join(timeout=10.0)
        # fail any request that raced past the close signal
        for rec in self._admission.drain():
            _try_fail(rec.fut, RuntimeError("engine shut down"))
        self._watchdog_stop.set()
        if self._watchdog_th is not None:
            self._watchdog_th.join(timeout=5.0)
        self._close()

    # ---- watchdog (liveness backstop under fault injection) ----
    def _watchdog_register(self, fut: ResponseFuture,
                           deadline_abs: Optional[float]):
        if self._watchdog_th is None or deadline_abs is None:
            return
        fail_at = deadline_abs + self._watchdog_grace_s
        with self._watchdog_lock:
            self._watchdog_futs[id(fut)] = (fut, fail_at)
        fut.add_done_callback(self._watchdog_forget)

    def _watchdog_forget(self, fut):
        with self._watchdog_lock:
            self._watchdog_futs.pop(id(fut), None)

    def _watchdog_loop(self):
        interval = min(max(self._watchdog_grace_s / 2, 0.01), 0.25)
        grace_ms = self._watchdog_grace_s * 1e3
        while not self._watchdog_stop.wait(interval):
            now = time.perf_counter()
            with self._watchdog_lock:
                due = [fut for fut, t in self._watchdog_futs.values()
                       if now > t]
            for fut in due:
                # a worker may resolve it in this window — count only wins
                if _try_fail(fut, WatchdogTimeout(
                        f"request {fut.request.request_id} unresolved "
                        f"{grace_ms:.3g} ms past its deadline")):
                    self._metrics.incr("watchdog_timeouts")

    # ---- graceful degradation plumbing ----
    def _observe_pressure(self, queue_delay_s: float):
        level = self._degradation.observe(queue_delay_s)
        if level != self._degrade_applied:
            # benign race: concurrent workers converge on the same level
            self._degrade_applied = level
            self._metrics.set_gauge("degrade_level", float(level))
            self._metrics.incr("degrade_steps")
            self._on_degrade(level)

    def _on_degrade(self, level: int):
        """Engine-specific degradation effects (subclass hook); called on a
        worker thread whenever the applied level changes."""

    # ---- worker side ----
    def _worker_loop(self):
        while True:
            rec = self._admission.get()
            if rec is None:            # queue closed: stop signal
                return
            fut, t_submit = rec.fut, rec.t_submit
            t_deq = time.perf_counter()
            req = fut.request
            try:
                if self._faults is not None:
                    self._faults.worker_stall()
                output, timings = self._execute(req)
                t_done = time.perf_counter()
                latency = t_done - t_submit
                timings = {"queue_s": t_deq - t_submit, **timings}
                n_items = req.m if req.candidates is not None \
                    and getattr(req, "generate", None) is None \
                    else len(output)
                self._metrics.record(n_items, latency)
                dl = self._effective_deadline(req)
                if dl:
                    if t_done > req.arrival_t + dl:
                        self._metrics.incr("deadline_misses")
                        self._metrics.incr(f"deadline_misses_{rec.tier}")
                    else:
                        self._metrics.incr("deadline_met")
                        self._metrics.incr(f"goodput_{rec.tier}")
                fut.set_result(ServeResponse(req.request_id, output,
                                             latency, timings))
            except BaseException as e:  # noqa: BLE001 — surface via future
                _try_fail(fut, e)
            finally:
                dt = time.perf_counter() - t_deq
                self._metrics.add_time("service", dt)
                with self._ewma_lock:
                    s = self._service_ewma_s
                    self._service_ewma_s = dt if s is None \
                        else _SERVICE_EWMA * dt + (1 - _SERVICE_EWMA) * s
                if self._degradation is not None:
                    self._observe_pressure(t_deq - t_submit)


def _make_features(feature_mode: str, store, cache_capacity: int,
                   cache_ttl_s: float, feature_dim: int):
    store = store or PDA.RemoteFeatureStore(feature_dim=feature_dim)
    cache = None if feature_mode == "off" else PDA.BucketedLRUCache(
        cache_capacity, cache_ttl_s)
    return store, PDA.FeatureQueryEngine(store, cache, mode=feature_mode)


class _HostPlaneMixin:
    """The host planes a bundle declares (``ModelBundle.host_planes``),
    filled per request: the history window, PDA in action (item features
    of the history aggregated into the side-feature vector, user-profile
    style) and per-action timestamps (the request's own, else PDA's
    user-history service)."""

    def _init_planes(self, bundle: ModelBundle, feature_mode: str, store,
                     cache_capacity: int, cache_ttl_s: float):
        self._planes = tuple(bundle.host_planes)
        unknown = {p.source for p in self._planes} - {
            "window", "side_features", "action_times"}
        if not self._planes or unknown:
            raise ValueError(
                f"model {bundle.cfg.name!r} declares host planes "
                f"{self._planes}: every split-surface bundle declares its "
                f"planes, from window / side_features / action_times")
        side = [p for p in self._planes if p.source == "side_features"]
        self._side_width = side[0].width if side else 0
        self.store, self.features = _make_features(
            feature_mode, store, cache_capacity, cache_ttl_s,
            self._side_width)

    def _plane_specs(self, batch: int) -> tuple:
        """AOT argument specs of the planes for ``batch`` rows."""
        return tuple(jax.ShapeDtypeStruct(
            (batch, p.width or self.n_history), jnp.dtype(p.dtype))
            for p in self._planes)

    def _host_planes(self, req: ServeRequest, hist: np.ndarray) -> tuple:
        """Each declared plane of one request, [1, width] in its dtype;
        ``hist`` is the request's [1, n_history] window."""
        out = []
        for p in self._planes:
            if p.source == "window":
                out.append(hist)
            elif p.source == "side_features":
                out.append(self._side_features(req.history, req.request_id))
            else:
                out.append(self._action_times(req, hist))
        return tuple(out)

    def _plane_batch(self, planes) -> dict:
        """The surface's batch dict of the declared planes, in order."""
        return {p.name: a for p, a in zip(self._planes, planes)}

    def _window(self, planes: tuple) -> np.ndarray:
        """What a pool entry encoded, position by position: the window ids
        [n], or with per-action planes besides (action times) [P, n]."""
        rows = [a[0] for a, p in zip(planes, self._planes) if not p.width]
        return rows[0] if len(rows) == 1 else np.stack(rows)

    def _check_request(self, req: ServeRequest):
        """Reject malformed requests before their chunks reach the shared
        coalescing queue — a bad shape there would fail every co-rider
        batched into the same dispatch, not just this request."""
        generative = getattr(req, "generate", None) is not None
        if not generative and (req.candidates is None
                               or req.candidates.ndim != 1 or req.m < 1):
            raise ValueError(
                f"request {req.request_id}: candidates must be a non-empty "
                f"1-D id array, got "
                f"{None if req.candidates is None else req.candidates.shape}")
        if generative and req.candidates is not None \
                and (req.candidates.ndim != 1 or req.m < 1):
            raise ValueError(
                f"request {req.request_id}: a generative request's "
                f"candidates (its token universe) must be a non-empty 1-D "
                f"id array, got {req.candidates.shape}")
        if req.candidates is not None and req.m and int(np.min(
                req.candidates)) < 0:  # flamecheck: host-sync-ok(admission validation over the caller's host id array)
            raise ValueError(
                f"request {req.request_id}: candidate ids must be >= 0 "
                f"(negative ids are reserved for chunk-padding sentinels)")
        if req.history.ndim != 1 or \
                req.history.shape[0] < self.n_history:  # flamecheck: recompile-ok(admission validation that raises; selects no executor)
            raise ValueError(
                f"request {req.request_id}: history must be a 1-D id array "
                f"with >= n_history={self.n_history} entries, got "
                f"{req.history.shape}")
        if req.timestamps is not None and \
                req.timestamps.shape != req.history.shape:
            raise ValueError(
                f"request {req.request_id}: timestamps must align with the "
                f"history {req.history.shape}, got {req.timestamps.shape}")

    def _side_features(self, history: np.ndarray,
                       request_id: int = -1) -> np.ndarray:
        with DSO.stage("pda.features", request_id=request_id) as st:
            feats = self.features.query([int(i) for i in history])
            got = [v for v in feats.values() if v is not None]
            side = np.mean(got, axis=0, keepdims=True).astype(np.float32) \
                if got else np.zeros((1, self._side_width), np.float32)
        self._metrics.add_time("features", st.s)
        return side

    def _action_times(self, req: ServeRequest, hist: np.ndarray
                      ) -> np.ndarray:  # flamecheck: host-sync-ok(request timestamps arrive as host numpy; the service's lookup is host numpy)
        """[1, n] int32 times of the window's actions: the request's own
        ``timestamps``, else PDA's user-history service."""
        with DSO.stage("pda.times", request_id=req.request_id) as st:
            if req.timestamps is not None:
                t = np.asarray(req.timestamps[None, :hist.shape[1]],
                               np.int32)
            else:
                t = PDA.action_times(req.user_id, hist[0])[None]
        self._metrics.add_time("times", st.s)
        return t

    def _admit_hook(self, request: ServeRequest):
        if self._side_width:
            self.features.prefetch([int(i) for i in request.history])


class _Beam:
    """Host-side state of one in-flight hypothesis (ISSUE 8).

    ``leaves`` holds the beam's padded KV cache locally ONLY while the
    pool has rejected (or not yet accepted) it — the steady state is
    ``leaves is None`` with the cache living in the :class:`HistoryKVPool`
    under ``pool_key``/``pool_fp``, where it is subject to the same LRU /
    byte-budget discipline as every history entry.  An evicted beam is
    recovered by replaying its appends from a re-encoded base (counted in
    ``gen_replays``)."""

    __slots__ = ("tokens", "cum", "finished", "leaves", "pool_key",
                 "pool_fp")

    def __init__(self, tokens, cum, finished=False, leaves=None,
                 pool_key=None, pool_fp=None):
        self.tokens = tokens            # tuple of generated item ids
        self.cum = cum                  # cumulative log-probability
        self.finished = finished
        self.leaves = leaves
        self.pool_key = pool_key
        self.pool_fp = pool_fp


class _ParamsBound:
    """An AOT executable whose first argument, the model params, is bound:
    the DSO calls it with the per-dispatch operands only.  Attribute reads
    (``as_text``, ``memory_analysis``, ...) reach the executable."""

    def __init__(self, compiled, params):
        self.compiled = compiled
        self.params = params

    def __call__(self, *args):
        return self.compiled(self.params, *args)

    def __getattr__(self, name):
        return getattr(self.compiled, name)


@register_engine("flame")
class FlameEngine(_HostPlaneMixin, _PipelinedEngine):
    """PDA -> coalescing DSO -> the bundle's GR model (Climber, HSTU), per
    the paper's Fig 1/Fig 4.

    Executors are AOT-compiled with a real batch axis ``(max_batch,
    bucket)``; the DSO dispatcher merges same-bucket chunks from different
    in-flight requests into one executor call (time-window + fill-target
    policy) and scatters rows back to per-request futures.  Batch rows are
    independent, so coalesced scores are bitwise-identical to sequential
    per-request serving (tests assert this).

    With ``history_cache=True`` the engine splits the SUMI forward
    (MTServe-style hierarchical caching): the per-request history encode is
    keyed into a :class:`HistoryKVPool` (by ``request.user_id``, else a
    content hash of the history prefix) and scoring always runs the cheap
    candidate-only executor family against the pooled K/V.  A pool hit
    skips the history encode entirely; a miss routes one batched
    ``encode`` dispatch first and parks the result for the next request
    from that user.  Scores are numerically identical to the full pass
    (bitwise under the reference/chunked impls).

    PDA v2 pool knobs (all riding on ``history_cache=True``):

    ``pool_budget_bytes`` / ``pool_slots``
        byte and/or entry bound on the pool (LRU-evicted; bytes are the
        real HBM constraint — entries scale with ``n_history``).
    ``pool_dtype``
        stored precision: ``native`` | ``bf16`` | ``int8`` (per-head
        scales; ~4x users-per-budget vs f32 at a bounded score drift).
    ``pool_placement`` / ``pool_spill_bytes``
        ``device`` keeps entries as JAX device arrays that flow
        dispatcher -> pool -> dispatch without host round-trips (``host``
        reproduces the PR 2 behavior for A/B); a nonzero spill budget adds
        a host-RAM second tier that absorbs evictions.
    ``incremental_history`` / ``extend_buckets``
        stale hits whose cached entry encoded a window sharing a prefix
        with the new history re-encode ONLY the changed suffix + side
        token against the cached prefix K/V (``extend`` executor family;
        buckets are trusted-prefix lengths, default the full window — the
        tail-append case that re-encodes one token per block).  Note:
        under a lossy ``pool_dtype`` each extension re-quantizes the
        dequantized prefix, so drift can accumulate over a long-lived
        user's repeated extensions (bounded per step by the dtype's error;
        periodic forced re-encode is a ROADMAP follow-up).
    KV-row dedup (derived, not an option)
        identity-dedup of KV rows in the cached-scoring dispatcher: a
        multi-chunk request (or co-batched requests hitting one pool
        entry) stacks each user's KV rows once per dispatch, not once per
        chunk.  ON for accelerator backends
        (the saved cost is the per-chunk host->HBM transfer; the
        executor-side row gather is an HBM-local copy, ~30x cheaper) and
        OFF for the CPU backend (stacking is a plain memcpy there, so the
        gather would be pure overhead — measured ~15% on 2 cores) —
        EXCEPT under ``impl="fused"``, where it is ON everywhere: the FKE
        folds the gather into the kernel's KV block reads, so dedup is
        free on every backend.
    ``extend_buckets`` / ``extend_refresh_limit``
        trusted-prefix lengths for the extend executor family (default:
        the (n, 3n/4, n/2) ladder) and the extension-drift cap — after
        this many incremental extensions of one entry (each of which
        re-quantizes under a lossy ``pool_dtype``) the next stale hit
        re-encodes in full (``pool_refresh_reencodes`` metric; 0 = off).
        Prefixes below half the window always re-encode (the
        re-encode-vs-extend crossover: the extension would redo most of
        the window while layering another requantization).

    DSO v2 (``pack_tails`` / ``deadline_s``):

    ``pack_tails``
        segment-packed ragged dispatch (needs ``history_cache``): partial
        tail chunks from DIFFERENT requests pack into shared ``(1,
        bucket)`` rows as independent segments, each steered to its own
        user's pooled history KV through a per-candidate ``[B, bucket]``
        KV slot index (candidates never attend to each other under SUMI,
        so packing is bitwise-clean — asserted in tests/test_dso_v2.py).
        Reclaims the 20-40% ``padded_fraction`` the greedy bucket split
        dispatches on non-uniform candidate traffic; subsumes KV-row
        dedup (same-user segments share one stacked KV slot).
        ``pack_rows`` (default ``max_batch / 4``) sizes the packed
        executors' row axis: packed rows are dense, so fewer rows carry
        the unpacked fill target's candidate throughput at a fraction of
        the per-dispatch executor cost, while ``max_batch`` still sizes
        the unique-KV axis (distinct users per dispatch).
    ``deadline_s``
        default per-request latency budget (seconds; a request's own
        ``ServeRequest.deadline_s`` overrides).  Pending chunks flush
        earliest-deadline-first with a shortest-remaining-work tie-break,
        and the DSO stops collecting co-riders as soon as its per-bucket
        cost model says waiting longer would miss the earliest collected
        deadline.  Overruns count into the ``deadline_misses`` metric.

    Mesh-sharded serving (``mesh=...``): executors AOT-compile with
    ``NamedSharding`` in/out specs resolved from
    ``sharding.serving_rules`` — the request-batch axis rides the mesh's
    ``data`` axis, attention heads ride ``model`` (tensor-parallel; when
    the KV heads don't divide the model ways, the history length takes
    the model axis instead, the context-parallel fallback shared with
    ``impl="cp"``), and the pooled-user row axis of stacked history KV is
    REPLICATED so the dedup/packed row gathers never cross shards.  The
    pool commits its entries to the same layout (``shard_spec``) and
    splits its byte budget per model shard; the DSO rounds batch/row
    capacities up to multiples of the data ways so one coalesced flush
    feeds every device without resharding on the hot path.

    FKE (``impl="fused"``): the ``cached`` executor family is compiled
    against the pool's RAW stored representation (int8/bf16 values + per-
    (layer, head) scales, ``serving/kv_cache.py::raw_kv_specs``) plus the
    dedup row index, and ``kernels/fused_score`` dequantizes tiles and
    resolves the row gather in-kernel — a pool hit dispatches without the
    host-side dequantize or the ``kv[idx]`` materialization the framework
    impls pay.  Hit and miss paths share the stored representation, so
    repeat scores are bitwise-stable."""

    def __init__(self, bundle: ModelBundle, params, *, n_history: int,
                 buckets: Sequence[int] = (512, 256, 128),
                 n_streams: int = 2,
                 feature_mode: str = "sync",
                 cache_capacity: int = 50_000, cache_ttl_s: float = 30.0,
                 store: Optional[PDA.RemoteFeatureStore] = None,
                 coalesce: bool = True, max_batch: int = 4,
                 window_s: float = 0.002,
                 max_pending: int = 64, n_workers: int = 4,
                 impl: str = "chunked",
                 history_cache: bool = False, pool_slots: int = 256,
                 pool_budget_bytes: Optional[int] = None,
                 pool_dtype: str = "native",
                 pool_placement: str = "device",
                 pool_spill_bytes: int = 0,
                 incremental_history: bool = False,
                 extend_buckets: Optional[Sequence[int]] = None,
                 extend_refresh_limit: int = 0,
                 pack_tails: bool = False,
                 pack_rows: Optional[int] = None,
                 deadline_s: float = 0.0,
                 mesh: Optional[jax.sharding.Mesh] = None,
                 generate: int = 0,
                 gen_vocab: int = 256,
                 admission: str = "edf",
                 shed_policy: str = "none",
                 slo_tier_defaults: Optional[Dict[str, float]] = None,
                 watchdog_grace_s: float = 0.0,
                 degradation=None,
                 faults=None,
                 dispatch_retries: int = 2):
        self.bundle = bundle
        if mesh is not None:
            # the executors take the params as an argument: replicate them
            # over the mesh once, so no dispatch moves weights
            params = jax.device_put(params, jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec()))
        self.params = params
        self.cfg = bundle.cfg
        self.n_history = n_history
        self.impl = impl
        self._fused = impl == "fused"
        # mesh-sharded serving: executors compile with NamedSharding in/out
        # specs (batch over "data", attention heads over "model", pooled
        # user rows replicated) so one coalesced flush feeds every device
        self.mesh = mesh
        self._shard_rules: Optional[dict] = None
        self._data_ways = 1
        self._model_ways = 1
        if mesh is not None:
            self._shard_rules = shd.serving_rules(
                mesh, kv_heads=bundle.cfg.n_kv_heads)
            self._data_ways = int(mesh.shape.get("data", 1))
            self._model_ways = int(mesh.shape.get("model", 1))
        self._pack_tails = bool(pack_tails)
        if pack_rows is None and pack_tails:
            # packed rows are dense where unpacked rows are mostly padding:
            # a quarter of the row capacity carries a comparable candidate
            # throughput on the heavy-tailed traffic packing targets, at a
            # quarter of the per-dispatch executor cost.  max_batch still
            # sizes the unique-KV axis (distinct users per dispatch).
            pack_rows = max(1, max_batch // 4)
        self._pack_rows = pack_rows
        # the packer's segment alignment: the fused kernel reads 2-D seg
        # indices once per q block, so under impl="fused" every segment
        # starts on the kernel's PACK_ALIGN contract; the other impls read
        # per candidate and pack densely (first fit)
        self._pack_align = PACK_ALIGN if self._fused else 1
        self._deadline_s = float(deadline_s)
        if pack_tails and not history_cache:
            raise ValueError(
                "pack_tails=True needs history_cache=True: segment packing "
                "steers each candidate segment to its own user's POOLED "
                "history KV — the monolithic full-pass family has no "
                "per-user KV rows to steer to")
        self._init_planes(bundle, feature_mode, store, cache_capacity,
                          cache_ttl_s)

        self.history_pool: Optional[HistoryKVPool] = None
        self._extend_buckets: tuple = ()
        self._extend_refresh_limit = int(extend_refresh_limit)
        if history_cache:
            if bundle.encode_history is None or bundle.score_candidates is None:
                raise ValueError(
                    "history_cache=True needs a bundle with the split "
                    "encode_history/score_candidates serving surface")
            if incremental_history:
                if bundle.extend_history is None:
                    raise ValueError(
                        "incremental_history=True needs a bundle with the "
                        "extend_history serving surface")
                explicit_buckets = extend_buckets is not None
                if extend_buckets is None:
                    # default trusted-prefix ladder (n, 3n/4, n/2): the
                    # dominant tail-append case extends from the full
                    # window, mid-window edits from the nearest rung
                    extend_buckets = (n_history, 3 * n_history // 4,
                                      n_history // 2)
                # buckets below the re-encode-vs-extend crossover are
                # dropped HERE so no AOT executor is ever compiled for a
                # rung the dispatch policy would never route to (executor
                # builds dominate engine startup)
                min_prefix = int(_EXTEND_CROSSOVER * n_history)
                self._extend_buckets = tuple(sorted(
                    {b for b in extend_buckets if b >= max(min_prefix, 1)},
                    reverse=True))
                if explicit_buckets and not self._extend_buckets:
                    # every user-supplied rung fell below the crossover:
                    # silently serving full re-encodes would contradict
                    # the explicit incremental request — fail loudly
                    raise ValueError(
                        f"extend_buckets {tuple(extend_buckets)} all fall "
                        f"below the re-encode-vs-extend crossover "
                        f"({min_prefix} = {_EXTEND_CROSSOVER:g} * "
                        f"n_history); raise the buckets")
            self.history_pool = HistoryKVPool(
                pool_slots, budget_bytes=pool_budget_bytes, dtype=pool_dtype,
                placement=pool_placement, spill_bytes=pool_spill_bytes,
                mesh=mesh, shard_spec=self._kv_leaf_sharding)
            kv_specs = bundle.history_kv_specs(params, n_history, batch=1)
            # the FKE ("fused") executors consume the pool's RAW
            # representation — stored-precision values + per-(layer, head)
            # scales, dequantized in-kernel (cached scoring) or in-graph
            # (extend basis) — so their compiled signature quantizes the
            # row specs instead of the engine dequantizing every hit (or
            # every stale basis) on the host
            cached_specs = raw_kv_specs(kv_specs, pool_dtype) \
                if self._fused else kv_specs
            cleaves, self._cached_treedef = jax.tree.flatten(cached_specs)
            self._cached_row_specs = cleaves
            # compute dtype the stored representation dequantizes back to
            # (prequantized puts must record it so later dequantizing
            # lookups round-trip to the executors' compiled input dtype)
            self._kv_compute_dtype = jax.tree.leaves(kv_specs)[0].dtype
            # KV-row dedup: ON for accelerator backends (each deduped row
            # is a skipped H2D transfer) and, under the fused impl, on EVERY
            # backend — the row gather is folded into the kernel's KV block
            # reads, so dedup costs nothing even on CPU
            self._kv_dedup = jax.default_backend() != "cpu" or self._fused
            self._encode_inflight: Dict[tuple, Future] = {}
            self._encode_lock = threading.Lock()
            self._key_memo: Dict[int, tuple] = {}   # request_id -> (key, fp)

        # generative candidate decode (ISSUE 8): ``generate`` is the
        # engine's per-request generation CAPACITY in steps — beam caches
        # are padded by this many extra sequence slots up front so every
        # append is a fixed-shape in-place write (one compiled executor,
        # no recompiles as beams grow)
        self._generate = int(generate)
        self._gen_vocab = int(gen_vocab)
        self._gen_lock = threading.Lock()
        self._beams_in_flight = 0
        if self._generate:
            if not history_cache:
                raise ValueError(
                    "generate>0 needs history_cache=True: in-flight beams "
                    "live in the HistoryKVPool as growing entries and the "
                    "decode step reads pooled history KV as its prompt")
            if mesh is not None:
                raise ValueError(
                    "generate>0 under a mesh is not supported yet: beam "
                    "caches are per-request host-orchestrated state and "
                    "would reshard on every append")
            if bundle.decode_logits is None or bundle.append_token is None:
                raise ValueError(
                    f"generate>0 needs a bundle with the decode_logits/"
                    f"append_token generative serving surface; model "
                    f"{bundle.cfg.name!r} (family {bundle.cfg.family!r}) "
                    f"has none")
            # decode/append executors speak PADDED beam caches: the cached
            # row specs with ``generate`` extra slots on the sequence axis,
            # filled one per appended token (valid prefix = lengths).
            # Under the fused impl the raw specs interleave per-(layer,
            # head) scale leaves (trailing singleton) with the value
            # leaves; a beam keeps its ROOT scales for the whole
            # generation (appended tokens quantize against them in the
            # epilogue), so scale leaves don't grow with the beam
            self._decode_row_specs = tuple(
                s if s.shape[-1] == 1 else jax.ShapeDtypeStruct(
                    s.shape[:2] + (s.shape[2] + self._generate,)
                    + s.shape[3:], s.dtype)
                for s in self._cached_row_specs)
            self._s0 = int(self._cached_row_specs[0].shape[2])

        _batched = lambda specs, batch: tuple(  # noqa: E731
            jax.ShapeDtypeStruct((batch,) + s.shape[1:], s.dtype)
            for s in specs)
        cached_row_shapes = lambda batch: _batched(  # noqa: E731
            self._cached_row_specs, batch)
        decode_row_shapes = lambda batch: _batched(  # noqa: E731
            getattr(self, "_decode_row_specs", ()), batch)

        def build_fn(kind: str, bucket: int, batch: int, signature):
            # every executor takes the model params as its FIRST argument
            # (bound at build time, see _ParamsBound): params closed over
            # by the traced function would be embedded in each program as
            # HLO constants — a copy of the item embedding per executable
            if kind == "full":
                def fn(params, *args):
                    *planes, candidates = args
                    # -1 chunk-padding sentinel -> a real (ignored) row
                    b = dict(self._plane_batch(planes),
                             candidates=jnp.maximum(candidates, 0))
                    return bundle.prefill(params, b, impl=self.impl)
                shapes = self._plane_specs(batch) + (
                    jax.ShapeDtypeStruct((batch, bucket), jnp.int32),)
            elif kind == "encode":
                # Under the fused impl the executor quantizes IN-EPILOGUE
                # (FKE v2): its output is the pool's stored representation
                # — (values, scale) leaves from quantize_kv_graph — so a
                # miss pools what it just computed via put(prequantized=
                # True) and scores from the same leaves, with no separate
                # quantize pass and no raw read-back
                def fn(params, *planes):
                    kv = bundle.encode_history(
                        params, self._plane_batch(planes), impl=self.impl)
                    if self._fused:
                        kv = quantize_kv_graph(kv, self.history_pool.dtype)
                    return kv
                shapes = self._plane_specs(batch)
            elif kind == "extend":
                # bucket = trusted prefix length: re-encode window positions
                # >= bucket (plus any side token) against the cached prefix.
                # Under the fused impl the basis arrives RAW (the pool's
                # stored int8/bf16 leaves + scales, 4x fewer dispatch bytes
                # for int8) and dequantizes in-graph inside extend_history
                def fn(params, *args):
                    n_kv = len(self._cached_row_specs)
                    kv = jax.tree.unflatten(self._cached_treedef,
                                            list(args[:n_kv]))
                    out = bundle.extend_history(
                        params, kv, self._plane_batch(args[n_kv:]),
                        prefix_len=bucket, impl=self.impl)
                    if self._fused:
                        # in-epilogue re-quantize: same contract as encode
                        out = quantize_kv_graph(out, self.history_pool.dtype)
                    return out
                shapes = cached_row_shapes(batch) + self._plane_specs(batch)
            elif kind == "cached":
                if self._pack_tails:
                    # DSO v2 segment-packed signature: one row may carry
                    # candidate segments of several users; seg_idx [B,
                    # bucket] steers every candidate to its own user's
                    # stacked KV row (per-candidate generalization of the
                    # dedup row index — consumed in-kernel under fused,
                    # via the reference-structured segment attention
                    # elsewhere)
                    def fn(params, *args):
                        *kv_leaves, seg_idx, candidates = args
                        kv = jax.tree.unflatten(self._cached_treedef,
                                                list(kv_leaves))
                        return bundle.score_candidates(
                            params, kv, jnp.maximum(candidates, 0),
                            impl=self.impl, row_index=seg_idx)
                    # policy.rows (late-bound: build_fn runs inside the
                    # orchestrator's executor build) carries the mesh
                    # rounding, so compiled rows match the packer's capacity
                    rows = policy.rows
                    shapes = cached_row_shapes(batch) + (
                        jax.ShapeDtypeStruct((rows, bucket), jnp.int32),
                        jax.ShapeDtypeStruct((rows, bucket), jnp.int32))
                elif self._kv_dedup:
                    # deduped signature: unique KV rows + per-row gather idx
                    def fn(params, *args):
                        *kv_leaves, idx, candidates = args
                        if self._fused:
                            # FKE: the raw (stored-precision) rows and the
                            # gather index flow straight into the kernel —
                            # no host dequant, no kv[idx] materialization
                            kv = jax.tree.unflatten(self._cached_treedef,
                                                    list(kv_leaves))
                            return bundle.score_candidates(
                                params, kv, jnp.maximum(candidates, 0),
                                impl=self.impl, row_index=idx)
                        kv = jax.tree.unflatten(
                            self._cached_treedef,
                            [jnp.take(a, idx, axis=0) for a in kv_leaves])
                        return bundle.score_candidates(
                            params, kv, jnp.maximum(candidates, 0),
                            impl=self.impl)
                    shapes = cached_row_shapes(batch) + (
                        jax.ShapeDtypeStruct((batch,), jnp.int32),
                        jax.ShapeDtypeStruct((batch, bucket), jnp.int32))
                else:
                    def fn(params, *args):
                        *kv_leaves, candidates = args
                        kv = jax.tree.unflatten(self._cached_treedef,
                                                list(kv_leaves))
                        return bundle.score_candidates(
                            params, kv, jnp.maximum(candidates, 0),
                            impl=self.impl)
                    shapes = cached_row_shapes(batch) + (
                        jax.ShapeDtypeStruct((batch, bucket), jnp.int32),)
            elif kind == "decode":
                # one generative-decode step: score ``bucket`` next-token
                # candidates per row against padded beam caches with valid
                # prefix ``lengths``.  Under pack_tails the family is
                # SEGMENT-PACKED exactly like "cached" — in-flight beams
                # from different requests (at different lengths) bin-pack
                # into shared rows, each candidate steered to its own
                # beam's stacked cache row AND its own valid length by the
                # per-candidate seg index; ``lengths`` rides as an extra
                # packable lead arg alongside the KV leaves.
                if self._pack_tails:
                    def fn(params, *args):
                        *kv_leaves, lengths, seg_idx, candidates = args
                        kv = jax.tree.unflatten(self._cached_treedef,
                                                list(kv_leaves))
                        return bundle.decode_logits(
                            params, kv, jnp.maximum(candidates, 0),
                            lengths, impl=self.impl, row_index=seg_idx)
                    rows = policy.rows
                    shapes = decode_row_shapes(batch) + (
                        jax.ShapeDtypeStruct((batch,), jnp.int32),
                        jax.ShapeDtypeStruct((rows, bucket), jnp.int32),
                        jax.ShapeDtypeStruct((rows, bucket), jnp.int32))
                else:
                    def fn(params, *args):
                        *kv_leaves, lengths, candidates = args
                        kv = jax.tree.unflatten(self._cached_treedef,
                                                list(kv_leaves))
                        return bundle.decode_logits(
                            params, kv, jnp.maximum(candidates, 0),
                            lengths, impl=self.impl)
                    shapes = decode_row_shapes(batch) + (
                        jax.ShapeDtypeStruct((batch,), jnp.int32),
                        jax.ShapeDtypeStruct((batch, bucket), jnp.int32))
            elif kind == "append":
                # grow a beam cache by its chosen token's K/V at position
                # ``lengths`` — a fixed-shape scatter into the padded cache,
                # so every step of every beam reuses this one executor
                def fn(params, *args):
                    *kv_leaves, lengths, tokens = args
                    kv = jax.tree.unflatten(self._cached_treedef,
                                            list(kv_leaves))
                    return bundle.append_token(
                        params, kv, jnp.maximum(tokens, 0), lengths,
                        impl=self.impl)
                shapes = decode_row_shapes(batch) + (
                    jax.ShapeDtypeStruct((batch,), jnp.int32),
                    jax.ShapeDtypeStruct((batch, 1), jnp.int32))
            else:
                raise ValueError(kind)
            # the DSO's per-row contract for this kind: KV rows arrive one
            # [1, ...] array per slot and concatenate in graph; device-
            # output kinds return their rows split, from the pool's layout
            # under a mesh (the publish all-gather)
            fn, shapes = signature(
                fn, shapes,
                layout=None if self.mesh is None else self._arg_sharding)
            # a stable name per executor: its HLO module (and its host
            # events in a trace) read jit_flame_<kind>_b<bucket>
            fn.__name__ = fn.__qualname__ = f"flame_{kind}_b{bucket}"
            if self.mesh is not None:
                # attach the resolved NamedSharding specs to the AOT
                # signature: the executor consumes its operands in exactly
                # the layout the dispatcher stacks / the pool stores them,
                # so the steady-state hot path never reshards.  Tracing
                # under mesh_rules() binds the model's constrain_ctx
                # annotations (and the impl="cp" shard_map route) to the
                # same rule table.
                shapes = tuple(
                    jax.ShapeDtypeStruct(
                        s.shape, s.dtype,
                        sharding=self._arg_sharding(s.shape))
                    for s in shapes)
                out_sh = jax.tree.map(
                    lambda s: self._arg_sharding(s.shape),
                    jax.eval_shape(fn, self.params, *shapes))
                with shd.mesh_rules(self.mesh, self._shard_rules):
                    compiled = jax.jit(fn, out_shardings=out_sh) \
                        .lower(self.params, *shapes).compile()
            else:
                compiled = jax.jit(fn).lower(self.params, *shapes).compile()
            return _ParamsBound(compiled, self.params)

        # the bucket key gains a hit/miss dimension: candidate-only
        # ("cached") executors serve pool traffic, "encode" repopulates the
        # pool on miss, "extend" refreshes a stale entry from its cached
        # prefix, "full" is the monolithic path when the pool is off.
        # Every family that carries history-KV rows declares how many
        # leading args they are and how they share KV slots (DSO kv_kinds);
        # the DSO hands them over per slot and binds build_fn's signature
        kv_kinds: Dict[str, Tuple[int, str]] = {}
        device_output_kinds: tuple = ()
        if history_cache:
            n_kv = len(self._cached_row_specs)
            families = {"cached": tuple(buckets), "encode": (n_history,)}
            if self._extend_buckets:
                families["extend"] = self._extend_buckets
                kv_kinds["extend"] = (n_kv, "row")
            # packing subsumes KV-row dedup: same-user segments share one
            # KV slot inside the packer
            kv_kinds["cached"] = (n_kv, "packed" if self._pack_tails else
                                  "dedup" if self._kv_dedup else "row")
            if self._generate:
                families["decode"] = tuple(buckets)
                families["append"] = (1,)
                kv_kinds["append"] = (n_kv, "row")
                # packed: the beam's valid length packs alongside its KV
                # leaves (one lead-arg tuple per unique beam -> one slot),
                # so a packed row mixes beams at different lengths without
                # padding any of them
                kv_kinds["decode"] = (n_kv + 1, "packed") \
                    if self._pack_tails else (n_kv, "row")
            if pool_placement == "device":
                # encode/extend outputs feed the pool: keep them on device,
                # split per row inside the executor
                device_output_kinds = ("encode", "extend")
                if self._generate:
                    device_output_kinds += ("append",)
        else:
            families = {"full": tuple(buckets)}
        policy = DSO.CoalescePolicy(enabled=coalesce, max_batch=max_batch,
                                    window_s=window_s,
                                    pack_rows=self._pack_rows,
                                    pack_align=self._pack_align,
                                    data_ways=self._data_ways,
                                    tier_windows=dict(_TIER_WINDOW_SCALE))
        self.dso = DSO.CoalescingOrchestrator(
            build_fn, pad_slice_fn=self._pad_slice, gather_fn=self._gather,
            policy=policy, n_streams=n_streams, families=families,
            kv_kinds=kv_kinds, device_output_kinds=device_output_kinds,
            # multi-device executables must not overlap their collectives
            # (XLA rendezvous has no cross-computation ordering — see
            # CoalescingOrchestrator); a 1x1 mesh stays fully concurrent
            serialize_dispatch=mesh is not None and mesh.size > 1,
            fault_hook=None if faults is None else faults.dispatch,
            dispatch_retries=dispatch_retries)
        super().__init__(max_pending=max_pending, n_workers=n_workers,
                         name="flame", admission=admission,
                         shed_policy=shed_policy,
                         slo_tier_defaults=slo_tier_defaults,
                         watchdog_grace_s=watchdog_grace_s,
                         degradation=degradation, faults=faults)

    # back-compat alias: callers used to read eng.pool.build_time_s
    @property
    def pool(self):
        return self.dso

    # ---- mesh sharding (logical layouts -> NamedSharding) ----
    def _kv_leaf_sharding(self, shape):
        """Sharding for one stored/stacked history-KV leaf (5-d: [rows, L,
        S, Hkv, D] values or [rows, L, 1, Hkv, 1] scales): heads ride the
        model axis, the pooled-user row axis stays replicated.  Doubles as
        the pool's placement callback so pooled KV lives where its heads
        live; returns None for non-KV shapes or mesh-less engines."""
        if self.mesh is None or len(shape) != 5:
            return None
        return shd.logical_to_sharding(shd.SERVING_KV_LEAF, shape,
                                       self.mesh, self._shard_rules)

    def _arg_sharding(self, shape):
        """NamedSharding for one executor operand/result: 5-d arrays are
        history-KV leaves; everything else (history / side / candidates /
        seg_idx / scores) leads with the request-batch axis, which rides
        the data axis."""
        kv = self._kv_leaf_sharding(shape)
        if kv is not None:
            return kv
        logical = ("batch",) + (None,) * (len(shape) - 1)
        return shd.logical_to_sharding(logical, shape, self.mesh,
                                       self._shard_rules)

    def _pool_key(self, request: ServeRequest
                  ):  # flamecheck: host-sync-ok(admission-time canonicalization: histories arrive as host numpy and the content hash must read host bytes)
        fp = self._fingerprint(np.asarray(request.history, np.int32),
                               request.timestamps)
        key = ("u", int(request.user_id)) \
            if request.user_id is not None else ("h", fp)
        return key, fp

    def _admit_hook(self, request: ServeRequest):
        if self.history_pool is not None and (
                request.candidates is not None
                or request.generate is not None):
            key, fp = self._pool_key(request)
            # stash for _execute so the O(n_history) hash runs once; the
            # memo is written on the submitter thread and consumed on a
            # pipeline worker, so it shares the encode lock
            with self._encode_lock:
                self._key_memo[request.request_id] = (key, fp)
            if self.history_pool.contains(key, fp):
                return      # pool hit ahead: side features never consumed
        super()._admit_hook(request)

    # ---- chunk plumbing (host-side; the dispatcher assembles the args) ----
    @staticmethod
    def _slice_candidates(candidates, chunk: DSO.Chunk):
        sl = candidates[:, chunk.start:chunk.start + chunk.valid]
        if chunk.valid < chunk.bucket:
            # -1 sentinel: padding is never a real item id (0 is)
            sl = np.pad(sl, ((0, 0), (0, chunk.bucket - chunk.valid)),
                        constant_values=-1)
        return sl

    def _pad_slice(self, request, chunk: DSO.Chunk, kind: str):
        if kind == "encode":
            return tuple(request)                # the host planes
        if kind == "extend":
            kv_leaves, planes = request
            return tuple(kv_leaves) + tuple(planes)
        if kind == "full":
            planes, candidates = request
            return tuple(planes) + (self._slice_candidates(candidates,
                                                           chunk),)
        if kind == "append":
            kv_leaves, lengths, tokens = request
            return tuple(kv_leaves) + (lengths, tokens)
        if kind == "decode":
            kv_leaves, lengths, candidates = request
            if self._pack_tails:
                sl = candidates[:, chunk.start:chunk.start + chunk.valid]
                return tuple(kv_leaves) + (lengths, sl)
            return tuple(kv_leaves) + (
                lengths, self._slice_candidates(candidates, chunk))
        kv_leaves, candidates = request          # cached
        if self._pack_tails:
            # packed family: hand the dispatcher the UNPADDED segment —
            # the packer places it at an arbitrary row offset and pads the
            # assembled row once
            sl = candidates[:, chunk.start:chunk.start + chunk.valid]
            return tuple(kv_leaves) + (sl,)
        return tuple(kv_leaves) + (self._slice_candidates(candidates, chunk),)

    def _gather(self, rows, chunks: List[DSO.Chunk], m: int,
                kind: str = "full"):
        if kind in ("encode", "extend", "append"):
            return rows[0]                      # one chunk: the KV pytree
        parts = [r[:, :c.valid] for r, c in zip(rows, chunks)]
        return np.concatenate(parts, axis=1)

    # ---- history-KV pool ----
    @staticmethod
    def _fingerprint(history: np.ndarray,
                     timestamps: Optional[np.ndarray] = None) -> str:
        """Content hash of the FULL history array — the model truncates to
        n_history, but side features average over every entry, so a
        tail-only change must read as stale too (full-pass parity) — and
        of the request's own action times, where it gives them."""
        h = hashlib.blake2b(np.ascontiguousarray(history).tobytes(),
                            digest_size=16)
        if timestamps is not None:
            h.update(np.ascontiguousarray(timestamps, np.int32).tobytes())
        return h.hexdigest()

    @staticmethod
    def _shared_prefix(cached: Optional[np.ndarray], new: np.ndarray
                       ) -> int:  # flamecheck: host-sync-ok(prefix diff of two host-resident id windows; no device arrays involved)
        """Length of the common leading run of two history windows, [n] ids
        or [P, n] per-action planes (-1 when no basis window is
        available)."""
        if cached is None or cached.shape != new.shape:
            return -1
        diff = np.asarray(cached) != np.asarray(new)
        neq = np.nonzero(diff.reshape(-1, new.shape[-1]).any(axis=0))[0]
        return int(neq[0]) if neq.size else int(new.shape[-1])

    def _cached_rows(self, kv) -> tuple:
        """Flatten a pool lookup result into the cached-executor arg order.
        Under the fused impl the result is a raw view — (values, scale)
        tuples over the stored arrays — whose flatten order matches the
        compiled raw-spec signature; otherwise it is the dequantized leaf
        tuple unchanged."""
        return tuple(jax.tree.leaves(kv))

    def _lookup_or_encode(self, req: ServeRequest, hist: np.ndarray,
                          memo: Optional[tuple] = None,
                          deadline: Optional[float] = None,
                          _retry: bool = True
                          ) -> Tuple[tuple, str, float]:
        """Returns (kv_leaves, path, features_s) with path one of ``hit`` /
        ``encode`` / ``extend`` / ``wait``; encodes (or, on an extendable
        stale hit, suffix-extends the dropped entry) and repopulates the
        pool on miss.  Concurrent misses for one (key, fingerprint) are
        single-flighted: the first worker encodes, co-arriving session
        requests wait on its future instead of dispatching duplicate
        O(n_history) encodes.  Under the fused impl the stale basis is
        read back RAW (``raw_basis``): the extend executors are compiled
        against the pool's stored representation and dequantize in-graph,
        so the host-side dequant of the dropped entry is gone."""
        key, fp = memo if memo is not None else self._pool_key(req)
        kv, status, basis = self.history_pool.lookup(
            key, fp, want_basis=bool(self._extend_buckets),
            raw=self._fused, raw_basis=self._fused)
        if status == "hit":
            return self._cached_rows(kv), "hit", 0.0
        with self._encode_lock:
            fut = self._encode_inflight.get((key, fp))
            leader = fut is None
            if leader:
                # a racing leader may have put + deregistered between our
                # counted miss and taking this lock — re-check (uncounted)
                # before electing ourselves and re-encoding
                kv = self.history_pool.peek(key, fp, raw=self._fused)
                if kv is not None:
                    return self._cached_rows(kv), "wait", 0.0
                fut = Future()
                self._encode_inflight[(key, fp)] = fut
        if not leader:
            try:
                return fut.result(), "wait", 0.0
            except BaseException:
                # single-flight recovery: the leader we coalesced behind
                # died (e.g. a poisoned request or an injected fault) — its
                # failure is ITS OWN, not ours.  Re-enter once: the dead
                # leader has deregistered, so we either become the new
                # leader or join a healthy one.  One retry only, so a
                # deterministically-failing encode still fails everyone.
                if not _retry:
                    raise
                self._metrics.incr("encode_recoveries")
                return self._lookup_or_encode(req, hist, memo, deadline,
                                              _retry=False)
        try:
            t0 = time.perf_counter()
            planes = self._host_planes(req, hist)
            window = self._window(planes)
            t1 = time.perf_counter()
            kv_tree, path, refreshes = None, "encode", 0
            if basis is not None and self._extend_buckets:
                # stale hit sharing a window prefix with the dropped entry:
                # re-encode only the suffix (+ side token) against its K/V
                shared = self._shared_prefix(basis.hist_window, window)
                bucket = max((b for b in self._extend_buckets if b <= shared),
                             default=None)
                if bucket is not None and self._extend_refresh_limit and \
                        basis.refreshes >= self._extend_refresh_limit:
                    # extension-drift cap: this entry has been extended
                    # (re-quantized) K times since its last full encode
                    bucket = None
                    self.history_pool.count_refresh_reencode()
                if bucket is not None:
                    basis_leaves = tuple(jax.tree.leaves(basis.kv))
                    kv_tree = self.dso.score((basis_leaves, planes),
                                             bucket, kind="extend",
                                             deadline=deadline,
                                             tier=req.slo_tier)
                    path = "extend"
                    refreshes = basis.refreshes + 1
                    self.history_pool.count_extension()
            if kv_tree is None:
                kv_tree = self.dso.score(planes, self.n_history,
                                         kind="encode", deadline=deadline,
                                         tier=req.slo_tier)
            # device-placed pools get the executor's own per-row output
            # buffers; host rows (pool_placement="host") are numpy VIEWS
            # into the (max_batch, ...) stacked parent — copy those so
            # pooling them doesn't pin the padded parent or make pool_bytes
            # under-report
            kv = tuple(np.array(a) if isinstance(a, np.ndarray) else a
                       for a in jax.tree.leaves(
                           kv_tree))  # flamecheck: host-sync-ok(copies host VIEWS out of the padded stacked parent so pooling them cannot pin it)
            if self._fused:
                # in-epilogue quantize (FKE v2): the encode/extend
                # executors already emitted the pool's stored
                # representation, so pool it as-is (no second quantize
                # pass) and score from the very same leaves — hit, wait,
                # encode and extend paths all share one representation
                # without the raw read-back the un-fused flow needs
                self._pool_put(
                    key, fp, jax.tree.unflatten(self._cached_treedef,
                                                list(kv)),
                    hist_window=window, refreshes=refreshes,
                    prequantized=True,
                    compute_dtype=self._kv_compute_dtype)
            else:
                self._pool_put(key, fp, kv, hist_window=window,
                               refreshes=refreshes)
            self._metrics.set_gauge("pool_bytes_used",
                                    self.history_pool.bytes_used)
            for i, b in enumerate(self.history_pool.shard_bytes()):
                self._metrics.set_gauge(f"pool_bytes_used_shard{i}", b)
            fut.set_result(kv)
        except BaseException as e:
            fut.set_exception(e)
            raise
        finally:
            with self._encode_lock:
                self._encode_inflight.pop((key, fp), None)
        return kv, path, t1 - t0

    def _degrade_level(self) -> int:
        return 0 if self._degradation is None else self._degradation.level

    def _execute(self, req: ServeRequest):
        memo = None
        if self.history_pool is not None:
            with self._encode_lock:
                memo = self._key_memo.pop(req.request_id, None)
            if self._faults is not None:
                # eviction-storm arm: pressure-spike / cold-restart stand-in
                dropped = self._faults.pool_storm(self.history_pool)
                if dropped:
                    self._metrics.incr("fault_pool_evictions", dropped)
        self._check_request(req)
        if req.generate is not None:
            return self._execute_generate(req, memo)
        t0 = time.perf_counter()
        dl = self._effective_deadline(req)
        deadline = (req.arrival_t + dl) if dl else None
        hist = np.asarray(req.history[None, :self.n_history],
                          np.int32)  # flamecheck: host-sync-ok(request arrays arrive as host numpy; dtype canonicalized once at admission)
        cand = np.asarray(req.candidates[None],
                          np.int32)  # flamecheck: host-sync-ok(request arrays arrive as host numpy; dtype canonicalized once at admission)
        if self.history_pool is None:
            planes = self._host_planes(req, hist)
            t1 = time.perf_counter()
            out = self.dso.score((planes, cand), req.m, kind="full",
                                 deadline=deadline, tier=req.slo_tier)
            t2 = time.perf_counter()
            return out[0], {"features_s": t1 - t0, "execute_s": t2 - t1}
        key_fp = memo if memo is not None else self._pool_key(req)
        if req.slo_tier == "bulk" and self._degrade_level() >= 3:
            # level-3 degradation: bulk-tier encodes are suppressed — serve
            # only from cache, shed the rest (cached-hit-or-shed)
            kv_raw = self.history_pool.peek(key_fp[0], key_fp[1],
                                            raw=self._fused)
            if kv_raw is None:
                self._metrics.incr("degrade_shed")
                raise DegradedError(
                    f"request {req.request_id} (bulk) shed: level-3 "
                    f"degradation suppresses encodes and the pool has no "
                    f"entry for this session")
            kv, path, features_s = self._cached_rows(kv_raw), "hit", 0.0
        else:
            kv, path, features_s = self._lookup_or_encode(req, hist, key_fp,
                                                          deadline)
        t1 = time.perf_counter()
        # On a HIT the (key, fingerprint) pair is a stable content identity
        # for the loaded rows (every hit dequantizes the same payload), so
        # co-batched requests for one user dedup even when a quantized pool
        # dequantizes to fresh arrays per lookup.  Under the framework
        # impls, miss paths carry the leader's PRE-quantization KV — under
        # a lossy pool dtype that is a different representation than a
        # hit's, so they fall back to object identity (which still dedups
        # one request's own chunks and single-flight followers sharing the
        # leader's tuple).  Under the FUSED impl every path reads the
        # stored (quantized) representation — the miss leader reads the
        # entry back raw after put — so hit, wait, encode and extend rows
        # all share one content identity and dedup across co-batched
        # requests unconditionally.
        token = None
        if (self._kv_dedup or self._pack_tails) \
                and (self._fused or path == "hit"):
            token = ("kv",) + key_fp[0] + (key_fp[1],)
        out = self.dso.score((kv, cand), req.m, kind="cached",
                             dedup_token=token, deadline=deadline,
                             tier=req.slo_tier)
        t2 = time.perf_counter()
        build_s = (t1 - t0) - features_s
        return out[0], {"features_s": features_s,
                        "encode_s": build_s if path == "encode" else 0.0,
                        "extend_s": build_s if path == "extend" else 0.0,
                        "pool_hit": 1.0 if path == "hit" else 0.0,
                        "execute_s": t2 - t1}

    # ---- generative candidate decode (ISSUE 8) ----
    def _pad_beam_leaves(self, kv_leaves) -> tuple:
        """Pad base (s0-row) cache leaves to the decode executors' S_pad =
        s0 + generate slots — once per request root, on the host; every
        subsequent append is a fixed-shape in-place write.  Raw (fused)
        leaf tuples interleave per-(layer, head) scale leaves — trailing
        singleton — which stay at their root shape: appended tokens
        quantize against the root scales (see ``_decode_row_specs``)."""
        pad = ((0, 0), (0, 0), (0, self._generate), (0, 0), (0, 0))
        return tuple(
            np.asarray(a) if a.shape[-1] == 1 else np.pad(np.asarray(a), pad)
            for a in
            kv_leaves)  # flamecheck: host-sync-ok(one-time root-cache padding; beam orchestration is host-side by design)

    def _copy_kv_rows(self, kv_tree) -> tuple:
        """Flatten an executor KV result and copy host VIEWS out of the
        padded stacked dispatch parent (same rule as the encode path)."""
        return tuple(
            np.array(a) if isinstance(a, np.ndarray) else a
            for a in jax.tree.leaves(
                kv_tree))  # flamecheck: host-sync-ok(copies host VIEWS out of the padded stacked parent so holding them cannot pin it)

    def _shift_beams_in_flight(self, delta: int):
        with self._gen_lock:
            self._beams_in_flight += delta
            n = self._beams_in_flight
        self._metrics.set_gauge("beams_in_flight", n)

    def _beam_leaves(self, req, hist, memo, beam: _Beam, deadline) -> tuple:
        """The beam's padded KV cache: local copy if the pool rejected it,
        else a pool lookup — and, when the entry was LRU-evicted
        mid-generation, a replay (re-encode the history base, re-append
        every generated token; ``gen_replays`` counts these)."""
        if beam.leaves is not None:
            return beam.leaves
        kv, status, _ = self.history_pool.lookup(beam.pool_key, beam.pool_fp,
                                                 raw=self._fused)
        if status == "hit":
            return tuple(jax.tree.leaves(kv))
        self._metrics.incr("gen_replays")
        base, _, _ = self._lookup_or_encode(req, hist, memo, deadline)
        leaves = self._pad_beam_leaves(base)
        for i, tok in enumerate(beam.tokens):
            kv_tree = self.dso.score(
                (leaves, np.full((1,), self._s0 + i, np.int32),
                 np.asarray(
                     [[tok]],
                     np.int32)),  # flamecheck: host-sync-ok(replayed tokens are host python ints; beam orchestration is host-side by design)
                1, kind="append", deadline=deadline, tier=req.slo_tier)
            leaves = self._copy_kv_rows(kv_tree)
        return leaves

    def _pool_put(self, *args, **kwargs) -> bool:
        """``history_pool.put`` of executor outputs, in the DSO's launch
        order: a put's quantize and layout publish are eager multi-device
        ops under a mesh, and must not race a dispatch's collectives."""
        with self.dso.serialized():
            return self.history_pool.put(*args, **kwargs)

    def _park_beam(self, req, slot: int, beam: _Beam, leaves: tuple,
                   hist_fp) -> None:
        """Hand a beam's cache to the pool (key = (\"g\", request id, beam
        slot); fingerprint = the token path, so a slot overwritten by a
        different hypothesis next step reads as a miss, not a wrong hit).
        On accept the local copy is dropped — the pool's LRU/byte budget
        governs the beam like any user entry; on reject it stays local."""
        key = ("g", req.request_id, slot)
        fp = (hist_fp,) + beam.tokens
        if self._fused:
            # the appended cache is already the stored representation
            # (climber's append epilogue quantizes the new token against
            # the root scales in-graph) — park it without re-quantizing
            accepted = self._pool_put(
                key, fp, jax.tree.unflatten(self._cached_treedef,
                                            list(leaves)),
                prequantized=True, compute_dtype=self._kv_compute_dtype)
        else:
            accepted = self._pool_put(key, fp, leaves)
        if accepted:
            beam.pool_key, beam.pool_fp, beam.leaves = key, fp, None
        else:
            beam.leaves = leaves

    def _execute_generate(self, req: ServeRequest, memo: Optional[tuple]):
        from repro.serving import generate as G
        from repro.serving.api import BeamConfig, TopKConfig
        gen = req.generate
        if isinstance(gen, TopKConfig):
            width, steps, eos, beam_mode = int(gen.k), int(gen.steps), \
                gen.eos, False
        elif isinstance(gen, BeamConfig):
            width, steps, eos, beam_mode = int(gen.width), int(gen.steps), \
                gen.eos, True
        else:
            raise ValueError(
                f"request {req.request_id}: generate must be a TopKConfig "
                f"or BeamConfig, got {type(gen).__name__}")
        if not self._generate:
            raise ValueError(
                "this engine was built without generative capacity; "
                "construct it with generate=<max steps>")
        if not 1 <= steps <= self._generate:
            raise ValueError(
                f"request {req.request_id}: steps={steps} outside the "
                f"engine's generate capacity [1, {self._generate}]")
        if req.candidates is not None:
            # np.unique sorts AND dedups: duplicate ids would make two
            # "distinct" hypotheses identical, breaking beam uniqueness
            universe = np.unique(np.asarray(
                req.candidates,
                np.int32))  # flamecheck: host-sync-ok(admission-time canonicalization of the caller's host id array)
        else:
            universe = np.arange(self._gen_vocab, dtype=np.int32)
        # top-k seeds k INDEPENDENT greedy beams from the k best first
        # tokens, so k is capped by the universe; beam search may run wider
        # than the universe (hypotheses multiply V-fold per step — step 0
        # seeds min(width, V) beams and beam_step grows toward width)
        if width < 1 or (not beam_mode and width > len(universe)):
            raise ValueError(
                f"request {req.request_id}: width={width} must be in "
                f"[1, |universe|={len(universe)}] for top-k decode")
        if req.slo_tier == "bulk" and self._degrade_level() >= 2:
            # level-2 degradation: bulk-tier generation runs at half beam
            # width and half the steps — a cheaper, shorter answer beats a
            # shed one, and the freed decode slots drain the backlog
            width = max(1, width // 2)
            steps = max(1, steps // 2)
            self._metrics.incr("degrade_gen_shrunk")
        t0 = time.perf_counter()
        dl = self._effective_deadline(req)
        deadline = (req.arrival_t + dl) if dl else None
        hist = np.asarray(
            req.history[None, :self.n_history],
            np.int32)  # flamecheck: host-sync-ok(request arrays arrive as host numpy; dtype canonicalized once at admission)
        key_fp = memo if memo is not None else self._pool_key(req)
        hist_fp = key_fp[1]
        base, path, features_s = self._lookup_or_encode(req, hist, key_fp,
                                                        deadline)
        root_leaves = self._pad_beam_leaves(base)
        t1 = time.perf_counter()
        self._shift_beams_in_flight(width)
        try:
            beams = self._generate_loop(
                req, hist, key_fp, root_leaves, universe, width, steps,
                eos, beam_mode, deadline, G)
        finally:
            self._shift_beams_in_flight(-width)
        # best-first [width, steps] id matrix; -1 pads rows finished early
        order = np.argsort(
            -np.asarray([b.cum for b in beams]),
            kind="stable")  # flamecheck: host-sync-ok(final ranking over host python floats; beam orchestration is host-side by design)
        out = np.full((width, steps), -1, np.int32)
        for r, o in enumerate(order):
            toks = beams[o].tokens
            out[r, :len(toks)] = toks
        t2 = time.perf_counter()
        build_s = (t1 - t0) - features_s
        return out, {"features_s": features_s,
                     "encode_s": build_s if path == "encode" else 0.0,
                     "extend_s": build_s if path == "extend" else 0.0,
                     "pool_hit": 1.0 if path == "hit" else 0.0,
                     "execute_s": t2 - t1}

    def _generate_loop(self, req, hist, memo, root_leaves, universe,
                       width, steps, eos, beam_mode, deadline, G):
        """Run ``steps`` decode rounds; returns the final beam list.

        Each round: fetch every live beam's cache (local / pool / replay),
        submit ALL their vocab-scoring chunks to the ``decode`` family at
        once (under ``pack_tails`` beams from this and other in-flight
        requests bin-pack into shared ragged rows), rank continuations
        host-side (greedy per-beam for top-k, global beam_step for beam
        search), then submit the surviving children's KV appends as one
        coalesced ``append`` round and park the grown caches in the pool."""
        rid = req.request_id
        v = len(universe)
        # ---- step 0: one decode from the shared history root ----
        fut = self.dso.submit((root_leaves,
                               np.full((1,), self._s0, np.int32),
                               universe[None]),
                              v, kind="decode",
                              dedup_token=("g", rid, "root"),
                              deadline=deadline, tier=req.slo_tier)
        probs = np.asarray(
            fut.result(),
            np.float32)[0]  # flamecheck: host-sync-ok(beam ranking is host-side search logic by design)
        self._metrics.incr("decode_steps")
        lp = G.log_softmax(probs.sum(-1))
        order = np.argsort(-lp, kind="stable")[:width]
        beams = [
            _Beam(tokens=(int(universe[o]),), cum=float(lp[o]),
                  finished=(eos is not None and int(universe[o]) == eos))
            for o in order]
        self._metrics.incr("gen_tokens", len(beams))
        parent_leaves = {i: root_leaves for i in range(len(beams))}
        parent_of = {i: i for i in range(len(beams))}
        for step in range(1, steps + 1):
            # ---- append round: grow every unfinished child's cache ----
            if step < steps:     # the final round's tokens are never scored
                afuts = []
                for i, b in enumerate(beams):
                    if b.finished:
                        continue
                    plv = parent_leaves[parent_of[i]]
                    afuts.append((i, self.dso.submit(
                        (plv,
                         np.full((1,), self._s0 + len(b.tokens) - 1,
                                 np.int32),
                         np.asarray(
                             [[b.tokens[-1]]],
                             np.int32)),  # flamecheck: host-sync-ok(chosen tokens are host python ints; beam orchestration is host-side by design)
                        1, kind="append", deadline=deadline,
                        tier=req.slo_tier)))
                for i, f in afuts:
                    leaves = self._copy_kv_rows(f.result())
                    self._park_beam(req, i, beams[i], leaves, memo[1])
            if step == steps:
                break
            if self._faults is not None:
                # mid-generation eviction pressure: a storm HERE lands in
                # the window between a beam's park and its next-round
                # lookup — the only place an eviction can force a replay
                # (request-start storms almost never catch it)
                dropped = self._faults.pool_storm(self.history_pool)
                if dropped:
                    self._metrics.incr("fault_pool_evictions", dropped)
            # ---- decode round over the live hypotheses ----
            live = [i for i, b in enumerate(beams) if not b.finished]
            if not live:
                # EOS early exit: every hypothesis terminated with decode
                # budget left — the remaining rounds' decode/append
                # dispatches are skipped entirely (step < steps holds
                # here: the final round breaks before this check)
                self._metrics.incr("gen_early_exits")
                break
            leaves_of = {}
            dfuts = []
            for i in live:
                leaves_of[i] = self._beam_leaves(req, hist, memo, beams[i],
                                                 deadline)
                dfuts.append((i, self.dso.submit(
                    (leaves_of[i],
                     np.full((1,), self._s0 + len(beams[i].tokens),
                             np.int32),
                     universe[None]),
                    v, kind="decode",
                    dedup_token=("g", rid, i, len(beams[i].tokens)),
                    deadline=deadline, tier=req.slo_tier)))
            self._metrics.incr("decode_steps")
            step_lp = np.zeros((len(beams), v))
            for i, f in dfuts:
                probs = np.asarray(
                    f.result(),
                    np.float32)[0]  # flamecheck: host-sync-ok(beam ranking is host-side search logic by design)
                step_lp[i] = G.log_softmax(probs.sum(-1))
            if beam_mode:
                cum = np.asarray(
                    [b.cum for b in beams])  # flamecheck: host-sync-ok(beam scores are host python floats; ranking is host-side by design)
                seqs = [b.tokens for b in beams]
                fin = np.asarray(
                    [b.finished for b in beams])  # flamecheck: host-sync-ok(beam flags are host python bools; ranking is host-side by design)
                new_cum, new_seqs, new_fin, parents = G.beam_step(
                    cum, seqs, fin, step_lp, width, eos, universe)
                new_beams = []
                parent_of = {}
                grew_n = 0
                for slot in range(len(new_cum)):
                    p = int(parents[slot])
                    grew_n += len(new_seqs[slot]) > len(seqs[p])
                    parent_of[slot] = p
                    new_beams.append(
                        _Beam(tokens=new_seqs[slot],
                              cum=float(new_cum[slot]),
                              finished=bool(new_fin[slot])))
                self._metrics.incr("gen_tokens", grew_n)
                # the next append round reads each UNFINISHED child's
                # parent cache: keep those addressable host-side (decode
                # already fetched live parents; a pool-parked one rides
                # its pooled entry via _beam_leaves)
                parent_leaves = {}
                for slot, nb in enumerate(new_beams):
                    p = parent_of[slot]
                    if nb.finished or p in parent_leaves:
                        continue
                    plv = leaves_of.get(p)
                    if plv is None:
                        plv = beams[p].leaves
                    if plv is None:
                        plv = self._beam_leaves(req, hist, memo, beams[p],
                                                deadline)
                    parent_leaves[p] = plv
                beams = new_beams
            else:
                # top-k: each hypothesis follows its own greedy path
                parent_of = {i: i for i in range(len(beams))}
                parent_leaves = leaves_of
                appended = 0
                for i in live:
                    j = int(np.argmax(
                        step_lp[i]))  # flamecheck: host-sync-ok(argmax over a host fp64 ranking buffer; greedy selection is host-side by design)
                    tok = int(universe[j])
                    beams[i] = _Beam(
                        tokens=beams[i].tokens + (tok,),
                        cum=beams[i].cum + float(step_lp[i][j]),
                        finished=(eos is not None and tok == eos))
                    appended += 1
                self._metrics.incr("gen_tokens", appended)
        return beams

    def _extra_metrics(self):
        st = self.dso.stats()
        # surface the DSO v2 dispatch-economics gauges through ServeMetrics
        # so summary() carries them alongside the request stats.  The
        # padded-fraction gauge covers the CANDIDATE-SCORING kinds only:
        # encode/extend dispatches always run full rows, so folding them
        # in (as the all-kind dso_padded_fraction does) would read near
        # zero on miss-heavy traffic even while cached dispatches are
        # mostly padding — the exact regime the gauge exists to expose
        slots = sum(st.get(f"cand_slots_{k}", 0) for k in ("cached", "full"))
        valid = sum(st.get(f"cand_valid_{k}", 0) for k in ("cached", "full"))
        self._metrics.set_gauge(
            "padded_fraction", 1.0 - valid / slots if slots else 0.0)
        out = {f"dso_{k}": v for k, v in st.items()}
        out["dso_build_s"] = self.dso.build_time_s
        out.update({f"pda_{k}": v for k, v in
                    dataclasses.asdict(self.features.stats).items()})
        if self.history_pool is not None:
            out.update({f"pool_{k}": v
                        for k, v in self.history_pool.stats().items()})
        if self._faults is not None:
            out.update(self._faults.stats())
        return out

    def _on_degrade(self, level: int):
        # level >= 1: stop waiting for co-riders — flush every coalescing
        # window immediately (tail-packing windows add latency the backlog
        # can no longer afford); reversible when pressure recedes
        self.dso.set_window_override(0.0 if level >= 1 else None)

    def _close(self):
        self.features.shutdown()
        self.dso.shutdown()
        if self.history_pool is not None:
            self.history_pool.release()


@register_engine("text")
class TextServingEngine(_PipelinedEngine):
    """Continuous-batching-lite decode serving for text architectures.

    Through the API v2 surface, ``request.history`` is the prompt token-id
    array and ``request.n_tokens`` the generation budget; the batched
    ``generate`` entry point remains for direct callers."""

    def __init__(self, bundle: ModelBundle, params, *, batch: int = 4,
                 max_len: int = 256, max_pending: int = 64, **cache_kw):
        self.bundle = bundle
        self.params = params
        self.kv = KVCacheManager(bundle, batch, max_len, **cache_kw)
        self._decode = jax.jit(
            lambda p, c, b: bundle.decode_step(p, c, b))
        self._gen_lock = threading.Lock()
        # decode state is single-stream: exactly one pipeline worker
        super().__init__(max_pending=max_pending, n_workers=1, name="text")

    def _execute(self, req: ServeRequest
                 ):  # flamecheck: host-sync-ok(decode engine: prompts are host token arrays by contract)
        t0 = time.perf_counter()
        out = self.generate([np.asarray(req.history)],
                            n_tokens=req.n_tokens)[0]
        return out, {"execute_s": time.perf_counter() - t0}

    def generate(self, prompts: List[np.ndarray], n_tokens: int = 16,
                 greedy: bool = True) -> List[np.ndarray]:
        """Serve a batch of prompts (token id arrays) for n_tokens each."""
        assert len(prompts) <= self.kv.batch
        with self._gen_lock:
            plen = max(len(p) for p in prompts)
            padded = np.stack([np.pad(p, (0, plen - len(p)))
                               for p in prompts])
            batch = {"tokens": jnp.asarray(padded, jnp.int32)}
            # prefill all at once (batch-padded)
            caches, _ = self.bundle.cache_init(len(prompts), self.kv.max_len)
            logits, caches = self.bundle.prefill(self.params, batch,
                                                 caches=caches)
            last = jnp.argmax(logits[:, -1], axis=-1)
            outs = [[int(t)] for t in last]
            cur = plen
            for _ in range(n_tokens - 1):
                step = {"tokens": last[:, None].astype(jnp.int32),
                        "cur_index": jnp.int32(cur)}
                logits, caches = self._decode(self.params, caches, step)
                last = jnp.argmax(logits[:, -1], axis=-1)
                for i, t in enumerate(last):
                    outs[i].append(int(t))
                cur += 1
            return [np.array(o) for o in
                    outs]  # flamecheck: host-sync-ok(autoregressive decode emits host token ids per step by design)
