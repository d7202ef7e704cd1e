"""PRF serving layer — bucketed, batched, tree-sharded forest inference.

The ROADMAP north star is serving "heavy traffic from millions of
users"; arXiv:1804.06755 (PAPERS.md) shows deployed-RF cost is
dominated by inference, not training. This module turns a trained
:class:`repro.core.api.PRFModel` into a serving endpoint built on the
fused traversal+voting path (``ForestConfig.predict_backend``):

* **Power-of-two batch bucketing** — request batches are padded up to
  the next power-of-two bucket (clamped to ``[min_bucket, max_batch]``)
  with an explicit validity mask, so the jit cache holds at most
  ``log2(max_batch / min_bucket) + 1`` compiled shapes no matter what
  batch sizes arrive. Padded rows are masked out of the scores and
  sliced off; they can never leak into a real row (per-sample
  traversal is row-independent, and tests/test_serving.py pins it).

* **Async micro-batch queue** — ``submit()`` enqueues a request and
  returns a :class:`PRFFuture`; ``drain()`` aggregates everything
  pending into one bucketed forward pass and resolves the futures in
  submission order. ``submit`` auto-drains when the queue reaches
  ``max_batch`` rows, so latency under load is one forward pass.

* **Tree-sharded multi-device voting** — ``make_sharded_vote_fn``
  shards the forest's node-pool arrays (and vote payloads) over a mesh
  axis, each shard accumulates the weighted votes of its own trees
  into an ``[N, C]`` partial score, and a single ``psum`` combines
  them (Eq. 9/10 is a sum over trees) — mirroring
  ``core/distributed``'s T_GR histogram combine, with O(N*C) words on
  the wire instead of O(k*N*C).

* **Resilience** — overload is shed at admission with typed errors
  (``max_queue_rows`` -> :class:`ServiceOverloaded`, a cheap queue-depth
  check, never a forward pass); a per-service
  :class:`CircuitBreaker` opens after consecutive model failures and
  half-open-probes its way back (:class:`CircuitOpenError` while open —
  queued requests are kept, new ones shed); ``shutdown()`` settles
  every pending future deterministically (served on drain, rejected
  with :class:`ServiceClosedError` on cancel); and
  :class:`ModelRegistry` gives each published model version its own
  bulkheaded service, hot-swapping versions with an atomic pointer
  flip that drops zero in-flight futures (the old service drains with
  the old model). tests/test_serving.py pins all of it.

* **Cache-aside result cache** — an optional per-service LRU
  (``cache_size`` entries) keyed by a SHA-1 digest of the submitted
  row batch (bytes + shape + dtype). A hit returns the stored
  prediction bitwise-identically with zero device work — it is checked
  before the circuit breaker, so hot rows keep serving even while the
  model is failing. Hit/miss/eviction counters surface in ``health()``
  and ``stats()``; :class:`ModelRegistry.publish` explicitly
  invalidates the outgoing service's cache at hot-swap so a retired
  fallback never compounds a stale model with stale cached rows.

* **Degraded mode** — per-request deadlines bound queue staleness
  (:class:`DeadlineExceeded`, settled through the future at drain); a
  per-client token-bucket :class:`RateLimiter` sheds abusive clients in
  front of admission control (:class:`RateLimited`); while the live
  breaker is open, ``ModelRegistry.predict`` answers from the newest
  *healthy* retired version (stale-but-correct beats erroring); and
  ``health()`` exposes breaker / queue / shed / deadline / rate-limit /
  quarantine counters as a flat snapshot a load balancer can scrape.
"""
from __future__ import annotations

import hashlib
import threading
import time
from collections import OrderedDict
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..core.api import PRFModel
from ..core.binning import apply_bins
from ..core.types import Forest
from ..core.voting import (
    _vote_weights, build_payload, predict_regression, predict_scores,
    resolve_predict_backend,
)


def bucket_size(n: int, *, min_bucket: int = 8, max_batch: int = 1024) -> int:
    """Next power-of-two >= n, clamped to [min_bucket, max_batch]."""
    if n <= 0:
        raise ValueError(f"batch size must be positive, got {n}")
    b = 1 << max(0, n - 1).bit_length()
    return max(min_bucket, min(b, max_batch))


class ServiceError(RuntimeError):
    """Base class of the serving layer's typed rejections — a caller
    catching it handles every fast-shed path (overload, open circuit,
    shutdown) without also swallowing model/compiler failures."""


class ServiceOverloaded(ServiceError):
    """Admission control: the queue is at ``max_queue_rows``."""


class CircuitOpenError(ServiceError):
    """The service's circuit breaker is open (model keeps failing)."""


class ServiceClosedError(ServiceError):
    """The service was shut down (or the registry has no model)."""


class DeadlineExceeded(ServiceError):
    """The request's deadline expired before it was served. Settled
    through the normal future path at drain time — a late future is
    rejected, never silently dropped."""


class RateLimited(ServiceError):
    """The client's token bucket is empty (per-client rate limiting in
    front of admission control)."""


class RateLimiter:
    """Per-client token-bucket rate limiter (cloud-patterns style).

    Each client id owns a bucket holding up to ``burst`` tokens that
    refills at ``rate`` tokens/second; a request for ``n`` rows is
    admitted iff ``n`` tokens are available (and consumes them). Tokens
    are charged per ROW, the same currency as ``max_queue_rows``, so
    ``burst`` must cover a client's largest single request. Lazy refill
    (computed from elapsed time at each call) keeps it O(1) per request
    with no background thread; ``clock`` is injectable so tests drive
    refills without sleeping.
    """

    def __init__(
        self, rate: float, burst: float,
        *,
        clock: Callable[[], float] = time.monotonic,
    ):
        if rate <= 0:
            raise ValueError(f"rate must be > 0 tokens/s, got {rate}")
        if burst < 1:
            raise ValueError(f"burst must be >= 1 token, got {burst}")
        self.rate = float(rate)
        self.burst = float(burst)
        self._clock = clock
        self._lock = threading.Lock()
        self._buckets: Dict[str, Tuple[float, float]] = {}  # id -> (tokens, t)
        self.granted = 0
        self.rejected = 0

    def allow(self, client: str = "", n: float = 1.0) -> bool:
        """Take ``n`` tokens from ``client``'s bucket; False = shed."""
        now = self._clock()
        with self._lock:
            tokens, last = self._buckets.get(client, (self.burst, now))
            tokens = min(self.burst, tokens + (now - last) * self.rate)
            if tokens >= n:
                self._buckets[client] = (tokens - n, now)
                self.granted += 1
                return True
            self._buckets[client] = (tokens, now)
            self.rejected += 1
            return False

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "rate": self.rate, "burst": self.burst,
                "clients": len(self._buckets),
                "granted": self.granted, "rejected": self.rejected,
            }


class CircuitBreaker:
    """Per-service circuit breaker with half-open probing.

    ``failure_threshold`` consecutive model failures open the circuit;
    while open, ``allow()`` is False (callers shed with
    :class:`CircuitOpenError` instead of burning a forward pass on a
    broken model). After ``reset_timeout`` seconds ONE probe call is
    let through (half-open): success closes the circuit, failure
    re-opens it for another full timeout. ``clock`` is injectable so
    tests drive the state machine without sleeping.
    """

    def __init__(
        self,
        failure_threshold: int = 5,
        reset_timeout: float = 30.0,
        *,
        clock: Callable[[], float] = time.monotonic,
    ):
        if failure_threshold < 1:
            raise ValueError("failure_threshold must be >= 1")
        if reset_timeout < 0:
            raise ValueError("reset_timeout must be >= 0")
        self.failure_threshold = failure_threshold
        self.reset_timeout = reset_timeout
        self._clock = clock
        self._lock = threading.Lock()
        self._failures = 0
        self._state = "closed"
        self._opened_at = 0.0

    @property
    def state(self) -> str:
        """"closed" | "open" | "half_open" (open, probe window reached).
        A peek — never consumes the half-open probe."""
        with self._lock:
            if (
                self._state == "open"
                and self._clock() - self._opened_at >= self.reset_timeout
            ):
                return "half_open"
            return self._state

    def allow(self) -> bool:
        """May a call proceed? Consumes the single half-open probe."""
        with self._lock:
            if self._state == "closed":
                return True
            if (
                self._state == "open"
                and self._clock() - self._opened_at >= self.reset_timeout
            ):
                self._state = "half_open"        # this call IS the probe
                return True
            return False          # open, or a half-open probe in flight

    def record_success(self) -> None:
        with self._lock:
            self._failures = 0
            self._state = "closed"

    def record_failure(self) -> None:
        with self._lock:
            self._failures += 1
            if self._state == "half_open" or self._failures >= self.failure_threshold:
                self._state = "open"
                self._opened_at = self._clock()


class PRFFuture:
    """Result handle for a queued request (settled by ``drain`` /
    ``shutdown``): resolved with a value, or rejected with an exception
    that ``result()`` re-raises."""

    __slots__ = ("_value", "_exc", "_done")

    def __init__(self):
        self._value = None
        self._exc = None
        self._done = False

    def done(self) -> bool:
        return self._done

    def result(self) -> np.ndarray:
        if not self._done:
            raise RuntimeError("request not served yet — call drain()")
        if self._exc is not None:
            raise self._exc
        return self._value

    def exception(self) -> Optional[BaseException]:
        """The rejection, or None if resolved with a value."""
        if not self._done:
            raise RuntimeError("request not served yet — call drain()")
        return self._exc

    def _resolve(self, value: np.ndarray) -> None:
        self._value = value
        self._done = True

    def _reject(self, exc: BaseException) -> None:
        self._exc = exc
        self._done = True


class PRFService:
    """Serving wrapper around a trained PRF model.

    >>> svc = PRFService(model)
    >>> labels = svc.predict(x)                  # any batch size
    >>> fut = svc.submit(x1); svc.submit(x2)     # micro-batch queue
    >>> svc.drain(); fut.result()
    """

    def __init__(
        self,
        model: PRFModel,
        *,
        max_batch: int = 1024,
        min_bucket: int = 8,
        backend: Optional[str] = None,
        max_queue_rows: Optional[int] = None,
        breaker: Optional[CircuitBreaker] = None,
        rate_limiter: Optional[RateLimiter] = None,
        default_deadline: Optional[float] = None,
        cache_size: int = 0,
        clock: Callable[[], float] = time.monotonic,
    ):
        if max_batch & (max_batch - 1) or min_bucket & (min_bucket - 1):
            raise ValueError("max_batch and min_bucket must be powers of two")
        if min_bucket > max_batch:
            raise ValueError(
                f"min_bucket={min_bucket} must not exceed max_batch={max_batch}"
            )
        if max_queue_rows is not None and max_queue_rows < 1:
            raise ValueError("max_queue_rows must be >= 1")
        if backend is not None:
            model = model.with_predict_backend(backend)
        self.model = model
        self.max_batch = max_batch
        self.min_bucket = min_bucket
        # Admission control: queue depth past which submit() sheds with
        # ServiceOverloaded — a counter compare under the lock, so a
        # saturated service rejects in O(1) instead of queueing without
        # bound. None = unbounded (the pre-hardening behavior).
        self.max_queue_rows = max_queue_rows
        self.breaker = breaker if breaker is not None else CircuitBreaker()
        # Degraded-mode knobs: a per-client token bucket sheds abusive
        # traffic BEFORE the queue-depth check (RateLimited), and
        # deadlines bound how stale a queued request may get before it
        # is rejected instead of served (DeadlineExceeded at drain).
        self.rate_limiter = rate_limiter
        if default_deadline is not None and default_deadline <= 0:
            raise ValueError("default_deadline must be > 0 seconds")
        self.default_deadline = default_deadline
        self._clock = clock
        self._edges = jnp.asarray(model.bin_edges)
        self._n_features = int(np.asarray(model.bin_edges).shape[0])
        # One entry per request — a single list (under one lock) so the
        # request order and its rows can never diverge across threads.
        # Entries: (x, single, future, absolute-deadline-or-None).
        self._queue: List[
            Tuple[np.ndarray, bool, PRFFuture, Optional[float]]
        ] = []
        self._queued_rows = 0
        self._lock = threading.Lock()
        self._closed = False
        self._buckets_seen: set = set()
        # Cache-aside result cache: digest of the request batch -> its
        # prediction. cache_size=0 disables it entirely (no hashing
        # cost). Entries hold private copies so a caller mutating its
        # input or output array can never poison a later hit.
        if cache_size < 0:
            raise ValueError(f"cache_size must be >= 0, got {cache_size}")
        self.cache_size = cache_size
        self._cache: "OrderedDict[bytes, np.ndarray]" = OrderedDict()
        self._cache_hits = 0
        self._cache_misses = 0
        self._cache_evictions = 0
        self._requests_served = 0
        self._requests_shed = 0
        self._requests_cancelled = 0
        self._requests_deadline_exceeded = 0
        self._requests_rate_limited = 0

        forest = model.forest
        cfg = forest.config
        use_pallas = resolve_predict_backend(cfg.predict_backend) == "pallas"
        # Payloads depend only on the trained forest — precompute ONCE
        # at service construction so the per-request fused path does no
        # O(k*P*C) payload work (mirrors make_sharded_vote_fn). Forest
        # and payload are jit ARGUMENTS, not closure captures: every
        # bucket shape compiles its own executable, and constants would
        # embed a private copy of the model per bucket.
        self._forest = forest
        self._payload = build_payload(forest) if use_pallas else None

        def bucket_predict(forest, payload, xb, valid):
            # The mask zeroes padded rows' scores before the argmax /
            # normalization — padded rows can never leak a non-neutral
            # value even if a caller forgets to slice.
            from ..core.forest import fused_vote_scores

            if cfg.regression:
                if use_pallas:
                    norm = jnp.maximum(_vote_weights(forest).sum(), 1e-38)
                    vals = fused_vote_scores(forest, xb, payload)[:, 0] / norm
                else:
                    vals = predict_regression(forest, xb)
                return jnp.where(valid, vals, 0.0)
            scores = (
                fused_vote_scores(forest, xb, payload)
                if use_pallas
                else predict_scores(forest, xb)
            )
            scores = jnp.where(valid[:, None], scores, 0.0)
            return jnp.argmax(scores, axis=-1)

        self._bucket_predict = jax.jit(bucket_predict)

    def _validate(self, x: np.ndarray) -> np.ndarray:
        """Shape-check a request up front: a malformed request must fail
        at its own submit/predict call, never poison an aggregated
        micro-batch that other requests ride in."""
        x = np.asarray(x)
        if x.ndim == 1:
            x = x[None]
        if x.ndim != 2 or x.shape[1] != self._n_features:
            raise ValueError(
                f"expected [n, {self._n_features}] features, got {x.shape}"
            )
        if len(x) == 0:
            raise ValueError("empty request")
        return x

    # -- cache-aside result cache ----------------------------------------

    @staticmethod
    def _cache_key(x: np.ndarray) -> bytes:
        h = hashlib.sha1()
        h.update(str(x.dtype).encode())
        h.update(np.asarray(x.shape, np.int64).tobytes())
        h.update(np.ascontiguousarray(x).tobytes())
        return h.digest()

    def _cache_get(self, key: bytes) -> Optional[np.ndarray]:
        with self._lock:
            out = self._cache.get(key)
            if out is None:
                self._cache_misses += 1
                return None
            self._cache.move_to_end(key)
            self._cache_hits += 1
            return out.copy()

    def _cache_put(self, key: bytes, out: np.ndarray) -> None:
        with self._lock:
            if key not in self._cache and len(self._cache) >= self.cache_size:
                self._cache.popitem(last=False)
                self._cache_evictions += 1
            self._cache[key] = out.copy()
            self._cache.move_to_end(key)

    def invalidate_cache(self) -> int:
        """Drop every cached prediction; returns how many were dropped.
        Called by :class:`ModelRegistry.publish` on the outgoing
        service at hot-swap."""
        with self._lock:
            n = len(self._cache)
            self._cache.clear()
            return n

    # -- direct (synchronous) path ---------------------------------------

    def predict(self, x: np.ndarray) -> np.ndarray:
        """Predict labels/values for any batch size (bucketed + padded).

        The circuit breaker brackets the forward pass: while open it
        sheds with :class:`CircuitOpenError` before any device work
        (a ``drain`` hitting it keeps its requests queued for the next
        probe); client-side :class:`ValueError`/``ServiceError`` never
        count as model failures. Stateless, so it stays usable after
        ``shutdown`` (only admission closes).

        With ``cache_size > 0`` the batch digest is looked up first: a
        hit returns the stored prediction bitwise-identically — before
        the breaker, since no model work is needed.
        """
        squeeze = np.ndim(x) == 1
        x = self._validate(x)
        key = self._cache_key(x) if self.cache_size > 0 else None
        if key is not None:
            hit = self._cache_get(key)
            if hit is not None:
                return hit[0] if squeeze else hit
        if not self.breaker.allow():
            raise CircuitOpenError(
                f"circuit open after repeated model failures; retrying in "
                f"<= {self.breaker.reset_timeout:g}s"
            )
        try:
            # Bin once on device and keep it there: padding with jnp.pad
            # avoids the device->host->device round-trip a numpy pad
            # costs on every request.
            xb = apply_bins(jnp.asarray(x), self._edges)
            outs = []
            for i in range(0, len(xb), self.max_batch):
                outs.append(self._predict_bucketed(xb[i : i + self.max_batch]))
            out = np.concatenate(outs, axis=0)
        except ServiceError:
            raise
        except Exception:
            self.breaker.record_failure()
            raise
        self.breaker.record_success()
        if key is not None:
            self._cache_put(key, out)
        return out[0] if squeeze else out

    def _predict_bucketed(self, xb: jnp.ndarray) -> np.ndarray:
        n = len(xb)
        b = bucket_size(n, min_bucket=self.min_bucket, max_batch=self.max_batch)
        self._buckets_seen.add(b)
        padded = jnp.pad(xb, ((0, b - n), (0, 0)))
        valid = jnp.arange(b) < n
        out = self._bucket_predict(self._forest, self._payload, padded, valid)
        return np.asarray(out)[:n]

    # -- async micro-batch queue -----------------------------------------

    def submit(
        self, x: np.ndarray, *,
        client: str = "",
        deadline: Optional[float] = None,
    ) -> PRFFuture:
        """Enqueue a request; returns a future resolved by ``drain``.

        Auto-drains when the aggregated queue reaches ``max_batch``
        rows, so a saturated queue costs one forward pass per batch.

        Admission is the fast-shed point: a shut-down service raises
        :class:`ServiceClosedError`, an open circuit
        :class:`CircuitOpenError`, a drained token bucket
        :class:`RateLimited` (per-``client``, charged by rows), and a
        queue at ``max_queue_rows`` :class:`ServiceOverloaded` — all
        typed, all before the request touches the queue, so accepted
        requests keep their bounded one-forward-pass latency under
        overload.

        ``deadline`` (seconds from now; default ``default_deadline``)
        bounds queue staleness: a request still queued when its deadline
        passes is settled with :class:`DeadlineExceeded` at the next
        drain — through the future, never dropped.
        """
        single = np.ndim(x) == 1
        x = self._validate(x)
        if self.breaker.state == "open":
            with self._lock:
                self._requests_shed += 1
            raise CircuitOpenError(
                "circuit open after repeated model failures; request shed"
            )
        if self.rate_limiter is not None and not self.rate_limiter.allow(
            client, n=len(x)
        ):
            with self._lock:
                self._requests_rate_limited += 1
            raise RateLimited(
                f"client {client!r} exceeded its token bucket "
                f"({self.rate_limiter.rate:g} rows/s, burst "
                f"{self.rate_limiter.burst:g}) — request of {len(x)} shed"
            )
        if deadline is None:
            deadline = self.default_deadline
        elif deadline <= 0:
            raise ValueError(f"deadline must be > 0 seconds, got {deadline}")
        expires = None if deadline is None else self._clock() + deadline
        fut = PRFFuture()
        with self._lock:
            if self._closed:
                raise ServiceClosedError("submit on a shut-down service")
            if (
                self.max_queue_rows is not None
                and self._queued_rows + len(x) > self.max_queue_rows
            ):
                self._requests_shed += 1
                raise ServiceOverloaded(
                    f"queue full: {self._queued_rows} rows pending, request "
                    f"of {len(x)} exceeds max_queue_rows={self.max_queue_rows}"
                )
            self._queue.append((x, single, fut, expires))
            self._queued_rows += len(x)
            full = self._queued_rows >= self.max_batch
        if full:
            self.drain()
        return fut

    @property
    def pending(self) -> int:
        """Number of queued (unserved) requests."""
        return len(self._queue)

    def drain(self) -> int:
        """Settle every queued request: expired deadlines are rejected
        (:class:`DeadlineExceeded`), the rest served in one aggregated
        micro-batch.

        Resolves futures in submission order; returns the number of
        requests settled (served + deadline-rejected).
        """
        # Snapshot-and-clear under the lock, run the forward pass outside
        # it — concurrent submits keep aggregating into the NEXT batch
        # while this one is in flight. On failure the snapshot is
        # re-prepended, so requests are never silently lost.
        with self._lock:
            if not self._queue:
                return 0
            queue = self._queue
            self._queue, self._queued_rows = [], 0
        now = self._clock()
        live = [e for e in queue if e[3] is None or now <= e[3]]
        expired = [e for e in queue if not (e[3] is None or now <= e[3])]
        for (_, _, fut, dl) in expired:
            fut._reject(DeadlineExceeded(
                f"request expired {now - dl:.3f}s past its deadline while "
                f"queued — shed at drain"
            ))
        if expired:
            with self._lock:
                self._requests_deadline_exceeded += len(expired)
        if not live:
            return len(expired)
        try:
            out = self.predict(np.concatenate([x for x, _, _, _ in live]))
        except Exception:
            with self._lock:
                self._queue = live + self._queue
                self._queued_rows += sum(len(x) for x, _, _, _ in live)
            raise
        served = 0
        offset = 0
        for (x, single, fut, _) in live:
            chunk = out[offset : offset + len(x)]
            fut._resolve(chunk[0] if single else chunk)
            offset += len(x)
            served += 1
        self._requests_served += served
        return served + len(expired)

    def shutdown(self, drain: bool = True) -> int:
        """Stop admission and settle every pending future.

        After this, ``submit`` raises :class:`ServiceClosedError`.
        With ``drain=True`` pending requests are served one last time
        (this is how :class:`ModelRegistry` hot-swaps without dropping
        an in-flight future); with ``drain=False`` — or if the final
        drain itself fails — the remainder is rejected with
        :class:`ServiceClosedError`, so every future is deterministically
        ``done()`` either way. Returns the number of futures settled.
        Idempotent; the direct ``predict`` path stays usable (it holds
        no queue state).
        """
        with self._lock:
            self._closed = True
        settled = 0
        if drain:
            try:
                settled = self.drain()
            except Exception:
                pass                  # failed drain re-queued — cancel below
        with self._lock:
            queue, self._queue, self._queued_rows = self._queue, [], 0
        for (_, _, fut, _) in queue:
            fut._reject(
                ServiceClosedError("service shut down before request was served")
            )
        with self._lock:
            self._requests_cancelled += len(queue)
        return settled + len(queue)

    def stats(self) -> dict:
        """Serving counters — bounded-recompilation evidence included."""
        return {
            "buckets_compiled": sorted(self._buckets_seen),
            "max_buckets": self.max_batch.bit_length()
            - self.min_bucket.bit_length()
            + 1,
            "requests_served": self._requests_served,
            "requests_shed": self._requests_shed,
            "requests_cancelled": self._requests_cancelled,
            "requests_deadline_exceeded": self._requests_deadline_exceeded,
            "requests_rate_limited": self._requests_rate_limited,
            "cache_hits": self._cache_hits,
            "cache_misses": self._cache_misses,
            "cache_evictions": self._cache_evictions,
            "breaker_state": self.breaker.state,
            "closed": self._closed,
            "pending": self.pending,
        }

    def health(self) -> dict:
        """Scrapeable health snapshot for a load balancer / monitor.

        Flat scalars: breaker state, queue depth (requests and rows),
        the shed / deadline / rate-limit / cancel counters, and the
        quarantined-block count of the model's training-time integrity
        report (0 when validation was off or found nothing). One lock
        acquisition; no device work.
        """
        q = self.model.quarantine
        with self._lock:
            snap = {
                "breaker": self.breaker.state,
                "closed": self._closed,
                "queue_requests": len(self._queue),
                "queue_rows": self._queued_rows,
                "max_queue_rows": self.max_queue_rows,
                "served": self._requests_served,
                "shed": self._requests_shed,
                "cancelled": self._requests_cancelled,
                "deadline_exceeded": self._requests_deadline_exceeded,
                "rate_limited": self._requests_rate_limited,
                "cache_size": self.cache_size,
                "cache_entries": len(self._cache),
                "cache_hits": self._cache_hits,
                "cache_misses": self._cache_misses,
                "cache_evictions": self._cache_evictions,
                "quarantined_blocks": (
                    0 if q is None else len(q.quarantined)
                ),
            }
        if self.rate_limiter is not None:
            snap["rate_limiter"] = self.rate_limiter.snapshot()
        return snap


# ---------------------------------------------------------------------------
# Versioned model registry: bulkheaded services, atomic hot-swap
# ---------------------------------------------------------------------------


class ModelRegistry:
    """Versioned registry of :class:`PRFService` instances with atomic
    hot-swap.

    Every ``publish`` wraps its model in a **fresh** service — its own
    queue, circuit breaker, and counters — so versions are bulkheaded:
    a failing or breaker-open version cannot shed, block, or fail
    requests of any other version. The live version is a single
    reference flipped under a lock; readers grab it with one attribute
    read, so a request routed to the old service the instant before a
    flip simply completes against the old model — ``publish`` then
    calls ``old.shutdown(drain=True)``, which serves (never drops) its
    in-flight futures. tests/test_serving.py pins zero dropped futures
    across a swap with a concurrent submitter.
    """

    def __init__(self, **service_opts):
        self._service_opts = service_opts
        self._lock = threading.Lock()
        self._current: Optional[Tuple[int, PRFService]] = None
        self._retired: Dict[int, PRFService] = {}
        self._next_version = 1
        self._fallback_served = 0

    def publish(self, model: PRFModel, **overrides) -> int:
        """Swap in ``model`` (constructor kwargs: registry defaults +
        ``overrides``). Returns its version number. The previous
        version is drained (every pending future resolves against the
        model it was submitted to) and closed to new submits. The old
        service's result cache is invalidated: a retired fallback
        answering during degraded mode recomputes every row rather than
        compounding a stale model with stale cached predictions."""
        svc = PRFService(model, **{**self._service_opts, **overrides})
        with self._lock:
            version = self._next_version
            self._next_version += 1
            old = self._current
            self._current = (version, svc)           # the atomic flip
            if old is not None:
                self._retired[old[0]] = old[1]
        if old is not None:
            old[1].shutdown(drain=True)
            old[1].invalidate_cache()
        return version

    @property
    def service(self) -> PRFService:
        """The live service (one reference read — safe vs. publish)."""
        cur = self._current
        if cur is None:
            raise ServiceClosedError("no model published")
        return cur[1]

    @property
    def version(self) -> int:
        cur = self._current
        if cur is None:
            raise ServiceClosedError("no model published")
        return cur[0]

    # Thin delegation so callers can hold the registry, not a service
    # reference that goes stale at the next publish.

    def _newest_healthy_retired(self) -> Optional[Tuple[int, PRFService]]:
        """Newest retired version whose own breaker is not open (retired
        services are closed for submit but their stateless ``predict``
        stays fully usable — the degraded-mode fallback)."""
        with self._lock:
            candidates = sorted(self._retired.items(), reverse=True)
        for version, svc in candidates:
            if svc.breaker.state != "open":
                return version, svc
        return None

    def predict(self, x: np.ndarray) -> np.ndarray:
        """Predict against the live version; while its breaker is open,
        fall back to the newest *healthy* retired version — a stale but
        correct answer beats an error while the live model recovers.
        With no healthy fallback the :class:`CircuitOpenError`
        propagates. Fallback answers are counted in ``health()``
        (``fallback_served``)."""
        try:
            return self.service.predict(x)
        except CircuitOpenError:
            fallback = self._newest_healthy_retired()
            if fallback is None:
                raise
            out = fallback[1].predict(x)
            with self._lock:
                self._fallback_served += 1
            return out

    def submit(self, x: np.ndarray, **kwargs) -> PRFFuture:
        return self.service.submit(x, **kwargs)

    def drain(self) -> int:
        return self.service.drain()

    def stats(self) -> dict:
        return {"version": self.version, **self.service.stats()}

    def health(self) -> dict:
        """Registry-level health: the live service's ``health()`` plus
        version bookkeeping (live version, per-retired-version breaker
        states, stale-fallback counter)."""
        cur = self._current
        with self._lock:
            retired = {v: s.breaker.state for v, s in self._retired.items()}
            snap = {
                "fallback_served": self._fallback_served,
                "retired": retired,
            }
        if cur is None:
            snap.update({"version": None, "live": None})
        else:
            snap.update({"version": cur[0], "live": cur[1].health()})
        return snap

    def shutdown(self, drain: bool = True) -> int:
        """Shut down the live service AND release every retired version.

        Retired services were closed to new submits at publish time, but
        the registry still held them (they back the stale-prediction
        fallback), keeping their jit caches and queue state alive.
        Shutdown settles the live queue (``drain``), re-runs the
        (idempotent) shutdown of each retired service, and drops the
        references so their compiled executables can be collected.
        Returns the number of futures settled.
        """
        cur = self._current
        settled = 0 if cur is None else cur[1].shutdown(drain=drain)
        with self._lock:
            retired, self._retired = self._retired, {}
        for _, svc in sorted(retired.items()):
            settled += svc.shutdown(drain=False)
        return settled


# ---------------------------------------------------------------------------
# Tree-sharded multi-device voting
# ---------------------------------------------------------------------------


def make_sharded_vote_fn(forest: Forest, mesh, *, tree_axis: str = "data"):
    """Build a jit'd multi-device predictor with trees sharded over
    ``tree_axis``.

    Each shard walks only its own ``k / axis_size`` trees (fused kernel
    on TPU, XLA oracle elsewhere — ``config.predict_backend``) and
    accumulates their weighted votes into an ``[N, C]`` partial score;
    one ``psum`` combines the partials (the Eq. 9/10 sum over trees is
    associative), then argmax / Eq. 9 normalization runs replicated.
    Mirrors ``core/distributed``'s training-side histogram combine:
    O(N*C) words cross the wire, never the ``[k, N, C]`` tensor.

    Returns ``fn(x_binned) -> [N]`` labels (classification) or values
    (regression). ``n_trees`` must divide evenly over ``tree_axis``.
    """
    cfg = forest.config
    w = _vote_weights(forest)
    payload = build_payload(forest)
    depth = cfg.max_depth
    use_pallas = resolve_predict_backend(cfg.predict_backend) == "pallas"
    norm = jnp.maximum(w.sum(), 1e-38)

    def shard(feat, thr, left, pay, xb):
        from ..kernels.tree_traverse.ops import fused_vote

        partial = fused_vote(
            xb, feat, thr, left, pay, depth=depth, use_pallas=use_pallas
        )
        scores = jax.lax.psum(partial, tree_axis)            # the ONE combine
        if cfg.regression:
            return scores[:, 0] / norm
        return jnp.argmax(scores, axis=-1)

    fn = jax.jit(
        jax.shard_map(
            shard,
            mesh=mesh,
            in_specs=(P(tree_axis), P(tree_axis), P(tree_axis), P(tree_axis), P()),
            out_specs=P(),
            check_vma=False,
        )
    )

    def run(x_binned):
        return fn(
            forest.feature, forest.threshold, forest.left_child, payload,
            x_binned,
        )

    return run
