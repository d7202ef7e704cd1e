"""Token data pipeline with DSI-style multiplexed sampling (paper §4.1.2).

The paper's data-multiplexing idea applied to LM training: the tokenized
corpus is materialized ONCE (shared, read-only); every epoch/replica is
just an *index table* over it. Shuffling, repeats, and replica splits
never copy token data — the same flat-in-k volume property as the PRF
DSI table. Synthetic corpus here (Zipf-ish token stream with injected
bigram structure so loss visibly decreases); swap `corpus` for a memmap
of real tokens in production.
"""
from __future__ import annotations

import dataclasses
import queue
import threading
import time
from typing import (
    Any, Callable, Dict, Iterator, List, Optional, Sequence, Tuple, Union,
)

import numpy as np


def sample_blocks(
    x: Union[np.ndarray, Sequence[np.ndarray]], block_rows: int = 0,
    row_range: Optional[Tuple[int, int]] = None,
) -> List[np.ndarray]:
    """Zero-copy ``[Nb, F]`` row views over a host array / ``np.memmap``.

    The block-feed API of the out-of-core trainer
    (``repro.core.grow_forest_streamed``): an array source is
    sliced into ``block_rows``-row views (no copy — memmap blocks are
    only paged in when a block is fed to the device). An explicit
    list/tuple of blocks passes through with ndarray blocks (memmap
    views included) kept **by identity** — only non-array entries
    (e.g. nested lists) are materialized, once, here — so callers can
    stream from any host source that yields row blocks.
    ``block_rows <= 0`` means one block (the degenerate resident feed).

    ``row_range=(lo, hi)`` restricts each block to its intersection with
    the **global** row interval ``[lo, hi)`` — the shard-aware feed of
    the multi-process plane (``launch.multiproc.MultiHostMesh``): block
    boundaries stay where a single-process sweep would put them, but
    each process's views cover only its own rows of the memmap, so only
    those pages are ever read. Blocks that fall entirely outside the
    range become empty ``[0, F]`` views (block indexing stays global).
    """
    if isinstance(x, (list, tuple)):
        blocks = [b if isinstance(b, np.ndarray) else np.asarray(b) for b in x]
        if row_range is None:
            return blocks
        lo, hi = row_range
        out, off = [], 0
        for b in blocks:
            b0, b1 = off, off + b.shape[0]
            out.append(b[max(lo - b0, 0):max(min(hi, b1) - b0, 0)])
            off = b1
        return out
    src = np.asarray(x)
    nb = block_rows if block_rows > 0 else src.shape[0]
    if row_range is None:
        return [src[i:i + nb] for i in range(0, src.shape[0], nb)]
    lo, hi = row_range
    return [
        src[min(max(lo, i), i + nb):min(max(hi, i), i + nb)]
        for i in range(0, src.shape[0], nb)
    ]


def stream_blocks(
    x: Union[np.ndarray, Sequence[Any]],
    sample_block: Optional[int],
    *,
    what: str,
    n_y: Optional[int] = None,
    n_w: Optional[int] = None,
) -> List[Any]:
    """The ONE block-list constructor + validator of the streaming data
    plane (growth, dimred, OOB, prediction — local and mesh).

    An explicit block sequence passes through (device arrays included);
    an array/memmap source is sliced per ``sample_block``, which must be
    > 0 so the full ``[N, F]`` matrix can never silently become one
    device block. Rejects empty block sequences, and — when the caller
    supplies its label/weight lengths — blocks that do not cover them.
    """
    if isinstance(x, (list, tuple)):
        blocks = list(x)
    else:
        if sample_block is None or sample_block <= 0:
            raise ValueError(
                f"{what} with an array/memmap source needs sample_block > 0 "
                "— sample_block=0 would feed the whole [N, F] matrix as one "
                "device block, which is exactly what the streaming plane "
                "exists to avoid (pass an explicit block list to stream "
                "from a custom source)"
            )
        blocks = sample_blocks(x, sample_block)
    if not blocks:
        raise ValueError(
            f"{what} got an empty block sequence — the data source yielded "
            "no [Nb, F] sample blocks (empty block list, or an array source "
            "with 0 rows)"
        )
    if n_y is not None or n_w is not None:
        covered = sum(int(b.shape[0]) for b in blocks)
        if (n_y is not None and covered != n_y) or (
            n_w is not None and covered != n_w
        ):
            raise ValueError(
                f"{what}: blocks cover {covered} samples, but y has {n_y} "
                f"and weights {n_w}"
            )
    return blocks


class FeedError(RuntimeError):
    """A block feed failed permanently (retry budget exhausted, a
    non-retryable error, or a producer thread that would not stop)."""


class DataIntegrityError(ValueError):
    """A sample block failed integrity validation (non-finite features,
    out-of-range labels, or shape drift). Carries the offending block
    index, columns, and reason so operators can find the bad shard."""

    def __init__(
        self, message: str, *,
        block_index: Optional[int] = None,
        columns: Sequence[int] = (),
        reason: str = "",
    ):
        super().__init__(message)
        self.block_index = block_index
        self.columns = tuple(int(c) for c in columns)
        self.reason = reason


@dataclasses.dataclass(frozen=True)
class BlockIssue:
    """One validation finding for one sample block."""

    index: int                    # block index in the sweep order
    reason: str                   # "nonfinite" | "label" | "shape"
    columns: Tuple[int, ...]      # offending feature columns ((): n/a)
    bad_cells: int = 0            # non-finite feature cells
    bad_labels: int = 0           # out-of-range / non-finite labels

    def describe(self) -> str:
        if self.reason == "shape":
            return f"block {self.index}: shape drift"
        if self.reason == "label":
            return f"block {self.index}: {self.bad_labels} bad label(s)"
        return (
            f"block {self.index}: {self.bad_cells} non-finite cell(s) in "
            f"columns {list(self.columns)}"
        )


@dataclasses.dataclass
class QuarantineReport:
    """What the block validator found and did — attached to the trained
    model (``PRFModel.quarantine``) and surfaced by serving ``health()``.

    ``quarantined`` lists blocks dropped from every sweep;
    ``sanitized_cells`` / ``sanitized_labels`` count deterministic
    imputations. ``clean`` is True when nothing was found, which is the
    guarantee that validation was a bitwise no-op on the model.
    """

    policy: str
    blocks_checked: int = 0
    quarantined: List[int] = dataclasses.field(default_factory=list)
    sanitized_cells: int = 0
    sanitized_labels: int = 0
    issues: List[BlockIssue] = dataclasses.field(default_factory=list)

    @property
    def clean(self) -> bool:
        return not self.issues

    def counters(self) -> Dict[str, int]:
        return {
            "blocks_checked": self.blocks_checked,
            "blocks_quarantined": len(self.quarantined),
            "sanitized_cells": self.sanitized_cells,
            "sanitized_labels": self.sanitized_labels,
        }


class BlockValidator:
    """Deterministic per-block integrity validator of the data plane.

    Checks each ``[Nb, F]`` block for NaN/Inf cells, shape drift against
    the expected feature count, and (when labels are supplied)
    out-of-range or non-finite labels. ``policy`` decides what a finding
    does:

    * ``"raise"`` — typed :class:`DataIntegrityError` naming the block
      index and offending columns; nothing trains on poisoned data.
    * ``"sanitize"`` — deterministic imputation: bad feature cells are
      zeroed (the trainer maps them to bin 0), bad labels are imputed to
      0 and the sample's DSI weights neutralized — the model is
      reproducible run-to-run.
    * ``"quarantine"`` — the block is dropped from every sweep and
      recorded in the :class:`QuarantineReport`.

    Validation is pure numpy over host blocks (memmap pages are touched
    once, before any device transfer), and on clean data it mutates
    nothing — the trained model is bitwise identical with validation on
    or off.
    """

    POLICIES = ("raise", "sanitize", "quarantine")

    def __init__(
        self, policy: str = "raise", *,
        n_features: Optional[int] = None,
        n_classes: Optional[int] = None,
        regression: bool = False,
    ):
        if policy not in self.POLICIES:
            raise ValueError(
                f"bad_block_policy must be one of {self.POLICIES} (or None "
                f"to disable validation), got {policy!r}"
            )
        self.policy = policy
        self.n_features = n_features
        self.n_classes = n_classes
        self.regression = regression

    def check(
        self, block: np.ndarray, index: int,
        y_block: Optional[np.ndarray] = None,
    ) -> Optional[BlockIssue]:
        """Inspect one block (and its label slice); return the finding."""
        b = np.asarray(block)
        n_feat = self.n_features
        if b.ndim != 2 or (n_feat is not None and b.shape[1] != n_feat):
            return BlockIssue(index=index, reason="shape", columns=())
        bad_cells = 0
        cols: Tuple[int, ...] = ()
        if np.issubdtype(b.dtype, np.inexact):
            finite = np.isfinite(b)
            if not finite.all():
                bad = ~finite
                bad_cells = int(bad.sum())
                cols = tuple(int(c) for c in np.flatnonzero(bad.any(axis=0)))
        bad_labels = 0
        if y_block is not None:
            yb = np.asarray(y_block)
            bad_y = np.zeros(yb.shape[0], dtype=bool)
            if np.issubdtype(yb.dtype, np.inexact):
                bad_y |= ~np.isfinite(yb)
            if not self.regression and self.n_classes is not None:
                with np.errstate(invalid="ignore"):
                    bad_y |= (yb < 0) | (yb >= self.n_classes)
            bad_labels = int(bad_y.sum())
        if bad_cells or bad_labels:
            reason = "nonfinite" if bad_cells else "label"
            return BlockIssue(
                index=index, reason=reason, columns=cols,
                bad_cells=bad_cells, bad_labels=bad_labels,
            )
        return None

    def _label_mask(self, y_block: np.ndarray) -> np.ndarray:
        yb = np.asarray(y_block)
        bad = np.zeros(yb.shape[0], dtype=bool)
        if np.issubdtype(yb.dtype, np.inexact):
            bad |= ~np.isfinite(yb)
        if not self.regression and self.n_classes is not None:
            with np.errstate(invalid="ignore"):
                bad |= (yb < 0) | (yb >= self.n_classes)
        return bad

    def screen(
        self,
        blocks: Sequence[np.ndarray],
        y: Optional[np.ndarray] = None,
    ):
        """Validate every block and apply the policy.

        Returns ``(blocks, y, cell_masks, label_masks, report)`` —
        blocks/y are the originals when clean (bitwise no-op), imputed
        copies where sanitization touched them; ``cell_masks[i]`` /
        ``label_masks[i]`` are boolean masks of the imputed feature
        cells / labels of block ``i`` (the trainer forces masked cells
        to bin 0 and zeroes masked samples' weights); quarantined block
        indices are listed in ``report.quarantined``.
        """
        blocks = list(blocks)
        y_out = None if y is None else np.asarray(y)
        report = QuarantineReport(policy=self.policy, blocks_checked=len(blocks))
        cell_masks: Dict[int, np.ndarray] = {}
        label_masks: Dict[int, np.ndarray] = {}
        n_feat = self.n_features
        if n_feat is None:
            for b in blocks:
                bb = np.asarray(b)
                if bb.ndim == 2:
                    n_feat = int(bb.shape[1])
                    break
        offset = 0
        for i, b in enumerate(blocks):
            bb = np.asarray(b)
            rows = int(bb.shape[0]) if bb.ndim >= 1 else 0
            yb = None if y_out is None else y_out[offset:offset + rows]
            issue = None
            if bb.ndim != 2 or (n_feat is not None and bb.shape[1] != n_feat):
                issue = BlockIssue(index=i, reason="shape", columns=())
                if self.policy != "quarantine" or y_out is not None:
                    # A drifted block can't be sanitized, and with labels
                    # present its row count can't be reconciled against y.
                    raise DataIntegrityError(
                        f"block {i} drifted in shape: expected [Nb, "
                        f"{n_feat}], got {list(bb.shape)}",
                        block_index=i, reason="shape",
                    )
            else:
                issue = self.check(bb, i, yb)
            if issue is None:
                offset += rows
                continue
            report.issues.append(issue)
            if self.policy == "raise":
                raise DataIntegrityError(
                    issue.describe(), block_index=i,
                    columns=issue.columns, reason=issue.reason,
                )
            if issue.reason == "shape":
                report.quarantined.append(i)
                offset += rows
                continue
            # sanitize and quarantine both impute, so every downstream
            # consumer (bin-edge fitting included) sees finite data; a
            # quarantined block additionally drops out of every sweep.
            if issue.bad_cells:
                mask = ~np.isfinite(bb)
                fixed = bb.copy()
                fixed[mask] = 0.0
                blocks[i] = fixed
                cell_masks[i] = mask
                report.sanitized_cells += issue.bad_cells
            if issue.bad_labels:
                lmask = self._label_mask(yb)
                if y_out is y:
                    y_out = y_out.copy()
                y_out[offset:offset + rows][lmask] = 0
                label_masks[i] = lmask
                report.sanitized_labels += issue.bad_labels
            if self.policy == "quarantine":
                report.quarantined.append(i)
            offset += rows
        return blocks, y_out, cell_masks, label_masks, report


def screen_blocks(
    blocks: Sequence[np.ndarray],
    y: Optional[np.ndarray] = None,
    *,
    policy: str,
    n_features: Optional[int] = None,
    n_classes: Optional[int] = None,
    regression: bool = False,
):
    """Module-level convenience around :meth:`BlockValidator.screen`."""
    validator = BlockValidator(
        policy, n_features=n_features, n_classes=n_classes,
        regression=regression,
    )
    return validator.screen(blocks, y)


class _Sweep:
    """One prefetching pass over a feeder's blocks.

    A real iterator object (not a generator) so the background thread
    has an owner with a deterministic ``close()``: a producer-side
    exception is re-raised from the consumer's next ``__next__`` *after*
    the thread is joined, and early consumer exit (``break``, an
    exception in the loop body, or context-manager ``__exit__``) cancels
    the producer, drains its in-flight device buffers, and joins —
    never a leaked thread or a hung ``queue.put``.
    """

    def __init__(self, feeder: "BlockFeeder"):
        self._feeder = feeder
        self._q: "queue.Queue[Any]" = queue.Queue(maxsize=feeder.prefetch)
        self._stop = object()
        self._cancel = threading.Event()
        self._closed = False
        self._thread = threading.Thread(
            target=self._produce, daemon=True, name="prf-block-feeder"
        )
        self._thread.start()

    def _put_item(self, item) -> bool:
        """Enqueue with cancel polling so a gone consumer can't wedge us."""
        while not self._cancel.is_set():
            try:
                self._q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def _produce(self):
        try:
            for i in self._feeder.live_blocks:
                if self._cancel.is_set():
                    return
                b = self._feeder.blocks[i]
                if not self._put_item(self._feeder._put(b, f"block[{i}]", i)):
                    return
            self._put_item(self._stop)
        except BaseException as e:  # re-raised on the consumer side
            self._put_item(e)

    def __iter__(self) -> "_Sweep":
        return self

    def __next__(self):
        if self._closed:
            raise StopIteration
        item = self._q.get()
        if item is self._stop:
            self.close()
            raise StopIteration
        if isinstance(item, BaseException):
            self.close()
            raise item
        return item

    def close(self) -> None:
        """Cancel the producer, drain queued buffers, join the thread.

        A producer that fails to stop within ``feeder.join_timeout``
        seconds is a wedged device transfer — escalated to
        :class:`FeedError` (naming the last feed site) instead of
        silently leaking a live thread.
        """
        if self._closed:
            return
        self._closed = True
        self._cancel.set()
        try:
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass
        self._thread.join(timeout=self._feeder.join_timeout)
        self._feeder._sweeps.discard(self)
        if self._thread.is_alive():
            try:
                import jax

                proc = int(jax.process_index())
            except Exception:
                proc = 0
            raise FeedError(
                f"feeder thread {self._thread.name!r} on process {proc} "
                f"failed to stop within {self._feeder.join_timeout}s — a "
                f"transfer is wedged at site {self._feeder._last_site!r}"
            )

    def __enter__(self) -> "_Sweep":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False


class BlockFeeder:
    """Async double-buffered host->device feed of the streaming data plane.

    One feeder owns the host-side sample blocks for a whole training /
    evaluation run. Two jobs:

    * ``pin(a, sharding=None)`` — one-shot ``jax.device_put`` of a
      per-block constant (``y``, DSI weights, channel matrices) under
      ``sharding`` or ``placement``: uploaded ONCE and kept
      device-resident for every subsequent level sweep, instead of
      re-fed per level.
    * ``sweep()`` — yield device copies of the blocks in order, with a
      background thread running block ``i+1``'s host->device copy while
      block ``i``'s histogram/route call executes (``prefetch`` copies
      in flight; ``prefetch=0`` degrades to the synchronous feed). JAX
      dispatch is async, so the consumer's device work and the
      producer's ``device_put`` genuinely overlap.

    ``placement`` is anything ``jax.device_put`` accepts as a target —
    a device for the single-host carriers, or a ``NamedSharding`` so each
    mesh shard receives its (sample x feature) slice of every block
    (the streamed growth driver, ``distributed.grow_forest_streamed``).

    **Fault tolerance.** Every host->device transfer (``pin`` and each
    sweep block) runs through a bounded retry loop: a ``retryable``
    exception (default ``OSError`` — flaky memmap page-ins — and
    ``RuntimeError``, which covers transient device_put failures and
    ``launch.fault.SimulatedFailure``) is retried up to ``max_retries``
    times with exponential backoff (``backoff * backoff_factor**i``,
    capped at ``max_backoff`` seconds); exhaustion raises
    :class:`FeedError` from the last error. ``fault_hook`` is a
    deterministic chaos hook called before every transfer (see
    ``launch.fault.FaultInjector``) so injected-failure tests are
    reproducible. ``retries`` counts the retried attempts.

    A feeder is a context manager: ``close()`` (or ``__exit__``) shuts
    down any live sweep threads deterministically.
    """

    def __init__(
        self,
        blocks: Sequence[Any],
        *,
        placement: Any = None,
        prefetch: int = 2,
        max_retries: int = 3,
        backoff: float = 0.05,
        backoff_factor: float = 2.0,
        max_backoff: float = 2.0,
        retryable: Tuple[type, ...] = (OSError, RuntimeError),
        fault_hook: Optional[Callable[[str], None]] = None,
        validator: Optional[BlockValidator] = None,
        quarantined: Sequence[int] = (),
        join_timeout: float = 10.0,
    ):
        self.blocks = list(blocks)
        if not self.blocks:
            raise ValueError(
                "BlockFeeder needs at least one sample block — got an empty "
                "block sequence"
            )
        # Eager integrity screen: quarantine decisions are made ONCE at
        # construction (before any pin or sweep), so every level sweep
        # of a run sees the same live-block set deterministically.
        self.report: Optional[QuarantineReport] = None
        quar = {int(i) for i in quarantined}
        if validator is not None:
            self.blocks, _, _, _, self.report = validator.screen(self.blocks)
            quar |= set(self.report.quarantined)
        out_of_range = [i for i in quar if not 0 <= i < len(self.blocks)]
        if out_of_range:
            raise ValueError(
                f"quarantined block indices out of range: {sorted(out_of_range)}"
            )
        self.quarantined = tuple(sorted(quar))
        self.live_blocks = tuple(
            i for i in range(len(self.blocks)) if i not in quar
        )
        if not self.live_blocks:
            raise DataIntegrityError(
                f"every block quarantined ({len(self.blocks)} of "
                f"{len(self.blocks)}) — nothing left to train on",
                reason="quarantine",
            )
        self.placement = placement
        self.prefetch = int(prefetch)
        if max_retries < 0:
            raise ValueError(f"max_retries must be >= 0, got {max_retries}")
        if backoff < 0 or max_backoff < 0 or backoff_factor < 1.0:
            raise ValueError(
                "backoff/max_backoff must be >= 0 and backoff_factor >= 1"
            )
        self.max_retries = int(max_retries)
        self.backoff = float(backoff)
        self.backoff_factor = float(backoff_factor)
        self.max_backoff = float(max_backoff)
        self.retryable = tuple(retryable)
        self.fault_hook = fault_hook
        if join_timeout <= 0:
            raise ValueError(f"join_timeout must be > 0, got {join_timeout}")
        self.join_timeout = float(join_timeout)
        self.retries = 0                     # total retried attempts
        self._last_site: Optional[str] = None
        self._sweeps: set = set()

    def __len__(self) -> int:
        return len(self.blocks)

    def _put(
        self, host_array, site: str, index: Optional[int] = None,
        placement: Any = None,
    ):
        """One host->device transfer under the bounded retry policy, to
        ``placement`` (the feeder's own when ``None``)."""
        import jax

        if placement is None:
            placement = self.placement
        self._last_site = site
        attempt = 0
        while True:
            try:
                if self.fault_hook is not None:
                    self.fault_hook(site)
                if callable(placement):
                    # Multi-process placement: a callback building the
                    # global device array from this process's host-local
                    # rows (needs the block index for its row offset).
                    return placement(host_array, index)
                if placement is None:
                    return jax.device_put(host_array)
                return jax.device_put(host_array, placement)
            except self.retryable as e:
                attempt += 1
                if attempt > self.max_retries:
                    raise FeedError(
                        f"feed of {site} failed permanently after "
                        f"{self.max_retries} retries: {e}"
                    ) from e
                self.retries += 1
                time.sleep(min(
                    self.backoff * self.backoff_factor ** (attempt - 1),
                    self.max_backoff,
                ))

    def pin(self, host_array, sharding: Any = None):
        """Pin one host array on device, under ``sharding`` (the
        feeder's ``placement`` when ``None``)."""
        return self._put(host_array, "pin", placement=sharding)

    def sweep(self) -> Iterator[Any]:
        """Yield the *live* blocks as device arrays, prefetch-deep.

        Quarantined blocks are skipped entirely — never transferred,
        never histogrammed. Zip with ``live_blocks`` to recover the
        original block index of each yielded buffer.
        """
        if self.prefetch <= 0:
            def sync():
                for i in self.live_blocks:
                    yield self._put(self.blocks[i], f"block[{i}]", i)
            return sync()
        s = _Sweep(self)
        self._sweeps.add(s)
        return s

    def close(self) -> None:
        """Shut down any live sweep threads (idempotent)."""
        for s in list(self._sweeps):
            s.close()

    def __enter__(self) -> "BlockFeeder":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False


@dataclasses.dataclass
class TokenPipeline:
    vocab_size: int
    seq_len: int
    n_docs: int = 2048
    seed: int = 0

    def __post_init__(self):
        rng = np.random.default_rng(self.seed)
        # Zipf marginals + deterministic bigram transitions => learnable.
        probs = 1.0 / np.arange(1, self.vocab_size + 1) ** 1.1
        probs /= probs.sum()
        succ = rng.integers(0, self.vocab_size, self.vocab_size)
        toks = rng.choice(self.vocab_size, (self.n_docs, self.seq_len + 1), p=probs)
        follow = rng.random((self.n_docs, self.seq_len)) < 0.5
        for t in range(1, self.seq_len + 1):
            toks[:, t] = np.where(follow[:, t - 1], succ[toks[:, t - 1]], toks[:, t])
        self.corpus = toks.astype(np.int32)          # the single shared copy

    def dsi_epoch(self, epoch: int, batch: int, steps: int) -> np.ndarray:
        """Index table [steps, batch] — the DSI analogue (no data copied)."""
        rng = np.random.default_rng(self.seed * 1000 + epoch)
        return rng.integers(0, self.n_docs, (steps, batch)).astype(np.int32)

    def batch(self, dsi_row: np.ndarray) -> Dict[str, np.ndarray]:
        docs = self.corpus[dsi_row]                  # gather through the DSI
        return {"tokens": docs[:, :-1], "targets": docs[:, 1:]}

    def batches(self, batch: int, steps: int, *, epoch: int = 0,
                n_micro: int = 1) -> Iterator[Dict[str, np.ndarray]]:
        table = self.dsi_epoch(epoch, batch, steps)
        for s in range(steps):
            b = self.batch(table[s])
            if n_micro > 1:
                b = {
                    k: v.reshape(n_micro, batch // n_micro, *v.shape[1:])
                    for k, v in b.items()
                }
            else:
                b = {k: v[None] for k, v in b.items()}
            yield b
