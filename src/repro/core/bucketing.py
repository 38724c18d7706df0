"""Shape-bucketed execution for the embedding hot path.

The paper's deployment-cost argument makes per-batch service time the lever
behind concurrency-per-device, and its Fig. 5 shows the query-length
distribution is structured — yet the fixed-shape backend pads every batch to
the global ``max_tokens`` window and retraces jit for every distinct batch
size.  This module exploits the structure:

* ``next_pow2`` / ``bucket_length`` — round batch size and sequence length
  up to power-of-two buckets, so the set of compiled shapes is SMALL and
  ENUMERABLE (O(log max_batch x log max_tokens) instead of one shape per
  raw batch size) and padding stops at the bucket boundary.
* ``length_bucket_fn`` — a ``TierSpec.bucket_fn``: the queue drains queries
  grouped by length bucket (FIFO within the bucket, see
  ``repro.core.routing.BoundedQueue.pop_batch``), so one batch never pads
  its short queries to a long straggler's length.
* ``BucketedEmbedderBackend`` — a drop-in ``JaxEmbedderBackend`` that pads
  each batch only to its (B_bucket, S_bucket) bucket, keeps the jit compile
  cache warm per bucket, and supports eager pre-warming
  (``prewarm(default_buckets(...))``) so a serving process takes ZERO
  compile stalls after startup.

Correctness relies on the embedder being padding-invariant: padded key
positions are masked out of every attention softmax (``kv_mask`` in
``repro.models.embedder.embed``), so the same query embeds to the same
vector whether the batch is padded to 32 or 128 tokens.
"""
from __future__ import annotations

import threading
from typing import Callable, Iterable, List, Sequence, Tuple

import numpy as np

from repro.core.routing import Query
from repro.core.telemetry import NO_SPANS, Telemetry
from repro.core.windve import JaxEmbedderBackend


def next_pow2(n: int) -> int:
    """Smallest power of two >= n (1 for n <= 1)."""
    if n <= 1:
        return 1
    return 1 << (int(n) - 1).bit_length()


def bucket_length(length: int, min_bucket: int = 16,
                  max_bucket: int = 128) -> int:
    """Round a token count up to its power-of-two bucket in
    [min_bucket, max_bucket] (max_bucket also caps: longer payloads are
    truncated by the backend and counted in telemetry)."""
    return min(max(next_pow2(length), min_bucket), max_bucket)


def length_bucket_fn(min_bucket: int = 16, max_bucket: int = 128
                     ) -> Callable[[Query], int]:
    """A ``TierSpec.bucket_fn``: group queries by padded-length bucket."""

    def fn(q: Query) -> int:
        return bucket_length(q.length, min_bucket, max_bucket)

    return fn


def default_buckets(max_batch: int, max_tokens: int = 128,
                    min_seq_bucket: int = 16, min_batch_bucket: int = 1
                    ) -> List[Tuple[int, int]]:
    """The full (B_bucket, S_bucket) grid — the enumerable compile-cache
    key space, suitable for ``BucketedEmbedderBackend.prewarm``."""
    bs: List[int] = []
    b = max(1, min_batch_bucket)
    while b < max_batch:
        bs.append(b)
        b *= 2
    bs.append(next_pow2(max_batch))
    ss: List[int] = []
    s = max(1, min_seq_bucket)
    while s < max_tokens:
        ss.append(s)
        s *= 2
    ss.append(max_tokens)
    return [(b, s) for b in bs for s in ss]


class BucketedEmbedderBackend(JaxEmbedderBackend):
    """Length-aware JAX embedder: pad to the (B, S) bucket, not the max.

    The sequence dim rounds up to its power-of-two bucket (short batches
    stop paying full-window FLOPs).  The batch dim uses a *binary
    decomposition plan* (``_batch_plan``): a batch of 9 runs as pow2 chunks
    8 + 1 rather than padding up to 16, so batch-dim padding rows all but
    vanish while the compiled-shape space stays the pow2 grid.  Each chunk
    buckets its OWN sequence length, and any padding rows carry an all-zero
    mask and are dropped from the output.

    Counters (shared with the fixed backend, which tracks the same):
    ``traces`` (jit retraces), ``bucket_hits`` (chunk launches served from
    an already-warm bucket), ``real_tokens`` / ``padded_tokens`` (padding
    waste; see ``padded_waste``), ``truncated``.
    """

    def __init__(self, cfg, params, max_tokens: int = 128, *,
                 min_seq_bucket: int = 16, min_batch_bucket: int = 1,
                 telemetry: Telemetry | None = None,
                 dtype: str | None = None,
                 prewarm_buckets: Sequence[Tuple[int, int]] = ()):
        super().__init__(cfg, params, max_tokens, telemetry=telemetry,
                         dtype=dtype)
        self.name = (f"jax-cpu-bucketed/{cfg.name}"
                     + (f"/{dtype}" if dtype else ""))
        self.min_seq_bucket = min_seq_bucket
        self.min_batch_bucket = min_batch_bucket
        self.bucket_hits = 0
        self._buckets: set = set()
        self._bucket_lock = threading.Lock()
        if prewarm_buckets:
            self.prewarm(prewarm_buckets)

    # ------------------------------------------------------------------
    def bucket_shape(self, batch: int, seq_len: int) -> Tuple[int, int]:
        """(B, S) -> the (B_bucket, S_bucket) a single-launch batch would
        execute at (the largest chunk of ``_batch_plan``)."""
        return (self._batch_plan(batch)[0],
                bucket_length(seq_len, self.min_seq_bucket, self.max_tokens))

    def _batch_plan(self, batch: int) -> List[int]:
        """Pow2 chunk sizes covering ``batch`` with minimal padding rows.

        Greedy binary decomposition (13 -> 8 + 4 + 1), with chunks below
        ``min_batch_bucket`` rounded up to it; when a single rounded-up
        launch pads no more rows than the decomposition, prefer the single
        launch (fewer per-batch fixed costs — the paper's Eq. 12 beta is
        per execution).
        """
        g = max(1, self.min_batch_bucket)
        greedy: List[int] = []
        rem = batch
        while rem > 0:
            c = max(1 << (rem.bit_length() - 1), g)   # largest pow2 <= rem
            greedy.append(c)
            rem -= min(c, rem)
        single = max(next_pow2(batch), g)
        return [single] if single <= sum(greedy) else greedy

    @property
    def warm_buckets(self) -> frozenset:
        """Buckets with a compiled executable (cache keys)."""
        return frozenset(self._buckets)

    def prewarm(self, buckets: Iterable[Tuple[int, int]]) -> int:
        """Eagerly compile the given (B_bucket, S_bucket) shapes so serving
        takes no compile stalls.  Returns how many were newly compiled.

        Inputs are staged exactly the way serving stages them
        (``_stage_chunk``: same host arrays, same device placement), so the
        executable compiled here is the one serving looks up."""
        new = 0
        for bb, sb in buckets:
            key = (int(bb), int(sb))
            with self._bucket_lock:
                if key in self._buckets:
                    continue
            chunk = [Query(qid=-1, length=sb)] * key[0]
            toks, mask, _, _ = self._stage_chunk(chunk, *key)
            try:
                self._embed(self.params, toks, mask).block_until_ready()
            finally:
                self._release_staging([key])
            # mark warm only AFTER the compile succeeds, so an interrupted
            # prewarm can be retried instead of silently no-op'ing
            with self._bucket_lock:
                self._buckets.add(key)
            new += 1
        return new

    @staticmethod
    def _qlen(q: Query) -> int:
        return len(q.payload) if q.payload is not None else q.length

    def _stage_chunk(self, chunk: Sequence[Query], bb: int, sb: int,
                     spans=NO_SPANS):
        """Tokenize one chunk into (bb, sb) device-ready inputs.

        Returns (tokens, mask, real_tokens, truncated).  The sharded backend
        overrides this with its staging-ring + mesh-sharded transfer; here
        fresh host arrays are handed straight to jit.  Padding rows beyond
        the chunk stay all-zero (dropped by pooling).  ``spans`` times the
        ``tokenize`` and ``device_put`` phases.
        """
        with spans.span("tokenize"):
            toks, mask, real, truncated = self._tokenize(
                chunk, sb, out=(np.zeros((bb, sb), np.int32),
                                np.zeros((bb, sb), np.float32)))
        with spans.span("device_put"):
            return (self._jnp.asarray(toks), self._jnp.asarray(mask), real,
                    truncated)

    def _record_attended(self, chunk: Sequence[Query]) -> None:
        """Count the key positions the chunk's real tokens attend, per kind
        of layer, once an engine has wired the tier's telemetry."""
        if self.tier is None or self.telemetry is None:
            return
        from repro.models.embedder import attended_keys

        self.telemetry.record_attended_keys(self.tier, attended_keys(
            self.cfg, [min(self._qlen(q), self.max_tokens) for q in chunk]))

    def _release_staging(self, keys) -> None:
        """Hand staged buckets back once their execution is done (fresh host
        arrays here need no bookkeeping; the sharded backend's ring does)."""

    def _enqueue_chunks(self, queries: Sequence[Query]
                        ) -> List[Tuple[int, object]]:
        """The single chunking/accounting path for every bucketed backend:
        decompose the batch (``_batch_plan``), bucket each chunk's own
        sequence length, stage (``_stage_chunk``), count, and enqueue the
        jit execution.  Returns [(chunk_len, device_result), ...] in query
        order; results are fetched by the caller (sync or deferred, through
        ``_fetch``).  The whole is the tier's ``stage`` span, with
        ``tokenize``/``device_put``/``dispatch`` spans per chunk."""
        handles: List[Tuple[int, object]] = []
        spans = self._spans()
        with spans.span("stage", spans.batch):
            start = 0
            for bb in self._batch_plan(len(queries)):
                chunk = queries[start:start + bb]
                start += len(chunk)
                # pad only to this chunk's own bucket; truncation still
                # happens at the global max_tokens cap, exactly like the
                # fixed backend
                longest = max(min(self._qlen(q), self.max_tokens)
                              for q in chunk)
                sb = bucket_length(longest, self.min_seq_bucket,
                                   self.max_tokens)
                toks, mask, real, truncated = self._stage_chunk(chunk, bb, sb,
                                                                spans)
                self._record_truncations(truncated)
                self._record_attended(chunk)
                with self._bucket_lock:
                    if (bb, sb) in self._buckets:
                        self.bucket_hits += 1
                    else:
                        self._buckets.add((bb, sb))
                    self.real_tokens += real
                    self.padded_tokens += bb * sb - real
                with spans.span("dispatch"):   # a retrace or compile too
                    out = self._embed(self.params, toks, mask)
                handles.append((len(chunk), out))
        return handles

    @staticmethod
    def _fetch(handles, spans=NO_SPANS, batch: int | None = None
               ) -> List[np.ndarray]:
        """The batch's embeddings from its chunks' device results: the
        tier's ``fetch`` span, split into ``ready`` (the host waits for the
        results: the executions and their device-to-host transfers) and
        ``copy`` (the per-row split on the host).  The wait and the
        transfer share one synchronisation: a wait of its own before the
        transfer costs the device a round trip through the host per
        batch."""
        with spans.span("fetch", batch):
            with spans.span("ready"):
                # blocks until ready; gathers a sharded result
                arrs = [(n, np.asarray(dev)) for n, dev in handles]
            with spans.span("copy"):
                out: List[np.ndarray] = []
                for n, arr in arrs:
                    out.extend(arr[i] for i in range(n))
        return out

    def embed_batch(self, queries: Sequence[Query]) -> List[np.ndarray]:
        spans = self._spans()
        return self._fetch(self._enqueue_chunks(queries), spans, spans.batch)
