"""Device-sharded, donation-aware bf16 embedding serving backend.

The paper's deployment-cost formula (Eq. 12) makes per-batch service time on
the accelerator tier the lever behind concurrency-per-device; PR 2 made the
hot path's shapes stable and enumerable (the bucketed (B, S) compile cache).
This module spends that stability on the device side of the batch:

* **mesh fan-out** — one embedding tier becomes a jax ``Mesh`` over N
  devices of one platform (the TPU chips, or the host CPU).  Every bucketed
  batch is data-parallel sharded over the mesh (``serve_embed_shardings``:
  weights RESIDENT and replicated, so no per-batch weight all-gathers —
  batch over ``data``) and each device embeds its own rows
  (``embed_step``).  A single-device mesh serves the same vectors as the
  bucketed backend.
* **bf16-resident serving weights** — ``dtype="bf16"`` casts the param tree
  ONCE at load and runs every trunk matmul in bf16; the ``pool_norm``
  epilogue always accumulates fp32 (see ``repro.kernels.pool_norm``), so
  served vectors stay fp32 unit vectors within 1e-2 cosine of the
  ``dtype="fp32"`` oracle (guarded by tests + the sharded microbench).
* **buffer donation** — ``donate=True`` passes the token/mask device buffers
  as ``jit(..., donate_argnums=(1, 2))`` so XLA may reuse their memory
  instead of allocating fresh HBM per batch; paired with one reusable host
  staging array pair per (B, S) bucket, steady-state serving performs zero
  fresh host allocations and zero retraces.
* **pipelined drain** — ``embed_batch_async`` returns as soon as every
  chunk execution is enqueued; the returned fetch thunk blocks for the
  device->host transfer.  On an accelerator mesh the engine worker
  (``repro.core.windve``) pipelines its drain: it stages and enqueues batch
  N+1 before it fetches batch N, so the host's staging, completion and hooks
  run while the device computes.  A CPU mesh drains synchronously: its
  XLA:CPU step runs on the cores the worker needs, so overlap buys nothing.

Correctness notes: the batch bucket floor is raised to the mesh's
data-parallel size so every chunk's batch dim divides the mesh exactly (jit
input shardings require it); padding rows carry an all-zero mask and pool to
zero vectors that are dropped from the output, so sharding never changes
served embeddings.
"""
from __future__ import annotations

import threading
import warnings
from typing import Callable, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.bucketing import BucketedEmbedderBackend, default_buckets, \
    next_pow2
from repro.core.routing import Query
from repro.core.telemetry import NO_SPANS, Telemetry


_cpu_donation_warning_filtered = False


def _filter_cpu_donation_warning() -> None:
    """Once-only: silence XLA's "donated buffers were not usable" warning on
    the CPU backend, where donation is unimplemented and the warning cannot
    indicate a real mis-specification."""
    global _cpu_donation_warning_filtered
    if not _cpu_donation_warning_filtered:
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        _cpu_donation_warning_filtered = True


def _serve_devices(devices=None) -> list:
    """Local devices the serve mesh fans out over, clamped to a power of two
    so every pow2 batch bucket divides the data axis exactly."""
    import jax

    devices = list(jax.local_devices() if devices is None else devices)
    if not devices:
        raise ValueError("need at least one device")
    usable = 1 << (len(devices).bit_length() - 1)   # largest pow2 <= n
    return devices[:usable]


def embed_step(cfg, mesh, compute_dtype, act_quant: bool, *,
               donate: bool = False, on_trace: Optional[Callable] = None):
    """The jitted serving step ``(params, tokens, mask) -> (B, D)`` on
    ``mesh``: weights replicated, the batch split over the data axes, and
    ``embedder.embed`` run per data shard under ``shard_map``.

    Every device embeds its own rows with no collective.  Running the
    embedder per shard is also what lets a multi-chip mesh use the TPU's
    Mosaic kernels, which XLA cannot partition.  Kernels follow the mesh's
    platform (``repro.kernels.placement``): a TPU mesh runs them compiled,
    a CPU mesh runs their jnp references.  ``on_trace`` runs once per
    trace (the backends' retrace counter).
    """
    import jax

    from repro.models import embedder
    from repro.parallel.sharding import serve_embed_shardings

    if mesh.shape.get("model", 1) != 1:
        raise ValueError(f"the serving mesh is data-parallel only; got "
                         f"model axis {mesh.shape['model']}")
    psh, bsh = serve_embed_shardings(mesh)

    def local(p, toks, mask):
        if on_trace is not None:
            on_trace()
        return embedder.embed(p, cfg, toks, mask,
                              compute_dtype=compute_dtype,
                              act_quant=act_quant)

    # no collective runs inside, so there is no replication to type-check
    # (the embedder's scan carries start from fresh zeros)
    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(psh.spec, bsh.spec, bsh.spec),
                       out_specs=bsh.spec, check_vma=False)
    return jax.jit(fn, in_shardings=(psh, bsh, bsh), out_shardings=bsh,
                   donate_argnums=(1, 2) if donate else ())


class ShardedEmbedderBackend(BucketedEmbedderBackend):
    """Bucketed embedder fanned out over a data-parallel device mesh.

    ``dtype`` / ``donate`` default to the §Perf flags (``embed_dtype`` /
    ``embed_donate``), so a default-constructed backend is the
    paper-faithful fp32 baseline and every optimization is a reproducible
    baseline-vs-change row.  ``async_dispatch`` (whether the engine worker
    pipelines this tier's drain) defaults to the mesh's platform: on for an
    accelerator, off for the host CPU; pass it to pin either path.
    ``dtype`` policies (``repro.models.quantize.serve_params``):
    ``fp32`` oracle, ``bf16`` resident weights, ``int8`` weight-only
    quantized projections (int8 weights + fp32 dequant scales, fp32
    activations, the fused quant matmul in the trunk; served vectors stay
    fp32 unit vectors within 1e-2 cosine of the oracle), or ``int8_w8a8``
    (the same tree with dynamic per-row activation quantization — int8 x
    int8 projections, int32 accumulation, within 2e-2 cosine).  Counters are
    inherited from the bucketed backend (``traces``, ``bucket_hits``,
    ``real_tokens``/``padded_tokens``, ``truncated``).
    """

    def __init__(self, cfg, params, max_tokens: int = 128, *,
                 mesh=None, devices=None,
                 dtype: Optional[str] = None,
                 donate: Optional[bool] = None,
                 async_dispatch: Optional[bool] = None,
                 min_seq_bucket: int = 16, min_batch_bucket: int = 1,
                 staging_slots: int = 4,
                 telemetry: Optional[Telemetry] = None,
                 prewarm_buckets: Sequence[Tuple[int, int]] = ()):
        import jax

        from repro import perf_flags
        from repro.launch.mesh import make_serve_mesh
        from repro.models.quantize import serve_params, wants_act_quant
        from repro.parallel.sharding import dp_axes, serve_embed_shardings

        flags = perf_flags.FLAGS
        dtype = flags.embed_dtype if dtype is None else dtype
        # realise the serving precision policy ONCE at load: fp32 oracle,
        # bf16-resident weights, or int8 weight-only quantized projections
        # (validates dtype and raises a ValueError listing the policies)
        served, cdt = serve_params(params, dtype)
        donate = flags.embed_donate if donate is None else bool(donate)
        if mesh is None:
            mesh = make_serve_mesh(_serve_devices(devices))
        self.mesh = mesh
        # kernels follow this platform: compiled on "tpu", jnp references
        # on "cpu" (see ``embed_step``)
        self.platform = mesh.devices.flat[0].platform
        # an accelerator's executions leave the host's cores free, so its
        # worker stages the next batch while the device computes; on the
        # CPU the step itself needs those cores
        self.async_dispatch = (self.platform != "cpu" if async_dispatch is None
                               else bool(async_dispatch))
        ndev = 1
        for a in dp_axes(mesh):
            ndev *= mesh.shape[a]
        if ndev != next_pow2(ndev):
            raise ValueError(f"data-parallel mesh size must be a power of "
                             f"two, got {ndev}")
        self.device_count = ndev
        self.donate = donate

        # the parent wires counters, telemetry and the bucket planner; its
        # single-device jit is replaced below, before anything compiles
        # batch buckets must divide the data axis: floor the bucket at the
        # mesh size and keep it a power of two
        super().__init__(cfg, params, max_tokens,
                         min_seq_bucket=min_seq_bucket,
                         min_batch_bucket=max(next_pow2(min_batch_bucket),
                                              ndev),
                         telemetry=telemetry)
        self.dtype = dtype
        # the trunk's ACTIVATION dtype: weight-only int8 keeps fp32
        # activations, so quantization error enters via the weights alone;
        # int8_w8a8 additionally quantizes activations per projection
        self.serve_dtype = cdt
        aq = wants_act_quant(dtype)
        self.act_quant = aq
        self.name = (f"jax-sharded/{cfg.name}@{ndev}{self.platform}/{dtype}"
                     + ("+donate" if donate else "")
                     + ("+async" if self.async_dispatch else ""))

        # (a) weights realised ONCE at load (cast / quantized) and laid out
        # resident on the mesh; dequant scales ride the tree as fp32 leaves
        psh, self._batch_sharding = serve_embed_shardings(mesh)
        self.params = jax.device_put(served, psh)

        def count_trace():
            self.traces += 1          # python side effect: runs once per trace

        # (b) donate the per-batch token/mask device buffers; on a backend
        # where donation is unimplemented (the CPU) the "not usable"
        # warning is pure noise, so it is filtered ONCE and only there — on
        # TPU a donation diagnostic stays visible
        if donate and self.platform == "cpu":
            _filter_cpu_donation_warning()
        self._embed = embed_step(cfg, mesh, cdt, aq, donate=donate,
                                 on_trace=count_trace)
        self._jax = jax

        # reusable pinned host staging arrays: a small RING of pairs per
        # (B, S) bucket.  ``device_put`` may defer (or, for large aligned
        # arrays, zero-copy alias) the host buffer, so a slot must not be
        # refilled while an enqueued execution can still read it.  The
        # default depth covers the worker's pipelined drain (at most 2
        # undelivered batches per worker) for up to 2 workers;
        # callers sharing one backend across more workers, or holding more
        # fetches back, must raise ``staging_slots`` to 2 x workers.
        # Steady-state host allocation stays bounded at ``staging_slots``
        # pairs per live bucket.
        self._staging_slots = max(2, int(staging_slots))
        self._staging: dict = {}        # (bb, sb) -> list[(toks, mask)]
        self._staging_use: dict = {}    # (bb, sb) -> fills so far
        self._staging_lock = threading.Lock()
        # overrun guard: staged-but-unfetched executions per bucket.  A slot
        # is reused ``staging_slots`` stagings later; if that many are still
        # pending, refilling would overwrite host data a deferred/aliased
        # ``device_put`` may still read — the served embeddings would be
        # silently ROTATED between batches.  Raise loudly instead (the
        # documented fix: staging_slots >= 2 x worker threads).  Every
        # fetch thunk returned by ``embed_batch_async`` must be called
        # exactly once — dropping one permanently occupies its slots.
        self._staging_pending: dict = {}   # (bb, sb) -> in-flight stagings
        self._staging_tl = threading.local()

        if prewarm_buckets:
            self.prewarm(prewarm_buckets)

    # ------------------------------------------------------------------
    def warm_grid(self, max_batch: int) -> List[Tuple[int, int]]:
        """The enumerable (B, S) grid this backend serves ``max_batch`` with
        (batch buckets floored at the mesh size) — feed to ``prewarm``."""
        return default_buckets(max(max_batch, self.min_batch_bucket),
                               self.max_tokens, self.min_seq_bucket,
                               self.min_batch_bucket)

    def _stage_chunk(self, chunk: Sequence[Query], bb: int, sb: int,
                     spans=NO_SPANS):
        """Tokenize into the (bb, sb) bucket's next staging slot and ship it
        to the mesh.  The slot rotates through the ring so a buffer is only
        refilled ``staging_slots`` batches later — by which point the
        pipelined worker has fetched (hence the device has consumed)
        the execution that read it.  The lock covers slot pick + fill +
        transfer, so worker threads can share one backend (raise
        ``staging_slots`` beyond 2 workers).  ``spans`` times the
        ``tokenize`` and ``device_put`` (both transfers) phases."""
        key = (bb, sb)
        with self._staging_lock:
            pending = self._staging_pending.get(key, 0)
            if pending >= self._staging_slots:
                raise RuntimeError(
                    f"staging ring overrun on bucket {key}: {pending} "
                    f"staged batches not yet fetched with staging_slots="
                    f"{self._staging_slots}.  Refilling now would overwrite "
                    f"host buffers an enqueued execution may still read "
                    f"(rotated embeddings).  More than 2 worker threads — "
                    f"or callers holding fetches back beyond the worker's "
                    f"pipelined drain — share this backend: construct it "
                    f"with staging_slots >= 2 x workers.")
            self._staging_pending[key] = pending + 1
            try:
                ring = self._staging.setdefault(key, [])
                use = self._staging_use.get(key, 0)
                self._staging_use[key] = use + 1
                if len(ring) < self._staging_slots:
                    ring.append((np.zeros((bb, sb), np.int32),
                                 np.zeros((bb, sb), np.float32)))
                out = ring[use % len(ring)]
                with spans.span("tokenize"):
                    toks, mask, real, truncated = self._tokenize(chunk, sb,
                                                                 out=out)
                with spans.span("device_put"):
                    td = self._jax.device_put(toks, self._batch_sharding)
                    md = self._jax.device_put(mask, self._batch_sharding)
            except Exception:
                # failed BEFORE the caller could capture the key for its
                # own rollback: undo the pending count here or the bucket
                # is poisoned into spurious overrun errors forever
                n = self._staging_pending.get(key, 1) - 1
                if n > 0:
                    self._staging_pending[key] = n
                else:
                    self._staging_pending.pop(key, None)
                raise
        keys = getattr(self._staging_tl, "keys", None)
        if keys is not None:        # capture for the enclosing async call
            keys.append(key)
        return td, md, real, truncated

    def _release_staging(self, keys) -> None:
        with self._staging_lock:
            for k in keys:
                n = self._staging_pending.get(k, 0) - 1
                if n > 0:
                    self._staging_pending[k] = n
                else:
                    self._staging_pending.pop(k, None)

    def embed_batch_async(self, queries: Sequence[Query]
                          ) -> Callable[[], List[np.ndarray]]:
        """Enqueue every chunk of the batch; returns the deferred fetch.

        (c) async dispatch: jit calls return as soon as the computation is
        enqueued, so this method costs staging + dispatch only (the shared
        chunking/accounting path in ``BucketedEmbedderBackend
        ._enqueue_chunks``).  The fetch thunk performs the blocking
        device->host copy — a pipelining engine worker calls it after it
        has enqueued the next batch, so the device never waits on the host
        between the two.
        Staging is the tier's ``stage`` span and the thunk its ``fetch``
        span, both carrying the batch number the worker gave this batch.
        """
        spans = self._spans()
        batch = spans.batch
        self._staging_tl.keys = []
        try:
            handles = self._enqueue_chunks(queries)
        except Exception:
            # roll back this call's pending counts (e.g. the overrun guard
            # fired on a later chunk) so one failed batch cannot poison the
            # accounting for every batch after it
            self._release_staging(self._staging_tl.keys)
            raise
        finally:
            keys, self._staging_tl.keys = self._staging_tl.keys, None

        def fetch() -> List[np.ndarray]:
            try:
                return self._fetch(handles, spans, batch)
            finally:
                # results copied out: the executions consumed their staged
                # inputs, so the slots may rotate again
                self._release_staging(keys)

        return fetch

    def embed_batch(self, queries: Sequence[Query]) -> List[np.ndarray]:
        # route the sync path through the async one so staging-pending
        # accounting (stage -> fetch) stays balanced for every caller
        return self.embed_batch_async(queries)()
