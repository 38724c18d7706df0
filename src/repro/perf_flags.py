"""Optimization toggles for the §Perf hillclimb.

The PAPER-FAITHFUL baseline is all-defaults; `launch/dryrun.py --opt k=v`
flips individual flags so every EXPERIMENTS.md §Perf row is reproducible as
baseline-vs-change.  Flags default OFF so tests exercise the baseline unless
they opt in.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass


@dataclass
class PerfFlags:
    # mamba selective scan: 0 = per-timestep lax.scan (baseline);
    # N = outer scan over S/N chunks with an N-step unrolled inner body so
    # XLA fuses the chunk and the ssm state stops round-tripping HBM per step
    # (the pure-XLA analogue of the Pallas ssm_scan kernel).
    mamba_chunk: int = 0
    # flash attention: skip kv chunks that are fully masked (outside the
    # causal/sliding-window band) instead of masking them — fewer chunk
    # iterations, less score traffic, fewer flops.
    attn_band_skip: bool = False
    # attn_forward backend: "jnp" (baseline: chunked pure-JAX flash), "auto"
    # (the Pallas kernel when running on TPU, pure-JAX elsewhere), "pallas" /
    # "interpret" (force the kernel, compiled / interpreter).  The kernel
    # route assumes contiguous [0, S) positions (what train / prefill /
    # encoder / embedder all pass) and prefix-style kv masks.
    attn_kernel: str = "jnp"
    # decode: pick label/argmax paths that avoid gathers over the
    # vocab-sharded logits (one-hot dot instead of take_along_axis).
    ce_onehot: bool = False
    # train: all-reduce gradients in bf16 instead of fp32 (halves the
    # gradient-sync collective bytes; optimizer math stays fp32).
    grad_bf16: bool = False
    # decode: carry the stacked KV cache through a fori_loop with per-layer
    # in-place dynamic-update-slice instead of scan-ys stacking.  The scan
    # path makes XLA rewrite the FULL cache with a bf16->f32->bf16 roundtrip
    # every layer iteration (measured 870 GB/step on qwen2-72b decode_32k).
    decode_fori: bool = False
    # decode: flash-decode attention via shard_map — the seq-sharded cache
    # is attended locally per shard (partial softmax, pmax/psum combine) and
    # only the owner shard writes the new token.  Avoids GSPMD's
    # full-cache select/copy lowering of DUS on a sharded dim entirely.
    decode_shard_map: bool = False
    # MoE: dispatch tokens to expert buckets PER BATCH ROW (indices local to
    # each data shard) instead of one global scatter — the global scatter
    # from token-sharded to expert-sharded layouts makes GSPMD all-gather
    # every token to every device.
    moe_row_dispatch: bool = False
    # serving: shard weights tensor/expert-parallel ONLY (resident weights,
    # no FSDP all-gathers).  FSDP amortizes over training batches; at decode
    # it all-gathers every layer's weights per token step.
    serve_tp_only: bool = False
    # dry-run artifact control: the CPU backend legalizes bf16 arithmetic to
    # f32, wrapping the cache DUS in FULL-BUFFER converts that would not
    # exist on the TPU target.  f32 caches sidestep the legalization so the
    # dry-run traffic matches what TPU bf16 caches would do (modulo 2x raw
    # cache bytes, which we report).
    cache_f32: bool = False
    # train remat policy: "full" (baseline: save only layer inputs) or
    # "dots" (save no-batch-dim dot outputs, i.e. the weight-matmul
    # activations; recompute only the cheap elementwise/attention math).
    remat_policy: str = "full"
    # embedding serving precision: "fp32" (baseline oracle: fp32-resident
    # weights, fp32 trunk), "bf16" (weights cast ONCE at load, all matmuls
    # bf16), "int8" (weight-only per-output-channel symmetric int8
    # quantization of every dense/attention projection at load, fp32 scales,
    # fp32 activations, the fused quant-matmul kernel in the trunk — 4x
    # smaller resident weights), or "int8_w8a8" (the int8 tree plus dynamic
    # per-row symmetric int8 activation quantization: every projection
    # contracts int8 x int8 with int32 accumulation, dequantized once in the
    # kernel epilogue — the MXU int8-rate path).  The pool_norm epilogue
    # always accumulates fp32 so served vectors stay fp32 unit vectors
    # within 1e-2 cosine (>= 0.99) of the oracle for the weight-only
    # policies and 2e-2 (>= 0.98) for int8_w8a8.
    embed_dtype: str = "fp32"
    # embedding serving: donate the token/mask device buffers to the jit'd
    # embed (jit donate_argnums) so XLA reuses them instead of allocating
    # fresh HBM per batch.  No-op (with the warning suppressed) on backends
    # that cannot alias, e.g. this CPU container.
    embed_donate: bool = False
    # serving: N > 0 puts an exact-match embedding cache of N entries at
    # the head of the dispatch topology (token-hash keyed LRU, zero-latency
    # TierSpec — repro.core.cache).  Hits serve the stored embedding
    # bitwise at ~zero latency / zero FLOPs; misses fall through to the
    # policy cascade and are admitted on batch completion.  0 = no cache
    # (baseline).
    cache: int = 0
    # serving: optional byte budget for the cache tier (summed embedding
    # nbytes) on top of the entry count; 0 = entries-only bound.
    cache_bytes: int = 0
    # serving fault tolerance: N > 0 arms every submitted query with a
    # relative deadline of N milliseconds — queries still QUEUED past it
    # are swept out (their futures fail with DeadlineExceeded, counted as
    # deadline_misses) instead of serving uselessly late.  0 = no deadline
    # (baseline).
    deadline_ms: int = 0
    # serving fault tolerance: re-dispatch each query of a failed batch up
    # to N times through the normal policy path (survivors fail over to
    # whatever healthy tier the policy ranks first); exhausted attempts
    # fail the future with a structured ServeError.  0 = one attempt,
    # failures terminal (baseline).
    retries: int = 0
    # serving fault tolerance: base exponential backoff (milliseconds)
    # before retry attempt k: backoff * 2^(k-1), slept by the FAILED
    # tier's worker (healthy tiers keep draining).  0 = immediate retry.
    retry_backoff_ms: int = 0
    # serving fault tolerance: trip a tier's circuit breaker after N
    # consecutive batch failures — dispatch routes around the open tier
    # until a half-open probe succeeds.  0 = no breakers (baseline).
    breaker: int = 0
    # serving fault tolerance: how long (milliseconds) a tripped breaker
    # stays open before the half-open recovery probe.  Only meaningful
    # with breaker > 0.
    breaker_cooldown_ms: int = 1000
    # serving overload control: SLO-aware admission at dispatch — arrivals
    # a calibrated fit predicts past their budget, or over every tier's
    # backpressure watermark, are rejected with ServeError(kind="admission")
    # instead of queueing into a guaranteed deadline miss (off = baseline:
    # queue until BUSY).
    admission: bool = False
    # serving overload control: the admission price of turning a query
    # away, against an expected SLO-violation cost of 1.0 — reject when
    # rejecting is cheaper (reject_cost < 1.0); >= 1.0 disables pricing
    # rejections outside brownout shedding, leaving watermarks only.
    reject_cost: float = 0.5
    # serving overload control: fraction of each tier's depth open to NEW
    # arrivals (1.0 = full depth); the band above the watermark stays
    # reserved for retry/failover re-dispatch.  Halved under brownout
    # shedding.
    watermark: float = 1.0
    # serving overload control: three-stage brownout (normal -> degraded ->
    # shedding) on a dispatch-time utilization EWMA — degraded prefers the
    # quantized tier at equal backlog and tightens effective deadlines,
    # shedding also tightens the admission watermark.  Off = baseline.
    brownout: bool = False


FLAGS = PerfFlags()


def set_flags(**kw) -> PerfFlags:
    global FLAGS
    FLAGS = dataclasses.replace(FLAGS, **kw)
    return FLAGS


def reset_flags() -> None:
    global FLAGS
    FLAGS = PerfFlags()


def parse_opt(spec: str) -> dict:
    """'mamba_chunk=16,attn_band_skip=1' -> kwargs dict."""
    out = {}
    for part in filter(None, spec.split(",")):
        k, _, v = part.partition("=")
        k = k.strip()
        if k not in PerfFlags.__dataclass_fields__:
            valid = ", ".join(sorted(PerfFlags.__dataclass_fields__))
            raise ValueError(f"unknown perf flag {k!r}; valid flags: {valid}")
        field = PerfFlags.__dataclass_fields__[k]
        if field.type in ("int", int):
            out[k] = int(v)
        elif field.type in ("float", float):
            out[k] = float(v)
        elif field.type in ("str", str):
            out[k] = v.strip()
        else:
            out[k] = v.strip() in ("1", "true", "True", "yes", "on")
        if k == "embed_dtype":
            # validate the VALUE here too: a typo'd policy must fail at the
            # CLI, not at first backend construction minutes into a run
            from repro.models.quantize import EMBED_DTYPES
            if out[k] not in EMBED_DTYPES:
                raise ValueError(
                    f"unknown embed_dtype {out[k]!r}; valid values: "
                    f"{'|'.join(EMBED_DTYPES)}")
    return out
