"""npu_batch_p50_ms: median service time of the accelerator tier's batches
in the window (enqueue to results on the host), from the engine's
per-tier batch latencies."""
from bench.stats import percentile


def read(run):
    p = percentile([b.service for b in run.window_batches("NPU")], 50)
    return None if p is None else p * 1e3
