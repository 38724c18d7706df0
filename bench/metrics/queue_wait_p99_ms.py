"""queue_wait_p99_ms: 99th percentile of the time a query waited in the
accelerator tier's queue (its batch's completion less the batch's service
time less its submission, as the engine's batch hook reports them), over
the batches that started in the window."""
from bench.stats import percentile


def read(run):
    waits = [w for b in run.window_batches("NPU") for w in b.queue_wait]
    p = percentile(waits, 99)
    return None if p is None else p * 1e3
