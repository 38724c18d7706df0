"""embed_step_mfu: the operations the accelerator tier's real tokens
require (``bench/flops.py``, batches started in the window) over the summed
device time of the embed step's executions in the traced window, over the
chip's bf16 peak."""
from bench.flops import batch_flops

# the embed step's program (``sharded_backend.embed_step``, a jit of the
# shard_map'd ``local``) on the trace's ``XLA Modules`` line
STEP = r"^jit_local\("


def read(run):
    if run.trace is None:
        return None
    secs, count = run.trace.modules_matching(STEP)
    if not count or secs <= 0:
        return None
    need = batch_flops((n for b in run.window_batches("NPU")
                        for n in b.lengths), run.config)
    return 100.0 * need / secs / run.peak("bf16_flops")
