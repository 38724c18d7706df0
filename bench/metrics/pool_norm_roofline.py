"""pool_norm_roofline: the least time the chip could take for the pooling
and L2 normalisation that the accelerator tier's queries require (the
larger of their operations over the bf16 peak and their bytes over HBM
bandwidth, ``bench/flops.py``) over the device time of the ``pool_norm``
kernel in the traced window."""
from bench.flops import pool_norm_work

# the Pallas kernel's op in the trace (``bench.trace.op_label``): a Mosaic
# custom call from a (B, S, d) operand to a float32 (B, d) output, which on
# the bf16 path no other kernel is
KERNEL = r"^custom-call:tpu_custom_call f32\[\d+,{d}\] <- \w+\[\d+,\d+,{d}\]$"
# bytes per hidden-state element the kernel is handed, by serving precision
ITEMSIZE = {"bf16": 2}


def read(run):
    # mean pooling's (B, S, d) operand sits in on-chip memory (memory space
    # S(1) in its layout), and the peaks table gives no bandwidth for that:
    # against HBM bandwidth the share reads about 195%
    if run.trace is None or run.config["pooling"] != "cls":
        return None
    secs, count = run.trace.ops_matching(
        KERNEL.format(d=run.config["hidden_size"]))
    if not count or secs <= 0:
        return None
    lengths = [n for b in run.window_batches("NPU") for n in b.lengths]
    ops, nbytes = pool_norm_work(
        lengths, run.config, ITEMSIZE.get(run.config["precision"], 4))
    least = max(ops / run.peak("bf16_flops"),
                nbytes / run.peak("hbm_bytes_per_s"))
    return 100.0 * least / secs
