"""attn_roofline: the least time the chip could take for the attention that
the accelerator tier's real tokens require (per kind of layer, the larger
of its operations over the bf16 peak and its bytes over HBM bandwidth,
``bench/attn_flops.py``) over the device time of the flash-attention
kernel's ops in the traced window."""
from bench.attn_flops import attention_work

# the Pallas kernel's op in the trace (``bench.trace.op_label``): a Mosaic
# custom call whose output is the token-major (B, S, heads x head_dim)
# attention and whose first operand is the scalar-prefetched (B,) count of
# valid keys; no other kernel of the embed step has that signature
KERNEL = r"^custom-call:tpu_custom_call \w+\[\d+,\d+,{inner}\] <- s32\[\d+\]$"
# bytes per q, k, v and output element, by serving precision
ITEMSIZE = {"bf16": 2}


def read(run):
    if run.trace is None:
        return None
    cfg = run.config
    inner = cfg["num_attention_heads"] * cfg["head_dim"]
    secs, count = run.trace.ops_matching(KERNEL.format(inner=inner))
    if not count or secs <= 0:
        return None
    lengths = [n for b in run.window_batches("NPU") for n in b.lengths]
    work = attention_work(lengths, cfg, ITEMSIZE.get(cfg["precision"], 4))
    least = sum(max(ops / run.peak("bf16_flops"),
                    nbytes / run.peak("hbm_bytes_per_s"))
                for ops, nbytes in work.values())
    return 100.0 * least / secs
