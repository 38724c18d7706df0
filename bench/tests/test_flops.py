"""The yardstick's operation and byte counts against hand counts."""
import json
from pathlib import Path

from bench import flops

# two layers, d = 8, two heads of 4, FFN 16
CFG = dict(arch="prenorm_encoder", num_hidden_layers=2, hidden_size=8,
           num_attention_heads=2, head_dim=4, intermediate_size=16,
           pooling="cls")
BGE = json.loads((Path(__file__).resolve().parents[1] / "configs"
                  / "bge-large-zh-v1.5.json").read_text())


def test_encoder_flops_matches_a_hand_count():
    L = 3
    # per layer, (rows x inner x cols) of every matmul, 2 ops per MAC:
    per_layer = 2 * (
        3 * (L * 8 * 8)          # x W_q, x W_k, x W_v: (3x8) @ (8x8)
        + L * 8 * 8              # o W_o: (3x8) @ (8x8)
        + L * 8 * 16             # b W_in: (3x8) @ (8x16)
        + L * 16 * 8             # g W_out: (3x16) @ (16x8)
        + 2 * (L * 4 * L)        # q k^T per head: (3x4) @ (4x3), 2 heads
        + 2 * (L * L * 4))       # p v per head: (3x3) @ (3x4), 2 heads
    assert flops.encoder_flops(L, CFG) == 2 * per_layer


def test_attention_grows_with_the_square_of_the_real_length():
    # attention at L = 2: layers x (qk + pv) x 2 ops x L^2 x inner
    attn = 2 * 2 * 2 * (2 * 2) * 8
    # doubling the length doubles everything and attention once more
    assert flops.encoder_flops(4, CFG) == 2 * flops.encoder_flops(2, CFG) \
        + 2 * attn


def test_batch_flops_sums_the_queries():
    assert flops.batch_flops([3, 5, 3], CFG) == (
        2 * flops.encoder_flops(3, CFG) + flops.encoder_flops(5, CFG))


def test_pool_norm_work_cls_needs_one_row():
    ops, nbytes = flops.pool_norm_work([10, 20], CFG, in_itemsize=2)
    # per query: normalise 8 values (2 x 8 + 8 + 1), read one bf16 row,
    # write one float32 row
    assert ops == 2 * (3 * 8 + 1)
    assert nbytes == 2 * (8 * 2 + 8 * 4)


def test_pool_norm_work_mean_needs_every_real_row():
    cfg = dict(CFG, pooling="mean")
    ops, nbytes = flops.pool_norm_work([10], cfg, in_itemsize=2)
    # sum 10 rows and divide (10 x 8 + 8), normalise (3 x 8 + 1)
    assert ops == 10 * 8 + 8 + 3 * 8 + 1
    # 10 bf16 rows, 10 float32 mask values, one float32 output row
    assert nbytes == 10 * 8 * 2 + 10 * 4 + 8 * 4


def test_bge_counts_are_pinned():
    """A (64, 75) batch of bge-large-zh-v1.5, as the counts read before the
    encoder's count moved into its architecture module."""
    assert flops.batch_flops([75] * 64, BGE) == 2934492364800
    assert flops.pool_norm_work([75] * 64, BGE) == (196672, 393216)
