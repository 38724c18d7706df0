"""The ModernBERT architecture module (``bench/arch/modernbert.py``) and its
cell, on the CPU at the module's small cut: the reference's weights are the
program's own, its operation count is the hand count, and the cell runs
correct through the harness, where int8 serving and a planted fault do
not."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench import arch, flops, reference, run
from bench.attn_flops import attention_work
from bench.conftest import program_config, small_cut, write_json
from bench.faults import FAULTS

ROOT = Path(__file__).resolve().parents[2]
CONFIG = ROOT / "bench" / "configs" / "modernbert-large.json"
CELL = "modernbert.ingest_8k"
SEED = 2 ** 31 + 417


def small_cfg():
    return small_cut(json.loads(CONFIG.read_text()))


def test_weights_are_the_programs_own():
    import jax

    from bench.run import program_init

    cfg = small_cfg()
    mod = arch.of(cfg)
    init = mod.seeded_init(getattr(*program_init(mod)),
                           cfg["embedding_init_std"])
    params = init(jax.random.PRNGKey(SEED), program_config(cfg))
    w = reference.init_weights(cfg, SEED)
    np.testing.assert_array_equal(w["embed"], params["embed"])
    for name in ("wq", "wk", "wv", "wo"):
        np.testing.assert_array_equal(w["layers"][name],
                                      params["blocks"]["attn"][name])
    for name in ("w_in", "w_out"):
        np.testing.assert_array_equal(w["layers"][name],
                                      params["blocks"]["ffn"][name])
    # no projection or norm carries a bias
    assert not any("bias" in str(path) for path, _ in
                   jax.tree_util.tree_flatten_with_path(params)[0])


def test_encoder_flops_is_the_hand_count():
    """At 40 tokens with a band of |i - j| <= 8: query i attends
    min(39, i + 8) - max(0, i - 8) + 1 keys on the local layers (1, 2),
    every one of the 40 on the global layers (0, 3)."""
    cfg = small_cfg()
    n, d, inner, ffn = 40, 128, 128, 256
    band = sum(min(n - 1, i + 8) - max(0, i - 8) + 1 for i in range(n))
    assert band == 40 * 17 - 2 * sum(range(1, 9))
    per_layer = 2 * n * d * inner * 4 + 2 * n * d * ffn * 3
    want = 4 * per_layer + 2 * 4 * inner * n * n + 2 * 4 * inner * band
    assert flops.encoder_flops(n, cfg) == want
    assert flops.batch_flops([n, n], cfg) == 2 * want
    # the attention's share, per kind of layer, for the kernel's roofline
    work = attention_work([n], cfg)
    assert work["global"] == (2 * 4 * inner * n * n, 2 * 4 * n * inner * 2)
    assert work["local"] == (2 * 4 * inner * band, 2 * 4 * n * inner * 2)


@pytest.fixture
def doc_root(bench_root):
    """The checkout at the small cut, its documents filling the cut's
    context as the cell's fill 8,192 tokens."""
    cfg = small_cfg()
    mix = bench_root / "bench" / "traffic" / "doc_8k.json"
    m = json.loads(mix.read_text())
    n = cfg["max_position_embeddings"]
    m["lengths"] = {"dist": "fixed", "value": n, "lo": n, "hi": n}
    write_json(mix, m)
    return bench_root


def test_the_cell_is_correct_and_int8_and_a_fault_are_not(doc_root,
                                                          small_program):
    ok = run.run_cell(doc_root, CELL, SEED, 1.0, False, require_tpu=False)
    assert ok["correct"] is True, ok["check"]
    assert set(ok["metrics"]) == {"setup_s", "ingest_tokens_s"}
    low = run.run_cell(doc_root, CELL, SEED, 1.0, False, require_tpu=False,
                       precision="int8")
    assert low["correct"] is False, low["check"]
    bad = run.run_cell(doc_root, CELL, SEED, 1.0, False, require_tpu=False,
                       fault=FAULTS["tokens_shuffled"])
    assert bad["correct"] is False, bad["check"]
    assert bad["check"]["max_l2_gap"]["value"] > \
        bad["check"]["max_l2_gap"]["limit"]
