"""The plain reference against the program's embedder, on the CPU at the
small width: the same weights from the seed, the same vectors in float32,
and a run in a lower precision fails the float32 tolerance."""
import json
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from bench import arch, reference
from bench.conftest import program_config as program
from bench.conftest import small_cut

CONFIGS = sorted((Path(__file__).resolve().parents[1] / "configs")
                 .glob("*.json"))
# the configurations of the pre-LN block, whose leaves the program names as
# ``test_weights_are_the_programs_own`` reads them
PRENORM = [p for p in CONFIGS
           if json.loads(p.read_text())["arch"] == "prenorm_encoder"]
SEED = 2 ** 31 + 5
# float32 against float32: the program's chunked online-softmax attention
# and fused reductions sum in another order than the reference, about 2e-7
# apart at this width; 1e-5 leaves fifty times that and stays a hundred
# times below what bf16 serving reads (about 3e-3 here)
FP32_TOL = 1e-5


def small_cfg(path):
    return small_cut(json.loads(Path(path).read_text()))


def queries(cfg, n=12):
    rng = np.random.default_rng(1)
    lens = rng.integers(3, 40, size=n)
    return [rng.integers(1, cfg["vocab_size"], size=k).astype(np.int32)
            for k in lens]


def served_params(cfg):
    """The program's parameters as the benchmark serves them: its own
    initialisation, with the configuration's token table."""
    import jax

    from bench.run import program_init

    mod = arch.of(cfg)
    init = mod.seeded_init(getattr(*program_init(mod)),
                           cfg["embedding_init_std"])
    return init(jax.random.PRNGKey(SEED), program(cfg))


def program_embed(cfg, qs, dtype):
    from repro.models import embedder
    from repro.models.quantize import serve_params

    pcfg = program(cfg)
    params = served_params(cfg)
    served, cdt = serve_params(params, dtype)
    S = max(len(q) for q in qs)
    toks = np.zeros((len(qs), S), np.int32)
    mask = np.zeros((len(qs), S), np.float32)
    for i, q in enumerate(qs):
        toks[i, :len(q)], mask[i, :len(q)] = q, 1.0
    return np.asarray(embedder.embed(served, pcfg, jnp.asarray(toks),
                                     jnp.asarray(mask), compute_dtype=cdt))


@pytest.mark.parametrize("path", PRENORM, ids=lambda p: p.stem)
def test_weights_are_the_programs_own(path):
    cfg = small_cfg(path)
    params = served_params(cfg)
    w = reference.init_weights(cfg, SEED)
    np.testing.assert_array_equal(w["embed"], params["embed"])
    for name in ("wq", "wk", "wv", "wo"):
        np.testing.assert_array_equal(w["layers"][name],
                                      params["blocks"]["attn"][name])
    for name in ("w_in", "w_out"):
        np.testing.assert_array_equal(w["layers"][name],
                                      params["blocks"]["ffn"][name])


@pytest.mark.parametrize("path", CONFIGS, ids=lambda p: p.stem)
def test_float32_program_agrees_and_bf16_fails(path):
    cfg = small_cfg(path)
    qs = queries(cfg)
    ref = reference.embed(cfg, SEED, qs, block=8)
    gap32 = reference.l2_gaps(program_embed(cfg, qs, "fp32"), ref).max()
    gap16 = reference.l2_gaps(program_embed(cfg, qs, "bf16"), ref).max()
    assert gap32 < FP32_TOL
    assert gap16 > 10 * FP32_TOL


@pytest.mark.parametrize("path", CONFIGS, ids=lambda p: p.stem)
def test_the_answer_depends_on_the_tokens(path):
    """A query's tokens, not only its length, set its answer: other tokens
    of one length, or its own in another order, move it far past what
    bf16 serving does."""
    cfg = small_cfg(path)
    qs = queries(cfg, n=8)
    rng = np.random.default_rng(2)
    other = [rng.integers(1, cfg["vocab_size"], size=len(q)).astype(np.int32)
             for q in qs]
    shuffled = [q[rng.permutation(len(q))] for q in qs]
    ref = reference.embed(cfg, SEED, qs, block=8)
    bf16 = reference.l2_gaps(program_embed(cfg, qs, "bf16"), ref).max()
    for moved in (other, shuffled):
        gaps = reference.l2_gaps(reference.embed(cfg, SEED, moved, block=8),
                                 ref)
        assert gaps.min() > 10 * bf16


def test_padding_and_blocking_change_nothing():
    cfg = small_cfg(CONFIGS[0])
    qs = queries(cfg, n=10)
    a = reference.embed(cfg, SEED, qs, block=4)
    b = np.concatenate([reference.embed(cfg, SEED, [q], block=1)
                        for q in qs])
    np.testing.assert_allclose(a, b, atol=1e-6)
    np.testing.assert_allclose(np.linalg.norm(a, axis=-1), 1.0, atol=1e-6)


def test_mixed_lengths_with_a_long_input_block_as_one_at_a_time(monkeypatch):
    """Longest first, each block padded to its own longest input and cut to
    fewer rows where a long input's attention scores would pass the budget:
    the vectors are those of one query at a time, in the order given."""
    cfg = small_cfg(CONFIGS[0])
    qs = queries(cfg, n=10)
    long = np.random.default_rng(3).integers(
        1, cfg["vocab_size"], size=600).astype(np.int32)
    qs.insert(4, long)
    # the scores of two rows of the long input fit, not three
    monkeypatch.setattr(reference, "SCORE_BUDGET",
                        2 * 4 * cfg["num_attention_heads"] * 600 ** 2)
    mod = arch.of(cfg)
    forward, shapes = mod.forward, []

    def spy(c):
        fwd = forward(c)

        def call(w, toks, mask):
            shapes.append(toks.shape)
            return fwd(w, toks, mask)

        return call

    monkeypatch.setattr(mod, "forward", spy)
    blocked = reference.embed(cfg, SEED, qs, block=8)
    lens = sorted((len(q) for q in qs), reverse=True)
    assert shapes == [(2, 600), (8, lens[2]), (8, lens[10])]
    one = np.concatenate([reference.embed(cfg, SEED, [q], block=1)
                          for q in qs])
    assert reference.l2_gaps(blocked, one).max() < FP32_TOL
