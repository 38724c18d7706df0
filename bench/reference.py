"""The plain float32 reference of the served encoder.

The equations and the weights are the configuration's architecture
module's (``bench/arch/<arch>.py``, named by the file's ``"arch"`` key),
which is written from the configuration file alone and imports nothing of
the program.  This module runs it over a sample of queries.
"""
from __future__ import annotations

from typing import Dict, Sequence

import numpy as np

from bench import arch

# bytes of float32 attention scores (rows x heads x S x S) that one block
# of the reference may hold: a block of long inputs has fewer rows
SCORE_BUDGET = 2 ** 30


def init_weights(cfg: dict, seed: int) -> Dict:
    """float32 weights of the configuration, remade from ``seed``."""
    return arch.of(cfg).init_weights(cfg, seed)


def embed(cfg: dict, seed: int, queries: Sequence[np.ndarray],
          block: int = 32, weights=None) -> np.ndarray:
    """Reference embeddings of token-id arrays, in the order given.

    The queries run longest first, in blocks padded to the longest query of
    the block: ``block`` rows, or as many as keep the block's attention
    scores within ``SCORE_BUDGET``, and at least one.  A block with fewer
    queries than rows is filled with rows of one token."""
    import jax
    import jax.numpy as jnp

    if weights is None:
        weights = init_weights(cfg, seed)
    fwd = arch.of(cfg).forward(cfg)
    heads = cfg["num_attention_heads"]
    order = np.argsort([-len(q) for q in queries], kind="stable")
    blocks = []
    i = 0
    while i < len(order):
        S = len(queries[order[i]])
        rows = max(1, min(block, SCORE_BUDGET // (4 * heads * S * S)))
        part = order[i:i + rows]
        toks = np.zeros((rows, S), np.int32)
        mask = np.zeros((rows, S), np.float32)
        mask[:, 0] = 1.0            # filler rows keep one real token
        for r, j in enumerate(part):
            toks[r, :len(queries[j])] = queries[j]
            mask[r, :len(queries[j])] = 1.0
        with jax.default_matmul_precision("highest"):
            e = fwd(weights, jnp.asarray(toks), jnp.asarray(mask))
        blocks.append(np.asarray(e)[:len(part)])
        i += rows
    done = np.concatenate(blocks)
    out = np.empty_like(done)
    out[order] = done
    return out


def l2_gaps(served: np.ndarray, ref: np.ndarray) -> np.ndarray:
    """Per-query L2 distance between served and reference unit vectors."""
    return np.linalg.norm(np.asarray(served, np.float64)
                          - np.asarray(ref, np.float64), axis=-1)
