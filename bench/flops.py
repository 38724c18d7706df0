"""Operations and bytes the served work requires, computed from shapes.

An encoder's matmul count is its architecture module's
(``bench/arch/<arch>.py``, ``encoder_flops``); pooling and normalisation
are counted here.  A multiply-add is two operations.  Padding, elementwise
work, norms and the embedding gather are not counted: these are the
operations the real tokens require, not what an implementation happens to
execute.
"""
from __future__ import annotations

from typing import Iterable, Tuple

from bench import arch


def encoder_flops(length: int, cfg: dict) -> int:
    """Matmul operations of one sequence of ``length`` real tokens."""
    return arch.of(cfg).encoder_flops(length, cfg)


def batch_flops(lengths: Iterable[int], cfg: dict) -> int:
    return sum(encoder_flops(int(n), cfg) for n in lengths)


def pool_norm_work(lengths: Iterable[int], cfg: dict, in_itemsize: int = 2
                   ) -> Tuple[int, int]:
    """(operations, bytes) that pooling and L2 normalisation require.

    CLS pooling needs one row of hidden states per query; mean pooling needs
    every real row and its mask, a sum over the rows and a divide by their
    count (rows * d + d).  Normalising a ``d``-vector is a sum of squares
    (2d), a square root and a divide (d + 1).  The output is one float32
    vector per query.
    """
    d = cfg["hidden_size"]
    mean = cfg["pooling"] == "mean"
    ops = nbytes = 0
    for n in lengths:
        rows = int(n) if mean else 1
        ops += (rows * d + d if mean else 0) + 3 * d + 1
        nbytes += rows * d * in_itemsize + (rows * 4 if mean else 0) + d * 4
    return ops, nbytes
