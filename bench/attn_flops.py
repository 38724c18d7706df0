"""Operations and bytes that the served work's attention requires, computed
from shapes, per kind of layer.

The keys each query attends are the architecture module's
(``bench/arch/<arch>.py``: ``layer_kinds`` and ``attended_keys``); a module
without them has global layers only.  A multiply-add is two operations:
the scores q.k^T and the weighted values p.v are 2 x 2 x heads x head_dim
per (query, key) pair.  The bytes are the least any implementation moves:
q, k and v read once and the output written once, per layer.  Padding,
the softmax's elementwise work and masks are not counted.
"""
from __future__ import annotations

from typing import Dict, Iterable, Tuple

from bench import arch


def attention_work(lengths: Iterable[int], cfg: dict, itemsize: int = 2
                   ) -> Dict[str, Tuple[int, int]]:
    """kind of layer -> (operations, bytes) of the attention of sequences
    of these real lengths, summed over the layers of that kind."""
    mod = arch.of(cfg)
    inner = cfg["num_attention_heads"] * cfg["head_dim"]
    kinds = (mod.layer_kinds(cfg) if hasattr(mod, "layer_kinds")
             else {"global": cfg["num_hidden_layers"]})
    out = {k: [0, 0] for k, n in kinds.items() if n}
    for length in lengths:
        n = int(length)
        keys = (mod.attended_keys(n, cfg) if hasattr(mod, "attended_keys")
                else {"global": n * n})
        for kind in out:
            out[kind][0] += kinds[kind] * 4 * inner * keys[kind]
            out[kind][1] += kinds[kind] * 4 * n * inner * itemsize
    return {k: (ops, nbytes) for k, (ops, nbytes) in out.items()}
