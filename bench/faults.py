"""Faults planted in the timed path, for the readings that set the limits of
``correct`` (``bench/proof.py --faults``) and for the tests that see each
come out not correct (``bench/tests/test_checks.py``).

Each fault takes the built engine and wraps the calls its workers make
into every tier's backend, before any traffic:

- ``tokens_shuffled``: each query's tokens reach the device in another
  order;
- ``token_altered``: one token of each query (the middle one) is changed
  to the next id;
- ``half_left_out``: only the first half of each query's tokens is staged
  as real, so the embedding is taken over the rest;
- ``answer_altered``: each batch hands its answers back rotated one query
  out of place (a batch of one gets its answer negated).
"""
from __future__ import annotations

import numpy as np


def _on_tokens(engine, change) -> None:
    """Apply ``change(toks, mask, lengths)`` to every staged batch."""
    for be in engine.backends.values():
        inner = be._tokenize

        def tokenize(queries, seq_len, out=None, inner=inner):
            toks, mask, real, trunc = inner(queries, seq_len, out=out)
            n = mask[:len(queries)].sum(axis=1).astype(int)
            change(toks, mask, n)
            return toks, mask, real, trunc

        be._tokenize = tokenize


def tokens_shuffled(engine) -> None:
    rng = np.random.default_rng(0)

    def change(toks, mask, n):
        for i, k in enumerate(n):
            toks[i, :k] = toks[i, rng.permutation(k)]

    _on_tokens(engine, change)


def token_altered(engine) -> None:
    vocab = engine.backends[next(iter(engine.backends))].cfg.vocab_size

    def change(toks, mask, n):
        for i, k in enumerate(n):
            j = k // 2
            toks[i, j] = toks[i, j] % (vocab - 1) + 1

    _on_tokens(engine, change)


def half_left_out(engine) -> None:
    def change(toks, mask, n):
        for i, k in enumerate(n):
            mask[i, max(1, (k + 1) // 2):] = 0.0

    _on_tokens(engine, change)


def answer_altered(engine) -> None:
    for be in engine.backends.values():
        inner = be.embed_batch_async

        def enqueue(queries, inner=inner):
            fetch = inner(queries)

            def fetched():
                out = fetch()
                if len(out) < 2:
                    return [-x for x in out]
                return out[1:] + out[:1]

            return fetched

        be.embed_batch_async = enqueue


FAULTS = {f.__name__: f for f in (tokens_shuffled, token_altered,
                                  half_left_out, answer_altered)}
