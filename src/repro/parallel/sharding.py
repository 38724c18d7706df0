"""PartitionSpec rules for params, optimizer state, activations and caches.

Scheme (DESIGN.md §5):
* ``model`` axis — tensor/expert parallelism: d_ff-like dims, vocab of the
  embedding table, expert dim of MoE weights, d_inner of mamba.
* ``data`` axis — FSDP: the d_model-like dim of every weight is sharded over
  ``data`` and all-gathered per layer; the batch dim of activations also runs
  over ``data`` (plus ``pod`` when present).
* ``pod`` axis — data parallelism across pods (batch only; params replicated
  across pods — they already fit at 256-chip FSDPxTP).
* decode KV caches shard their *sequence* dim over ``model`` (flash-decode
  style partial-softmax via GSPMD reductions); ``long_500k`` (batch=1) shards
  sequence over ``('data','model')`` jointly.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.tree_util import DictKey, tree_map_with_path

from repro.configs.base import ModelConfig, ShapeConfig

STACK_KEYS = ("blocks", "enc_blocks", "dec_blocks")


def dp_axes(mesh) -> Tuple[str, ...]:
    """Axes the batch dim is sharded over."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


# ---------------------------------------------------------------------------
# param rules
# ---------------------------------------------------------------------------

_RULES: Dict[str, Tuple] = {
    # name -> spec for the *unstacked* shape
    "embed": ("model", "data"),
    "lm_head": ("data", "model"),
    "wq": ("data", "model"),
    "wk": ("data", "model"),
    "wv": ("data", "model"),
    "wo": ("model", "data"),
    "bq": ("model",),
    "bk": ("model",),
    "bv": ("model",),
    "router": ("data", None),
    "in_proj": ("data", "model"),
    "x_proj": ("model", None),
    "dt_proj": (None, "model"),
    "dt_bias": ("model",),
    "A_log": ("model", None),
    "D": ("model",),
    "conv_w": (None, "model"),
    "conv_b": ("model",),
    "scale": (None,),
    "bias": (None,),
}

_MOE_RULES: Dict[str, Tuple] = {
    # 3-D expert-stacked weights: experts over `model` (expert parallelism)
    "w_gate": ("model", "data", None),
    "w_up": ("model", "data", None),
    "w_down": ("model", None, "data"),
}

_MLP_RULES: Dict[str, Tuple] = {
    "w_gate": ("data", "model"),
    "w_up": ("data", "model"),
    "w_down": ("model", "data"),
    "w_in": ("data", "model"),
    "w_out": ("model", "data"),
}


# fallback when the expert count does not divide the model axis (e.g.
# granite's 40 experts on a 16-way axis): shard the FFN dims instead.
_MOE_FALLBACK: Dict[str, Tuple] = {
    "w_gate": (None, "data", "model"),
    "w_up": (None, "data", "model"),
    "w_down": (None, "model", "data"),
}


def _path_names(path) -> Tuple[str, ...]:
    return tuple(p.key for p in path if isinstance(p, DictKey))


def _axis_size(mesh, entry) -> int:
    if entry is None:
        return 1
    axes = entry if isinstance(entry, tuple) else (entry,)
    n = 1
    for a in axes:
        n *= dict(zip(mesh.axis_names, mesh.devices.shape))[a]
    return n


def _fit(mesh, shape, rule) -> Tuple:
    """Drop spec entries whose mesh-axis size does not divide the dim.
    jit input shardings (unlike intermediates) require exact divisibility."""
    return tuple(
        (a if d % _axis_size(mesh, a) == 0 else None)
        for d, a in zip(shape, rule))


def param_spec(path, leaf, mesh, mode: str = "train") -> P:
    names = _path_names(path)
    name = names[-1]
    stacked = any(n in STACK_KEYS for n in names)
    eff_ndim = leaf.ndim - (1 if stacked else 0)
    moe = name in _MOE_RULES and eff_ndim == 3
    if moe:
        rule = _MOE_RULES[name]
    elif name in _MLP_RULES:
        rule = _MLP_RULES[name]
    elif name in _RULES:
        rule = _RULES[name]
    else:
        rule = (None,) * eff_ndim
    rule = tuple(rule)[:eff_ndim]
    rule = rule + (None,) * (eff_ndim - len(rule))
    if mode == "serve":
        # §Perf: serving keeps weights RESIDENT — tensor/expert parallelism
        # only.  FSDP's per-layer weight all-gathers amortize over large
        # training batches but dominate the decode collective term
        # (measured 17.9 GB/step = 359 ms on qwen2-72b decode_32k).
        rule = tuple(None if a == "data" else a for a in rule)
    if stacked:
        rule = (None,) + rule
    rule = _fit(mesh, leaf.shape, rule)
    if moe and rule[1 if stacked else 0] is None:
        # expert axis didn't divide: shard the FFN dims instead
        alt = _MOE_FALLBACK[name]
        if mode == "serve":
            alt = tuple(None if a == "data" else a for a in alt)
        alt = ((None,) + alt) if stacked else alt
        rule = _fit(mesh, leaf.shape, alt)
    return P(*rule)


def param_pspecs(mesh, params_shape, mode: str = "train") -> Any:
    """Pytree of PartitionSpec matching a param (or opt-state) pytree."""
    return tree_map_with_path(
        lambda p, l: param_spec(p, l, mesh, mode), params_shape)


def param_shardings(mesh, params_shape, mode: str = "train") -> Any:
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        param_pspecs(mesh, params_shape, mode))


def serve_embed_shardings(mesh) -> Tuple[NamedSharding, NamedSharding]:
    """(param sharding, batch sharding) for the data-parallel embed path.

    Weights are RESIDENT and replicated on every device of the serving
    mesh, so per-batch weight all-gathers never enter the service-time term
    the paper's Eq. 12 prices; the (B, S) token/mask batch is sharded over
    the data axes.  The batch sharding also shards the (B, D) output, whose
    trailing dim is always replicated.
    """
    dp = dp_axes(mesh)
    b = dp if len(dp) > 1 else (dp[0] if dp else None)
    return NamedSharding(mesh, P()), NamedSharding(mesh, P(b, None))


# ---------------------------------------------------------------------------
# activation / batch / cache rules
# ---------------------------------------------------------------------------

def batch_pspecs(cfg: ModelConfig, shape: ShapeConfig, mesh) -> Dict[str, P]:
    """Specs for the input batch dict of a step (see steps.inputs)."""
    dp = dp_axes(mesh)
    dps = dp if len(dp) > 1 else (dp[0] if dp else None)
    big_batch = shape.global_batch >= _dp_size(mesh)
    b = dps if big_batch else None
    specs: Dict[str, P] = {}
    if shape.kind == "train":
        specs["tokens"] = P(b, None)
        specs["labels"] = P(b, None)
    elif shape.kind == "prefill":
        specs["tokens"] = P(b, None)
    else:  # decode
        specs["token"] = P(b)
    if shape.kind != "decode":
        if cfg.frontend == "vision":
            specs["patches"] = P(b, None, None)
        if cfg.frontend == "audio":
            specs["frames"] = P(b, None, None)
    return specs


def _dp_size(mesh) -> int:
    n = 1
    for a in dp_axes(mesh):
        n *= mesh.shape[a]
    return n


def cache_pspecs(cfg: ModelConfig, shape: ShapeConfig, mesh, cache_shape) -> Any:
    """Specs for the decode cache pytree (built via jax.eval_shape)."""
    dp = dp_axes(mesh)
    dps = dp if len(dp) > 1 else (dp[0] if dp else None)
    big_batch = shape.global_batch >= _dp_size(mesh)
    b = dps if big_batch else None
    # batch=1 long-context: shard the cache sequence over every axis we have
    seq_axes = ("model",) if big_batch else tuple(dp) + ("model",)
    seq = seq_axes if len(seq_axes) > 1 else seq_axes[0]

    def spec(path, leaf):
        name = _path_names(path)[-1]
        if name in ("k", "v"):            # (L, B, S, KV, hd)
            rule = (None, b, seq, None, None)
        elif name in ("cross_k", "cross_v"):  # (L, B, F, KV, hd)
            rule = (None, b, None, None, None)
        elif name == "kpos":              # (S,)
            rule = (seq,)
        elif name == "ssm":               # (L, B, DI, N)
            rule = (None, b, "model", None)
        elif name == "conv":              # (L, B, CK-1, DI)
            rule = (None, b, None, "model")
        else:
            return P()                    # pos scalar
        return P(*_fit(mesh, leaf.shape, rule))

    return tree_map_with_path(spec, cache_shape)


def hidden_constraint(mesh, batch_sharded: bool):
    """with_sharding_constraint for the residual stream inside layer scans.

    Keeps the hidden (B, S, D) sharded batch-over-dp, D replicated — GSPMD's
    natural layout between FSDP all-gathers."""
    dp = dp_axes(mesh)
    dps = dp if len(dp) > 1 else (dp[0] if dp else None)
    b = dps if batch_sharded else None
    sh = NamedSharding(mesh, P(b, None, None))

    def constrain(h):
        if h.ndim == 3:
            return jax.lax.with_sharding_constraint(h, sh)
        return h

    return constrain


def logits_pspec(mesh, batch_sharded: bool) -> P:
    dp = dp_axes(mesh)
    dps = dp if len(dp) > 1 else (dp[0] if dp else None)
    b = dps if batch_sharded else None
    return P(b, None, "model")
