"""Kernel choice by placement.

One serving process may run the same jitted embedder on a TPU tier and on
a host-CPU tier side by side, so ``jax.default_backend()`` cannot say which
implementation a computation needs.  The platform a computation is
*lowered* for can: ``lax.platform_dependent`` traces both branches and each
lowering keeps only the branch of its own platform.  A jit placed on TPU
devices therefore runs the compiled Pallas kernel, and the same jit placed
on CPU devices runs the jnp reference.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax.numpy as jnp
from jax import lax


def by_placement(kernel: Callable, reference: Callable, *args):
    """``kernel(*args)`` where the enclosing computation is lowered for a
    TPU, ``reference(*args)`` on every other platform."""
    return lax.platform_dependent(*args, tpu=kernel, default=reference)


def dot_precision(dtype) -> Optional[lax.Precision]:
    """Precision of a kernel's matmul on operands of ``dtype``: fp32
    operands contract in fp32 (the TPU's default would round them to one
    bf16 pass), narrower ones at the MXU's native precision."""
    return lax.Precision.HIGHEST if dtype == jnp.float32 else None


__all__ = ["by_placement", "dot_precision"]
