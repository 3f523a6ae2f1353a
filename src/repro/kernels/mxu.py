"""Contraction precision of the Pallas kernels' dots.

Mosaic contracts float32 operands in one bfloat16 pass unless a dot asks
for more: a float32 kernel then differs from its float32 oracle by about
1e-2.  A dot with a float32 operand — every dot of a kernel over float32
inputs, and LoRA's dots over its float32 rank-r intermediates (x@A,
g@B^T) — asks for full float32 (`Precision.HIGHEST`).  A dot over
bfloat16 values keeps the single pass, which multiplies them exactly;
the attention kernels hold bfloat16 tiles in float32 registers, so they
pass their input dtype.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def precision(*dtypes):
    """The dot precision for operands of `dtypes`."""
    return jax.lax.Precision.HIGHEST \
        if any(jnp.dtype(d) == jnp.float32 for d in dtypes) else None
