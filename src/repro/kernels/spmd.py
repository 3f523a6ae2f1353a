"""Pallas calls inside a program sharded over a device mesh.

The chip's compiler cannot partition a Mosaic kernel: under a mesh whose
batch axes span several devices, every kernel call must run inside a
shard_map.  The engines trace under their mesh (runtime.sharding
`under_mesh`), and `per_batch_shard` reads it from there: each kernel
then runs on its device's slice of the leading (batch-like) axis.  With
no mesh, or a mesh of one device, the call is left as it is.
"""

from __future__ import annotations

import math

import jax
from jax.sharding import PartitionSpec as P

from repro.runtime.sharding import FSDP_AXES


def per_batch_shard(fn, *args, split):
    """fn(*args) on each device's slice of the leading axis.

    split: one bool per arg — True where the arg's leading axis is the
    batch axis, False where the arg is replicated (weights, scalars).
    Every output of fn is batch-split along its leading axis, so a
    reduction over the batch comes back as one partial per shard.  Where
    the leading size does not divide over the mesh, every device runs the
    whole call."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return fn(*args)
    axes = tuple(a for a in FSDP_AXES
                 if a in mesh.axis_names and mesh.shape[a] > 1)
    if not axes:
        return fn(*args)
    lead = next(a.shape[0] for a, s in zip(args, split) if s)
    spec = P(axes) if lead % math.prod(mesh.shape[a] for a in axes) == 0 \
        else P()
    return jax.shard_map(fn, in_specs=tuple(spec if s else P()
                                            for s in split),
                         out_specs=spec, check_vma=False)(*args)
