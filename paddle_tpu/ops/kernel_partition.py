"""Running the Pallas kernels inside a sharded program.

XLA cannot partition a Mosaic kernel by itself: lowering a
``pallas_call`` whose operands are sharded raises "Mosaic kernels cannot
be automatically partitioned. Please wrap the call in a shard_map", and
``custom_partitioning`` is not an alternative on this installation (the
libtpu compiler has no partitioner callback: "Custom emitter for
CustomSPMDPartitioning not found"). So a sharded model tells its kernels
where its activations live — a :class:`KernelPartition`, recorded on the
layers by the model's shard plan (``llama_shard_plan``) and carried to
the forward AND the backward kernel as a primitive static, since the
tape calls them as separate primitives — and each array-level kernel
function runs under ``shard_map`` on the per-shard shapes. Batch and
head (rows, for RMSNorm) are independent; sequence and feature are kept
whole on every shard.
"""
from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
from jax.sharding import PartitionSpec


class KernelPartition(NamedTuple):
    """Which mesh axes shard a model's activations, as far as the kernels
    need to know. Hashable: it rides in primitive statics and jit keys."""

    mesh: Any                     # auto_parallel.ProcessMesh
    batch: Optional[str] = None   # mesh axis over the batch (row) dimension
    heads: Optional[str] = None   # mesh axis over attention heads

    def axis_if_divides(self, axis, *dims):
        """``axis`` when it splits every one of ``dims`` evenly, else
        None: that dimension is then whole on every shard, which is
        always correct (XLA reshards around the shard_map)."""
        if axis is None:
            return None
        n = self.mesh.get_dim_size(axis)
        return axis if all(d % n == 0 for d in dims) else None


def shard_kernel(local, partition: KernelPartition, in_specs, out_specs):
    """``local`` (one shard's arrays -> array or tuple of arrays) over
    the partition's mesh. A spec is a tuple of mesh-axis names / None,
    one per dimension; ``out_specs`` is one spec, or a list of them when
    ``local`` returns a tuple."""
    return jax.shard_map(
        local, mesh=partition.mesh.jax_mesh,
        in_specs=tuple(PartitionSpec(*s) for s in in_specs),
        out_specs=(tuple(PartitionSpec(*s) for s in out_specs)
                   if isinstance(out_specs, list)
                   else PartitionSpec(*out_specs)),
        # the kernels' outputs are opaque to the replication checker
        check_vma=False)
