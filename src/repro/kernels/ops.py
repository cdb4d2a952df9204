"""jit'd wrappers: execute merge plans / layout transforms with the kernels.

``merge_blocks_device`` is the TPU path of the paper's §4 merge: block data
already on device in log order (the chunked layout), output merged-cuboid
buffers — one pack_rows kernel launch, compiled for the TPU.  CPU tests pass
interpret=True.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..core.merge import MergePlan
from .pack_blocks import pack_rows
from .ref import plan_row_tables

__all__ = ["merge_blocks_device", "split_merged"]


def merge_blocks_device(plan: MergePlan, data: dict, *,
                        interpret: bool = False) -> list:
    """Execute ``plan`` on device.  ``data``: block_id -> array (block
    shape).  Returns the merged buffers (cluster order)."""
    width, src_rows, dst_rows, total_dst, src_off = plan_row_tables(plan)
    order = sorted(src_off, key=lambda k: src_off[k])
    flat_src = jnp.concatenate(
        [jnp.asarray(data[bid]).reshape(-1) for bid in order])
    packed = pack_rows(flat_src, jnp.asarray(src_rows),
                       jnp.asarray(dst_rows),
                       n_dst_rows=total_dst // width, width=width,
                       interpret=interpret)
    return split_merged(plan, packed.reshape(-1))


def split_merged(plan: MergePlan, flat_dst: jax.Array) -> list:
    out = []
    pos = 0
    for cl in plan.clusters:
        v = cl.cuboid.volume
        out.append(flat_dst[pos:pos + v].reshape(cl.cuboid.shape))
        pos += v
    return out
