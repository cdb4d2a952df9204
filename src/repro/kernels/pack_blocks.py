"""TPU pack/merge kernel — the paper's block-merge as an on-device copy
engine.

The merge (Alg. 1's final loop) and the read-side linearization are both
"move contiguous runs between two flat buffers" problems.  ``ops.py`` lowers
a MergePlan to a *row table*: both buffers are viewed as (rows, W) with W =
the largest common contiguous width, and each table entry copies one W-wide
row ``dst[dst_row[i]] = src[src_row[i]]``.

TPU mapping: the row tables are scalar-prefetched (SMEM), at most
``ROWS_PER_CALL`` rows per launch; both data buffers stay in HBM
(memory_space=ANY) and each grid step is one HBM -> HBM DMA of a row.  A row copy is blind to dtype, so rows move as 32-bit words viewed as
(rows, 1, words): one row per (1, 128-lane) tile, which the DMA engine slices
for any dtype.  A compiled row must therefore span whole 128-lane tiles
(``ROW_ALIGN_BYTES``); narrower rows are refused with a ``ValueError``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["pack_rows"]

#: a compiled row DMA moves whole tiles of 128 lanes of 32-bit words
ROW_ALIGN_BYTES = 128 * 4
#: rows per kernel launch: both int32 row tables are scalar-prefetched into
#: SMEM (1 MiB on v5e, and the compile fails past it), so longer tables are
#: copied in several launches of at most this many rows (256 KiB of tables)
ROWS_PER_CALL = 32768


def _pack_kernel(src_rows_ref, dst_rows_ref, src_ref, zeros_ref, dst_ref,
                 sem):
    del zeros_ref                       # aliased to dst_ref
    i = pl.program_id(0)
    cp = pltpu.make_async_copy(src_ref.at[pl.ds(src_rows_ref[i], 1)],
                               dst_ref.at[pl.ds(dst_rows_ref[i], 1)], sem)
    cp.start()
    cp.wait()


def _to_words(x2: jax.Array) -> jax.Array:
    """(R, W) of a 1-, 2- or 4-byte dtype -> (R, W * itemsize / 4) uint32."""
    rows, width = x2.shape
    per_word = 4 // x2.dtype.itemsize
    if per_word > 1:
        x2 = x2.reshape(rows, width // per_word, per_word)
    return jax.lax.bitcast_convert_type(x2, jnp.uint32).reshape(rows, -1)


def _from_words(w: jax.Array, dtype, width: int) -> jax.Array:
    return jax.lax.bitcast_convert_type(w, dtype).reshape(w.shape[0], width)


@functools.partial(jax.jit,
                   static_argnames=("n_dst_rows", "width", "interpret"))
def pack_rows(src: jax.Array, src_rows: jax.Array, dst_rows: jax.Array,
              *, n_dst_rows: int, width: int,
              interpret: bool = False) -> jax.Array:
    """Copy rows of ``src`` (viewed as (-1, width)) into a fresh
    (n_dst_rows, width) buffer at ``dst_rows``.

    ``src_rows``/``dst_rows``: int32 (R,) row tables.  Rows not named in
    ``dst_rows`` are zero.  A row must be a whole number of 32-bit words,
    and compiled (``interpret=False``) a multiple of ``ROW_ALIGN_BYTES``;
    ``interpret=True`` runs the same kernel in the Pallas interpreter (CPU
    tests) at any word-multiple width.
    """
    dtype = jnp.dtype(src.dtype)
    row_bytes = width * dtype.itemsize
    if src.size % width:
        raise ValueError(f"pack_rows: src of {src.size} elements is not a "
                         f"whole number of {width}-wide rows")
    if row_bytes % 4:
        raise ValueError(f"pack_rows: a {width}-wide {dtype.name} row is "
                         f"{row_bytes} B, not a whole number of 32-bit words")
    if not interpret and row_bytes % ROW_ALIGN_BYTES:
        raise ValueError(
            f"pack_rows: a {width}-wide {dtype.name} row is {row_bytes} B; "
            f"a compiled TPU row copy needs a multiple of {ROW_ALIGN_BYTES} B "
            f"(128 lanes of 32-bit words)")
    words = row_bytes // 4
    src3 = _to_words(src.reshape(-1, width)).reshape(-1, 1, words)
    src_rows = src_rows.astype(jnp.int32)
    dst_rows = dst_rows.astype(jnp.int32)
    # dst starts zeroed: pallas outputs are uninitialized, so each launch
    # takes the buffer so far as an operand aliased to its output.
    out = jnp.zeros((n_dst_rows, 1, words), jnp.uint32)
    for lo in range(0, src_rows.shape[0], ROWS_PER_CALL):
        hi = min(lo + ROWS_PER_CALL, src_rows.shape[0])
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(hi - lo,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.SemaphoreType.DMA],
        )
        out = pl.pallas_call(
            _pack_kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct(out.shape, jnp.uint32),
            input_output_aliases={3: 0},     # buffer so far -> output
            interpret=interpret,
            name="pack_rows",
        )(src_rows[lo:hi], dst_rows[lo:hi], src3, out)
    return _from_words(out.reshape(n_dst_rows, words), dtype, width)
