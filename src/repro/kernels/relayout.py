"""Chunked -> row-major relayout kernel (read-side linearization).

The static counterpart of :mod:`pack_blocks`: when the stored layout is a
regular chunk grid (paper §2.2 / the reorganized layout of §5), the mapping
from stored chunk (i, j) to its place in the row-major array is affine, so
it is expressed entirely through BlockSpec index maps — the grid walks
chunks, each grid step moves one (ch, cw) VMEM tile.  Compiled for the TPU,
a tile must be (8, 128)-aligned (``CHUNK_ALIGN``) so the copies stay on the
native register layout; other chunks are refused with a ``ValueError``.
"""

from __future__ import annotations

import functools

import jax
from jax.experimental import pallas as pl

__all__ = ["chunked_to_rowmajor", "rowmajor_to_chunked"]

#: (sublanes, lanes) a compiled chunk tile must be a multiple of
CHUNK_ALIGN = (8, 128)


def _check_chunk(chunk, interpret: bool) -> None:
    if interpret:
        return
    ch, cw = chunk
    if ch % CHUNK_ALIGN[0] or cw % CHUNK_ALIGN[1]:
        raise ValueError(
            f"relayout: chunk {tuple(chunk)} is not a multiple of the TPU "
            f"tile {CHUNK_ALIGN}; a compiled relayout moves whole tiles")


def _unchunk_kernel(src_ref, dst_ref):
    dst_ref[...] = src_ref[0, 0]


def _chunk_kernel(src_ref, dst_ref):
    dst_ref[0, 0] = src_ref[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def chunked_to_rowmajor(chunks: jax.Array, *, chunk: tuple,
                        interpret: bool = False) -> jax.Array:
    """``chunks``: (n_i, n_j, ch, cw) stored-chunk tensor -> (n_i*ch,
    n_j*cw) row-major array."""
    n_i, n_j, ch, cw = chunks.shape
    if (ch, cw) != tuple(chunk):
        raise ValueError(f"chunk {tuple(chunk)} != tile shape {(ch, cw)}")
    _check_chunk(chunk, interpret)
    return pl.pallas_call(
        _unchunk_kernel,
        grid=(n_i, n_j),
        in_specs=[pl.BlockSpec((1, 1, ch, cw), lambda i, j: (i, j, 0, 0))],
        out_specs=pl.BlockSpec((ch, cw), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n_i * ch, n_j * cw), chunks.dtype),
        interpret=interpret,
        name="chunked_to_rowmajor",
    )(chunks)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def rowmajor_to_chunked(arr: jax.Array, *, chunk: tuple,
                        interpret: bool = False) -> jax.Array:
    """Inverse: (H, W) row-major -> (H/ch, W/cw, ch, cw) chunk tensor (the
    write-side re-tiling a producer runs before emitting the reorganized
    layout)."""
    H, W = arr.shape
    ch, cw = chunk
    if H % ch or W % cw:
        raise ValueError(f"array {arr.shape} is not a whole grid of "
                         f"{tuple(chunk)} chunks")
    _check_chunk(chunk, interpret)
    n_i, n_j = H // ch, W // cw
    return pl.pallas_call(
        _chunk_kernel,
        grid=(n_i, n_j),
        in_specs=[pl.BlockSpec((ch, cw), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((1, 1, ch, cw), lambda i, j: (i, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_i, n_j, ch, cw), arr.dtype),
        interpret=interpret,
        name="rowmajor_to_chunked",
    )(arr)
