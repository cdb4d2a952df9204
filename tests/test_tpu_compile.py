"""Ahead-of-time compiles of every Pallas kernel for a described TPU v5e.

Nothing runs: each test lowers a kernel with ``interpret=False`` against a
device of the ``v5e:2x2`` topology and asks the TPU compiler to compile it,
which refuses what the chip would refuse (unaligned slices, tiles the
(8, 128) layout cannot hold, too much VMEM).  The widths are the ones the
chip smoke run uses: Phase B's qwen2.5-3b weights (d_model 2048, d_ff
11008) and Phase C's 128-wide mesh-variable rows.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import chunked_to_rowmajor, pack_rows, rowmajor_to_chunked
from repro.kernels.flash_attention import flash_attention


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # noqa: BLE001 - any failure means "none"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compilation cache off: a
    compile for a described device is written but can never be read back."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("dtype,width", [(jnp.float32, 128),
                                         (jnp.bfloat16, 256),
                                         (jnp.float32, 2048),
                                         (jnp.bfloat16, 2048),
                                         (jnp.float32, 11008),
                                         (jnp.bfloat16, 11008)])
def test_pack_rows_compiles(one_chip, dtype, width):
    rows = 64

    def f(src, src_rows, dst_rows):
        return pack_rows(src, src_rows, dst_rows, n_dst_rows=rows,
                         width=width)

    txt = _compiled_text(f, _sds((rows, width), dtype, one_chip),
                         _sds((rows,), jnp.int32, one_chip),
                         _sds((rows,), jnp.int32, one_chip))
    assert "tpu_custom_call" in txt


def test_pack_rows_compiles_at_phase_c_merge_length(one_chip):
    # the most-loaded process's merge at 512x1024x1024 in 128^3 boxes:
    # 147,456 rows of 128 float32, whose two row tables (1.125 MiB) would
    # overflow the chip's 1 MiB of SMEM in a single launch
    rows, width = 147456, 128

    def f(src, src_rows, dst_rows):
        return pack_rows(src, src_rows, dst_rows, n_dst_rows=rows,
                         width=width)

    txt = _compiled_text(f, _sds((rows, width), jnp.float32, one_chip),
                         _sds((rows,), jnp.int32, one_chip),
                         _sds((rows,), jnp.int32, one_chip))
    assert txt.count("tpu_custom_call") >= 2


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("chunk", [(8, 128), (256, 256)])
def test_relayout_compiles(one_chip, dtype, chunk):
    ch, cw = chunk
    slab = (4 * ch, 2 * cw)
    txt = _compiled_text(lambda a: rowmajor_to_chunked(a, chunk=chunk),
                         _sds(slab, dtype, one_chip))
    assert "tpu_custom_call" in txt
    txt = _compiled_text(lambda c: chunked_to_rowmajor(c, chunk=chunk),
                         _sds((4, 2, ch, cw), dtype, one_chip))
    assert "tpu_custom_call" in txt


def _qkv(one_chip, dtype):
    B, Hq, Hkv, L, D = 1, 16, 2, 512, 128          # qwen2.5-3b heads
    return (_sds((B, Hq, L, D), dtype, one_chip),
            _sds((B, Hkv, L, D), dtype, one_chip),
            _sds((B, Hkv, L, D), dtype, one_chip))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_forward_compiles(one_chip, dtype):
    txt = _compiled_text(lambda q, k, v: flash_attention(q, k, v),
                         *_qkv(one_chip, dtype))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_backward_compiles(one_chip, dtype):
    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v).astype(jnp.float32))

    txt = _compiled_text(jax.grad(loss, argnums=(0, 1, 2)),
                         *_qkv(one_chip, dtype))
    # forward (for the residuals), dq and dk/dv kernels
    assert txt.count("tpu_custom_call") >= 3


@pytest.mark.parametrize("dtype,width", [(jnp.float32, 64),
                                         (jnp.float32, 192),
                                         (jnp.bfloat16, 128)])
def test_pack_rows_unaligned_width_is_refused(dtype, width):
    src = jnp.zeros((8, width), dtype)
    rows = jnp.arange(8, dtype=jnp.int32)
    with pytest.raises(ValueError, match="multiple of 512 B"):
        pack_rows(src, rows, rows, n_dst_rows=8, width=width)


@pytest.mark.parametrize("chunk", [(64, 64), (4, 128)])
def test_relayout_unaligned_chunk_is_refused(chunk):
    with pytest.raises(ValueError, match=r"multiple of the TPU tile"):
        rowmajor_to_chunked(jnp.zeros((128, 128), jnp.float32), chunk=chunk)
    with pytest.raises(ValueError, match=r"multiple of the TPU tile"):
        chunked_to_rowmajor(jnp.zeros((2, 2, *chunk), jnp.float32),
                            chunk=chunk)
