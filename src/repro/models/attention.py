"""Attention: GQA/MHA/MQA, sliding windows, logit softcap, cross-attention.

Forward uses query-chunked (blockwise-softmax) attention so 32k-token
prefill never materializes a full (L, L) score tensor per head; decode is a
single-token path against either a full KV cache, a ring-buffered sliding
window cache, or a sequence-sharded long-context cache.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from ..distributed.sharding import shard
from .layers import rope, softcap
from .params import ParamDef

__all__ = ["attn_defs", "attn_forward", "attn_decode", "init_kv_cache_defs",
           "cross_attn_forward", "cross_kv"]


def attn_defs(d_model: int, n_heads: int, n_kv: int, head_dim: int,
              qkv_bias: bool = False, gated: bool = False) -> dict:
    d = {
        "wq": ParamDef((d_model, n_heads, head_dim),
                       ("embed", "heads", "head_dim"), init="fan_in"),
        "wk": ParamDef((d_model, n_kv, head_dim),
                       ("embed", "kv_heads", "head_dim"), init="fan_in"),
        "wv": ParamDef((d_model, n_kv, head_dim),
                       ("embed", "kv_heads", "head_dim"), init="fan_in"),
        "wo": ParamDef((n_heads, head_dim, d_model),
                       ("heads", "head_dim", "embed"), init="fan_in"),
    }
    if qkv_bias:
        d["bq"] = ParamDef((n_heads, head_dim), ("heads", "head_dim"),
                           init="zeros")
        d["bk"] = ParamDef((n_kv, head_dim), ("kv_heads", "head_dim"),
                           init="zeros")
        d["bv"] = ParamDef((n_kv, head_dim), ("kv_heads", "head_dim"),
                           init="zeros")
    if gated:   # cross-attn tanh gate (llama-3.2-vision)
        d["gate"] = ParamDef((), (), init="zeros")
    return d


def _project_q(p, x):
    q = jnp.einsum("blm,mhd->blhd", x, p["wq"].astype(x.dtype))
    if "bq" in p:
        q = q + p["bq"].astype(x.dtype)
    return q


def _project_kv(p, x):
    k = jnp.einsum("blm,mkd->blkd", x, p["wk"].astype(x.dtype))
    v = jnp.einsum("blm,mkd->blkd", x, p["wv"].astype(x.dtype))
    if "bk" in p:
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    return k, v


def _out(p, o, gated=False):
    y = jnp.einsum("blhd,hdm->blm", o, p["wo"].astype(o.dtype))
    if gated and "gate" in p:
        y = jnp.tanh(p["gate"].astype(y.dtype)) * y
    return y


def _scores_mask(qpos, kpos, causal: bool, window: int | None):
    m = jnp.ones((qpos.shape[0], kpos.shape[0]), bool)
    if causal:
        m &= qpos[:, None] >= kpos[None, :]
    if window is not None:
        m &= (qpos[:, None] - kpos[None, :]) < window
    return m


def attn_forward(p, x, *, n_heads: int, n_kv: int, head_dim: int,
                 causal: bool = True, window: int | None = None,
                 positions=None, rope_theta: float = 10000.0,
                 rotary_dim: int | None = None, use_rope: bool = True,
                 attn_cap: float | None = None, q_chunk: int = 512,
                 flash: bool = False, flash_block: int = 256):
    """Self-attention over a full sequence (training / prefill)."""
    B, L, M = x.shape
    if positions is None:
        positions = jnp.arange(L)
    q = _project_q(p, x)                     # (B, L, H, D)
    k, v = _project_kv(p, x)                 # (B, L, K, D)
    if use_rope:
        q = rope(q.swapaxes(1, 2), positions, rope_theta,
                 rotary_dim).swapaxes(1, 2)
        k = rope(k.swapaxes(1, 2), positions, rope_theta,
                 rotary_dim).swapaxes(1, 2)
    q = shard(q, "batch", None, "act_heads", None)
    k = shard(k, "batch", None, "act_heads", None)
    v = shard(v, "batch", None, "act_heads", None)
    g = n_heads // n_kv
    scale = 1.0 / math.sqrt(head_dim)

    if flash and L % flash_block == 0:
        o = _flash_sharded(q.swapaxes(1, 2), k.swapaxes(1, 2),
                           v.swapaxes(1, 2), scale, causal, window,
                           attn_cap, flash_block)
        return _out(p, o.swapaxes(1, 2))

    qg = q.reshape(B, L, n_kv, g, head_dim)

    n_chunks = max(1, L // q_chunk) if L % q_chunk == 0 else 1
    qc = L // n_chunks

    def chunk_out(qi, qpos):
        s = jnp.einsum("bqkgd,blkd->bkgql", qi, k).astype(jnp.float32)
        s = softcap(s * scale, attn_cap)
        mask = _scores_mask(qpos, positions, causal, window)
        s = jnp.where(mask[None, None, None], s, -1e30)
        pr = jax.nn.softmax(s, axis=-1).astype(x.dtype)
        return jnp.einsum("bkgql,blkd->bqkgd", pr, v)

    if n_chunks == 1:
        o = chunk_out(qg, positions)
    else:
        qs = qg.reshape(B, n_chunks, qc, n_kv, g, head_dim).swapaxes(0, 1)
        ps = positions.reshape(n_chunks, qc)

        def body(_, xs):
            qi, qpos = xs
            return None, chunk_out(qi, qpos)

        _, os = jax.lax.scan(body, None, (qs, ps))
        o = os.swapaxes(0, 1).reshape(B, L, n_kv, g, head_dim)
    o = o.reshape(B, L, n_heads, head_dim)
    return _out(p, o)


def _flash_sharded(q, k, v, scale, causal, window, softcap, block):
    """Run the Pallas flash kernel per shard: GSPMD cannot partition through
    a pallas_call (it would gather+replicate the operands), so the kernel is
    wrapped in a fully-manual shard_map over the batch/head axes the
    activations are sharded on."""
    import jax
    from jax.sharding import PartitionSpec as P
    from ..distributed import sharding as shd
    from ..kernels.flash_attention import flash_attention

    ctx = shd.current_ctx()

    def call(a, b, c):
        return flash_attention(a, b, c, scale, causal, window, softcap,
                               block, block)

    if ctx is None or ctx.mesh.size == 1:
        return call(q, k, v)
    qspec = ctx.spec(("batch", "act_heads", None, None), q.shape)
    kspec = ctx.spec(("batch", "act_heads", None, None), k.shape)
    manual = {a for e in (*qspec, *kspec) if e
              for a in ((e,) if isinstance(e, str) else e)}
    manual -= set(ctx.manual)
    if not manual:
        return call(q, k, v)

    Hq, Hkv = q.shape[1], k.shape[1]
    g = Hq // Hkv
    head_axis = qspec[1] if len(qspec) > 1 else None

    def body(a, b, c):
        with shd.use_sharding(ctx.mesh, ctx.rules.mapping,
                              manual=ctx.manual | manual):
            H_loc = a.shape[1]
            if b.shape[1] == Hkv and H_loc < Hq and head_axis is not None:
                # q-heads sharded, kv replicated: slice this shard's group
                idx = jax.lax.axis_index(head_axis)
                kvn = max(1, H_loc // g)
                start = (idx * H_loc) // g
                b = jax.lax.dynamic_slice_in_dim(b, start, kvn, axis=1)
                c = jax.lax.dynamic_slice_in_dim(c, start, kvn, axis=1)
            return call(a, b, c)

    return jax.shard_map(body, mesh=ctx.mesh,
                             in_specs=(qspec, kspec, kspec),
                             out_specs=qspec,
                             axis_names=manual, check_vma=False)(q, k, v)


# -- cross attention ----------------------------------------------------------

def cross_kv(p, kv_x):
    """Precompute cross-attention K/V from (vision/audio) memory tokens."""
    return _project_kv(p, kv_x)


def cross_attn_forward(p, x, k, v, *, n_heads: int, n_kv: int,
                       head_dim: int):
    B, L, M = x.shape
    q = _project_q(p, x)
    g = n_heads // n_kv
    scale = 1.0 / math.sqrt(head_dim)
    qg = q.reshape(B, L, n_kv, g, head_dim)
    s = jnp.einsum("bqkgd,blkd->bkgql", qg, k).astype(jnp.float32) * scale
    pr = jax.nn.softmax(s, axis=-1).astype(x.dtype)
    o = jnp.einsum("bkgql,blkd->bqkgd", pr, v).reshape(B, L, n_heads, head_dim)
    return _out(p, o, gated=True)


# -- decode -------------------------------------------------------------------

def init_kv_cache_defs(batch: int, cache_len: int, n_kv: int, head_dim: int,
                       dtype: str = "bfloat16",
                       seq_sharded: bool = False) -> dict:
    seq_ax = "kv_seq" if seq_sharded else None
    return {
        "k": ParamDef((batch, cache_len, n_kv, head_dim),
                      ("batch", seq_ax, "kv_heads", None), dtype=dtype,
                      init="zeros"),
        "v": ParamDef((batch, cache_len, n_kv, head_dim),
                      ("batch", seq_ax, "kv_heads", None), dtype=dtype,
                      init="zeros"),
    }


def attn_decode(p, x, cache, pos, *, n_heads: int, n_kv: int, head_dim: int,
                window: int | None = None, rope_theta: float = 10000.0,
                rotary_dim: int | None = None, use_rope: bool = True,
                attn_cap: float | None = None):
    """One decode step. ``x``: (B, 1, M); ``pos``: scalar int32 (current
    position).  ``cache['k']``: (B, S, K, D) where S == window for ring
    caches, else max_len.  Returns (y, new_cache)."""
    B, _, M = x.shape
    S = cache["k"].shape[1]
    q = _project_q(p, x)
    k1, v1 = _project_kv(p, x)
    if use_rope:
        posb = jnp.full((1,), pos)
        q = rope(q.swapaxes(1, 2), posb, rope_theta, rotary_dim).swapaxes(1, 2)
        k1 = rope(k1.swapaxes(1, 2), posb, rope_theta,
                  rotary_dim).swapaxes(1, 2)
    slot = pos % S
    ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], k1.astype(
        cache["k"].dtype), slot, axis=1)
    cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], v1.astype(
        cache["v"].dtype), slot, axis=1)
    # position held by each ring slot j: latest value p <= pos with p%S == j
    slots = jnp.arange(S)
    kpos = pos - ((pos - slots) % S)
    valid = kpos >= 0
    if window is not None:
        valid &= (pos - kpos) < window
    g = n_heads // n_kv
    scale = 1.0 / math.sqrt(head_dim)
    qg = q.reshape(B, 1, n_kv, g, head_dim)
    s = jnp.einsum("bqkgd,blkd->bkgql", qg,
                   ck.astype(x.dtype)).astype(jnp.float32)
    s = softcap(s * scale, attn_cap)
    s = jnp.where(valid[None, None, None, None, :], s, -1e30)
    pr = jax.nn.softmax(s, axis=-1).astype(x.dtype)
    o = jnp.einsum("bkgql,blkd->bqkgd", pr, cv.astype(x.dtype))
    o = o.reshape(B, 1, n_heads, head_dim)
    return _out(p, o), {"k": ck, "v": cv}
