"""Flash attention (Pallas TPU): online-softmax tiled attention.

Beyond-paper §Perf optimization: the baseline q-chunked attention writes
(Lq x Lk) score tiles to HBM; this kernel keeps (block_q x block_k) tiles in
VMEM with running max/sum, so attention HBM traffic collapses to Q/K/V/O.
Supports causal + sliding-window masks, logit softcap, GQA (q-head ->
kv-head mapping in the BlockSpec index maps), forward + custom-vjp backward.

Per-row softmax statistics (running max/sum, the saved log-sum-exp and the
backward's ``delta``) are (rows, 1) columns inside the kernels; in HBM the
saved ones are stored lane-replicated as (B*H, L, 128), the layout whose
(bq, 128) blocks the TPU's (8, 128) tiling accepts.

Validated in interpret mode against the pure-jnp oracle
(`repro.models.attention.attn_forward`) and compiled for v5e in the tests.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention"]

NEG = -1e30
#: lane width of the replicated per-row statistics in HBM
_LANES = 128


def _mask(iq, ik, bq, bk, causal, window):
    qp = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kp = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    m = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        m &= qp >= kp
    if window is not None:
        m &= (qp - kp) < window
    return m


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m_s, l_s, *,
                scale, causal, window, softcap, bq, bk, nk):
    iq, ik = pl.program_id(1), pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_s[...] = jnp.full_like(m_s, NEG)
        l_s[...] = jnp.zeros_like(l_s)

    # skip blocks entirely above the causal diagonal
    live = (ik * bk <= iq * bq + bq - 1) if causal else (ik >= 0)

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        msk = _mask(iq, ik, bq, bk, causal, window)
        s = jnp.where(msk, s, NEG)
        m_new = jnp.maximum(m_s[...], jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_s[...] - m_new)                  # (bq, 1)
        p = jnp.exp(s - m_new)
        l_new = l_s[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0].astype(jnp.float32)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc[...] = acc[...] * alpha + pv
        m_s[...] = m_new
        l_s[...] = l_new

    @pl.when(ik == nk - 1)
    def _finish():
        l = jnp.maximum(l_s[...], 1e-30)
        o_ref[0] = (acc[...] / l).astype(o_ref.dtype)
        lse_ref[0] = jnp.broadcast_to(m_s[...] + jnp.log(l), (bq, _LANES))


def _fwd(q, k, v, *, scale, causal, window, softcap, bq, bk, interpret):
    B, Hq, Lq, D = q.shape
    _, Hkv, Lk, _ = k.shape
    g = Hq // Hkv
    q2 = q.reshape(B * Hq, Lq, D)
    k2 = k.reshape(B * Hkv, Lk, D)
    v2 = v.reshape(B * Hkv, Lk, D)
    nq, nk = Lq // bq, Lk // bk

    def kv_idx(bh, iq, ik):
        return ((bh // Hq) * Hkv + (bh % Hq) // g, ik, 0)

    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          window=window, softcap=softcap, bq=bq, bk=bk,
                          nk=nk),
        grid=(B * Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, bk, D), kv_idx),
            pl.BlockSpec((1, bk, D), kv_idx),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, D), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, bq, _LANES), lambda bh, iq, ik: (bh, iq, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * Hq, Lq, D), q.dtype),
            jax.ShapeDtypeStruct((B * Hq, Lq, _LANES), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32)],
        interpret=interpret,
        name="flash_fwd",
    )(q2, k2, v2)
    return out.reshape(B, Hq, Lq, D), lse


def _p_ds(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, iq, ik, *,
          scale, causal, window, softcap, bq, bk):
    """Shared backward math: recompute p and ds for one (iq, ik) tile."""
    q = q_ref[0].astype(jnp.float32)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0][:, :1]                    # (bq, 1)
    delta = delta_ref[0][:, :1]
    sraw = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32) * scale
    if softcap is not None:
        t = jnp.tanh(sraw / softcap)
        s = softcap * t
        dcap = 1.0 - t * t                     # d softcap(s)/ds
    else:
        s = sraw
        dcap = jnp.ones_like(s)
    msk = _mask(iq, ik, bq, bk, causal, window)
    s = jnp.where(msk, s, NEG)
    p = jnp.exp(s - lse)                       # (bq, bk)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta) * dcap * scale
    ds = jnp.where(msk, ds, 0.0)
    return q, k, do, p, ds


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               dq_ref, dq_acc, *, scale, causal, window, softcap, bq, bk,
               nk):
    iq, ik = pl.program_id(1), pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    q, k, do, p, ds = _p_ds(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                            iq, ik, scale=scale, causal=causal,
                            window=window, softcap=softcap, bq=bq, bk=bk)
    dq_acc[...] += jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                       preferred_element_type=jnp.float32)

    @pl.when(ik == nk - 1)
    def _write():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_acc, dv_acc, *, scale, causal, window,
                softcap, bq, bk, nq):
    ik, iq = pl.program_id(1), pl.program_id(2)

    @pl.when(iq == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q, k, do, p, ds = _p_ds(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                            iq, ik, scale=scale, causal=causal,
                            window=window, softcap=softcap, bq=bq, bk=bk)
    dk_acc[...] += jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                       preferred_element_type=jnp.float32)
    dv_acc[...] += jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                                       preferred_element_type=jnp.float32)

    @pl.when(iq == nq - 1)
    def _write():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _bwd(scale, causal, window, softcap, bq, bk, interpret, res, do):
    q, k, v, o, lse = res
    B, Hq, Lq, D = q.shape
    _, Hkv, Lk, _ = k.shape
    g = Hq // Hkv
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    q2 = q.reshape(B * Hq, Lq, D)
    k2 = k.reshape(B * Hkv, Lk, D)
    v2 = v.reshape(B * Hkv, Lk, D)
    do2 = do.reshape(B * Hq, Lq, D)
    delta2 = jnp.broadcast_to(delta.reshape(B * Hq, Lq, 1),
                              (B * Hq, Lq, _LANES))
    nq, nk = Lq // bq, Lk // bk

    def kv_idx(bh, iq, ik):
        return ((bh // Hq) * Hkv + (bh % Hq) // g, ik, 0)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          window=window, softcap=softcap, bq=bq, bk=bk,
                          nk=nk),
        grid=(B * Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, bk, D), kv_idx),
            pl.BlockSpec((1, bk, D), kv_idx),
            pl.BlockSpec((1, bq, D), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, bq, _LANES), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, bq, _LANES), lambda bh, iq, ik: (bh, iq, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda bh, iq, ik: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B * Hq, Lq, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dq",
    )(q2, k2, v2, do2, lse, delta2)

    # dk/dv are emitted PER Q-HEAD (grid walks q-heads) and group-summed
    # outside — avoids cross-head accumulation races under GQA.
    dkh, dvh = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          window=window, softcap=softcap, bq=bq, bk=bk,
                          nq=nq),
        grid=(B * Hq, nk, nq),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda bh, ik, iq: (bh, iq, 0)),
            pl.BlockSpec((1, bk, D), lambda bh, ik, iq: kv_idx(bh, iq, ik)),
            pl.BlockSpec((1, bk, D), lambda bh, ik, iq: kv_idx(bh, iq, ik)),
            pl.BlockSpec((1, bq, D), lambda bh, ik, iq: (bh, iq, 0)),
            pl.BlockSpec((1, bq, _LANES), lambda bh, ik, iq: (bh, iq, 0)),
            pl.BlockSpec((1, bq, _LANES), lambda bh, ik, iq: (bh, iq, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, D), lambda bh, ik, iq: (bh, ik, 0)),
            pl.BlockSpec((1, bk, D), lambda bh, ik, iq: (bh, ik, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * Hq, Lk, D), jnp.float32),
            jax.ShapeDtypeStruct((B * Hq, Lk, D), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                        pltpu.VMEM((bk, D), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dkv",
    )(q2, k2, v2, do2, lse, delta2)
    dq = dq.reshape(B, Hq, Lq, D)
    dk = dkh.reshape(B, Hq, Lk, D).reshape(B, Hkv, g, Lk, D).sum(
        axis=2).astype(k.dtype)
    dv = dvh.reshape(B, Hq, Lk, D).reshape(B, Hkv, g, Lk, D).sum(
        axis=2).astype(v.dtype)
    return dq, dk, dv


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def flash_attention(q, k, v, scale=None, causal=True, window=None,
                    softcap=None, block_q=256, block_k=256,
                    interpret=False):
    """``q``: (B, Hq, Lq, D); ``k``/``v``: (B, Hkv, Lk, D); GQA via
    Hq % Hkv == 0.  Lq/Lk must divide the block sizes (caller pads)."""
    o, _ = _fwd(q, k, v, scale=scale or 1.0 / math.sqrt(q.shape[-1]),
                causal=causal, window=window, softcap=softcap,
                bq=block_q, bk=block_k, interpret=interpret)
    return o


def _vjp_fwd(q, k, v, scale, causal, window, softcap, block_q, block_k,
             interpret):
    o, lse = _fwd(q, k, v, scale=scale or 1.0 / math.sqrt(q.shape[-1]),
                  causal=causal, window=window, softcap=softcap,
                  bq=block_q, bk=block_k, interpret=interpret)
    return o, (q, k, v, o, lse)


def _vjp_bwd(scale, causal, window, softcap, block_q, block_k, interpret,
             res, do):
    q = res[0]
    return _bwd(scale or 1.0 / math.sqrt(q.shape[-1]), causal, window,
                softcap, block_q, block_k, interpret, res, do)


flash_attention.defvjp(_vjp_fwd, _vjp_bwd)
