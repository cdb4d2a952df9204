"""Mixture-of-Experts FFN: top-k routing with fixed capacity.

Two dispatch paths share the routing math:
  * ``gather``  — baseline: scatter/gather dispatch under GSPMD (the
    partitioner materializes cross-shard gathers as all-gathers; this is the
    collective hot-spot the §Perf hillclimb attacks);
  * ``a2a``     — optimized: shard_map + fixed-capacity ``lax.all_to_all``
    over the expert axis (added during the perf pass).

Supports DeepSeek-MoE shared experts (always-on) and Arctic's parallel dense
residual branch (handled at the block level).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from ..distributed.sharding import current_ctx, shard
from .layers import mlp_defs, mlp_forward
from .params import ParamDef

__all__ = ["MoEDims", "moe_defs", "moe_forward"]


@dataclasses.dataclass(frozen=True)
class MoEDims:
    d_model: int
    d_ff: int                 # per-expert hidden
    n_experts: int
    top_k: int
    n_shared: int = 0         # always-active shared experts (deepseek)
    capacity_factor: float = 1.25
    renorm_topk: bool = True  # renormalize the top-k gate weights
    dispatch: str = "gather"  # gather | a2a


def moe_defs(dims: MoEDims) -> dict:
    E, M, F = dims.n_experts, dims.d_model, dims.d_ff
    d = {
        "router": ParamDef((M, E), ("embed", None), init="fan_in"),
        "w_gate": ParamDef((E, M, F), ("experts", "embed", "expert_mlp"),
                           init="fan_in"),
        "w_up": ParamDef((E, M, F), ("experts", "embed", "expert_mlp"),
                         init="fan_in"),
        "w_down": ParamDef((E, F, M), ("experts", "expert_mlp", "embed"),
                           init="fan_in"),
    }
    if dims.n_shared:
        d["shared"] = mlp_defs(M, F * dims.n_shared, gated=True)
    return d


def _route(p, xf, dims: MoEDims):
    """Router: returns (weights (T,k), experts (T,k), aux_loss)."""
    logits = jnp.einsum("tm,me->te", xf.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, dims.top_k)
    if dims.renorm_topk:
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    # switch-style load-balance aux loss
    T = xf.shape[0]
    me = jnp.mean(probs, axis=0)                                  # (E,)
    ce = jnp.sum(jax.nn.one_hot(top_e[:, 0], dims.n_experts),
                 axis=0) / T
    aux = dims.n_experts * jnp.sum(me * ce)
    return top_w, top_e, aux


def _capacity(T: int, dims: MoEDims) -> int:
    c = int(T * dims.top_k / dims.n_experts * dims.capacity_factor)
    return max(8, (c + 7) // 8 * 8)


def _expert_ffn(p, h, x_dtype):
    g = jnp.einsum("ecm,emf->ecf", h, p["w_gate"].astype(x_dtype))
    u = jnp.einsum("ecm,emf->ecf", h, p["w_up"].astype(x_dtype))
    a = jax.nn.silu(g) * u
    a = shard(a, "act_experts", None, None)
    return jnp.einsum("ecf,efm->ecm", a, p["w_down"].astype(x_dtype))


def moe_forward(p, x, dims: MoEDims):
    """``x``: (B, L, M) -> (B, L, M), plus aux loss scalar."""
    if dims.dispatch == "local":
        ctx = current_ctx()
        if ctx is not None and "model" in ctx.mesh.shape \
                and "model" not in ctx.manual:
            return _moe_forward_local(p, x, dims, ctx)
    B, L, M = x.shape
    T = B * L
    xf = x.reshape(T, M)
    top_w, top_e, aux = _route(p, xf, dims)
    C = _capacity(T, dims)
    E, k = dims.n_experts, dims.top_k

    # position of each (token, choice) within its expert's capacity
    e_flat = top_e.reshape(T * k)                         # (T*k,)
    onehot = jax.nn.one_hot(e_flat, E, dtype=jnp.int32)   # (T*k, E)
    pos_all = jnp.cumsum(onehot, axis=0) - onehot
    pos = jnp.sum(pos_all * onehot, axis=-1)              # (T*k,)
    keep = pos < C
    pos_c = jnp.minimum(pos, C - 1)
    t_idx = jnp.arange(T * k) // k

    # dispatch: (E, C, M)
    disp = jnp.zeros((E, C, M), x.dtype)
    contrib = jnp.where(keep[:, None], xf[t_idx], 0).astype(x.dtype)
    disp = disp.at[e_flat, pos_c].add(contrib)
    disp = shard(disp, "act_experts", None, None)

    out_e = _expert_ffn(p, disp, x.dtype)                 # (E, C, M)

    # combine: gather back and weight
    gathered = out_e[e_flat, pos_c]                       # (T*k, M)
    w_flat = (top_w.reshape(T * k) * keep).astype(x.dtype)
    y = jnp.sum((gathered * w_flat[:, None]).reshape(T, k, M), axis=1)

    if dims.n_shared:
        y = y + mlp_forward(p["shared"], xf)
    return y.reshape(B, L, M), aux


# ---------------------------------------------------------------------------
# optimized dispatch: local expert slices (beyond-paper §Perf)
# ---------------------------------------------------------------------------

def _moe_forward_local(p, x, dims: MoEDims, ctx):
    """Expert-parallel dispatch without the (E, C, M) cross-shard reduction.

    The baseline gather dispatch lets GSPMD all-reduce the full dispatch
    buffer across the data axis (the dominant collective in MoE training —
    see EXPERIMENTS.md §Perf).  Here the 'model' axis runs manually: routing
    is computed replicated (tokens are replicated over 'model'), every shard
    builds the dispatch buffer ONLY for its local expert slice, and the
    combine is a single psum of the (T, M) output — the structurally minimal
    EP collective for this mesh.
    """
    import jax
    from jax.sharding import PartitionSpec as P
    from ..distributed import sharding as shd

    mesh = ctx.mesh
    n_ep = mesh.shape["model"]
    B, L, M = x.shape
    E, k = dims.n_experts, dims.top_k
    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    n_dp = 1
    for a in dp_axes:
        n_dp *= mesh.shape[a]
    if E % n_ep or B % n_dp:
        # can't slice experts/batch evenly: fall back to gather dispatch
        return moe_forward(p, x,
                           dataclasses.replace(dims, dispatch="gather"))
    E_loc = E // n_ep
    rules = ctx.rules.mapping
    manual = frozenset(dp_axes) | {"model"}

    def body(router, wg, wu, wd, xx):
        with shd.use_sharding(mesh, rules, manual=ctx.manual | manual):
            Bb, Ll, Mm = xx.shape
            T = Bb * Ll
            xf = xx.reshape(T, Mm)
            top_w, top_e, aux = _route({"router": router}, xf, dims)
            C = _capacity(T, dims)
            ep = jax.lax.axis_index("model")
            e_flat = top_e.reshape(T * k)
            onehot = jax.nn.one_hot(e_flat, E, dtype=jnp.int32)
            pos_all = jnp.cumsum(onehot, axis=0) - onehot
            pos = jnp.sum(pos_all * onehot, axis=-1)
            keep = pos < C
            local = keep & (e_flat >= ep * E_loc) \
                & (e_flat < (ep + 1) * E_loc)
            e_loc = jnp.clip(e_flat - ep * E_loc, 0, E_loc - 1)
            pos_c = jnp.minimum(pos, C - 1)
            t_idx = jnp.arange(T * k) // k

            disp = jnp.zeros((E_loc, C, Mm), xx.dtype)
            contrib = jnp.where(local[:, None], xf[t_idx], 0).astype(xx.dtype)
            disp = disp.at[e_loc, pos_c].add(contrib)

            out_e = _expert_ffn({"w_gate": wg, "w_up": wu, "w_down": wd},
                                disp, xx.dtype)
            gathered = out_e[e_loc, pos_c]
            w_flat = (top_w.reshape(T * k) * local).astype(xx.dtype)
            y = jnp.sum((gathered * w_flat[:, None]).reshape(T, k, Mm),
                        axis=1)
            y = jax.lax.psum(y, "model")      # THE one EP collective
            if dp_axes:
                aux = jax.lax.pmean(aux, dp_axes)
            return y.reshape(Bb, Ll, Mm), aux

    bspec = P(dp_axes if len(dp_axes) > 1 else (dp_axes[0] if dp_axes
                                                else None))
    y, aux = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), P("model"), P("model"), P("model"), bspec),
        out_specs=(bspec, P()),
        axis_names=set(manual), check_vma=False,
    )(p["router"], p["w_gate"], p["w_up"], p["w_down"], x)
    if dims.n_shared:
        B_, L_, M_ = x.shape
        y = y + mlp_forward(p["shared"], x.reshape(B_ * L_, M_)).reshape(
            B_, L_, M_)
    return y, aux
