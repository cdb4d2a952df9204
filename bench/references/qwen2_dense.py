"""Plain reference of a Qwen2 decoder's training step: weights from the
seed, the next-token loss, its gradients and AdamW, in straightforward
``jax.numpy`` at float32 with the ``highest`` matmul precision.

Follows the Qwen2 description (``Qwen2ForCausalLM``: RMSNorm before
attention and MLP, grouped-query attention with biases on q, k and v,
rotary embeddings by halves with ``inv_freq = theta ** (-2i / head_dim)``,
SwiGLU MLP, a final RMSNorm and an output head tied to the embedding).
Departures, each deliberate:

- each RMSNorm gain is held as ``1 + g`` with ``g`` starting at 0, the
  parametrisation the system under test trains; the function is the same,
  and AdamW's decay then pulls the gain towards 1, as it does there;
- the optimizer is the configuration's AdamW: global-norm clipping, a
  linear warm-up into a cosine decay, and weight decay added to the Adam
  direction before the learning rate, on every leaf.

``fp8=True`` is the control: every matrix product's operands are rounded
to float8 e4m3 and the gradient flowing into it to e5m2, one scale per
tensor, before exact products: the step a cheaper training path would
take.  Nothing here imports the system
under test.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.seeds import seed_key

def dims(cfg: dict) -> dict:
    if cfg["hidden_act"] != "silu":
        raise ValueError(f"a Qwen2 MLP is SwiGLU, not {cfg['hidden_act']!r}")
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    return dict(d=d, h=h, kv=cfg["num_key_value_heads"],
                hd=cfg.get("head_dim") or d // h,
                ff=cfg["intermediate_size"], v=cfg["vocab_size"],
                n=cfg["num_hidden_layers"])


def leaf_shapes(cfg: dict) -> dict:
    s = dims(cfg)
    n, d, h, kv, hd, ff = s["n"], s["d"], s["h"], s["kv"], s["hd"], s["ff"]
    return {"embed": (s["v"], d), "ln1": (n, d), "ln2": (n, d),
            "wq": (n, d, h, hd), "wk": (n, d, kv, hd), "wv": (n, d, kv, hd),
            "wo": (n, h, hd, d), "bq": (n, h, hd), "bk": (n, kv, hd),
            "bv": (n, kv, hd), "w_up": (n, d, ff), "w_gate": (n, d, ff),
            "w_down": (n, ff, d), "final_norm": (d,)}


def make_params(cfg: dict, key) -> dict:
    """The weights from a key: every matrix and bias N(0, std), norms at
    gain 1 (``g = 0``).  Biases are drawn too, so that none starts at a
    point where its gradient vanishes by symmetry alone."""
    std = cfg["init"]["std"]
    out = {}
    for i, (name, shape) in enumerate(leaf_shapes(cfg).items()):
        if name in ("ln1", "ln2", "final_norm"):
            out[name] = jnp.zeros(shape, jnp.float32)
        else:
            out[name] = std * jax.random.normal(jax.random.fold_in(key, i),
                                                shape, jnp.float32)
    return out


def batch(cfg: dict, seed: int, step: int) -> tuple:
    """(tokens, labels) of training step ``step`` (0-based): the synthetic
    Zipf corpus the configuration names, drawn from (seed, step, host 0)."""
    t = cfg["training"]
    rng = np.random.default_rng((seed, step, 0))
    raw = rng.zipf(t["zipf_a"], size=(t["global_batch"], t["seq_len"] + 1))
    toks = np.minimum(raw, cfg["vocab_size"] - 1).astype(np.int32)
    return toks[:, :-1], toks[:, 1:]


# -- the model -------------------------------------------------------------------

def _fp8(x, dtype):
    """Rounds to a float8 type with one scale for the whole tensor."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / float(jnp.finfo(dtype).max), 1.0)
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


def _exact(spec, a, b):
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _mm_fp8(spec, a, b):
    return _exact(spec, _fp8(a, jnp.float8_e4m3fn), _fp8(b, jnp.float8_e4m3fn))


def _mm_fp8_fwd(spec, a, b):
    qa, qb = _fp8(a, jnp.float8_e4m3fn), _fp8(b, jnp.float8_e4m3fn)
    return _exact(spec, qa, qb), (qa, qb)


def _mm_fp8_bwd(spec, res, g):
    # operands in e4m3, the incoming gradient in e5m2, as fp8 training does
    _, vjp = jax.vjp(functools.partial(_exact, spec), *res)
    return vjp(_fp8(g, jnp.float8_e5m2))


_mm_fp8.defvjp(_mm_fp8_fwd, _mm_fp8_bwd)


def _mm(spec, a, b, fp8: bool):
    return _mm_fp8(spec, a, b) if fp8 else _exact(spec, a, b)


def _rms(x, g, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * (1.0 + g)


def _rope(x, theta):
    """x: (B, S, H, D); rotate by halves."""
    s, d = x.shape[1], x.shape[-1]
    inv = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    ang = np.arange(s, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def loss_fn(p, tokens, labels, cfg: dict, fp8: bool = False):
    s = dims(cfg)
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    b, sl = tokens.shape
    g = s["h"] // s["kv"]
    x = p["embed"][tokens]
    causal = np.tril(np.ones((sl, sl), bool))
    for i in range(s["n"]):
        hN = _rms(x, p["ln1"][i], eps)
        q = _mm("bsd,dhe->bshe", hN, p["wq"][i], fp8) + p["bq"][i]
        k = _mm("bsd,dke->bske", hN, p["wk"][i], fp8) + p["bk"][i]
        v = _mm("bsd,dke->bske", hN, p["wv"][i], fp8) + p["bv"][i]
        q, k = _rope(q, theta), _rope(k, theta)
        k = jnp.repeat(k, g, axis=2)            # head j uses kv head j // g
        v = jnp.repeat(v, g, axis=2)
        sc = _mm("bqhe,bkhe->bhqk", q, k, fp8) / math.sqrt(s["hd"])
        sc = jnp.where(causal, sc, -jnp.inf)
        pr = jax.nn.softmax(sc, axis=-1)
        o = _mm("bhqk,bkhe->bqhe", pr, v, fp8)
        x = x + _mm("bshe,hed->bsd", o, p["wo"][i], fp8)
        hN = _rms(x, p["ln2"][i], eps)
        up = _mm("bsd,df->bsf", hN, p["w_up"][i], fp8)
        gate = _mm("bsd,df->bsf", hN, p["w_gate"][i], fp8)
        x = x + _mm("bsf,fd->bsd", jax.nn.silu(gate) * up, p["w_down"][i],
                    fp8)
    x = _rms(x, p["final_norm"], eps)
    logits = _mm("bsd,vd->bsv", x, p["embed"], fp8)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


# -- the optimizer ---------------------------------------------------------------

def learning_rate(opt: dict, count):
    c = jnp.asarray(count, jnp.float32)
    warm = opt["peak_lr"] * c / max(opt["warmup_steps"], 1)
    t = jnp.clip((c - opt["warmup_steps"])
                 / max(opt["total_steps"] - opt["warmup_steps"], 1), 0.0, 1.0)
    cos = opt["end_lr"] + 0.5 * (opt["peak_lr"] - opt["end_lr"]) \
        * (1 + jnp.cos(jnp.pi * t))
    return jnp.where(c < opt["warmup_steps"], warm, cos)


def make_step(cfg: dict, fp8: bool = False, rows=None):
    """A jitted ``(p, m, v, count, tokens, labels) -> (p, m, v, loss,
    clipped grads' norms by leaf)``.  ``rows`` keeps only those batch rows
    (a fault for the tests: half of the batch left out)."""
    opt = cfg["training"]["optimizer"]

    def step(p, m, v, count, tokens, labels):
        if rows is not None:
            tokens, labels = tokens[:rows], labels[:rows]
        loss, grads = jax.value_and_grad(loss_fn)(p, tokens, labels, cfg,
                                                  fp8)
        count = count + 1
        lr = learning_rate(opt, count)
        gn = jnp.sqrt(sum(jnp.sum(g * g) for g in grads.values()))
        scale = jnp.minimum(1.0, opt["grad_clip"] / (gn + 1e-9))
        grads = {k: g * scale for k, g in grads.items()}
        b1, b2 = opt["b1"], opt["b2"]
        cf = count.astype(jnp.float32)
        np_, nm, nv = {}, {}, {}
        for k in p:
            nm[k] = b1 * m[k] + (1 - b1) * grads[k]
            nv[k] = b2 * v[k] + (1 - b2) * grads[k] ** 2
            mh = nm[k] / (1 - b1 ** cf)
            vh = nv[k] / (1 - b2 ** cf)
            np_[k] = p[k] - lr * (mh / (jnp.sqrt(vh) + opt["eps"])
                                  + opt["weight_decay"] * p[k])
        norms = {k: jnp.sqrt(jnp.sum(g * g)) for k, g in grads.items()}
        return np_, nm, nv, count, loss, norms

    return jax.jit(step, donate_argnums=(0, 1, 2))


def readings(cfg: dict, seed: int, steps: int = 3, fp8: bool = False,
             rows=None) -> dict:
    """The reference's numbers over the first ``steps`` steps: each step's
    loss, the first step's clipped gradient norm by leaf, and the norm by
    leaf of the parameters' change after the last step."""
    key = seed_key(seed)
    p = jax.jit(make_params, static_argnums=0)(_frozen(cfg), key)
    m = {k: jnp.zeros_like(a) for k, a in p.items()}
    v = {k: jnp.zeros_like(a) for k, a in p.items()}
    count = jnp.zeros((), jnp.int32)
    step = make_step(cfg, fp8, rows)
    losses, grad_norms = [], None
    for i in range(steps):
        tokens, labels = batch(cfg, seed, i)
        p, m, v, count, loss, norms = step(p, m, v, count, tokens, labels)
        losses.append(float(loss))
        if grad_norms is None:
            grad_norms = {k: float(x) for k, x in norms.items()}
    del m, v
    p0 = jax.jit(make_params, static_argnums=0)(_frozen(cfg), key)
    change = {k: float(jnp.sqrt(jnp.sum((p[k] - p0[k]) ** 2))) for k in p}
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": change}


class _frozen(dict):
    """A hashable configuration, for ``static_argnums``."""

    def __hash__(self):
        return hash(repr(sorted(self.items(), key=lambda kv: kv[0])))
