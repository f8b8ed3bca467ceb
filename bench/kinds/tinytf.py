"""The repo's bidirectional pre-LN encoder classifier over hashed token
ids (``models/students.py`` ``tinytf``), trained by Adam: the BERT level,
and the expert's stand-in for the LLM."""
import jax
import jax.numpy as jnp
import numpy as np

import model

OPTIMIZER = "adam"
TINY = dict(vocab=256, max_len=32, d_model=32, n_heads=2, n_layers=1,
            d_ff=64)


# -- weights ---------------------------------------------------------------
def _dense(key, d_in, d_out):
    return jax.random.truncated_normal(key, -2.0, 2.0, (d_in, d_out),
                                       jnp.float32) * d_in ** -0.5


def _layers(key, n, d, f):
    out = []
    for k in jax.random.split(key, n):
        ks = jax.random.split(k, 6)
        out.append({"wq": _dense(ks[0], d, d), "wk": _dense(ks[1], d, d),
                    "wv": _dense(ks[2], d, d), "wo": _dense(ks[3], d, d),
                    "w1": _dense(ks[4], d, f), "w2": _dense(ks[5], f, d),
                    "ln1": jnp.ones((d,), jnp.float32),
                    "ln2": jnp.ones((d,), jnp.float32)})
    return out


def weights(key, spec, n_classes: int, n_features: int):
    ks = jax.random.split(key, 8)
    d = spec["d_model"]
    return {"embed": jax.random.normal(ks[0], (spec["vocab"], d)) * 0.02,
            "pos": jax.random.normal(ks[1], (spec["max_len"], d)) * 0.02,
            "layers": _layers(ks[2], spec["n_layers"], d, spec["d_ff"]),
            "cls_w": _dense(ks[3], d, n_classes),
            "cls_b": jnp.zeros((n_classes,), jnp.float32)}


# -- reference forward -----------------------------------------------------
def _ln(x, scale):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + 1e-6) * scale


def _mm(a, b, rnd):
    return rnd(a) @ rnd(b)


def _ein(spec, a, b, rnd):
    return jnp.einsum(spec, rnd(a), rnd(b))


def _attend(q, k, v, bias, rnd):
    """q, k, v: (B, L, H, hd); bias broadcast to (B, H, L, L)."""
    hd = q.shape[-1]
    s = _ein("bqhd,bkhd->bhqk", q, k, rnd) * hd ** -0.5 + bias
    return _ein("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v, rnd)


def _stack(p, h, n_heads, bias, rnd):
    B, L, d = h.shape
    hd = d // n_heads
    for lp in p["layers"]:
        x = _ln(h, lp["ln1"])
        q, k, v = (_mm(x, lp[w], rnd).reshape(B, L, n_heads, hd)
                   for w in ("wq", "wk", "wv"))
        h = h + _mm(_attend(q, k, v, bias, rnd).reshape(B, L, d), lp["wo"],
                    rnd)
        x = _ln(h, lp["ln2"])
        h = h + _mm(jax.nn.gelu(_mm(x, lp["w1"], rnd)), lp["w2"], rnd)
    return h


def logits(p, tokens, spec, rnd):
    """Bidirectional pre-LN encoder over hashed ids, masked mean pool."""
    L = tokens.shape[1]
    mask = tokens > 0
    h = p["embed"][tokens] + p["pos"][None, :L]
    bias = jnp.where(mask, 0.0, -1e30)[:, None, None, :]
    h = _stack(p, h, spec["n_heads"], bias, rnd)
    m = mask.astype(h.dtype)[..., None]
    pooled = jnp.sum(h * m, axis=1) / jnp.maximum(jnp.sum(m, axis=1), 1.0)
    return _mm(pooled, p["cls_w"], rnd) + p["cls_b"]


# -- rows, cost, the expert's centring -------------------------------------
def featurize(spec, doc, n_features: int) -> np.ndarray:
    return model.hash_ids(doc, spec["vocab"], spec["max_len"])


def row(spec, n_features: int):
    return (spec["max_len"],), np.int32


def flops(spec, n_classes: int, n_features: int) -> float:
    L, d, f = spec["max_len"], spec["d_model"], spec["d_ff"]
    per_layer = 8.0 * L * d * d + 4.0 * L * L * d + 4.0 * L * d * f
    return per_layer * spec["n_layers"] + 2.0 * L * d + 2.0 * d * n_classes


def balance(params, spec, shift: float):
    b = np.asarray(params["cls_b"]).copy()
    b[0] += shift / 2
    b[1] -= shift / 2
    return {**params, "cls_b": jnp.asarray(b, jnp.float32)}
