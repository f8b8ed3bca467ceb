"""Weights from the seed, and the plain float32 reference of every level.

Nothing here imports the program.  The weight trees have the program's
layout (``models/students.py``, ``core/deferral.py``) so that the
benchmark can install them, but they are drawn by this file from
``--seed`` in one jitted call.

The reference is straight ``jax.numpy``: no kernels, no batching tricks,
no padding rules.  It runs in float32 under
``jax.default_matmul_precision("highest")``.  Every matmul takes its
operands through a rounding (``ROUNDINGS``): none for the reference,
float8 for the control one precision below the configuration's bfloat16
matmul operands (``check.py``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_HASH_PRIME = 2654435761


# -- features (the featurizers of data/features.py, restated) --------------
def hash_bow(tokens: np.ndarray, n_features: int) -> np.ndarray:
    """l2-normalised hashed bag-of-words counts of one document."""
    idx = (tokens.astype(np.int64) * _HASH_PRIME % (1 << 31)) % n_features
    feats = np.bincount(idx, minlength=n_features).astype(np.float32)
    norm = np.linalg.norm(feats)
    return feats / norm if norm > 0 else feats


def hash_ids(tokens: np.ndarray, vocab: int, max_len: int) -> np.ndarray:
    """Hashed token ids of the first ``max_len`` tokens, 0 = pad."""
    tokens = tokens[:max_len]
    ids = (tokens.astype(np.int64) * _HASH_PRIME % (1 << 31)) % (vocab - 1) + 1
    out = np.zeros((max_len,), np.int32)
    out[:len(ids)] = ids
    return out


def featurize(level: dict, docs, n_features: int) -> np.ndarray:
    """One level's input rows for ``docs``."""
    if not len(docs):
        return np.zeros((0,))
    if level["kind"] == "lr":
        return np.stack([hash_bow(d, n_features) for d in docs])
    s = level["spec"]
    return np.stack([hash_ids(d, s["vocab"], s["max_len"]) for d in docs])


# -- weights ---------------------------------------------------------------
def _dense(key, d_in, d_out):
    return jax.random.truncated_normal(key, -2.0, 2.0, (d_in, d_out),
                                       jnp.float32) * d_in ** -0.5


def _tf_layers(key, n, d, f):
    out = []
    for k in jax.random.split(key, n):
        ks = jax.random.split(k, 6)
        out.append({"wq": _dense(ks[0], d, d), "wk": _dense(ks[1], d, d),
                    "wv": _dense(ks[2], d, d), "wo": _dense(ks[3], d, d),
                    "w1": _dense(ks[4], d, f), "w2": _dense(ks[5], f, d),
                    "ln1": jnp.ones((d,), jnp.float32),
                    "ln2": jnp.ones((d,), jnp.float32)})
    return out


def _head(key, d, C):
    return {"cls_w": _dense(key, d, C), "cls_b": jnp.zeros((C,), jnp.float32)}


def _student(key, kind: str, s: dict, C: int, n_features: int):
    ks = jax.random.split(key, 8)
    if kind == "lr":
        return {"w": jax.random.normal(ks[0], (n_features, C)) * 0.1,
                "b": jnp.zeros((C,), jnp.float32)}
    d = s["d_model"]
    if kind == "tinytf":
        return {"embed": jax.random.normal(ks[0], (s["vocab"], d)) * 0.02,
                "pos": jax.random.normal(ks[1], (s["max_len"], d)) * 0.02,
                "layers": _tf_layers(ks[2], s["n_layers"], d, s["d_ff"]),
                **_head(ks[3], d, C)}
    raise ValueError(f"unknown level kind {kind!r}")


def _deferral(key, C: int, hidden: int, init_open: float):
    d_in = C + 2
    k1, k2 = jax.random.split(key)
    return {"w1": jax.random.normal(k1, (d_in, hidden)) * d_in ** -0.5,
            "b1": jnp.zeros((hidden,), jnp.float32),
            "w2": jax.random.normal(k2, (hidden, 1)) * hidden ** -0.5,
            "b2": jnp.full((1,), init_open, jnp.float32)}


def make_weights(cascade: dict, seed: int):
    """Every level's (student, deferral) tree and the expert's, from the
    seed, on the default device, in one jitted call."""
    C = cascade["n_classes"]
    nf = cascade["n_features"]
    dfr = cascade["deferral"]
    levels = cascade["levels"]

    def make(key):
        keys = jax.random.split(key, len(levels) + 1)
        return {"levels": [
            {"student": _student(jax.random.fold_in(k, 0), lv["kind"],
                                 lv.get("spec"), C, nf),
             "deferral": _deferral(jax.random.fold_in(k, 1), C,
                                   dfr["hidden"], dfr["init_open"])}
            for k, lv in zip(keys, levels)],
            "expert": _student(keys[-1], "tinytf", cascade["expert"], C, nf)}

    return jax.jit(make)(jax.random.PRNGKey(seed % (2 ** 31)))


# -- reference forwards ----------------------------------------------------
def _fp8(x):
    """``x`` rounded to float8 e4m3 under one per-tensor scale (its
    largest magnitude to 448, e4m3's largest), back in ``x``'s dtype.
    The gradient passes straight through."""
    amax = jnp.max(jnp.abs(x))
    s = jnp.where(amax > 0, 448.0 / amax, 1.0).astype(x.dtype)
    q = (x * s).astype(jnp.float8_e4m3fn).astype(x.dtype) / s
    return x + jax.lax.stop_gradient(q - x)


ROUNDINGS = {None: lambda x: x, "fp8": _fp8}


def _mm(a, b, rnd):
    return rnd(a) @ rnd(b)


def _ein(spec, a, b, rnd):
    return jnp.einsum(spec, rnd(a), rnd(b))


def _ln(x, scale):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + 1e-6) * scale


def _attend(q, k, v, bias, rnd):
    """q, k, v: (B, L, H, hd); bias broadcast to (B, H, L, L)."""
    hd = q.shape[-1]
    s = _ein("bqhd,bkhd->bhqk", q, k, rnd) * hd ** -0.5 + bias
    return _ein("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v, rnd)


def _tf_stack(p, h, n_heads, bias, rnd):
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


def tinytf_logits(p, tokens, spec, rnd=ROUNDINGS[None]):
    """Bidirectional pre-LN encoder over hashed ids, masked mean pool."""
    L = tokens.shape[1]
    mask = tokens > 0
    h = p["embed"][tokens] + p["pos"][None, :L]
    bias = jnp.where(mask, 0.0, -1e30)[:, None, None, :]
    h = _tf_stack(p, h, spec["n_heads"], bias, rnd)
    m = mask.astype(h.dtype)[..., None]
    pooled = jnp.sum(h * m, axis=1) / jnp.maximum(jnp.sum(m, axis=1), 1.0)
    return _mm(pooled, p["cls_w"], rnd) + p["cls_b"]


def lr_logits(p, feats, spec=None, rnd=ROUNDINGS[None]):
    """Affine logits over hashed bag-of-words."""
    return _mm(feats, p["w"], rnd) + p["b"]


LOGITS = {"lr": lr_logits, "tinytf": tinytf_logits}


def deferral_prob(dp, probs, rnd=ROUNDINGS[None]):
    """f_i over the sorted probabilities, their max and normalised entropy."""
    p = jnp.clip(probs, 1e-9, 1.0)
    srt = jnp.sort(p, axis=-1)[..., ::-1]
    ent = -jnp.sum(p * jnp.log(p), -1, keepdims=True) / jnp.log(p.shape[-1])
    f = jnp.concatenate([srt, jnp.max(p, -1, keepdims=True), ent], -1)
    h = jnp.tanh(_mm(f, dp["w1"], rnd) + dp["b1"])
    return jax.nn.sigmoid((_mm(h, dp["w2"], rnd) + dp["b2"])[..., 0])


@functools.partial(jax.jit,
                   static_argnames=("kind", "spec_items", "rounding"))
def _level_forward(student, dparams, x, kind, spec_items, rounding):
    spec = dict(spec_items) if spec_items else None
    rnd = ROUNDINGS[rounding]
    probs = jax.nn.softmax(LOGITS[kind](student, x, spec, rnd), axis=-1)
    return probs, deferral_prob(dparams, probs, rnd)


def level_forward(level: dict, student, dparams, x, rounding=None):
    """``(probs, dprob)`` of one level on rows ``x`` (jitted per shape)."""
    spec = level.get("spec")
    items = tuple(sorted(spec.items())) if spec else None
    return _level_forward(student, dparams, jnp.asarray(x), level["kind"],
                          items, rounding)


@functools.partial(jax.jit, static_argnames=("spec_items", "rounding"))
def _expert_logits(p, ids, spec_items, rounding):
    return tinytf_logits(p, ids, dict(spec_items), ROUNDINGS[rounding])


def expert_logits(spec: dict, params, ids, rounding=None):
    """The stand-in expert's logits on hashed ids."""
    return _expert_logits(params, jnp.asarray(ids),
                          tuple(sorted(spec.items())), rounding)


def balance_expert(params, spec: dict, docs):
    """Centre the expert's two logits on ``docs`` so that its labels split
    the corpus about evenly (a random head alone leans to one class)."""
    ids = np.stack([hash_ids(d, spec["vocab"], spec["max_len"]) for d in docs])
    with jax.default_matmul_precision("highest"):
        lg = np.asarray(expert_logits(spec, params, ids))
    shift = float(np.median(lg[:, 1] - lg[:, 0]))
    b = np.asarray(params["cls_b"]).copy()
    b[0] += shift / 2
    b[1] -= shift / 2
    return {**params, "cls_b": jnp.asarray(b, jnp.float32)}
