"""Weights from the seed, and the plain float32 reference of every level.

Nothing here imports the program.  The weight trees have the program's
layout (``models/students.py``, ``core/deferral.py``) so that the
benchmark can install them, but they are drawn by this file from
``--seed`` in one jitted call.  What a level or the expert is (its
weights, forward and input rows) is its kind's file, ``bench/kinds/``;
this file holds the deferral gate and what every kind shares.

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

import kinds

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


def rows(kind: str, spec, docs, n_features: int) -> np.ndarray:
    """The input rows of ``docs`` for a level or expert of ``kind``."""
    featurize = kinds.reference(kind).featurize
    return np.stack([featurize(spec, d, n_features) for d in docs])


def featurize(level: dict, docs, n_features: int) -> np.ndarray:
    """One level's input rows for ``docs``."""
    if not len(docs):
        return np.zeros((0,))
    return rows(level["kind"], level.get("spec"), docs, n_features)


# -- weights ---------------------------------------------------------------
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
    expert = cascade["expert"]

    def make(key):
        keys = jax.random.split(key, len(levels) + 1)
        return {"levels": [
            {"student": kinds.reference(lv["kind"]).weights(
                jax.random.fold_in(k, 0), lv.get("spec"), C, nf),
             "deferral": _deferral(jax.random.fold_in(k, 1), C,
                                   dfr["hidden"], dfr["init_open"])}
            for k, lv in zip(keys, levels)],
            "expert": kinds.reference(expert["kind"]).weights(
                keys[-1], expert, C, nf)}

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
    logits = kinds.reference(kind).logits(student, x, spec, rnd)
    probs = jax.nn.softmax(logits, axis=-1)
    return probs, deferral_prob(dparams, probs, rnd)


def level_forward(level: dict, student, dparams, x, rounding=None):
    """``(probs, dprob)`` of one level on rows ``x`` (jitted per shape)."""
    spec = level.get("spec")
    items = tuple(sorted(spec.items())) if spec else None
    return _level_forward(student, dparams, jnp.asarray(x), level["kind"],
                          items, rounding)


@functools.partial(jax.jit, static_argnames=("spec_items", "rounding"))
def _expert_logits(p, x, spec_items, rounding):
    spec = dict(spec_items)
    return kinds.reference(spec["kind"]).logits(p, x, spec,
                                                ROUNDINGS[rounding])


def expert_logits(spec: dict, params, x, rounding=None):
    """The expert's logits on its input rows ``x``."""
    return _expert_logits(params, jnp.asarray(x),
                          tuple(sorted(spec.items())), rounding)


def balance_expert(params, cascade: dict, docs):
    """Centre the expert's two logits on ``docs`` so that its labels split
    the corpus about evenly (a random head alone leans to one class)."""
    spec = cascade["expert"]
    x = rows(spec["kind"], spec, docs, cascade["n_features"])
    with jax.default_matmul_precision("highest"):
        lg = np.asarray(expert_logits(spec, params, x))
    shift = float(np.median(lg[:, 1] - lg[:, 0]))
    return kinds.reference(spec["kind"]).balance(params, spec, shift)
