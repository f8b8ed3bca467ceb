"""Logistic regression over hashed bag-of-words counts (the ladder's first
level), trained by online gradient descent."""
import jax
import jax.numpy as jnp
import numpy as np

import model

OPTIMIZER = "ogd"
TINY = {}


def weights(key, spec, n_classes: int, n_features: int):
    ks = jax.random.split(key, 8)
    return {"w": jax.random.normal(ks[0], (n_features, n_classes)) * 0.1,
            "b": jnp.zeros((n_classes,), jnp.float32)}


def logits(p, feats, spec, rnd):
    """Affine logits over hashed bag-of-words."""
    return rnd(feats) @ rnd(p["w"]) + p["b"]


def featurize(spec, doc, n_features: int) -> np.ndarray:
    return model.hash_bow(doc, n_features)


def row(spec, n_features: int):
    return (n_features,), np.float32


def flops(spec, n_classes: int, n_features: int) -> float:
    return 2.0 * n_features * n_classes
