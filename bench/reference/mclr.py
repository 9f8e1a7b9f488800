"""MCLR, the convex model of FedProx and FedSAE: logits = x W + b, trained
on the masked mean softmax cross-entropy.  The initial weights follow the
program's documented recipe (W ~ 0.01 N(0, 1) from the first half of the
key, b = 0), so the reference starts where the program does."""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from reference.common import xent


def init(cfg: Dict, key):
    kw, _ = jax.random.split(key)
    d, c = cfg["n_features"], cfg["n_classes"]
    return {"w": jax.random.normal(kw, (d, c)) * 0.01, "b": jnp.zeros((c,))}


def loss(p, x, y, mask):
    return xent(x @ p["w"] + p["b"], y, mask)
