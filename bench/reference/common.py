"""What the reference models share."""
from __future__ import annotations

import jax.numpy as jnp


def xent(logits, y, mask):
    """Masked mean softmax cross-entropy, in the logits' dtype."""
    z = logits - jnp.max(logits, axis=-1, keepdims=True)
    logp = z - jnp.log(jnp.sum(jnp.exp(z), axis=-1, keepdims=True))
    nll = -jnp.take_along_axis(logp, y[:, None], axis=-1)[:, 0]
    return (nll * mask).sum() / jnp.maximum(mask.sum(), 1)
