"""Normalization ops (RMSNorm, bias-free LayerNorm). XLA fuses these into surrounding matmuls;
no Pallas needed."""

from __future__ import annotations

import jax.numpy as jnp


def rms_norm(x: jnp.ndarray, weight: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    """RMSNorm in fp32 accumulation, cast back to input dtype."""
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    normed = xf * jnp.reciprocal(jnp.sqrt(var + eps))
    return (normed * weight.astype(jnp.float32)).astype(dtype)


def layer_norm(x: jnp.ndarray, weight: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    """Mean-centred LayerNorm without bias (Cohere): (x - mean) /
    sqrt(var + eps) * w, in fp32, cast back to the input dtype."""
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    xc = xf - jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    normed = xc * jnp.reciprocal(jnp.sqrt(var + eps))
    return (normed * weight.astype(jnp.float32)).astype(dtype)
