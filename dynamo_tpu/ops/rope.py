"""Rotary position embeddings (RoPE), Llama-style half-rotation layout.

Computed on the fly from positions — no precomputed cos/sin tables to ship
around, and XLA folds the trig into the attention fusion. Llama-3.1+
long-context checkpoints apply frequency-dependent scaling
(`rope_type: llama3`): low-frequency components are stretched by
``factor`` while high-frequency ones stay put, with a smooth ramp between
the two wavelength bands — without it, a 3.1/3.2 checkpoint decodes
garbage past the original 8k positions.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax.numpy as jnp


@dataclass(frozen=True)
class RopeScaling:
    """HF `rope_scaling` block: `llama3` frequency bands or `yarn`
    (DeepSeek-V2/V3/R1 long-context: NTK-by-parts interpolation with a
    log-scaled attention-temperature correction, `mscale`)."""

    kind: str = "llama3"
    factor: float = 8.0
    original_max_position: int = 8192
    # llama3 band parameters
    low_freq_factor: float = 1.0
    high_freq_factor: float = 4.0
    # yarn parameters
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0

    @staticmethod
    def from_hf(d: dict | None) -> "RopeScaling | None":
        if not d:
            return None
        kind = d.get("rope_type", d.get("type", "llama3"))
        if kind == "default":
            return None  # HF semantics: explicitly no scaling
        if kind == "llama3":
            return RopeScaling(
                kind="llama3",
                factor=float(d.get("factor", 8.0)),
                low_freq_factor=float(d.get("low_freq_factor", 1.0)),
                high_freq_factor=float(d.get("high_freq_factor", 4.0)),
                original_max_position=int(
                    d.get("original_max_position_embeddings", 8192)
                ),
            )
        if kind == "linear":
            return RopeScaling(kind="linear", factor=float(d.get("factor", 1.0)))
        if kind == "yarn":
            return RopeScaling(
                kind="yarn",
                factor=float(d.get("factor", 1.0)),
                original_max_position=int(
                    d.get("original_max_position_embeddings", 4096)
                ),
                beta_fast=float(d.get("beta_fast", 32.0)),
                beta_slow=float(d.get("beta_slow", 1.0)),
                mscale=float(d.get("mscale", 1.0)),
                mscale_all_dim=float(d.get("mscale_all_dim", 0.0)),
            )
        raise ValueError(f"unsupported rope_scaling {d!r}")

    def attn_mscale(self) -> float:
        """Score-scale multiplier DeepSeek folds into the softmax scale
        under yarn (applied as a q multiplier in models/llama.py
        _qkv_mla): yarn_get_mscale(factor, mscale_all_dim)."""
        if self.kind != "yarn":
            return 1.0
        return _yarn_mscale(self.factor, self.mscale_all_dim)

    def embed_mscale(self) -> float:
        """cos/sin magnitude correction baked into the rotary embedding
        (HF DeepseekV2YarnRotaryEmbedding: mscale / mscale_all_dim ratio —
        1.0 on shipped DeepSeek configs where the two are equal)."""
        if self.kind != "yarn":
            return 1.0
        return _yarn_mscale(self.factor, self.mscale) / _yarn_mscale(
            self.factor, self.mscale_all_dim
        )


def _yarn_mscale(scale: float, mscale: float) -> float:
    if scale <= 1.0 or mscale <= 0.0:
        return 1.0
    return 0.1 * mscale * math.log(scale) + 1.0


def _scaled_freqs(freqs: jnp.ndarray, s: RopeScaling) -> jnp.ndarray:
    if s.kind == "yarn":
        return _yarn_freqs(freqs, s)
    if s.kind == "linear":
        # Plain position interpolation (Gemma-3 global layers: factor 8).
        return freqs / s.factor
    # Frequency-dependent stretch (the Llama-3.1 formula): wavelengths
    # shorter than the high-freq band keep their frequency, longer than the
    # low-freq band divide by `factor`, and the band between ramps smoothly.
    wavelen = 2.0 * math.pi / freqs
    low_wl = s.original_max_position / s.low_freq_factor
    high_wl = s.original_max_position / s.high_freq_factor
    smooth = (s.original_max_position / wavelen - s.low_freq_factor) / (
        s.high_freq_factor - s.low_freq_factor
    )
    mid = (1.0 - smooth) * freqs / s.factor + smooth * freqs
    return jnp.where(
        wavelen < high_wl, freqs, jnp.where(wavelen > low_wl, freqs / s.factor, mid)
    )


def _yarn_freqs(freqs: jnp.ndarray, s: RopeScaling) -> jnp.ndarray:
    """YaRN NTK-by-parts: high-frequency dims (below the beta_fast
    correction point) keep the original frequency (extrapolation),
    low-frequency dims (above beta_slow) interpolate by 1/factor, with a
    linear ramp between (the HF DeepseekV2YarnRotaryEmbedding recipe)."""
    half = freqs.shape[0]
    dim = 2 * half
    # theta recovered from the frequency ladder: freqs[i] = theta^(-i/half)
    # => log(theta) = -log(freqs[1]) * half ... derive via the ladder ratio.
    log_theta = -jnp.log(freqs[1]) * half if half > 1 else jnp.float32(0.0)

    def correction_dim(num_rotations):
        return (
            dim
            * jnp.log(s.original_max_position / (num_rotations * 2 * math.pi))
        ) / (2 * log_theta)

    low = jnp.floor(correction_dim(s.beta_fast))
    high = jnp.ceil(correction_dim(s.beta_slow))
    # HF yarn_find_correction_range clamps low/high to [0, dim-1]; only
    # `low` additionally needs the half-1 bound (it indexes the ramp
    # start). Clamping `high` to half-1 would steepen the interpolation
    # ramp whenever beta_slow's correction dim exceeds half (large
    # original_max_position / small base) and diverge from checkpoints.
    low = jnp.clip(low, 0, half - 1)
    high = jnp.clip(high, 0, dim - 1)
    ramp = jnp.clip(
        (jnp.arange(half, dtype=jnp.float32) - low)
        / jnp.maximum(high - low, 1e-3),
        0.0,
        1.0,
    )
    extrapolation_mask = 1.0 - ramp
    return freqs / s.factor * (1.0 - extrapolation_mask) + (
        freqs * extrapolation_mask
    )


def _angles(
    positions: jnp.ndarray,
    head_dim: int,
    theta: float,
    scaling: RopeScaling | None = None,
) -> tuple:
    """positions [...]: returns cos/sin of shape [..., head_dim//2]."""
    half = head_dim // 2
    freqs = jnp.exp(
        -jnp.log(theta) * (jnp.arange(half, dtype=jnp.float32) / half)
    )
    if scaling is not None:
        freqs = _scaled_freqs(freqs, scaling)
    ang = positions.astype(jnp.float32)[..., None] * freqs
    m = scaling.embed_mscale() if scaling is not None else 1.0
    return jnp.cos(ang) * m, jnp.sin(ang) * m


def apply_rope(
    x: jnp.ndarray,
    positions: jnp.ndarray,
    theta: float = 10000.0,
    scaling: RopeScaling | None = None,
    interleaved: bool = False,
) -> jnp.ndarray:
    """Rotate q or k. x: [..., n_heads, head_dim]; positions broadcastable to
    x.shape[:-2]. ``interleaved``: pair i is channels (2i, 2i + 1) (HF
    rope_gptj) and not (i, i + head_dim / 2)."""
    head_dim = x.shape[-1]
    cos, sin = _angles(positions, head_dim, theta, scaling)
    cos = cos[..., None, :]  # broadcast over heads
    sin = sin[..., None, :]
    xf = x.astype(jnp.float32)
    if interleaved:
        x1, x2 = xf[..., 0::2], xf[..., 1::2]
        out = jnp.stack(
            [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
        ).reshape(x.shape)
        return out.astype(x.dtype)
    x1, x2 = jnp.split(xf, 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)
