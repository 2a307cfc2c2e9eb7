"""Rotary position embeddings.

The reference computes RoPE (cos, sin) once on chain-node 0 via HF
``LlamaRotaryEmbedding`` and *ships the tables along the chain* with every
activation hop (``/root/reference/utils/node_worker.py:149-153, 238-243,
267-272``). On TPU, recomputation beats communication: every stage derives
(cos, sin) locally from the scalar position carried in the decode state
(SURVEY.md §2 "cos/sin shipping becomes unnecessary").

Conventions match HF's ``rotate_half`` formulation so that weights converted
from HF checkpoints reproduce logits exactly. Includes Llama-3 frequency
scaling (``rope_type="llama3"``) for the Llama-3-8B config ladder entry
(BASELINE.md config #4) and YaRN (``rope_type="yarn"``, as ``deepseek_v3``
publishes it; the rotated width is ``cfg.rope_dim``).
"""

from __future__ import annotations

import math

import numpy as np
import jax.numpy as jnp

from ..models.config import ModelConfig, RopeScaling


def _llama3_scale_inv_freq(inv_freq: np.ndarray, rs: RopeScaling) -> np.ndarray:
    """Piecewise frequency scaling used by Llama-3.x (HF `_compute_llama3_parameters`)."""
    low_freq_wavelen = rs.original_max_position_embeddings / rs.low_freq_factor
    high_freq_wavelen = rs.original_max_position_embeddings / rs.high_freq_factor
    wavelen = 2 * np.pi / inv_freq
    # wavelen < high → keep; wavelen > low → scale by 1/factor; else smooth blend
    scaled = np.where(wavelen > low_freq_wavelen, inv_freq / rs.factor, inv_freq)
    smooth = (rs.original_max_position_embeddings / wavelen - rs.low_freq_factor) / (
        rs.high_freq_factor - rs.low_freq_factor
    )
    smoothed = (1 - smooth) / rs.factor * inv_freq + smooth * inv_freq
    is_medium = ~(wavelen < high_freq_wavelen) & ~(wavelen > low_freq_wavelen)
    return np.where(is_medium, smoothed, scaled)


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's magnitude correction ``0.1 · mscale · ln(factor) + 1`` (1 at
    factors up to 1)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_attention_factor(rs: RopeScaling) -> float:
    """What YaRN multiplies cos and sin by (HF ``_compute_yarn_parameters``):
    ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)`` when both
    are given, else ``mscale(factor)``."""
    if rs.mscale and rs.mscale_all_dim:
        return yarn_mscale(rs.factor, rs.mscale) / yarn_mscale(
            rs.factor, rs.mscale_all_dim
        )
    return yarn_mscale(rs.factor)


def _yarn_inv_freq(dim: int, base: float, rs: RopeScaling) -> np.ndarray:
    """YaRN (HF ``_compute_yarn_parameters``): frequencies that turn more
    than ``beta_fast`` times in the original context are kept, those that
    turn fewer than ``beta_slow`` times are divided by ``factor``, a linear
    ramp over the pair index in between."""
    def correction_dim(rotations):
        return dim * math.log(
            rs.original_max_position_embeddings / (rotations * 2 * math.pi)
        ) / (2 * math.log(base))

    low, high = correction_dim(rs.beta_fast), correction_dim(rs.beta_slow)
    if rs.truncate:
        low, high = math.floor(low), math.ceil(high)
    low, high = max(low, 0), min(high, dim - 1)
    if low == high:
        high += 0.001  # prevent singularity
    ramp = np.clip(
        (np.arange(dim // 2, dtype=np.float32) - low) / (high - low), 0, 1
    )
    pos_freqs = base ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    extrapolation = 1.0 - ramp
    return (
        (1.0 / (rs.factor * pos_freqs)) * (1 - extrapolation)
        + (1.0 / pos_freqs) * extrapolation
    )


def inv_frequencies(
    cfg: ModelConfig, theta: float | None = None, dim: int | None = None,
) -> np.ndarray:
    """Static (trace-time) inverse frequencies, shape [rope_dim/2], fp32.
    ``theta`` overrides ``cfg.rope_theta`` (a model whose kinds of layer
    rotate at bases of their own), ``dim`` the rotated width (an indexer's
    vectors, narrower than a head)."""
    d = cfg.rope_dim if dim is None else dim
    rs = cfg.rope_scaling
    theta = cfg.rope_theta if theta is None else theta
    if rs is not None and rs.rope_type == "yarn":
        return _yarn_inv_freq(d, theta, rs).astype(np.float32)
    inv_freq = 1.0 / (
        theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
    ).astype(np.float64)
    if rs is not None and rs.rope_type == "llama3":
        inv_freq = _llama3_scale_inv_freq(inv_freq, rs)
    return inv_freq.astype(np.float32)


def rope_cos_sin(
    positions: jnp.ndarray, cfg: ModelConfig, dtype=jnp.float32,
    theta: float | None = None, dim: int | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """cos/sin tables for absolute ``positions`` (any shape ``[...]``).

    Returns ``cos, sin`` of shape ``[..., head_dim]`` (HF layout: the half
    frequencies tiled twice, consumed by :func:`apply_rope`).
    """
    inv_freq = jnp.asarray(inv_frequencies(cfg, theta, dim))  # [D/2]
    freqs = positions.astype(jnp.float32)[..., None] * inv_freq  # [..., D/2]
    emb = jnp.concatenate([freqs, freqs], axis=-1)  # [..., D]
    cos, sin = jnp.cos(emb), jnp.sin(emb)
    rs = cfg.rope_scaling
    if rs is not None and rs.rope_type == "yarn":
        factor = yarn_attention_factor(rs)
        if factor != 1.0:
            cos, sin = cos * factor, sin * factor
    return cos.astype(dtype), sin.astype(dtype)


def apply_rope(
    x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray
) -> jnp.ndarray:
    """Rotate ``x: [B, S, N, D]`` by per-position ``cos/sin: [B, S, R]``:
    the first ``R`` of the ``D`` dims by halves, the rest pass (partial
    rotary; ``R == D`` rotates the whole head)."""
    R = cos.shape[-1]
    if R < x.shape[-1]:
        return jnp.concatenate(
            [apply_rope(x[..., :R], cos, sin), x[..., R:]], axis=-1
        )
    c = cos[:, :, None, :].astype(jnp.float32)
    s = sin[:, :, None, :].astype(jnp.float32)
    x32 = x.astype(jnp.float32)
    half = x.shape[-1] // 2
    rotated = jnp.concatenate([-x32[..., half:], x32[..., :half]], axis=-1)
    return (x32 * c + rotated * s).astype(x.dtype)
