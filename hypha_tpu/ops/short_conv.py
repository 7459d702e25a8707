"""The core of LFM2's gated short convolution: two gates around a depthwise
causal convolution of a few taps a channel.

``z = B * x``; ``c_t = sum_j w[j] * z_{t - (taps - 1) + j}`` with ``z`` zero
before the sequence's start (tap ``taps - 1`` weighs the current position,
tap 0 the oldest); ``y = C * c``. The gates carry no activation. Plain
``jax.numpy``: XLA fuses the shifts and products into a few elementwise
passes, which is all a bandwidth-bound operator of three taps asks for. The
operator's two projections are the caller's (``models/lfm2_moe.py``).
``causal_taps`` is the convolution alone, which Mamba's layer
(``models/phi4flash.py``: 4 taps, a bias and SiLU, no gates) shares.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["causal_taps", "short_conv"]


def causal_taps(z: jnp.ndarray, taps: jnp.ndarray) -> jnp.ndarray:
    """``z`` [B, S, D], ``taps`` [K, D] -> ``c_t = sum_j taps[j] * z_{t - (K - 1) + j}``."""
    k, s = taps.shape[0], z.shape[1]
    w = taps.astype(z.dtype)
    padded = jnp.pad(z, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(w[j] * padded[:, j:j + s] for j in range(k))


def short_conv(b: jnp.ndarray, c: jnp.ndarray, x: jnp.ndarray, taps: jnp.ndarray) -> jnp.ndarray:
    """``b``, ``c``, ``x`` [B, S, D] (the in-projection's three parts, in the
    source's order), ``taps`` [K, D] -> [B, S, D] in the inputs' type."""
    with jax.named_scope("short_conv"):
        return c * causal_taps(b * x, taps)
