"""The YaRN rotary table, for the families whose configurations give one
(``models/window_moe.py``'s full layers, ``models/latent_moe.py``'s
rotary part)."""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

import numpy as np


@dataclasses.dataclass(frozen=True)
class YarnRope:
    """A YaRN rotary table from the six numbers a configuration gives.
    ``attention_factor`` None is 0.1 ln(factor) + 1."""

    theta: float
    factor: float
    original_max_position: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: Optional[float] = None

    @property
    def cos_sin_scale(self) -> float:
        """What the table's cos and sin are multiplied by."""
        if self.attention_factor is not None:
            return self.attention_factor
        return 0.1 * math.log(self.factor) + 1.0


def yarn_inv_freq(rope: YarnRope, head_dim: int) -> np.ndarray:
    """The blended inverse frequencies: ``theta^(-2i/d)`` where a
    dimension turns more than ``beta_fast`` times over the original
    context, the same over ``factor`` where it turns fewer than
    ``beta_slow`` times, a linear ramp over the dimensions between the
    two correction dims (rounded down and up to whole dimensions)."""
    half = head_dim // 2
    extrapolation = rope.theta ** -(np.arange(half, dtype=np.float64) / half)
    interpolation = extrapolation / rope.factor

    def correction_dim(rotations):
        return (head_dim * math.log(rope.original_max_position
                                    / (rotations * 2 * math.pi))
                / (2 * math.log(rope.theta)))

    low = max(math.floor(correction_dim(rope.beta_fast)), 0)
    high = min(math.ceil(correction_dim(rope.beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(half) - low) / (high - low), 0.0, 1.0)
    return interpolation * ramp + extrapolation * (1.0 - ramp)
