"""Rotary position tables that more than one model computes.

``yarn_inv_freq``: YaRN's blend of the plain and the interpolated rotary
frequencies, as DeepSeek-V3's code and Hugging Face's
``_compute_yarn_parameters`` (``truncate`` true) compute it: below the
correction range found from ``beta_fast`` the plain frequency, above the
one from ``beta_slow`` the frequency divided by ``factor``, a linear ramp
between.  ``models/mistral4.py`` (the rotary part of a latent key) and
``models/mellum.py`` (the layers that see everything) call it with their
own sizes; what either puts on cos and sin or on the softmax scale is its
own.
"""
import math

import numpy as np


def yarn_inv_freq(dim, base, factor, original_max_position_embeddings,
                  beta_fast, beta_slow):
    """(dim / 2,) float64."""
    exponent = np.arange(0, dim, 2, dtype=np.float64) / dim
    plain = 1.0 / base ** exponent
    stretched = plain / factor

    def correction_dim(rotations):
        return dim * math.log(original_max_position_embeddings
                              / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / max(high - low, 0.001), 0.0, 1.0)
    keep_plain = 1.0 - ramp
    return stretched * (1.0 - keep_plain) + plain * keep_plain
