"""``{"dist": "exponential"}``, of mean 1 (or ``"mean": m``): the gaps
of a Poisson process."""

import numpy as np


def inverse_cdf(spec, u):
    return -spec.get("mean", 1.0) * np.log1p(-u)
