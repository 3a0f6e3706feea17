"""``{"dist": "lognormal", "median": m, "sigma": s}``."""

import statistics

import numpy as np

_NORMAL = statistics.NormalDist()


def inverse_cdf(spec, u):
    z = np.array([_NORMAL.inv_cdf(float(x)) for x in u])
    return spec["median"] * np.exp(spec["sigma"] * z)
