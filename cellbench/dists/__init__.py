"""Distributions a traffic mix can name, one module each:
``inverse_cdf(spec, u)`` gives the values at the quantiles ``u`` (a
numpy array in (0, 1)).  ``cellbench.loadgen.quantile_set`` finds a
module by the ``dist`` key of a mix's block; adding one is adding a
file."""
