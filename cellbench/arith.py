"""Metric arithmetic kept with the benchmark, where no later PR can
change it: percentiles, spreads, parameter counts and model FLOPs.

``model_flops_per_token`` is copied from
``apex_tpu/observability/goodput.py`` (6N + 12*L*S*H, the usual MFU
convention: no credit for recomputed operations, the attention term
counted over the full square).
"""

import math
import statistics
from typing import Dict, Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between
    order statistics; raises on an empty sample so that a metric with
    nothing to read is left out rather than printed as 0."""
    if not values:
        raise ValueError("percentile of an empty sample")
    xs = sorted(values)
    if len(xs) == 1:
        return float(xs[0])
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return float(xs[lo] + (xs[hi] - xs[lo]) * (pos - lo))


def iqr_share(values: Sequence[float]) -> float:
    """The spread the bounds are set from: distance between the first
    and third quartile (``statistics.quantiles(n=4)``) as a share of the
    median."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def gpt2_param_count(model: Dict) -> int:
    """Parameters of a GPT-2 shaped model with a tied head, from its
    sizes: embeddings, per block 4H^2 + 2*H*F matrices with biases and
    two LayerNorms, and the final LayerNorm."""
    H, L, V = model["n_embd"], model["n_layer"], model["vocab_size"]
    P, F = model["n_positions"], model.get("n_inner") or 4 * model["n_embd"]
    block = 4 * H * H + 4 * H + 2 * H * F + F + H + 4 * H
    return V * H + P * H + L * block + 2 * H


def model_flops_per_token(n_params: int, num_layers: int, seq: int,
                          hidden: int) -> float:
    """Training FLOPs per token: 6N for the forward and backward
    matrix multiplications plus 12*L*S*H for attention."""
    return 6.0 * n_params + 12.0 * num_layers * seq * hidden


def mfu_percent(tokens_per_s: float, flops_per_token: float, chips: int,
                peak_flops_per_s: float) -> float:
    return 100.0 * tokens_per_s * flops_per_token / (chips * peak_flops_per_s)
