"""The metric arithmetic kept with the benchmark."""

import json
import statistics

import pytest

from cellbench_tiny import REPO

from cellbench import arith


@pytest.mark.parametrize("q,want", [(0, 1.0), (50, 3.0), (100, 5.0),
                                    (90, 4.6), (25, 2.0)])
def test_percentile_interpolates(q, want):
    assert arith.percentile([5, 1, 4, 2, 3], q) == pytest.approx(want)


def test_percentile_of_nothing_raises():
    with pytest.raises(ValueError):
        arith.percentile([], 50)


def test_spread_is_the_quartile_distance_over_the_median():
    xs = [10.0, 10.2, 9.9, 10.1, 10.4, 9.8]
    q1, _, q3 = statistics.quantiles(xs, n=4)
    assert arith.iqr_share(xs) == pytest.approx(
        (q3 - q1) / statistics.median(xs))


@pytest.mark.parametrize("name,published_vocab,published_params", [
    # parameter counts of the released checkpoints at vocab 50257
    ("gpt2-medium-train", 50257, 354_823_168),
    ("gpt2-large-serve", 50257, 774_030_080),
])
def test_param_count_matches_the_published_models(name, published_vocab,
                                                  published_params):
    conf = json.loads((REPO / "cellbench" / "configs" / f"{name}.json")
                      .read_text())
    assert arith.gpt2_param_count(dict(conf, vocab_size=published_vocab)) \
        == published_params
    padded = arith.gpt2_param_count(conf)
    assert padded - published_params == \
        (conf["vocab_size"] - published_vocab) * conf["n_embd"]


def test_flops_per_token_and_mfu():
    n, L, S, H = 354_871_296, 24, 1024, 1024
    f = arith.model_flops_per_token(n, L, S, H)
    assert f == 6.0 * n + 12.0 * L * S * H
    # 30,000 tokens/s of such a model on one 197 TFLOP/s chip
    assert arith.mfu_percent(30_000, f, 1, 197e12) == pytest.approx(
        100 * 30_000 * f / 197e12)
    assert arith.mfu_percent(30_000, f, 4, 197e12) == pytest.approx(
        arith.mfu_percent(30_000, f, 1, 197e12) / 4)
