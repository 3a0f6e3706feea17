"""The ``afmoe`` family on the training path, at a small size on the
CPU: forward, loss and gradients against the plain reference
(``cellbench/reference/afmoe.py``; a window shorter than the sequence,
both layer kinds, a dense layer, grouped-query attention);
``make_train_step`` for the family against three reference steps, the
balance rule and the counters among them; and the trainable expert
layer (``held_experts_ffn(buffer_rows=...)``): the shares of an expert
group add up in the backward too, poisoned dead rows change no bit of
any gradient, a routing that sends every assignment to held experts
drops none, whatever the buffer, and the one-pass combine of a chunk's
rows (``_sum_own``, plain and through ``apex_moe_combine``) against the
``top_k`` gathers it replaced."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models import afmoe
from apex_tpu.transformer import expert_parallel as ep
from cellbench import weights_afmoe
from cellbench.reference import afmoe as reference

CONF = {
    "vocab_size": 128, "hidden_size": 64, "num_hidden_layers": 3,
    "num_dense_layers": 1,
    "layer_types": ["sliding_attention", "full_attention",
                    "sliding_attention"],
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "sliding_window": 8, "intermediate_size": 96,
    "moe_intermediate_size": 32, "num_experts": 4,
    "published": {"num_experts": 16}, "num_shared_experts": 1,
    "num_experts_per_tok": 2, "route_scale": 2.826,
    "load_balance_coeff": 0.001, "rms_norm_eps": 1e-5, "rope_theta": 10000}
HELD_START = 4


def _config(**more):
    kw = dict(num_experts=16, held_start=HELD_START, held_count=4,
              compute_dtype=jnp.float32)
    kw.update(more)
    return afmoe.AFMoEConfig.from_published(CONF, **kw)


def _tokens(seed=1, batch=2, seq=32):
    tok = jax.random.randint(jax.random.PRNGKey(seed), (batch, seq + 1), 0,
                             CONF["vocab_size"])
    return tok[:, :-1], tok[:, 1:]


def _params(config, key):
    w = weights_afmoe.weights(CONF, key)
    tree = weights_afmoe.to_program_tree(w, CONF)
    tree["state"] = afmoe.init_state(config)
    return w, tree


@pytest.mark.parametrize("variant", [
    dict(),                                                 # the plain twin
    dict(use_flash_attention=True, attn_impl="interpret", fused_ce=True,
         fused_ce_chunk=16, fused_ce_impl="interpret",
         expert_impl="interpret"),                          # every kernel
    dict(use_flash_attention=True, attn_impl="scan", fused_ce=True,
         fused_ce_chunk=16),                                # the scan twin
], ids=["dense", "kernels", "scan"])
def test_forward_loss_and_gradients_match_the_reference(variant):
    config = _config(**variant)
    w, params = _params(config, jax.random.PRNGKey(3))
    tokens, targets = _tokens()
    with jax.default_matmul_precision("highest"):
        (loss, aux), grads = jax.value_and_grad(
            lambda p: afmoe.loss_and_aux(p, tokens, targets, config),
            has_aux=True, allow_int=True)(params)
        ref_loss, ref_grads, ref_loads = reference.loss_and_grads(
            w, jnp.zeros((2, 16)), tokens, targets,
            reference.grad_function(CONF, HELD_START))
    assert abs(float(loss) - float(ref_loss)) < 2e-4
    np.testing.assert_array_equal(aux["load"], ref_loads)
    held = np.asarray(ref_loads)[:, HELD_START:HELD_START + 4].sum()
    assert int(aux["counted"][0]) == held       # none dropped
    got = weights_afmoe.to_published(
        {k: v for k, v in grads.items() if k != "state"}, CONF)
    for name, ref in ref_grads.items():
        scale = float(jnp.max(jnp.abs(ref))) + 1e-12
        # the fused cross-entropy kernels multiply in bfloat16
        tol = 5e-3 if variant.get("fused_ce_impl") == "interpret" else 2e-3
        assert float(jnp.max(jnp.abs(got[name] - ref))) / scale < tol, name


def test_the_train_step_follows_three_reference_steps():
    """``make_train_step`` over the family's whole tree: the optimizer
    holds the trainable part alone, the routers' biases move by the
    balance rule AFTER it, the counters count."""
    from apex_tpu.models.gpt import make_train_step
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.transformer import parallel_state as ps

    config = _config()
    family = config.train_family()
    w, params = _params(config, jax.random.PRNGKey(5))
    hyper = dict(lr=1e-3, betas=(0.9, 0.95), eps=1e-8, weight_decay=0.1)
    optimizer = FusedAdam(**hyper, param_group_fn=family.weight_decay_group,
                          group_hypers={"gain": {"weight_decay": 0.0}})
    state = optimizer.init(family.split(params)[0])
    assert "state" not in state.exp_avg         # in no optimizer's tree
    mesh = ps.initialize_model_parallel(
        tensor_model_parallel_size_=1, pipeline_model_parallel_size_=1,
        devices=jax.devices()[:1])
    step = make_train_step(config, optimizer, mesh)
    batches = [_tokens(seed) for seed in (11, 12, 13)]
    losses = []
    with jax.default_matmul_precision("highest"):
        for tokens, targets in batches:
            params, state, loss = step(params, state, tokens, targets)
            losses.append(float(loss))
        ref = reference.train_steps(         # (it donates its weights)
            jax.tree.map(jnp.copy, w), batches, CONF, lr=1e-3, beta1=0.9, beta2=0.95, eps_adam=1e-8,
            weight_decay=0.1, held_start=HELD_START)
    np.testing.assert_allclose(losses, [float(x) for x in ref["losses"]],
                               atol=5e-5)
    np.testing.assert_allclose(params["state"]["router_bias"], ref["biases"],
                               atol=1e-7)
    np.testing.assert_array_equal(params["state"]["last_load"],
                                  ref["loads"][-1])
    counters = dict(zip(afmoe.COUNTER_NAMES,
                        np.asarray(params["state"]["counters"]).tolist()))
    loads = np.stack([np.asarray(x) for x in ref["loads"]])
    assert counters["steps"] == 3 and counters["moe_spill_chunks"] == 0
    assert counters["moe_assignments_all"] == loads.sum() == 3 * 2 * 64 * 2
    assert counters["moe_assignments_held"] == \
        loads[:, :, HELD_START:HELD_START + 4].sum()
    assert counters["moe_load_max"] == loads.max(-1).sum()
    got = weights_afmoe.to_published(family.split(params)[0], CONF)
    for name, after in ref["params"].items():
        moved = float(jnp.max(jnp.abs(after - w[name])))
        assert float(jnp.max(jnp.abs(got[name] - after))) < 0.02 * moved \
            + 1e-7, name


def test_what_a_family_does_not_have_yet_raises():
    from apex_tpu.models.gpt import make_train_step
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.transformer import parallel_state as ps

    mesh = ps.initialize_model_parallel(
        tensor_model_parallel_size_=1, pipeline_model_parallel_size_=1,
        devices=jax.devices()[:1])
    for kw, why in ((dict(spmd="auto"), "spmd"),
                    (dict(cp_axis="dp"), "context"),
                    (dict(overlap_grad_sync=True), "overlap")):
        with pytest.raises(NotImplementedError, match=why):
            make_train_step(_config(), FusedAdam(), mesh, **kw)


# ---------------------------------------------- the trainable expert layer
T, H, F, E, K = 96, 32, 16, 128, 8
ROUTE = dict(top_k=K, n_group=1, topk_group=1, scale=2.826)


def _layer_params(held: range, seed=1):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    full = {"router": jax.random.normal(ks[0], (H, E)) * 0.5,
            "router_bias": jnp.zeros((E,)),
            "we_gate": jax.random.normal(ks[1], (E, H, F)) * 0.2,
            "we_up": jax.random.normal(ks[2], (E, H, F)) * 0.2,
            "we_down": jax.random.normal(ks[3], (E, F, H)) * 0.2}
    return {k: (v[held.start:held.stop] if k.startswith("we_") else v)
            for k, v in full.items()}


def _loss_and_grads(x, g, params, held, **kw):
    def f(x, params):
        out, counts = ep.held_experts_ffn(x, params, held, **ROUTE, **kw)
        return jnp.sum(out * g), (out, counts)

    (_, (out, counts)), grads = jax.value_and_grad(
        f, (0, 1), has_aux=True)(x, params)
    return out, counts, grads


def test_the_shares_add_up_in_the_backward_too():
    """Eight shares of 16 experts: the sum of their outputs and of
    their ``dx`` is the uncut layer's, each share's expert gradients
    are the uncut layer's for those experts, and the router's gradient
    is the sum of the shares'."""
    x = jax.random.normal(jax.random.PRNGKey(7), (T, H))
    g = jax.random.normal(jax.random.PRNGKey(8), (T, H))
    whole_out, _, (whole_dx, whole_dp) = _loss_and_grads(
        x, g, _layer_params(range(E)), range(E), impl="xla")
    out, dx, drouter, held_total = 0.0, 0.0, 0.0, 0
    for share in range(8):
        held = range(16 * share, 16 * share + 16)
        o, counts, (d, dp) = _loss_and_grads(
            x, g, _layer_params(held), held, impl="xla",
            buffer_rows=ep.expert_buffer_rows(T, K, 16, E, multiple=8))
        out, dx, drouter = out + o, dx + d, drouter + dp["router"]
        held_total += int(counts["assignments_held"])
        for k in ("we_gate", "we_up", "we_down"):
            np.testing.assert_allclose(
                dp[k], whole_dp[k][held.start:held.stop], atol=2e-5)
    assert held_total == T * K
    np.testing.assert_allclose(out, whole_out, atol=2e-5)
    np.testing.assert_allclose(dx, whole_dx, atol=2e-5)
    np.testing.assert_allclose(drouter, whole_dp["router"], atol=2e-5)


def test_poisoned_dead_rows_change_no_bit_of_any_gradient(monkeypatch):
    """Whatever a grouped matmul leaves in the rows past the live ones
    (undefined on the chip), in its output or in the cotangent it hands
    back for its rows, reaches no output and no gradient."""
    held = range(16, 32)
    x = jax.random.normal(jax.random.PRNGKey(7), (T, H))
    g = jax.random.normal(jax.random.PRNGKey(8), (T, H))
    params = _layer_params(held)
    kw = dict(impl="xla", buffer_rows=256)
    clean = _loss_and_grads(x, g, params, held, **kw)
    plain = ep._grouped_matmul

    @jax.custom_vjp
    def poisoned(rows, w, sizes):
        return _poison(plain(rows, w, sizes, "xla", True), sizes)

    def _poison(y, sizes):
        dead = jnp.arange(y.shape[0]) >= jnp.sum(sizes)
        return jnp.where(dead[:, None], jnp.nan, y)

    def fwd(rows, w, sizes):
        return poisoned(rows, w, sizes), (rows, w, sizes)

    def bwd(res, ct):
        rows, w, sizes = res
        _, vjp = jax.vjp(lambda r, w: plain(r, w, sizes, "xla", True),
                         rows, w)
        drows, dw = vjp(ct)
        return _poison(drows, sizes), dw, None

    poisoned.defvjp(fwd, bwd)
    monkeypatch.setattr(
        ep, "_grouped_matmul",
        lambda rows, w, sizes, impl, trainable=False: poisoned(rows, w,
                                                               sizes))
    dirty = _loss_and_grads(x, g, params, held, **kw)
    assert int(clean[1]["assignments_held"]) < 256      # there ARE dead rows
    for a, b in zip(jax.tree.leaves((clean[0], clean[2])),
                    jax.tree.leaves((dirty[0], dirty[2]))):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("buffer_rows", [64, 256, 1024])
def test_a_routing_that_sends_everything_to_held_experts_drops_none(
        buffer_rows):
    """The worst case for a share: a router that chooses held experts
    only.  Every one of ``T * top_k`` assignments is computed, in as
    many chunks as it takes, and the result is the uncut layer's."""
    held = range(0, 16)
    params = _layer_params(held)
    # the bias is for the choice only: push every choice into the share
    params["router_bias"] = jnp.where(jnp.arange(E) < 16, 10.0, 0.0)
    x = jax.random.normal(jax.random.PRNGKey(7), (T, H))
    g = jax.random.normal(jax.random.PRNGKey(8), (T, H))
    want = _loss_and_grads(x, g, params, held, impl="xla")
    got = _loss_and_grads(x, g, params, held, impl="xla",
                          buffer_rows=buffer_rows)
    counts = got[1]
    assert int(counts["assignments_held"]) == T * K == \
        int(counts["load"][:16].sum())
    assert int(counts["spill_chunks"]) == -(-T * K // buffer_rows) - 1
    assert int(counts["buffer_rows"]) >= T * K
    for a, b in zip(jax.tree.leaves((want[0], want[2])),
                    jax.tree.leaves((got[0], got[2]))):
        np.testing.assert_allclose(a, b, atol=3e-5)


def _combine_case(case, rows_per_chunk=128):
    """A chunk of a routing as ``_held_chunks`` hands it to the combine:
    ``(token, valid, slot, first_row)`` of chunk ``c`` of the held
    assignments of ``T`` tokens, sorted by expert as the layer sorts
    them."""
    expert = jax.random.randint(jax.random.PRNGKey(4), (T, K), 0, E)
    live = (expert >= 16) & (expert < 32)
    chunk = 0
    if case in ("all_held", "spill"):
        live = jnp.ones((T, K), bool)
        chunk = 1 if case == "spill" else 0
    elif case == "none_held":
        live = jnp.zeros((T, K), bool)
    elif case == "token_mask":
        live = live & (jax.random.uniform(jax.random.PRNGKey(5), (T,))
                       < 0.6)[:, None]
    A = T * K
    order = jnp.argsort(jnp.where(live, expert % 16, 16).reshape(A),
                        stable=True)
    slot = jnp.zeros((A,), jnp.int32).at[order].set(
        jnp.arange(A, dtype=jnp.int32)).reshape(T, K)
    first_row = chunk * rows_per_chunk
    pos = first_row + jnp.arange(rows_per_chunk)
    assignment = jnp.pad(order, (0, rows_per_chunk))[pos]
    return assignment // K, pos < jnp.sum(live), slot, first_row


@pytest.mark.parametrize("weighted", [True, False],
                         ids=["weighted", "unweighted"])
@pytest.mark.parametrize("impl", ["xla", "interpret"])
@pytest.mark.parametrize("case", ["eighth_held", "all_held", "none_held",
                                  "spill", "token_mask", "poisoned_dead"])
def test_the_one_pass_combine_is_the_sum_of_top_k_gathers(case, impl,
                                                          weighted):
    """``_sum_own`` against the combine it replaced, written out: a
    gather of (T, H) a slot out of the weighted float32 rows, added in
    slot order.  The plain form adds the same products in the same
    order: bit for bit.  The kernel adds them by expert inside the
    MXU's float32 accumulator: to the rounding of a ``top_k``-term
    sum.  Rows in both dtypes the layer computes in."""
    token, valid, slot, first_row = _combine_case(case)
    n_rows = token.shape[0]
    if case in ("all_held", "spill"):
        assert bool(jnp.all(valid))
        # a token's rows lie on both sides of the chunk's boundaries
        inside = (slot >= first_row) & (slot < first_row + n_rows)
        assert bool(jnp.any(jnp.any(inside, 1) & ~jnp.all(inside, 1)))
    elif case == "none_held":
        assert not bool(jnp.any(valid))
    else:
        assert 0 < int(jnp.sum(valid)) < n_rows     # there ARE dead rows
    keys = jax.random.split(jax.random.PRNGKey(6), 3)
    out = jax.random.normal(keys[0], (T, H))
    w = jnp.where(valid, jax.random.uniform(keys[1], (n_rows,)), 0.0) \
        if weighted else None
    local = ep._rows_of(slot, first_row, n_rows)
    for dtype in (jnp.bfloat16, jnp.float32):
        clean = jnp.where(valid[:, None], jax.random.normal(
            keys[2], (n_rows, H)).astype(dtype), 0)
        rows = jnp.where(valid[:, None], clean, jnp.nan) \
            if case == "poisoned_dead" else clean
        terms = clean.astype(jnp.float32) * (1.0 if w is None
                                             else w[:, None])
        total, size = 0.0, jnp.abs(out)
        for k in range(K):
            own = jnp.take(terms, local[:, k], axis=0, mode="fill",
                           fill_value=0)
            total, size = total + own, size + jnp.abs(own)
        got = ep._sum_own(out, rows, w, token, valid, slot, first_row, impl)
        assert got.dtype == jnp.float32
        if impl == "xla":
            np.testing.assert_array_equal(got, out + total)
        else:
            assert bool(jnp.all(jnp.abs(got - (out + total))
                                <= 4 * 2.0 ** -23 * size)), dtype


def test_the_combine_sweep_rehearses_on_the_cpu(capsys):
    """``benchmarks/moe_combine_sweep.py --interpret``: a line a
    candidate and routing, each with a timing and within float32
    rounding of the gathers that ran until PR 42."""
    import importlib.util
    import json
    from pathlib import Path

    spec = importlib.util.spec_from_file_location(
        "moe_combine_sweep", Path(__file__).resolve().parents[1]
        / "benchmarks" / "moe_combine_sweep.py")
    sweep = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(sweep)
    sweep.main(["--interpret", "--reps", "1"])
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    assert [(l["routing"], l["candidate"]) for l in lines] == [
        (r, c) for r in ("eighth", "all") for c in sweep.candidates(True)]
    assert all("ms" in l and "error" not in l for l in lines), lines
    assert all(l.get("max_err", 0) < 4 * 2.0 ** -23 for l in lines), lines
    eighth, full = lines[0], lines[len(lines) // 2]
    assert eighth["held_rows"] < eighth["rows"] == full["held_rows"]


def test_the_balance_rule_against_the_reference():
    load = jax.random.randint(jax.random.PRNGKey(2), (3, E), 0, 50)
    bias = jax.random.normal(jax.random.PRNGKey(3), (3, E)) * 0.01
    got = ep.balance_bias_update(bias, load, 0.001)
    np.testing.assert_allclose(
        got, reference.balance_update(bias, load, 0.001), atol=1e-8)
    # an expert over the mean goes down, one under it up, by the step
    mean = np.asarray(load).mean(-1, keepdims=True)
    np.testing.assert_allclose(
        np.asarray(got - bias), 0.001 * np.sign(mean - np.asarray(load)),
        atol=1e-8)
