"""Lowered-IR invariants on the REAL train steps, via
``apex_tpu.analysis.lowered`` (the analyzer's jax-importing second
tier).

PR 4 proved these invariants one-off with inline HLO greps pinned to
the ZeRO optimizer's ``update`` in isolation; this band pins the same
contracts on ``gpt.make_train_step`` itself — the seam every refactor
actually goes through — so a step-builder change that silently drops
the per-bucket reduce-scatter plan, reintroduces a whole-tree flatten,
or loses donation coverage fails HERE, in CI, not as a perf regression
three benchmark rounds later.

Everything asserts on the .lower() artifact (trace only, no XLA
compile) except the compiled input_output_alias check, which is the
one fact that only materializes at compile time and rides the slow
tier.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from apex_tpu.analysis import lowered as lw
from apex_tpu.contrib.optimizers import DistributedFusedAdam
from apex_tpu.models.gpt import (
    GPTConfig, init_params, make_train_step, param_specs,
)
from apex_tpu.optimizers import FusedAdam
from apex_tpu.optimizers.fused_adam import AdamState

DP = 8

CFG = GPTConfig(
    vocab_size=64,
    hidden_size=32,
    num_layers=2,
    num_attention_heads=4,
    max_seq_len=16,
    compute_dtype=jnp.float32,
    checkpoint_layers=False,
)

#: splits the tiny fp32 tree into several buckets (clamps at one dtype
#: tile), so "per-bucket" is distinguishable from "whole-tree"
TINY_CAP_MB = 4096 / 2 ** 20


def _mesh(devices8):
    return Mesh(np.array(devices8).reshape(DP, 1), ("dp", "tp"))


def _data():
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, CFG.vocab_size, size=(DP, 16)))
    return tokens, jnp.roll(tokens, -1, axis=1)


def _zero_lowering(devices8, **opt_kw):
    params = init_params(CFG, jax.random.PRNGKey(0))
    opt = DistributedFusedAdam(lr=1e-2, axis_name="dp",
                               bucket_cap_mb=TINY_CAP_MB, **opt_kw)
    state = opt.init(params, world_size=DP)
    step = make_train_step(CFG, opt, _mesh(devices8), donate_state=True)
    tokens, targets = _data()
    return step.lower(params, state, tokens, targets), opt, params, state


class TestZeroTrainStep:
    """The bucket plan's collective structure, read off the full
    ``make_train_step`` lowering with a cap that forces >= 2 buckets."""

    def test_grad_sync_is_one_reduce_scatter_per_bucket(self, devices8):
        low, opt, _params, _state = _zero_lowering(devices8)
        n_buckets = len(opt._plan.buckets)
        assert n_buckets >= 2, "cap should split the fp32 bucket"
        txt = low.as_text()
        # exactly one grad reduce-scatter per bucket, ON the dp axis —
        # a refactor that reroutes grads through pmean (replicated
        # sync), fuses the buckets back into one collective, or moves
        # the scatter to another axis changes this per-axis count
        lw.assert_collective_axes(txt, "reduce_scatter", ("dp",),
                                  _mesh(devices8), minimum=n_buckets,
                                  maximum=n_buckets, dtype="f32")
        # params come back per bucket too, on the same axis
        lw.assert_collective_axes(txt, "all_gather", ("dp",),
                                  _mesh(devices8), minimum=n_buckets)

    def test_no_whole_tree_concat(self, devices8):
        """With >= 2 buckets nothing may concatenate the FULL flat
        param tree — the pre-bucket ``_flatten`` signature (one extra
        whole-model HBM round trip per step)."""
        low, _opt, params, _state = _zero_lowering(devices8)
        total = sum(int(np.prod(p.shape))
                    for p in jax.tree_util.tree_leaves(params))
        lw.assert_no_whole_tree_concat(low.as_text(), total)

    def test_step_donates_params_and_shard_state(self, devices8):
        """``donate_state=True`` must cover every param leaf AND every
        resident ZeRO shard (m/v/master per bucket + step) at the
        lowering level — a dropped donation re-inflates the step's peak
        by the state bytes ZeRO exists to shard away."""
        low, _opt, params, state = _zero_lowering(devices8)
        lw.assert_donation_covers(low, params, state, compiled=False)

    @pytest.mark.slow
    def test_step_donation_survives_compilation(self, devices8):
        """The compiled module's input_output_alias table actually
        aliases the donated buffers (XLA silently DROPS donations it
        cannot use — the declaration alone proves nothing)."""
        low, _opt, params, state = _zero_lowering(devices8)
        lw.assert_donation_covers(low, params, state, compiled=True)


class TestQuantizedZeroTrainStep:
    """The compressed-sync pins (ISSUE 6): the grad wire really is
    int8/fp8 at the lowering level, no fp32 whole-bucket gradient
    collective survives, and donation still covers every shard buffer
    INCLUDING the error-feedback residuals."""

    def test_int8_wire_one_reduce_scatter_per_bucket(self, devices8):
        low, opt, _params, _state = _zero_lowering(
            devices8, grad_sync_dtype="int8")
        n_buckets = len(opt._plan.buckets)
        assert n_buckets >= 2
        txt = low.as_text()
        lw.assert_collective_axes(txt, "reduce_scatter", ("dp",),
                                  _mesh(devices8), minimum=n_buckets,
                                  maximum=n_buckets, dtype="i8")
        lw.assert_collective_dtype(txt, "reduce_scatter", "f32",
                                   mode="none")
        lw.assert_collective_axes(txt, "all_gather", ("dp",),
                                  _mesh(devices8), minimum=n_buckets)

    def test_fp8_wire_element_types(self, devices8):
        for wire, hlo_dtype in (("float8_e4m3fn", "f8E4M3FN"),
                                ("float8_e5m2", "f8E5M2")):
            low, _opt, _p, _s = _zero_lowering(devices8,
                                               grad_sync_dtype=wire)
            txt = low.as_text()
            lw.assert_collective_dtype(txt, "reduce_scatter", hlo_dtype,
                                       mode="all")
            lw.assert_collective_dtype(txt, "reduce_scatter", "f32",
                                       mode="none")

    def test_no_whole_bucket_fp32_gradient_collective(self, devices8):
        """The scale psums are the ONLY fp32 all-reduces the grad sync
        adds, and they are block-vector sized (total/QBLOCK), never
        bucket-sized: an fp32 collective at any bucket's total would
        mean the narrow wire is being shadowed by a wide one."""
        import re

        from apex_tpu.contrib.optimizers._quantized_sync import QBLOCK

        low, opt, params, _state = _zero_lowering(
            devices8, grad_sync_dtype="int8")
        txt = low.as_text()
        for b in opt._plan.buckets:
            assert not re.search(
                r'(?:stablehlo|mhlo)\.(?:all_reduce|reduce_scatter)'
                r'"?.*?tensor<' + str(b.total) + r'xf32>', txt), (
                f"fp32 collective at whole-bucket size {b.total}")
            # the scale vector for this bucket IS small
            assert b.total // QBLOCK < b.total // 8
        total = sum(int(np.prod(p.shape))
                    for p in jax.tree_util.tree_leaves(params))
        lw.assert_no_whole_tree_concat(txt, total)

    def test_donation_covers_residuals(self, devices8):
        """Every residual bucket is a donated resident buffer like
        m/v/master: the state gains n_buckets leaves and the lowering
        declares them all donatable."""
        low, opt, params, state = _zero_lowering(
            devices8, grad_sync_dtype="int8")
        n_buckets = len(opt._plan.buckets)
        assert len(jax.tree_util.tree_leaves(state)) == 1 + 4 * n_buckets
        lw.assert_donation_covers(low, params, state, compiled=False)

    @pytest.mark.slow
    def test_residual_donation_survives_compilation(self, devices8):
        low, _opt, params, state = _zero_lowering(
            devices8, grad_sync_dtype="int8")
        lw.assert_donation_covers(low, params, state, compiled=True)


# ------------------------------------------------------- hierarchical sync
HIER_AXES = ("dp_out", "dp_in")


def _hier_mesh(devices8):
    return Mesh(np.array(devices8[:4]).reshape(2, 2, 1),
                ("dp_out", "dp_in", "tp"))


def _hier_lowering(devices8, **opt_kw):
    params = init_params(CFG, jax.random.PRNGKey(0))
    opt = DistributedFusedAdam(lr=1e-2, dp_axes=HIER_AXES,
                               bucket_cap_mb=TINY_CAP_MB, **opt_kw)
    state = opt.init(params, world_size=4,
                     axis_sizes={"dp_out": 2, "dp_in": 2, "tp": 1})
    step = make_train_step(CFG, opt, _hier_mesh(devices8),
                           dp_axis=HIER_AXES, donate_state=True)
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, CFG.vocab_size, size=(4, 16)))
    return (step.lower(params, state, tokens,
                       jnp.roll(tokens, -1, axis=1)), opt, params, state)


class TestHierarchicalZeroTrainStep:
    """The multi-hop sync pins (ISSUE 12): per bucket, EXACTLY one
    reduce-scatter on the fast inner axis and one on the slow outer
    axis — both at the wire dtype (the compressed wire never widens on
    the cross-slice hop) — the param all-gathers mirrored per hop,
    zero new whole-tree concats, and donation still covering every
    shard buffer including the error-feedback residuals.  All read off
    the real ``make_train_step(dp_axis=("dp_out", "dp_in"))`` lowering
    via the per-axis ``replica_groups`` filtering in
    ``analysis.lowered``."""

    def test_wide_wire_one_reduce_scatter_per_bucket_per_hop(self, devices8):
        low, opt, _params, _state = _hier_lowering(devices8)
        n = len(opt._plan.buckets)
        assert n >= 2, "cap should split the fp32 bucket"
        txt = low.as_text()
        mesh = _hier_mesh(devices8)
        # fast hop: the full bucket scatters intra-slice...
        lw.assert_collective_axes(txt, "reduce_scatter", ("dp_in",),
                                  mesh, minimum=n, maximum=n, dtype="f32")
        # ...slow hop: the 1/dp_in chunk scatters cross-slice
        lw.assert_collective_axes(txt, "reduce_scatter", ("dp_out",),
                                  mesh, minimum=n, maximum=n, dtype="f32")
        # never a single-hop scatter over the combined dp world
        lw.count_collectives(txt, "reduce_scatter", axes=HIER_AXES,
                             mesh=mesh, maximum=0)
        # param sync mirrors: one gather per bucket per hop
        lw.assert_collective_axes(txt, "all_gather", ("dp_out",), mesh,
                                  minimum=n, maximum=n, dtype="f32")
        lw.assert_collective_axes(txt, "all_gather", ("dp_in",), mesh,
                                  minimum=n, maximum=n, dtype="f32")

    def test_int8_wire_stays_compressed_on_both_hops(self, devices8):
        low, opt, params, _state = _hier_lowering(
            devices8, grad_sync_dtype="int8")
        n = len(opt._plan.buckets)
        txt = low.as_text()
        mesh = _hier_mesh(devices8)
        lw.assert_collective_axes(txt, "reduce_scatter", ("dp_in",),
                                  mesh, minimum=n, maximum=n, dtype="i8")
        # the headline contract: the SLOW hop still carries int8 — a
        # dequantize-then-reduce regression would show f32 here
        lw.assert_collective_axes(txt, "reduce_scatter", ("dp_out",),
                                  mesh, minimum=n, maximum=n, dtype="i8")
        lw.assert_collective_dtype(txt, "reduce_scatter", "f32",
                                   mode="none")
        total = sum(int(np.prod(p.shape))
                    for p in jax.tree_util.tree_leaves(params))
        lw.assert_no_whole_tree_concat(txt, total)

    def test_fp8_wire_element_types_per_hop(self, devices8):
        low, opt, _p, _s = _hier_lowering(devices8,
                                          grad_sync_dtype="float8_e4m3fn")
        n = len(opt._plan.buckets)
        mesh = _hier_mesh(devices8)
        txt = low.as_text()
        for hop in (("dp_in",), ("dp_out",)):
            lw.assert_collective_axes(txt, "reduce_scatter", hop, mesh,
                                      minimum=n, maximum=n,
                                      dtype="f8E4M3FN")

    def test_no_whole_tree_concat_wide(self, devices8):
        low, _opt, params, _state = _hier_lowering(devices8)
        total = sum(int(np.prod(p.shape))
                    for p in jax.tree_util.tree_leaves(params))
        lw.assert_no_whole_tree_concat(low.as_text(), total)

    def test_donation_covers_shards_and_residuals(self, devices8):
        low, opt, params, state = _hier_lowering(devices8,
                                                 grad_sync_dtype="int8")
        n_buckets = len(opt._plan.buckets)
        assert len(jax.tree_util.tree_leaves(state)) == 1 + 4 * n_buckets
        lw.assert_donation_covers(low, params, state, compiled=False)

    @pytest.mark.slow
    def test_donation_survives_compilation(self, devices8):
        low, _opt, params, state = _hier_lowering(devices8,
                                                  grad_sync_dtype="int8")
        lw.assert_donation_covers(low, params, state, compiled=True)


class TestOverlappedInterleaving:
    """The backward-overlap tentpole pin (ISSUE 18): with
    ``overlap_grad_sync=True`` at least one pair of consecutive grad
    reduce-scatters has backward ``dot_general`` compute BETWEEN them
    in program order (bucket k's sync is in flight while a later
    segment's backward still runs — the shape the latency-hiding
    scheduler overlaps), while the knob off keeps the old
    all-at-the-end shape with zero dots between any pair.  The
    per-bucket collective count/dtype pins of PR 12/16 must hold
    UNCHANGED under overlap — only placement moves.

    The config needs final-LN leaves that fill a whole bucket tile
    (hidden 512: bias + scale = 1024 fp32 elements) so a pure
    head-stage bucket exists; with tiny hidden sizes the final-LN
    leaves share a bucket with layer leaves and every bucket becomes
    ready at the same backward stage — nothing to interleave."""

    OVL_CFG = GPTConfig(vocab_size=64, hidden_size=512, num_layers=2,
                        num_attention_heads=4, max_seq_len=16,
                        compute_dtype=jnp.float32,
                        checkpoint_layers=False)

    def _flat(self, devices8, overlap, **opt_kw):
        params = init_params(self.OVL_CFG, jax.random.PRNGKey(0))
        opt = DistributedFusedAdam(lr=1e-2, axis_name="dp",
                                   bucket_cap_mb=TINY_CAP_MB, **opt_kw)
        state = opt.init(params, world_size=DP)
        step = make_train_step(self.OVL_CFG, opt, _mesh(devices8),
                               donate_state=True,
                               overlap_grad_sync=overlap)
        rng = np.random.RandomState(0)
        tokens = jnp.asarray(rng.randint(0, self.OVL_CFG.vocab_size,
                                         size=(DP, 16)))
        return (step.lower(params, state, tokens,
                           jnp.roll(tokens, -1, axis=1)), opt)

    def test_flat_overlap_interleaves_scatters_with_backward(
            self, devices8):
        low, opt = self._flat(devices8, True)
        n = len(opt._plan.buckets)
        txt = low.as_text()
        mesh = _mesh(devices8)
        # the PR 12 count pin holds under overlap: still exactly one
        # f32 scatter per bucket on dp — only trace placement moved
        lw.assert_collective_axes(txt, "reduce_scatter", ("dp",), mesh,
                                  minimum=n, maximum=n, dtype="f32")
        gaps = lw.assert_interleaved(txt, "reduce_scatter", axes=("dp",),
                                     mesh=mesh, gaps="any")
        assert len(gaps) == n - 1

    def test_flat_unoverlapped_scatters_all_after_backward(
            self, devices8):
        low, _opt = self._flat(devices8, False)
        lw.assert_interleaved(low.as_text(), "reduce_scatter",
                              axes=("dp",), mesh=_mesh(devices8),
                              gaps="none")

    def test_int8_overlap_interleaves_on_the_compressed_wire(
            self, devices8):
        low, opt = self._flat(devices8, True, grad_sync_dtype="int8")
        n = len(opt._plan.buckets)
        txt = low.as_text()
        mesh = _mesh(devices8)
        lw.assert_collective_axes(txt, "reduce_scatter", ("dp",), mesh,
                                  minimum=n, maximum=n, dtype="i8")
        lw.assert_collective_dtype(txt, "reduce_scatter", "f32",
                                   mode="none")
        lw.assert_interleaved(txt, "reduce_scatter", axes=("dp",),
                              mesh=mesh, dtype="i8", gaps="any")

    def test_hier_overlap_interleaves_per_hop(self, devices8):
        params = init_params(self.OVL_CFG, jax.random.PRNGKey(0))
        opt = DistributedFusedAdam(lr=1e-2, dp_axes=HIER_AXES,
                                   bucket_cap_mb=TINY_CAP_MB)
        state = opt.init(params, world_size=4,
                         axis_sizes={"dp_out": 2, "dp_in": 2, "tp": 1})
        step = make_train_step(self.OVL_CFG, opt, _hier_mesh(devices8),
                               dp_axis=HIER_AXES, donate_state=True,
                               overlap_grad_sync=True)
        rng = np.random.RandomState(0)
        tokens = jnp.asarray(rng.randint(0, self.OVL_CFG.vocab_size,
                                         size=(4, 16)))
        low = step.lower(params, state, tokens,
                         jnp.roll(tokens, -1, axis=1))
        n = len(opt._plan.buckets)
        txt = low.as_text()
        mesh = _hier_mesh(devices8)
        # both hops keep their per-bucket counts under overlap...
        lw.assert_collective_axes(txt, "reduce_scatter", ("dp_in",),
                                  mesh, minimum=n, maximum=n, dtype="f32")
        lw.assert_collective_axes(txt, "reduce_scatter", ("dp_out",),
                                  mesh, minimum=n, maximum=n, dtype="f32")
        # ...and each hop's scatter stream interleaves with backward
        for hop in (("dp_in",), ("dp_out",)):
            lw.assert_interleaved(txt, "reduce_scatter", axes=hop,
                                  mesh=mesh, gaps="any")

    def test_checker_self_consistency(self):
        with pytest.raises(ValueError, match="at least two"):
            lw.interleave_gaps("module {}")
        with pytest.raises(ValueError, match="gaps"):
            lw.assert_interleaved(
                'x = "stablehlo.reduce_scatter"(a)\n'
                'y = "stablehlo.reduce_scatter"(b)\n', gaps="bogus")


class TestHierarchicalQuantizedReplicatedStep:
    """``make_train_step(grad_sync_dtype=..., dp_axis=(outer, inner))``
    on a NON-ZeRO optimizer: the replicated dp pmean becomes the
    two-hop quantized scatter + mirrored gathers, every payload hop on
    the wire dtype."""

    def test_int8_two_hop_rs_ag(self, devices8):
        params = init_params(CFG, jax.random.PRNGKey(0))
        opt = FusedAdam(lr=1e-2)
        state = opt.init(params)
        pspecs = param_specs(CFG)
        sspec = AdamState(step=P(), exp_avg=pspecs, exp_avg_sq=pspecs,
                          master=None)
        mesh = _hier_mesh(devices8)
        step = make_train_step(CFG, opt, mesh, dp_axis=HIER_AXES,
                               opt_state_spec=sspec,
                               grad_sync_dtype="int8")
        rng = np.random.RandomState(0)
        tokens = jnp.asarray(rng.randint(0, CFG.vocab_size, size=(4, 16)))
        txt = step.lower(params, state, tokens,
                         jnp.roll(tokens, -1, axis=1)).as_text()
        lw.assert_collective_axes(txt, "reduce_scatter", ("dp_in",),
                                  mesh, minimum=1, dtype="i8")
        lw.assert_collective_axes(txt, "reduce_scatter", ("dp_out",),
                                  mesh, minimum=1, dtype="i8")
        lw.assert_collective_axes(txt, "all_gather", ("dp_out",), mesh,
                                  minimum=1, dtype="i8")
        # the inner gather moves the int8 payload + the small fp32
        # hop-2 scale vector (dequantize needs every chunk's scales)
        for s in lw.collective_sites(txt, "all_gather"):
            assert s["dtype"] in ("i8", "f32")


class TestQuantizedReplicatedTrainStep:
    """``make_train_step(grad_sync_dtype=...)`` on a NON-ZeRO
    optimizer: the dp pmean lowers to a reduce-scatter + all-gather
    pair, both on the wire dtype."""

    def test_int8_rs_ag_pair(self, devices8):
        params = init_params(CFG, jax.random.PRNGKey(0))
        opt = FusedAdam(lr=1e-2)
        state = opt.init(params)
        pspecs = param_specs(CFG)
        sspec = AdamState(step=P(), exp_avg=pspecs, exp_avg_sq=pspecs,
                          master=None)
        step = make_train_step(CFG, opt, _mesh(devices8),
                               opt_state_spec=sspec,
                               grad_sync_dtype="int8")
        tokens, targets = _data()
        txt = step.lower(params, state, tokens, targets).as_text()
        lw.count_collectives(txt, "reduce_scatter", minimum=1)
        lw.assert_collective_dtype(txt, "reduce_scatter", "i8", mode="all")
        lw.assert_collective_dtype(txt, "all_gather", "i8")

    def test_knob_rejected_on_zero_and_wide_dtypes(self, devices8):
        from apex_tpu.contrib.optimizers import DistributedFusedAdam

        zopt = DistributedFusedAdam(lr=1e-2, axis_name="dp")
        with pytest.raises(ValueError, match="ZeRO optimizer owns"):
            make_train_step(CFG, zopt, _mesh(devices8),
                            grad_sync_dtype="int8")
        with pytest.raises(ValueError, match="int8"):
            make_train_step(CFG, FusedAdam(lr=1e-2), _mesh(devices8),
                            grad_sync_dtype=jnp.bfloat16)


class TestReplicatedTrainStep:
    """The replicated FusedAdam step: dp grad sync stays an all-reduce
    (pmean), never a reduce-scatter, and donation covers params +
    optimizer state."""

    def _lowering(self, devices8):
        params = init_params(CFG, jax.random.PRNGKey(0))
        opt = FusedAdam(lr=1e-2)
        state = opt.init(params)
        pspecs = param_specs(CFG)
        sspec = AdamState(step=P(), exp_avg=pspecs, exp_avg_sq=pspecs,
                          master=None)
        step = make_train_step(CFG, opt, _mesh(devices8),
                               donate_state=True, opt_state_spec=sspec)
        tokens, targets = _data()
        return step.lower(params, state, tokens, targets), params, state

    def test_grad_sync_is_all_reduce_not_scatter(self, devices8):
        low, _params, _state = self._lowering(devices8)
        txt = low.as_text()
        lw.count_collectives(txt, "reduce_scatter", maximum=0)
        lw.count_collectives(txt, "all_reduce", minimum=1)

    def test_step_donates_params_and_state(self, devices8):
        low, params, state = self._lowering(devices8)
        lw.assert_donation_covers(low, params, state, compiled=False)


class TestCheckerSelfConsistency:
    """The checkers themselves, against hand-built artifacts — the
    helpers guard real invariants, so their own failure modes (regex
    drift against a jax upgrade's StableHLO spelling) must be loud."""

    def test_counts_and_dtypes_on_a_real_psum_lowering(self, devices8):
        mesh = Mesh(np.array(devices8), ("dp",))
        f = jax.jit(jax.shard_map(
            lambda x: jax.lax.psum(x, "dp"), mesh=mesh,
            in_specs=P("dp"), out_specs=P(), check_vma=False))
        txt = f.lower(jnp.ones((8, 4), jnp.bfloat16)).as_text()
        assert lw.count_collectives(txt, "all_reduce", minimum=1) >= 1
        lw.assert_collective_dtype(txt, "all_reduce", "bf16")
        with pytest.raises(AssertionError):
            lw.count_collectives(txt, "all_reduce", maximum=0)
        with pytest.raises(AssertionError):
            lw.assert_collective_dtype(txt, "all_reduce", "f32",
                                       mode="all")

    def test_whole_tree_concat_detects_a_real_flatten(self):
        f = jax.jit(lambda a, b: jnp.concatenate(
            [a.ravel(), b.ravel()]))
        txt = f.lower(jnp.ones((13, 5)), jnp.ones((31,))).as_text()
        with pytest.raises(AssertionError, match="whole tree"):
            lw.assert_no_whole_tree_concat(txt, 13 * 5 + 31)
        lw.assert_no_whole_tree_concat(txt, 10_000)  # other sizes fine

    def test_donation_checker_flags_uncovered_state(self):
        tree = {"a": jnp.ones((4,)), "b": jnp.ones((2, 2))}
        donated = jax.jit(lambda t: jax.tree.map(lambda x: x + 1, t),
                          donate_argnums=(0,)).lower(tree)
        lw.assert_donation_covers(donated, tree, compiled=False)
        undonated = jax.jit(
            lambda t: jax.tree.map(lambda x: x + 1, t)).lower(tree)
        with pytest.raises(AssertionError, match="donatable"):
            lw.assert_donation_covers(undonated, tree, compiled=False)

    def test_text_passthrough_and_type_errors(self):
        assert lw.hlo_text("module {}") == "module {}"
        with pytest.raises(TypeError):
            lw.hlo_text(42)

    def test_host_transfer_checker_on_real_lowerings(self):
        clean = jax.jit(lambda x: x * 2.0).lower(jnp.ones((4,)))
        lw.assert_no_host_transfer(clean)

        def dirty(x):
            jax.debug.print("x={x}", x=x)
            return x * 2.0

        low = jax.jit(dirty).lower(jnp.ones((4,)))
        assert lw.host_transfer_sites(low), \
            "a debug.print callback must register as a host transfer"
        with pytest.raises(AssertionError, match="host-transfer"):
            lw.assert_no_host_transfer(low)


# --------------------------------------------------------------- telemetry
class TestTelemetryTrainStep:
    """ISSUE 10's zero-overhead pins: a telemetry-enabled
    ``make_train_step`` lowers with the SAME collective structure as
    the telemetry-off step (the grad-norm stat reuses the clip
    reduction — never a new psum), adds zero host transfers, donates
    the StepStats buffers, and never retraces across window resets."""

    KINDS = ("all_reduce", "reduce_scatter", "all_gather",
             "collective_permute", "all_to_all")

    @staticmethod
    def _telemetry():
        from apex_tpu.observability import StepTelemetry

        return StepTelemetry()

    def _pair(self, devices8, *, zero, clip=None, opt_kw=None):
        """(lowering_on, lowering_off, stats) for one optimizer mode."""
        params = init_params(CFG, jax.random.PRNGKey(0))
        tokens, targets = _data()
        tel = self._telemetry()
        stats = tel.init()

        def build(telemetry):
            if zero:
                opt = DistributedFusedAdam(lr=1e-2, axis_name="dp",
                                           bucket_cap_mb=TINY_CAP_MB,
                                           **(opt_kw or {}))
                state = opt.init(params, world_size=DP)
                step = make_train_step(CFG, opt, _mesh(devices8),
                                       donate_state=True,
                                       clip_grad_norm=clip,
                                       telemetry=telemetry)
            else:
                opt = FusedAdam(lr=1e-2)
                state = opt.init(params)
                sspec = AdamState(step=P(), exp_avg=param_specs(CFG),
                                  exp_avg_sq=param_specs(CFG), master=None)
                step = make_train_step(CFG, opt, _mesh(devices8),
                                       donate_state=True,
                                       opt_state_spec=sspec,
                                       clip_grad_norm=clip,
                                       telemetry=telemetry)
            args = (params, state, stats, tokens, targets) \
                if telemetry is not None else (params, state, tokens,
                                               targets)
            return step.lower(*args), state, step

        low_on, state, step_on = build(tel)
        low_off, _, _ = build(None)
        return low_on, low_off, stats, state, step_on

    @pytest.mark.parametrize("zero,clip,opt_kw", [
        (False, None, None),
        (False, 1.0, None),
        (True, 1.0, None),
        (True, None, {"grad_sync_dtype": "int8"}),
    ], ids=["replicated", "replicated_clip", "zero_clip", "zero_int8"])
    def test_same_collective_counts(self, devices8, zero, clip, opt_kw):
        low_on, low_off, *_ = self._pair(devices8, zero=zero, clip=clip,
                                         opt_kw=opt_kw)
        on, off = low_on.as_text(), low_off.as_text()
        for kind in self.KINDS:
            n_on = lw.count_collectives(on, kind, minimum=0)
            n_off = lw.count_collectives(off, kind, minimum=0)
            assert n_on == n_off, (
                f"telemetry changed {kind} count: {n_off} -> {n_on}")

    def test_zero_host_transfers(self, devices8):
        low_on, _, _, _, _ = self._pair(devices8, zero=True, clip=1.0)
        lw.assert_no_host_transfer(low_on)

    def test_pp_step_telemetry_same_collectives_no_host_transfer(
            self, devices8):
        """make_pp_train_step carries the same contract: the StepStats
        observer adds no collectives (the pipeline's ppermutes
        included) and no host transfers to the 3D step."""
        from apex_tpu.models.gpt import make_pp_train_step

        params = init_params(CFG, jax.random.PRNGKey(0))
        opt = FusedAdam(lr=1e-2)
        state = opt.init(params)
        mesh = Mesh(np.array(devices8[:4]).reshape(1, 2, 2),
                    ("dp", "pp", "tp"))
        tel = self._telemetry()
        stats = tel.init()
        tokens = jnp.asarray(np.random.RandomState(0).randint(
            0, CFG.vocab_size, size=(2, 16)))
        targets = jnp.roll(tokens, -1, axis=1)

        def build(telemetry):
            step = make_pp_train_step(CFG, opt, mesh, num_microbatches=2,
                                      clip_grad_norm=1.0,
                                      telemetry=telemetry)
            args = (params, state, stats, tokens, targets) \
                if telemetry is not None else (params, state, tokens,
                                               targets)
            return step.lower(*args)

        low_on, low_off = build(tel), build(None)
        on, off = low_on.as_text(), low_off.as_text()
        for kind in self.KINDS:
            assert lw.count_collectives(on, kind, minimum=0) \
                == lw.count_collectives(off, kind, minimum=0), kind
        lw.assert_no_host_transfer(low_on)

    #: StepStats inputs accumulate() READS in this (unscaled) config —
    #: steps, loss_sum, grad_norm_sum, notfinite, loss_scale.  The
    #: write-only last-value fields (loss_last, grad_norm_last,
    #: param_norm, update_norm) are dead inputs the lowering cannot —
    #: and need not — declare donatable.
    LIVE_STATS = 5

    def test_stats_buffers_are_donated(self, devices8):
        low_on, low_off, stats, state, _ = self._pair(
            devices8, zero=True, clip=1.0)
        params = init_params(CFG, jax.random.PRNGKey(0))
        lw.assert_donation_covers(low_on, params, state,
                                  extra=self.LIVE_STATS, compiled=False)
        # and the live-stat donors really are ADDITIONAL to the
        # telemetry-off step's params+state donations
        assert (lw.donated_buffer_count(low_on)
                - lw.donated_buffer_count(low_off)) == self.LIVE_STATS

    @pytest.mark.slow
    def test_stats_donation_survives_compilation(self, devices8):
        low_on, _low_off, stats, state, _ = self._pair(
            devices8, zero=True, clip=1.0)
        params = init_params(CFG, jax.random.PRNGKey(0))
        lw.assert_donation_covers(low_on, params, state,
                                  extra=self.LIVE_STATS, compiled=True)


# ------------------------------------------------------------- decode step
class TestDecodeStep:
    """The serving engine's compiled-step contracts (ROADMAP: 'decode
    step pinned to zero host transfers and zero re-compiles across
    cache lengths'): the one jitted decode step runs entirely on
    device, donates the KV pools, and is reused — one compiled
    executable — across every cache length and batch occupancy."""

    @staticmethod
    def _build():
        from apex_tpu.inference import (
            DecodeConfig, KVCacheConfig, alloc_pools,
        )
        from apex_tpu.inference.decode import make_decode_step, make_prefill
        from apex_tpu.models.gpt import init_params

        cfg = GPTConfig(
            vocab_size=64, hidden_size=32, num_layers=2,
            num_attention_heads=4, max_seq_len=64,
            position_embedding_type="rope",
            compute_dtype=jnp.float32, checkpoint_layers=False)
        dcfg = DecodeConfig(
            cache=KVCacheConfig(num_pages=8, page_size=4, pages_per_seq=4,
                                dtype=jnp.float32),
            max_batch=3, max_prompt_len=8, temperature=0.0,
            attn_impl="xla", sample_impl="xla")
        params = init_params(cfg, jax.random.PRNGKey(0))
        pools = alloc_pools(cfg.num_layers, cfg.kv_heads, cfg.head_dim,
                            dcfg.cache)
        return cfg, dcfg, params, pools, make_decode_step, make_prefill

    def _decode_args(self, dcfg, params, pools):
        B, P = dcfg.max_batch, dcfg.cache.pages_per_seq
        return (params, pools,
                jnp.zeros((B,), jnp.int32),          # tokens
                jnp.zeros((B,), jnp.int32),          # positions
                jnp.zeros((B,), bool),               # active
                jnp.zeros((B, P), jnp.int32),        # page tables
                jnp.zeros((B,), jnp.uint32))         # seeds

    def test_decode_step_has_zero_host_transfers(self):
        cfg, dcfg, params, pools, make_step, _ = self._build()
        step = make_step(cfg, dcfg)
        low = step.lower(*self._decode_args(dcfg, params, pools))
        lw.assert_no_host_transfer(low)

    def test_prefill_has_zero_host_transfers(self):
        cfg, dcfg, params, pools, _, make_prefill = self._build()
        prefill = make_prefill(cfg, dcfg)
        low = prefill.lower(
            params, pools, jnp.zeros((1, dcfg.max_prompt_len), jnp.int32),
            jnp.int32(3), jnp.int32(0),
            jnp.zeros((dcfg.cache.pages_per_seq,), jnp.int32),
            jnp.uint32(0))
        lw.assert_no_host_transfer(low)

    def test_kv_pools_donate_through_decode_step(self):
        """The pools are the resident serving state: both buffers must
        really alias through the compiled step, or every token pays a
        pool-sized copy."""
        cfg, dcfg, params, pools, make_step, _ = self._build()
        step = make_step(cfg, dcfg)
        low = step.lower(*self._decode_args(dcfg, params, pools))
        lw.assert_donation_covers(low, pools, compiled=True)

    def test_decode_step_compiles_once_across_lengths_and_occupancy(self):
        """One executable serves occupancy 0..B and any positions mix:
        shape-identical calls with different occupancy/length DATA must
        not add cache entries — the call-matrix spelling of
        ``analysis.lowered.assert_no_recompile``."""
        from apex_tpu.inference import alloc_pools

        cfg, dcfg, params, _pools, make_step, _ = self._build()
        step = make_step(cfg, dcfg)
        B, P = dcfg.max_batch, dcfg.cache.pages_per_seq
        pt = jnp.arange(B * P, dtype=jnp.int32).reshape(B, P) % 7 + 1
        calls = []
        for active, positions in [
            ((False,) * B, (0,) * B),
            ((True, False, False), (0, 0, 0)),
            ((True, True, True), (3, 9, 14)),
            ((False, True, False), (0, 15, 0)),
        ]:
            # fresh pools per call: the step donates them
            pools = alloc_pools(cfg.num_layers, cfg.kv_heads,
                                cfg.head_dim, dcfg.cache)
            calls.append((params, pools, jnp.zeros((B,), jnp.int32),
                          jnp.asarray(positions, jnp.int32),
                          jnp.asarray(active), pt,
                          jnp.zeros((B,), jnp.uint32)))
        lw.assert_no_recompile(step, calls, label="decode_step")

    def test_verify_and_chunk_steps_zero_host_transfer_and_donate(self):
        """The serving-v2 compiled steps inherit every decode-step
        contract: the speculative verify step and the prefill chunk
        step run entirely on device, donate the KV pools, and compile
        once across draft-hit/occupancy/chunk-phase mixes."""
        from apex_tpu.inference.decode import (
            make_prefill_chunk, make_verify_step,
        )

        cfg, dcfg, params, pools, _, _ = self._build()
        import dataclasses as _dc

        dcfg = _dc.replace(dcfg, draft_len=3, prefill_chunk=4)
        B, P = dcfg.max_batch, dcfg.cache.pages_per_seq
        W = dcfg.draft_len + 1
        verify = make_verify_step(cfg, dcfg)
        vargs = (params, pools, jnp.zeros((B, W), jnp.int32),
                 jnp.zeros((B,), jnp.int32), jnp.zeros((B,), bool),
                 jnp.zeros((B, P), jnp.int32),
                 jnp.zeros((B, W), jnp.uint32))
        low = verify.lower(*vargs)
        lw.assert_no_host_transfer(low)
        lw.assert_donation_covers(low, pools, compiled=True)
        # draft hit/miss and occupancy are DATA: shape-identical calls
        # (fresh pools per call — the step donates them)
        from apex_tpu.inference import alloc_pools

        def fresh():
            return alloc_pools(cfg.num_layers, cfg.kv_heads,
                               cfg.head_dim, dcfg.cache)

        calls = [
            (params, fresh(), jnp.full((B, W), toks, jnp.int32),
             jnp.asarray((2, 9, 0), jnp.int32), jnp.asarray(active),
             jnp.ones((B, P), jnp.int32), jnp.zeros((B, W), jnp.uint32))
            for active, toks in [
                ((True, True, True), 5), ((True, False, False), 0),
                ((False,) * B, 3),
            ]
        ]
        lw.assert_no_recompile(verify, calls, label="verify_step")

        chunk = make_prefill_chunk(cfg, dcfg)
        cargs = (params, fresh(), jnp.zeros((4,), jnp.int32),
                 jnp.int32(0), jnp.int32(4), jnp.int32(0),
                 jnp.zeros((P,), jnp.int32))
        lowc = chunk.lower(*cargs)
        lw.assert_no_host_transfer(lowc)
        lw.assert_donation_covers(lowc, cargs[1], compiled=True)


# ------------------------------------------------------------- GSPMD step
class TestGspmdTrainStep:
    """ISSUE 15's pins on ``make_train_step(spmd="auto")``: the
    annotations really reach the lowering (``assert_sharding``), the
    SPMD partitioner places exactly the sync the shard_map program
    spells by hand (``assert_spmd_collectives`` — the collectives only
    exist in the COMPILED module), donation survives compilation, and
    the optimizer runs its per-leaf path (no whole-tree bucket concat —
    the packed-bucket route was observed MIS-PARTITIONED under GSPMD:
    zeroed pack segments for tp-sharded stacked leaves)."""

    @pytest.fixture(scope="class")
    def gspmd(self, devices8):
        mesh = Mesh(np.array(devices8).reshape(4, 2), ("dp", "tp"))
        params = init_params(CFG, jax.random.PRNGKey(0))
        opt = FusedAdam(lr=1e-2)
        state = opt.init(params)
        sspec = AdamState(step=P(), exp_avg=param_specs(CFG),
                          exp_avg_sq=param_specs(CFG), master=None)
        step = make_train_step(CFG, opt, mesh, opt_state_spec=sspec,
                               donate_state=True, spmd="auto")
        tokens, targets = _data()
        low = step.lower(params, state, tokens, targets)
        return mesh, low, low.compile().as_text(), params, state

    def test_param_and_data_annotations_reach_the_lowering(self, gspmd):
        """Column/row/vocab-parallel param layouts and the dp batch
        shard, pinned at the mhlo.sharding attrs via argpath — a spec
        drift (the APX206 class, runtime-side) fails here."""
        mesh, low, _txt, _p, _s = gspmd
        lw.assert_sharding(low, (0, "embed"), mesh, P("tp", None))
        lw.assert_sharding(low, (0, "layers", "wq"), mesh,
                           P(None, "tp", None))
        lw.assert_sharding(low, (0, "layers", "wo"), mesh,
                           P(None, None, "tp"))
        lw.assert_sharding(low, (0, "layers", "ln1_scale"), mesh,
                           P(None, None))
        lw.assert_sharding(low, (2,), mesh, P("dp", None))   # tokens
        # optimizer state mirrors the param sharding (AdamState.exp_avg)
        lw.assert_sharding(low, (1, 1, "layers", "wq"), mesh,
                           P(None, "tp", None))

    def test_partitioner_places_dp_and_tp_sync(self, gspmd):
        """The GSPMD analog of the shard_map program's collective
        structure: a dp-group all-reduce (the grad pmean) and tp-group
        all-reduces (the Megatron f/g collectives) exist; nothing
        lowered to a reduce-scatter (no ZeRO here), and no collective
        spans the WHOLE mesh as one group (dp and tp sync stay
        separate, as in the hand-written program)."""
        mesh, _low, txt, _p, _s = gspmd
        lw.assert_spmd_collectives(txt, "all_reduce", ("dp",), mesh,
                                   minimum=1, dtype="f32")
        lw.assert_spmd_collectives(txt, "all_reduce", ("tp",), mesh,
                                   minimum=1)
        lw.assert_spmd_collectives(txt, "reduce_scatter", maximum=0)

    def test_combined_all_reduce_with_index_markers_is_read(self):
        """XLA prints ``/*index=5*/`` inside the result type of a
        combined collective with more than five operands — the fused
        grad all-reduce.  The site parser must still see it (it once
        read this module as "no dp sync at all")."""
        txt = ("  %all-reduce.118 = (f32[16]{0}, f32[32]{0}, f32[16]{0}, "
               "f32[16]{0}, f32[64,32]{1,0}, /*index=5*/f32[64]{0}) "
               "all-reduce(%a, %b, %c, %d, %e, /*index=5*/%f), "
               "channel_id=7, replica_groups=[2,4]<=[4,2]T(1,0), "
               "use_global_device_ids=true, to_apply=%add.clone\n")
        assert lw.spmd_collective_sites(txt, "all_reduce") == [
            {"dtype": "f32",
             "replica_groups": [[0, 2, 4, 6], [1, 3, 5, 7]]}]

    def test_donation_survives_spmd_compilation(self, gspmd):
        """donate_state=True must alias params AND optimizer state
        through the PARTITIONED executable — the APX208 hazard
        (sharding-mismatched donation) is exactly a silent drop here."""
        _mesh, low, _txt, params, state = gspmd
        lw.assert_donation_covers(low, params, state, compiled=True)

    def test_optimizer_runs_per_leaf_no_whole_tree_concat(self, gspmd):
        """The engine's bucket pack (one flat concat of every leaf)
        must NOT appear: under GSPMD it both forces all-gathers and
        was observed miscompiled (zeroed segments).  The per-leaf
        route's lowering has no tree-sized concatenate."""
        _mesh, low, _txt, params, _state = gspmd
        total = sum(int(np.prod(p.shape))
                    for p in jax.tree_util.tree_leaves(params))
        lw.assert_no_whole_tree_concat(low.as_text(), total)

    def test_rejects_explicit_collective_features(self, devices8):
        mesh = Mesh(np.array(devices8).reshape(4, 2), ("dp", "tp"))
        opt = FusedAdam(lr=1e-2)
        with pytest.raises(NotImplementedError, match="GSPMD"):
            make_train_step(CFG, opt, mesh, spmd="auto",
                            overlap_grad_sync=True)
        with pytest.raises(NotImplementedError, match="ZeRO"):
            make_train_step(CFG, DistributedFusedAdam(lr=1e-2,
                                                      axis_name="dp"),
                            mesh, spmd="auto")
        with pytest.raises(NotImplementedError, match="hierarchical"):
            make_train_step(CFG, opt, mesh, spmd="auto",
                            dp_axis=("dp", "tp"))
        with pytest.raises(ValueError, match="spmd"):
            make_train_step(CFG, opt, mesh, spmd="gspmd")


class TestShardingRuleProof:
    """The live half of APX206's silent-replication claim: the exact
    two-mesh program the analyzer flags COMPILES AND RUNS with zero
    exceptions on real jax — XLA rematerializes and quietly drops the
    intended layout.  If a jax upgrade starts raising here, the rule's
    message (and docs/static_analysis.md) should be re-verified."""

    SRC = """
        import jax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        mesh_ci = Mesh(devs, ("dp",))
        mesh_prod = Mesh(devs2, ("dp", "tp"))

        def f(x):
            return jax.lax.with_sharding_constraint(
                x * 2, NamedSharding(mesh_prod, P(None, "tp")))

        step = jax.jit(f, in_shardings=NamedSharding(mesh_ci, P("dp")))
    """

    def test_jit_compiles_and_runs_the_flagged_program(self, devices8):
        from jax.sharding import NamedSharding

        devs = np.array(devices8[:4])
        mesh_ci = Mesh(devs, ("dp",))
        mesh_prod = Mesh(devs.reshape(2, 2), ("dp", "tp"))

        def f(x):
            return jax.lax.with_sharding_constraint(
                x * 2, NamedSharding(mesh_prod, P(None, "tp")))

        step = jax.jit(f, in_shardings=NamedSharding(mesh_ci, P("dp")))
        out = step(jnp.ones((8, 8)))     # no exception: the silent class
        np.testing.assert_array_equal(np.asarray(out), 2.0)

    def test_analyzer_flags_the_same_source(self, tmp_path):
        import textwrap

        from apex_tpu.analysis import analyze_file
        from apex_tpu.analysis.rules_sharding import ShardingSpecAxisUnbound

        p = tmp_path / "silent.py"
        p.write_text(textwrap.dedent(self.SRC))
        got = analyze_file(str(p), [ShardingSpecAxisUnbound()],
                           {"dp", "tp"})
        assert [f.rule for f in got] == ["APX206"]
        assert "silently rematerializes" in got[0].message


# ------------------------------------------------------------------ tracing
class TestTracingTrainStep:
    """ISSUE 14's zero-overhead pins: the ``TracedStep`` dispatch
    wrapper lives entirely OUTSIDE jit, so a traced step's lowering is
    byte-identical to the bare step's — same collective counts/dtypes,
    zero host transfers — with a tracer ACTIVE while lowering (the
    bitwise loss/params side rides tests/test_tracing.py).  A wrapper
    change that sneaks host work (a callback, an id tag) into the
    compiled program fails here."""

    KINDS = ("all_reduce", "reduce_scatter", "all_gather",
             "collective_permute", "all_to_all")

    def _pair(self, build):
        """(lowering under an active tracer via TracedStep, bare
        lowering) for one step builder."""
        from apex_tpu.observability import tracing

        step, args = build()
        with tracing.TracingScope():
            traced = tracing.TracedStep(step, name="train.step.dispatch")
            low_on = traced.lower(*args)
        low_off = step.lower(*args)
        return low_on, low_off

    def _builders(self, devices8):
        def replicated():
            params = init_params(CFG, jax.random.PRNGKey(0))
            opt = FusedAdam(lr=1e-2)
            state = opt.init(params)
            sspec = AdamState(step=P(), exp_avg=param_specs(CFG),
                              exp_avg_sq=param_specs(CFG), master=None)
            step = make_train_step(CFG, opt, _mesh(devices8),
                                   donate_state=True,
                                   opt_state_spec=sspec,
                                   clip_grad_norm=1.0)
            tokens, targets = _data()
            return step, (params, state, tokens, targets)

        def zero_clip():
            params = init_params(CFG, jax.random.PRNGKey(0))
            opt = DistributedFusedAdam(lr=1e-2, axis_name="dp",
                                       bucket_cap_mb=TINY_CAP_MB)
            state = opt.init(params, world_size=DP)
            step = make_train_step(CFG, opt, _mesh(devices8),
                                   donate_state=True, clip_grad_norm=1.0)
            tokens, targets = _data()
            return step, (params, state, tokens, targets)

        def hier_int8():
            params = init_params(CFG, jax.random.PRNGKey(0))
            opt = DistributedFusedAdam(lr=1e-2, dp_axes=HIER_AXES,
                                       bucket_cap_mb=TINY_CAP_MB,
                                       grad_sync_dtype="int8")
            state = opt.init(params, world_size=4,
                             axis_sizes={"dp_out": 2, "dp_in": 2,
                                         "tp": 1})
            step = make_train_step(CFG, opt, _hier_mesh(devices8),
                                   dp_axis=HIER_AXES, donate_state=True)
            rng = np.random.RandomState(0)
            tokens = jnp.asarray(rng.randint(0, CFG.vocab_size,
                                             size=(4, 16)))
            return step, (params, state, tokens,
                          jnp.roll(tokens, -1, axis=1))

        return {"replicated": replicated, "zero_clip": zero_clip,
                "hier_int8": hier_int8}

    @pytest.mark.parametrize("variant",
                             ["replicated", "zero_clip", "hier_int8"])
    def test_lowering_is_byte_identical(self, devices8, variant):
        low_on, low_off = self._pair(self._builders(devices8)[variant])
        assert low_on.as_text() == low_off.as_text()

    @pytest.mark.parametrize("variant",
                             ["replicated", "zero_clip", "hier_int8"])
    def test_same_collective_counts_zero_host_transfers(self, devices8,
                                                        variant):
        low_on, low_off = self._pair(self._builders(devices8)[variant])
        on, off = low_on.as_text(), low_off.as_text()
        for kind in self.KINDS:
            n_on = lw.count_collectives(on, kind, minimum=0)
            assert n_on == lw.count_collectives(off, kind, minimum=0), (
                f"tracing changed {kind} count")
        lw.assert_no_host_transfer(low_on)

    def test_wire_dtype_survives_the_wrapper(self, devices8):
        """The int8 two-hop wire is untouched by tracing: per bucket,
        one i8 reduce-scatter on each hop under the traced lowering."""
        from apex_tpu.observability import tracing

        build = self._builders(devices8)["hier_int8"]
        step, args = build()
        with tracing.TracingScope():
            low = tracing.TracedStep(step).lower(*args)
        mesh = _hier_mesh(devices8)
        txt = low.as_text()
        lw.assert_collective_axes(txt, "reduce_scatter", ("dp_in",),
                                  mesh, minimum=1, dtype="i8")
        lw.assert_collective_axes(txt, "reduce_scatter", ("dp_out",),
                                  mesh, minimum=1, dtype="i8")

# ------------------------------------------------------- schedule pins
class TestCollectiveSchedule:
    """``collective_schedule`` / ``assert_same_collective_schedule``
    pins (ISSUE 16): the ORDERED cross-device communication sequence —
    kind, dtype, shape, replica groups, position by position — of
    every production step family, asserted identical across two
    independent builds.  Two processes that lower different schedules
    for the same step wedge a pod device-side; this is the
    single-process, lowering-level spelling of that contract (the
    runtime spelling is ``resilience.uniformity``, the static one
    APX209–211)."""

    def test_flat_zero_schedule_pinned_across_builds(self, devices8):
        low1, opt, _p, _s = _zero_lowering(devices8)
        low2, _opt2, _p2, _s2 = _zero_lowering(devices8)
        scheds = lw.assert_same_collective_schedule(
            low1.as_text(), low2.as_text(), mesh=_mesh(devices8),
            labels=["build 1", "build 2"])
        n = len(opt._plan.buckets)
        kinds = [e["kind"] for e in scheds[0]]
        assert kinds.count("reduce_scatter") == n
        assert kinds.count("all_gather") >= n
        # every grad scatter rides the dp axis at the fp32 wire
        for e in scheds[0]:
            if e["kind"] == "reduce_scatter":
                assert e["axes"] == ("dp",) and e["dtype"] == "f32"

    def test_hierarchical_zero_schedule_pinned(self, devices8):
        low1, opt, _p, _s = _hier_lowering(devices8)
        low2, _opt2, _p2, _s2 = _hier_lowering(devices8)
        scheds = lw.assert_same_collective_schedule(
            low1.as_text(), low2.as_text(), mesh=_hier_mesh(devices8))
        hops = [e["axes"] for e in scheds[0]
                if e["kind"] == "reduce_scatter"]
        # both hops present, in a fixed interleaving across builds
        assert ("dp_in",) in hops and ("dp_out",) in hops

    def test_quantized_zero_schedule_pins_the_i8_wire(self, devices8):
        low1, opt, _p, _s = _zero_lowering(devices8,
                                           grad_sync_dtype="int8")
        low2, _opt2, _p2, _s2 = _zero_lowering(devices8,
                                               grad_sync_dtype="int8")
        scheds = lw.assert_same_collective_schedule(
            low1.as_text(), low2.as_text(), mesh=_mesh(devices8))
        rs_dtypes = {e["dtype"] for e in scheds[0]
                     if e["kind"] == "reduce_scatter"}
        assert "i8" in rs_dtypes, (
            "the compressed wire must appear in the schedule as i8 "
            "reduce-scatters")

    def test_gspmd_auto_schedule_pinned_across_compiles(self, devices8):
        """GSPMD's collectives exist only in the COMPILED module; two
        compiles of the same auto-sharded step must place the identical
        sequence (the partitioner is deterministic — a schedule drift
        here is a jax upgrade changing sync placement under us)."""
        mesh = Mesh(np.array(devices8).reshape(4, 2), ("dp", "tp"))
        params = init_params(CFG, jax.random.PRNGKey(0))
        opt = FusedAdam(lr=1e-2)
        state = opt.init(params)
        sspec = AdamState(step=P(), exp_avg=param_specs(CFG),
                          exp_avg_sq=param_specs(CFG), master=None)
        step = make_train_step(CFG, opt, mesh, opt_state_spec=sspec,
                               donate_state=True, spmd="auto")
        tokens, targets = _data()
        low = step.lower(params, state, tokens, targets)
        txt1 = low.compile().as_text()
        txt2 = step.lower(params, state, tokens,
                          targets).compile().as_text()
        scheds = lw.assert_same_collective_schedule(txt1, txt2)
        assert any(e["kind"] == "all_reduce" for e in scheds[0]), (
            "the partitioned module must carry the dp/tp all-reduces")

    def test_decode_and_verify_schedules_pinned(self):
        """Single-host serving steps lower a fixed (here: empty)
        collective schedule — a collective appearing in the decode or
        verify lowering is a topology change the scheduler's
        single-process page bookkeeping is not built for."""
        import dataclasses as dc

        cfg, dcfg, params, pools, make_step, _ = TestDecodeStep._build()
        step = make_step(cfg, dcfg)
        B, Pg = dcfg.max_batch, dcfg.cache.pages_per_seq
        dargs = (params, pools, jnp.zeros((B,), jnp.int32),
                 jnp.zeros((B,), jnp.int32), jnp.zeros((B,), bool),
                 jnp.zeros((B, Pg), jnp.int32),
                 jnp.zeros((B,), jnp.uint32))
        low1 = step.lower(*dargs)
        low2 = make_step(cfg, dcfg).lower(*dargs)
        scheds = lw.assert_same_collective_schedule(
            low1.as_text(), low2.as_text(),
            labels=["decode build 1", "decode build 2"])
        assert scheds[0] == []
        from apex_tpu.inference.decode import make_verify_step

        vcfg = dc.replace(dcfg, draft_len=2)
        W = vcfg.draft_len + 1
        vargs = (params, pools, jnp.zeros((B, W), jnp.int32),
                 jnp.zeros((B,), jnp.int32), jnp.zeros((B,), bool),
                 jnp.zeros((B, Pg), jnp.int32),
                 jnp.zeros((B, W), jnp.uint32))
        vlow1 = make_verify_step(cfg, vcfg).lower(*vargs)
        vlow2 = make_verify_step(cfg, vcfg).lower(*vargs)
        vscheds = lw.assert_same_collective_schedule(
            vlow1.as_text(), vlow2.as_text())
        assert vscheds[0] == []


class TestDivergenceRuleProof:
    """The live half of APX209's deadlock claim, provable on one
    process: rank-specialize the SAME step the way the flagged code
    would at runtime (rank 0 takes the branch, rank 1 does not), lower
    both variants, and show their collective schedules diverge — on a
    pod those two programs block in different collectives forever.
    The analyzer flags the source; the lowering mismatch is the
    ground truth it predicts."""

    SRC = """
        import jax
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        def grad_sync(g):
            return jax.lax.psum(g, "dp")

        step = shard_map(grad_sync, mesh=mesh, in_specs=P("dp"),
                         out_specs=P("dp"))

        def maybe_probe(x):
            if jax.process_index() == 0:
                return step(x)
            return x
    """

    def test_analyzer_flags_the_rank_gated_launch(self, tmp_path):
        import textwrap

        from apex_tpu.analysis import analyze_file
        from apex_tpu.analysis.rules_divergence import (
            TaintedPredicateGuardsCollective,
        )

        p = tmp_path / "gated.py"
        p.write_text(textwrap.dedent(self.SRC))
        got = analyze_file(str(p), [TaintedPredicateGuardsCollective()],
                           {"dp"})
        assert [f.rule for f in got] == ["APX209"]
        assert "wedges" in got[0].message

    def test_rank_specialized_variants_lower_divergent_schedules(
            self, devices8):
        """What each process would actually lower under the flagged
        ``if``: rank 0's trace launches the psum, rank 1's skips it.
        ``assert_same_collective_schedule`` names the divergence — the
        proof the static rule's deadlock claim rests on."""
        from jax import shard_map

        mesh = Mesh(np.array(devices8).reshape(DP), ("dp",))
        sync = shard_map(lambda x: jax.lax.psum(x, "dp"), mesh=mesh,
                         in_specs=P("dp"), out_specs=P(None))

        def as_rank(rank):
            def maybe_probe(x):
                return sync(x) if rank == 0 else x * 1.0
            return jax.jit(maybe_probe).lower(
                jnp.ones((DP, 4), jnp.float32))

        rank0, rank1 = as_rank(0), as_rank(1)
        with pytest.raises(AssertionError, match="diverge"):
            lw.assert_same_collective_schedule(
                rank0.as_text(), rank1.as_text(),
                labels=["process 0", "process 1"])
        # and the uniform spelling passes: both ranks launching is fine
        lw.assert_same_collective_schedule(rank0.as_text(),
                                           as_rank(0).as_text())


class TestRingOverlapLowering:
    """The overlapped ring's lowering shape, pinned at the StableHLO
    tier: ``overlap=True`` unrolls the ring and issues hop r+1's
    ppermute before chunk r's compute, so ``collective_permute`` sites
    interleave with the per-chunk matmuls — the latency-hiding
    scheduler has compute to hide every hop behind.  The serial scan
    traces its two permutes back-to-back at the end of the loop body
    (no dots between any consecutive pair).  ``impl="scan"`` keeps the
    chunk matmuls visible as ``dot_general`` (Pallas kernel bodies are
    opaque to the HLO text)."""

    def _lowering(self, devices8, overlap):
        from apex_tpu.transformer.context_parallel import ring_attention

        cp = 4
        mesh = Mesh(np.array(devices8[:cp]), ("cp",))
        q = jnp.zeros((1, 2, cp * 64, 16), jnp.float32)
        f = jax.shard_map(
            lambda q, k, v: ring_attention(q, k, v, "cp", causal=False,
                                           impl="scan", overlap=overlap),
            mesh=mesh, in_specs=(P(None, None, "cp", None),) * 3,
            out_specs=P(None, None, "cp", None), check_vma=False)
        return jax.jit(f).lower(q, q, q)

    def test_overlap_permutes_interleave_with_chunk_dots(self, devices8):
        low = self._lowering(devices8, True)
        # unrolled: cp-1 = 3 hops x (k, v), the final rotation elided
        lw.count_collectives(low, "collective_permute", minimum=6,
                             maximum=6)
        gaps = lw.assert_interleaved(low, "collective_permute", gaps="any")
        # hop r+1's pair issues before chunk r's dots, so at least one
        # chunk's matmuls sit between consecutive permute sites
        assert max(gaps) >= 1

    def test_serial_permutes_trace_back_to_back(self, devices8):
        low = self._lowering(devices8, False)
        lw.assert_interleaved(low, "collective_permute", gaps="none")
