"""API-surface parity additions: amp module-level functions, disable_casts,
MemoryBuffer, syncbn subgroup helper, pipeline next/prev rank, bottleneck
blocks, Megatron-style arguments/global_vars, DistributedTestBase."""

import ast
import importlib
import importlib.util
import inspect
import pathlib
import re
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

import apex_tpu.amp as amp
from apex_tpu.contrib.bottleneck import (
    Bottleneck,
    HaloExchangerPeer,
    SpatialBottleneck,
)
from apex_tpu.optimizers import FusedAdam
from apex_tpu.parallel import create_syncbn_process_group, SYNCBN_AXIS
from apex_tpu.transformer import parallel_state
from apex_tpu.transformer.tensor_parallel import (
    MemoryBuffer,
    RingMemBuffer,
    get_cuda_rng_tracker,
    get_rng_state_tracker,
)
from apex_tpu.transformer.tensor_parallel import memory as tp_memory
from apex_tpu.transformer.testing import global_vars
from apex_tpu.transformer.testing.arguments import parse_args
from apex_tpu.transformer.testing.distributed_test_base import DistributedTestBase


class TestAmpModuleSurface:
    def test_scale_loss_and_state_dict_roundtrip(self):
        params = {"w": jnp.ones((4,), jnp.float32)}
        cast, a = amp.initialize(params, opt_level="O2", half_dtype=jnp.float16)
        state = a.init_state()
        loss = jnp.float32(2.0)
        scaled = amp.scale_loss(loss, a, state)
        assert float(scaled) == float(loss) * float(state.loss_scale)
        d = amp.state_dict(state)
        restored = amp.load_state_dict(d)
        assert float(restored.loss_scale) == float(state.loss_scale)

    def test_master_params_iterates_fp32(self):
        params = {"w": jnp.ones((4,), jnp.bfloat16)}
        opt = FusedAdam(lr=1e-3, master_weights=True)
        st = opt.init(params)
        masters = list(amp.master_params(st))
        assert masters and all(m.dtype == jnp.float32 for m in masters)

    def test_disable_casts(self):
        @amp.half_function
        def f(x):
            return x.dtype

        x = jnp.ones((2,), jnp.float32)
        assert f(x) == jnp.bfloat16
        with amp.disable_casts():
            assert f(x) == jnp.float32
        assert f(x) == jnp.bfloat16

    def test_legacy_init(self):
        handle = amp.init(enabled=True)
        st = handle.init_state()
        assert st is not None
        noop = amp.init(enabled=False)
        assert noop.scaler is None
        # legacy kwargs are accepted and ignored
        amp.init(enabled=True, verbose=False, enable_caching=True)

    def test_set_half_dtype_affects_existing_decorations(self):
        @amp.half_function
        def f(x):
            return x.dtype

        x = jnp.ones((2,), jnp.float32)
        assert f(x) == jnp.bfloat16
        try:
            amp.set_half_dtype(jnp.float16)
            assert f(x) == jnp.float16
        finally:
            amp.set_half_dtype(jnp.bfloat16)

    def test_promote_function_casts_kwargs(self):
        @amp.promote_function
        def f(x, y=None):
            return x.dtype, y.dtype

        dx, dy = f(jnp.ones(2, jnp.bfloat16), y=jnp.ones(2, jnp.float32))
        assert dx == jnp.float32 and dy == jnp.float32

    def test_adam_swa_skips_overflow_steps(self):
        from apex_tpu.contrib.openfold_triton import FusedAdamSWA

        params = {"w": jnp.ones((4,), jnp.float32)}
        opt = FusedAdamSWA(lr=0.1)
        st = opt.init(params)
        grads = {"w": jnp.full((4,), 0.5)}
        p1, st = opt.update(grads, st, params, grads_finite=jnp.bool_(False))
        np.testing.assert_array_equal(np.asarray(p1["w"]), np.asarray(params["w"]))
        assert int(st.n_averaged) == 0
        np.testing.assert_array_equal(
            np.asarray(st.swa_params["w"]), np.asarray(params["w"])
        )
        p2, st = opt.update(grads, st, p1, grads_finite=jnp.bool_(True))
        assert int(st.n_averaged) == 1
        np.testing.assert_allclose(
            np.asarray(st.swa_params["w"]), np.asarray(p2["w"]), rtol=1e-6
        )


class TestMemoryBuffer:
    def setup_method(self, method):
        tp_memory.reset_mem_buffs()

    def test_add_get_reset(self):
        buf = MemoryBuffer("act", 64, jnp.float32, track_usage=True)
        a = jnp.arange(12, dtype=jnp.float32).reshape(3, 4)
        view = buf.add(a)
        np.testing.assert_array_equal(np.asarray(view), np.asarray(a))
        assert buf.numel_in_use() == 12
        b = jnp.ones((8,), jnp.float32)
        buf.add(b)
        assert buf.numel_in_use() == 20
        np.testing.assert_array_equal(
            np.asarray(buf.get_data()[:12]), np.asarray(a).ravel()
        )
        buf.reset()
        assert not buf.is_in_use()

    def test_overflow_and_dtype_checks(self):
        buf = MemoryBuffer("small", 4, jnp.float32)
        with pytest.raises(AssertionError):
            buf.add(jnp.ones((8,), jnp.float32))
        with pytest.raises(AssertionError):
            buf.add(jnp.ones((2,), jnp.bfloat16))

    def test_ring(self):
        ring = RingMemBuffer("ring", 2, 16, jnp.float32)
        b0 = ring.get_next_buffer()
        b0.add(jnp.ones((4,), jnp.float32))
        b1 = ring.get_next_buffer()
        assert b1 is not b0
        b0_again = ring.get_next_buffer()
        assert b0_again is b0 and not b0.is_in_use()  # reset on rotation

    def test_named_registry(self):
        buf = tp_memory.allocate_mem_buff("x", 8, jnp.float32)
        assert tp_memory.get_mem_buff("x") is buf
        with pytest.raises(AssertionError):
            tp_memory.allocate_mem_buff("x", 8, jnp.float32)


class TestSyncbnGroups:
    def test_split(self):
        axis, (outer, inner) = create_syncbn_process_group(2, world_size=8)
        assert axis == SYNCBN_AXIS and (outer, inner) == (4, 2)
        with pytest.raises(ValueError):
            create_syncbn_process_group(3, world_size=8)

    def test_subgroup_stats_differ_across_groups(self, devices8):
        # Two groups of 4: stats must sync within, not across.
        from apex_tpu.parallel.sync_batchnorm import sync_batch_norm_stats

        axis, (outer, inner) = create_syncbn_process_group(4, world_size=8)
        mesh = Mesh(np.array(devices8).reshape(outer, inner), ("dp", axis))
        x = jnp.concatenate(
            [jnp.zeros((4, 2, 2, 3)), jnp.ones((4, 2, 2, 3))]
        )  # group 0 all-zero, group 1 all-one

        def f(xs):
            mean, var, n = sync_batch_norm_stats(xs, (0, 1, 2), axis)
            return mean

        means = jax.shard_map(
            f, mesh=mesh, in_specs=P("dp"), out_specs=P("dp"), check_vma=False
        )(x)
        np.testing.assert_allclose(np.asarray(means[0]), 0.0)
        np.testing.assert_allclose(np.asarray(means[-1]), 1.0)


class TestPipelineRankGetters:
    def test_next_prev(self, devices8):
        with parallel_state_ctx(pp=4):
            mesh = parallel_state.get_mesh()

            def f():
                nxt = parallel_state.get_pipeline_model_parallel_next_rank()
                prv = parallel_state.get_pipeline_model_parallel_prev_rank()
                return jnp.reshape(nxt, (1,)), jnp.reshape(prv, (1,))

            nxt, prv = jax.shard_map(
                f, mesh=mesh, in_specs=(), out_specs=P(parallel_state.PIPELINE_AXIS),
                check_vma=False,
            )()
            np.testing.assert_array_equal(np.asarray(nxt), [1, 2, 3, 0])
            np.testing.assert_array_equal(np.asarray(prv), [3, 0, 1, 2])


def parallel_state_ctx(**kw):
    from apex_tpu.transformer.testing.commons import DistributedTestContext

    return DistributedTestContext(**kw)


class TestRngTrackerAlias:
    def test_alias(self):
        assert get_cuda_rng_tracker is get_rng_state_tracker


class TestBottleneck:
    @pytest.mark.slow
    def test_forward_shapes(self):
        m = Bottleneck(in_channels=8, bottleneck_channels=4, out_channels=16, stride=2)
        x = jnp.ones((2, 8, 8, 8), jnp.bfloat16)
        params = m.init(jax.random.PRNGKey(0), x)
        y = m.apply(params, x)
        assert y.shape == (2, 4, 4, 16)

    @pytest.mark.slow
    def test_spatial_matches_single_device(self, devices8):
        # H split over 4 devices + halo exchange == unsharded block.
        mesh = Mesh(np.array(devices8[:4]), ("spatial",))
        m = SpatialBottleneck(
            in_channels=6, bottleneck_channels=4, out_channels=6, axis_name="spatial",
            dtype=jnp.float32,
        )
        x = jnp.asarray(np.random.RandomState(0).randn(2, 16, 8, 6), jnp.float32)
        # Oracle params from the unsharded block (identical param structure:
        # Conv_0..2 + FrozenScaleBias_0..2 in the same order).
        ref_m = Bottleneck(
            in_channels=6, bottleneck_channels=4, out_channels=6, stride=1,
            dtype=jnp.float32,
        )
        params = ref_m.init(jax.random.PRNGKey(0), x)
        y_ref = ref_m.apply(params, x)

        def shard_fn(xs):
            return m.apply(params, xs)

        y_sharded = jax.shard_map(
            shard_fn, mesh=mesh, in_specs=P(None, "spatial"),
            out_specs=P(None, "spatial"), check_vma=False,
        )(x)
        np.testing.assert_allclose(
            np.asarray(y_sharded), np.asarray(y_ref), rtol=1e-5, atol=1e-5
        )

    def test_halo_peer_alias(self):
        ex = HaloExchangerPeer("spatial", halo=1, peer_pool=object())
        assert ex.halo == 1


class TestArguments:
    def test_derived_values(self):
        args = parse_args(args=[
            "--num-layers", "4", "--hidden-size", "64",
            "--num-attention-heads", "4", "--micro-batch-size", "2",
            "--tensor-model-parallel-size", "2", "--world-size", "8", "--bf16",
        ])
        assert args.ffn_hidden_size == 256
        assert args.kv_channels == 16
        assert args.data_parallel_size == 4
        assert args.global_batch_size == 8
        assert args.params_dtype == "bfloat16"

    def test_consistency_errors(self):
        with pytest.raises(ValueError):
            parse_args(args=["--tensor-model-parallel-size", "3", "--world-size", "8"])
        with pytest.raises(ValueError):
            parse_args(args=["--fp16", "--bf16", "--world-size", "1"])

    def test_extra_args_provider_and_overrides(self):
        def extra(parser):
            parser.add_argument("--my-flag", type=int, default=1)
            return parser

        args = parse_args(
            extra_args_provider=extra,
            defaults={"hidden_size": 32},
            override_args={"seq_length": 128},
            args=["--world-size", "1"],
        )
        assert args.my_flag == 1 and args.hidden_size == 32 and args.seq_length == 128


class TestGlobalVars:
    def teardown_method(self, method):
        global_vars.destroy_global_vars()
        from apex_tpu.transformer.pipeline_parallel import utils as ppu
        ppu.destroy_num_microbatches_calculator()

    def test_set_and_get(self):
        global_vars.destroy_global_vars()
        args = global_vars.set_global_variables(args=[
            "--micro-batch-size", "2", "--global-batch-size", "8",
            "--world-size", "1",
        ])
        assert global_vars.get_args() is args
        assert global_vars.get_num_microbatches() == 4
        assert global_vars.get_current_global_batch_size() == 8
        assert global_vars.get_timers() is not None
        assert global_vars.get_adlr_autoresume() is None
        with pytest.raises(AssertionError):
            global_vars.set_global_variables(args=["--world-size", "1"])


class TestDistributedTestBase(DistributedTestBase):
    TP = 2

    def test_mesh_built(self):
        assert self.mesh is not None
        assert parallel_state.get_tensor_model_parallel_world_size() == 2
        assert self.world_size == 8


class TestGroupGetters:
    """Group handles are mesh-axis names usable directly as axis_name."""

    def test_groups_are_axis_names(self):
        with parallel_state_ctx(tp=2, pp=2):
            tp_g = parallel_state.get_tensor_model_parallel_group()
            pp_g = parallel_state.get_pipeline_model_parallel_group()
            dp_g = parallel_state.get_data_parallel_group()
            assert tp_g == parallel_state.TENSOR_AXIS and tp_g.size() == 2
            assert pp_g == parallel_state.PIPELINE_AXIS and pp_g.size() == 2
            assert dp_g == parallel_state.DATA_AXIS and dp_g.size() == 2
            emb = parallel_state.get_embedding_group()
            assert emb.members == (0, 1)
            assert parallel_state.get_position_embedding_group().members == (0,)
            assert parallel_state.get_amax_reduction_group() == parallel_state.TENSOR_AXIS

    def test_group_usable_in_collective(self):
        from jax import shard_map

        with parallel_state_ctx(tp=4):
            mesh = parallel_state.get_mesh()
            g = parallel_state.get_tensor_model_parallel_group()

            def f(x):
                return jax.lax.psum(x, g)

            x = jnp.arange(4, dtype=jnp.float32)
            out = shard_map(
                f, mesh=mesh,
                in_specs=P(parallel_state.TENSOR_AXIS),
                out_specs=P(parallel_state.TENSOR_AXIS),
            )(x)
            np.testing.assert_array_equal(np.asarray(out), [6.0, 6.0, 6.0, 6.0])

    def test_multislice_mesh_and_hierarchical_dp_group(self):
        """num_distributed_slices splits dp into (dcn, dp); the dp group
        spans both axes so one psum is the hierarchical reduction."""
        from jax import shard_map

        with parallel_state_ctx(tp=2, slices=2):
            mesh = parallel_state.get_mesh()
            assert mesh.axis_names == ("dcn", "dp", "pp", "cp", "tp")
            assert mesh.devices.shape == (2, 2, 1, 1, 2)
            assert parallel_state.get_num_distributed_slices() == 2
            assert parallel_state.get_data_parallel_world_size() == 2  # per slice
            g = parallel_state.get_data_parallel_group()
            assert tuple(g) == ("dcn", "dp") and g.size() == 4

            x = jnp.arange(8, dtype=jnp.float32)
            out = shard_map(
                lambda x: jax.lax.psum(x, g), mesh=mesh,
                in_specs=P(("dcn", "dp", "pp", "cp", "tp")),
                out_specs=P(("dcn", "dp", "pp", "cp", "tp")),
            )(x)
            # per tp-coordinate: tp=0 holds {0,2,4,6} → 12, tp=1 {1,3,5,7} → 16
            np.testing.assert_array_equal(np.asarray(out), [12, 16] * 4)

    def test_multislice_requires_divisible_dp(self):
        with pytest.raises(RuntimeError, match="slices"):
            parallel_state.initialize_model_parallel(
                tensor_model_parallel_size_=2, num_distributed_slices_=3,
                devices=jax.devices()[:8],
            )
        parallel_state.destroy_model_parallel()

    def test_masked_psum_sums_members_only(self):
        from jax import shard_map

        with parallel_state_ctx(pp=4):
            mesh = parallel_state.get_mesh()
            g = parallel_state.get_embedding_group()  # members (0, 3)

            def f(x):
                return g.masked_psum(x)

            x = jnp.arange(4, dtype=jnp.float32) + 1.0  # stage s holds s+1
            out = shard_map(
                f, mesh=mesh,
                in_specs=P(parallel_state.PIPELINE_AXIS),
                out_specs=P(parallel_state.PIPELINE_AXIS),
            )(x)
            # only stages 0 and 3 contribute: 1 + 4 = 5
            np.testing.assert_array_equal(np.asarray(out), [5.0] * 4)
            # full-membership group degrades to a plain psum
            tp_like = parallel_state.get_pipeline_model_parallel_group()
            out2 = shard_map(
                lambda x: tp_like.masked_psum(x), mesh=mesh,
                in_specs=P(parallel_state.PIPELINE_AXIS),
                out_specs=P(parallel_state.PIPELINE_AXIS),
            )(x)
            np.testing.assert_array_equal(np.asarray(out2), [10.0] * 4)

    def test_model_parallel_group_is_axis_tuple(self):
        from jax import shard_map

        with parallel_state_ctx(tp=2, pp=2):
            mesh = parallel_state.get_mesh()
            g = parallel_state.get_model_parallel_group()
            assert tuple(g) == (parallel_state.PIPELINE_AXIS, parallel_state.TENSOR_AXIS)
            assert g.size() == 4

            def f(x):
                return jax.lax.psum(x, g)

            x = jnp.arange(4, dtype=jnp.float32)
            out = shard_map(
                f, mesh=mesh,
                in_specs=P(None, (parallel_state.PIPELINE_AXIS, parallel_state.TENSOR_AXIS)),
                out_specs=P(None, (parallel_state.PIPELINE_AXIS, parallel_state.TENSOR_AXIS)),
            )(x.reshape(1, 4))
            np.testing.assert_array_equal(np.asarray(out), [[6.0, 6.0, 6.0, 6.0]])

    def test_embedding_group_pp1_dedup(self):
        with parallel_state_ctx(tp=2):
            assert parallel_state.get_embedding_group().members == (0,)

    def test_usage_tracked_at_get_data(self):
        # sampling happens at get_data, as in the reference (memory.py:115)
        buf = MemoryBuffer("cyc", 100, jnp.float32, track_usage=True)
        for _ in range(10):
            buf.add(jnp.ones((10,), jnp.float32))
        assert buf.in_use_value == 0.0  # not sampled yet
        buf.get_data()
        assert buf.in_use_value == 100.0 and buf.total_value == 100.0
        buf.reset()
        assert buf.in_use_value == 100.0  # reset does not sample

    def test_add_rejects_tracers(self):
        buf = MemoryBuffer("tr", 16, jnp.float32)
        with pytest.raises(TypeError, match="jit"):
            jax.jit(lambda t: buf.add(t))(jnp.ones((4,), jnp.float32))


class TestGlobalVarsCalculatorWiring:
    def test_set_global_variables_installs_pp_calculator(self):
        from apex_tpu.transformer.pipeline_parallel import utils as ppu

        global_vars.destroy_global_vars()
        try:
            global_vars.set_global_variables(args=[
                "--world-size", "8", "--tensor-model-parallel-size", "2",
                "--micro-batch-size", "2",
            ])
            # the pipeline schedules read this module-global; it must be set
            assert ppu.get_num_microbatches() == global_vars.get_num_microbatches()
        finally:
            global_vars.destroy_global_vars()

    def test_validate_args_accounts_for_cp(self):
        from apex_tpu.transformer.testing.arguments import parse_args

        a = parse_args(args=[
            "--world-size", "8", "--tensor-model-parallel-size", "2",
            "--context-parallel-size", "2", "--micro-batch-size", "2",
        ])
        assert a.data_parallel_size == 2
        with pytest.raises(ValueError):
            parse_args(args=[
                "--world-size", "4", "--tensor-model-parallel-size", "2",
                "--context-parallel-size", "4", "--micro-batch-size", "1",
            ])


class TestPublicSurfaceInventory:
    """Every name the docs/migration guide promises must import — the
    one-stop check that the reference's component inventory is reachable."""

    def test_inventory_imports(self):
        from apex_tpu.amp import DynamicLossScaler, StaticLossScaler, initialize, value_and_grad  # noqa: F401
        from apex_tpu.contrib.bottleneck import halo_exchange_1d  # noqa: F401
        from apex_tpu.contrib.conv_bias_relu import (  # noqa: F401
            ConvBias, ConvBiasMaskReLU, ConvBiasReLU, ConvFrozenScaleBiasReLU,
        )
        from apex_tpu.contrib.fmha import fmha, fmha_varlen  # noqa: F401
        from apex_tpu.contrib.groupbn import BatchNorm2d_NHWC, GroupBatchNorm2d  # noqa: F401
        from apex_tpu.contrib.multihead_attn import EncdecMultiheadAttn, SelfMultiheadAttn  # noqa: F401
        from apex_tpu.contrib.openfold_triton import (  # noqa: F401
            CanSchTriMHA, FusedAdamSWA, attention_core,
        )
        from apex_tpu.contrib.optimizers import DistributedFusedAdam, DistributedFusedLAMB  # noqa: F401
        from apex_tpu.contrib.sparsity import ASP, compute_sparse_masks  # noqa: F401
        from apex_tpu.contrib.sparsity.permutation_lib import search_channel_permutation  # noqa: F401
        from apex_tpu.contrib.transducer import TransducerJoint, transducer_loss  # noqa: F401
        from apex_tpu.contrib.xentropy import softmax_xentropy  # noqa: F401
        from apex_tpu.fp16_utils import FP16_Optimizer, network_to_half  # noqa: F401
        from apex_tpu.fused_dense import FusedDense, FusedDenseGeluDense  # noqa: F401
        from apex_tpu.io import (  # noqa: F401
            load_checkpoint, load_sharded_checkpoint, save_checkpoint,
            save_sharded_checkpoint,
        )
        from apex_tpu.mlp import MLP  # noqa: F401
        from apex_tpu.models.bert import bert_forward, bert_mlm_loss  # noqa: F401
        from apex_tpu.models.gpt import gpt_forward, make_pp_train_step, make_train_step  # noqa: F401
        from apex_tpu.normalization import (  # noqa: F401
            FusedLayerNorm, FusedRMSNorm, MixedFusedLayerNorm, MixedFusedRMSNorm,
        )
        from apex_tpu.ops.attention import flash_attention, mha_reference  # noqa: F401
        from apex_tpu.optimizers import (  # noqa: F401
            FusedAdagrad, FusedAdam, FusedLAMB, FusedMixedPrecisionLamb,
            FusedNovoGrad, FusedSGD,
        )
        from apex_tpu.parallel import LARC, SyncBatchNorm, allreduce_gradients  # noqa: F401
        from apex_tpu.RNN import GRU, LSTM, ReLU, Tanh, mLSTM  # noqa: F401
        from apex_tpu.transformer.context_parallel import ring_attention  # noqa: F401
        from apex_tpu.transformer.expert_parallel import moe_ffn  # noqa: F401
        from apex_tpu.transformer.functional import FusedScaleMaskSoftmax, scaled_masked_softmax  # noqa: F401
        from apex_tpu.transformer.pipeline_parallel import p2p_communication  # noqa: F401
        from apex_tpu.transformer.pipeline_parallel.schedules import (  # noqa: F401
            forward_backward_no_pipelining,
            forward_backward_pipelining_with_interleaving,
            forward_backward_pipelining_without_interleaving,
            get_forward_backward_func,
        )
        from apex_tpu.transformer.tensor_parallel import (  # noqa: F401
            ColumnParallelLinear, RowParallelLinear, VocabParallelEmbedding,
            accumulate_gradients, vocab_parallel_cross_entropy,
        )
        from apex_tpu.transformer.microbatches import build_num_microbatches_calculator  # noqa: F401
        from apex_tpu.transformer._data._batchsampler import (  # noqa: F401
            MegatronPretrainingRandomSampler, MegatronPretrainingSampler,
        )


class TestSplitRankMachinery:
    """Encoder/decoder split predicates, membership checks, src/first/
    last rank getters, and test-support setters (reference
    parallel_state.py:504-759)."""

    def test_split_predicates(self):
        with parallel_state_ctx(pp=4, split_rank=2):
            ps = parallel_state
            assert ps.get_pipeline_model_parallel_split_rank() == 2
            assert [ps.is_pipeline_stage_before_split(s) for s in range(4)] == [True, True, False, False]
            assert [ps.is_pipeline_stage_after_split(s) for s in range(4)] == [False, False, True, True]
            assert [ps.is_pipeline_stage_at_split(s) for s in range(4)] == [False, True, False, False]

    def test_split_predicates_no_split(self):
        with parallel_state_ctx(pp=4):
            ps = parallel_state
            assert ps.is_pipeline_stage_before_split(3)
            assert ps.is_pipeline_stage_after_split(0)
            assert not ps.is_pipeline_stage_at_split(1)

    def test_membership_and_ranks(self):
        with parallel_state_ctx(pp=4, split_rank=2):
            ps = parallel_state
            assert ps.get_pipeline_model_parallel_first_rank() == 0
            assert ps.get_pipeline_model_parallel_last_rank() == 3
            assert ps.get_tensor_model_parallel_src_rank() == 0
            assert ps.get_data_parallel_src_rank() == 0
            # with split=2 the embedding group is {0, 2, 3} (the first
            # decoder stage owns the decoder's tied embedding) and the
            # position group {0, 2} — reference :352-372
            assert ps.is_rank_in_embedding_group(stage=0)
            assert ps.is_rank_in_embedding_group(stage=2)
            assert ps.is_rank_in_embedding_group(stage=3)
            assert not ps.is_rank_in_embedding_group(stage=1)
            assert ps.get_embedding_group().members == (0, 2, 3)
            assert ps.is_rank_in_position_embedding_group(stage=0)
            assert ps.is_rank_in_position_embedding_group(stage=2)
            assert not ps.is_rank_in_position_embedding_group(stage=1)
            assert ps.get_position_embedding_group().members == (0, 2)
            # encoder stages {0,1}; decoder stages {2,3}
            assert ps.is_rank_in_encoder_relative_position_embedding_group(stage=1)
            assert not ps.is_rank_in_encoder_relative_position_embedding_group(stage=2)
            assert ps.is_rank_in_decoder_relative_position_embedding_group(stage=2)
            enc = ps.get_encoder_relative_position_embedding_group()
            dec = ps.get_decoder_relative_position_embedding_group()
            assert enc.members == (0, 1) and dec.members == (2, 3)
            assert enc == parallel_state.PIPELINE_AXIS  # usable as axis_name

    def test_setters_and_uninitialized(self):
        assert parallel_state.is_unitialized()
        with parallel_state_ctx(tp=2, pp=2):
            ps = parallel_state
            assert not ps.is_unitialized()
            ps.set_pipeline_model_parallel_split_rank(1)
            assert ps.get_pipeline_model_parallel_split_rank() == 1
            ps.set_tensor_model_parallel_world_size(1)
            assert ps.get_tensor_model_parallel_world_size() == 1
            ps.set_tensor_model_parallel_rank(1)
            assert ps.get_tensor_model_parallel_rank() == 1  # static override
            ps.set_tensor_model_parallel_rank(None)
            ps.set_pipeline_model_parallel_rank(0)
            assert ps.get_pipeline_model_parallel_rank() == 0

    def test_nccl_plumbing_shims(self):
        parallel_state.init_nccl_net()
        parallel_state.set_nccl_ib_envs()
        parallel_state.set_nccl_socket_envs()
        for fn in (parallel_state.new_process_group,
                   parallel_state.new_nccl_ib_group,
                   parallel_state.new_nccl_socket_group):
            with pytest.raises(RuntimeError, match="mesh axes"):
                fn([0, 1])


# ------------------------------------------- the scripts outside the package
# Every runnable script beside the package (examples, benchmarks, the chip
# smoke) must load and name only what apex_tpu still exports: most of their
# imports are function-local, so loading alone would not see a dangling one.
_REPO = pathlib.Path(__file__).resolve().parents[1]
_SCRIPTS = sorted(
    str(p.relative_to(_REPO))
    for pat in ("*.py", "examples/*/*.py", "benchmarks/*.py")
    for p in _REPO.glob(pat)
    if 'if __name__ == "__main__":' in p.read_text())


_GONE = object()


def _package_name(module: str, name: str):
    """What ``from module import name`` would bind — an attribute or a
    submodule — or ``_GONE``."""
    mod = importlib.import_module(module)
    if hasattr(mod, name):
        return getattr(mod, name)
    try:
        return importlib.import_module(f"{module}.{name}")
    except ImportError:
        return _GONE


def _dangling_package_names(tree: ast.AST) -> list:
    """``from apex_tpu.x import y`` anywhere in the file whose ``y`` is
    gone, and ``alias.attr`` on a module imported from the package whose
    ``attr`` is gone."""
    missing, aliases = [], {}
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.level == 0 \
                and (node.module or "").split(".")[0] == "apex_tpu":
            for a in node.names:
                obj = _package_name(node.module, a.name)
                if obj is _GONE:
                    missing.append(f"{node.module}.{a.name}")
                elif isinstance(obj, types.ModuleType):
                    aliases[a.asname or a.name] = obj.__name__
        elif isinstance(node, ast.Import):
            for a in node.names:
                if a.name.split(".")[0] == "apex_tpu":
                    importlib.import_module(a.name)
    for node in ast.walk(tree):
        if isinstance(node, ast.Attribute) \
                and isinstance(node.value, ast.Name) \
                and node.value.id in aliases \
                and _package_name(aliases[node.value.id],
                                  node.attr) is _GONE:
            missing.append(f"{aliases[node.value.id]}.{node.attr}")
    return missing


@pytest.mark.parametrize("script", _SCRIPTS)
def test_script_loads_and_names_only_what_the_package_exports(
        script, monkeypatch, capsys):
    path = _REPO / script
    assert not _dangling_package_names(ast.parse(path.read_text()))

    spec = importlib.util.spec_from_file_location(
        "_script_" + path.stem, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)  # top-level imports; no __main__ block

    # the argument parser builds: --help exits 0 before any work
    monkeypatch.setattr(sys, "argv", [str(path), "--help"])
    build = getattr(mod, "parse_args", None) or getattr(mod, "build_args",
                                                         None)
    if build is None and "ArgumentParser" in inspect.getsource(mod.main):
        build = mod.main
    if build is None:
        return  # takes no arguments (chip_smoke.py, asp_permutation.py)
    with pytest.raises(SystemExit) as done:
        parser = build()
        parser.parse_args()  # build_args() hands the parser back
    assert done.value.code == 0
    assert "usage:" in capsys.readouterr().out


# ------------------------------------ the documents point at what exists
_DOCUMENTS = ["README.md", "Makefile"] + sorted(
    str(p.relative_to(_REPO)) for p in (_REPO / "docs").glob("*.md")
    if p.name != "api.md")  # generated from the package by docs/gen_api.py
#: a repo path is a token under one of these directories ...
_REPO_PATH = re.compile(
    r"(?<![\w/.\-~])(?:apex_tpu|tests|examples|benchmarks|cellbench|docs)/"
    r"[^\s`'\"()\[\]|,;]*")
#: ... or the script of a `python <script>.py ...` command line
_PY_OPERAND = re.compile(
    r"(?:python3?|\$\(PYTHON\))\s+(?:-[^m\s]\S*\s+)*([\w./\-]+\.py)\b")
#: `make <target>` in inline code, or leading a (comment) line
_MAKE_TARGET = re.compile(r"(?:`|^[ \t#]*)make\s+([a-z][\w\-]*)", re.M)


def _path_resolves(token: str) -> bool:
    """``dir/file.py``, ``dir/``, ``file.py:12``, ``file.py::test`` and
    ``dir/module.symbol`` (through ``dir/module.py``, which must name the
    symbol); ``*`` globs must match something."""
    token = token.rstrip(".:")
    path = re.split(r"::|:\d", token)[0]
    if "*" in path:
        return any(_REPO.glob(path))
    if (_REPO / path).exists():
        return True
    module, _, symbol = path.rpartition(".")
    source = _REPO / (module + ".py")
    return bool(module) and source.is_file() \
        and re.search(rf"\b{re.escape(symbol)}\b", source.read_text())


@pytest.mark.parametrize("document", _DOCUMENTS)
def test_document_points_at_files_and_targets_that_exist(document):
    text = (_REPO / document).read_text()
    paths = set(_REPO_PATH.findall(text)) | set(_PY_OPERAND.findall(text))
    assert not sorted(p for p in paths if not _path_resolves(p))
    targets = set(re.findall(r"^([a-z][\w\-]*):", (_REPO / "Makefile")
                             .read_text(), re.M))
    assert not sorted(set(_MAKE_TARGET.findall(text)) - targets)
