"""Tensor-parallel tests — mirrors tests/L0/run_transformer
(test_mapping.py, test_layers.py, test_cross_entropy.py) of the
reference: the parallel computation on a device mesh must match a
single-device oracle, forward and backward."""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from apex_tpu.transformer import parallel_state
from apex_tpu.transformer.tensor_parallel import (
    column_parallel_linear,
    copy_to_tensor_model_parallel_region,
    gather_from_sequence_parallel_region,
    gather_from_tensor_model_parallel_region,
    reduce_from_tensor_model_parallel_region,
    reduce_scatter_to_sequence_parallel_region,
    row_parallel_linear,
    scatter_to_tensor_model_parallel_region,
    vocab_parallel_cross_entropy,
    vocab_parallel_embedding,
)

TP = 4


@pytest.fixture
def tp_mesh(devices8):
    return Mesh(np.array(devices8[:TP]), ("tp",))


def smap(mesh, f, in_specs, out_specs):
    # check_vma=False: the custom_vjp collectives hide replication info
    # from the static checker (same pattern as Megatron-style shard_map code)
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False)


class TestMappings:
    def test_copy_forward_identity_backward_psum(self, tp_mesh):
        x = jnp.arange(8.0)

        def f(x):
            return copy_to_tensor_model_parallel_region(x, "tp")

        out = smap(tp_mesh, f, P(), P())(x)
        np.testing.assert_allclose(np.asarray(out), np.asarray(x))

        # backward: grad of sum over all tp ranks = psum(1) = TP
        def loss(x):
            y = copy_to_tensor_model_parallel_region(x, "tp")
            return jnp.sum(y * y)

        g = smap(tp_mesh, jax.grad(loss), P(), P())(x)
        np.testing.assert_allclose(np.asarray(g), TP * 2 * np.asarray(x))

    def test_gather_scatter_roundtrip(self, tp_mesh):
        x = jnp.arange(16.0).reshape(2, 8)  # last dim sharded 8/4=2

        def f(x):
            full = gather_from_tensor_model_parallel_region(x, "tp")
            back = scatter_to_tensor_model_parallel_region(full, "tp")
            return full, back

        full, back = smap(tp_mesh, f, P(None, "tp"), (P(None, None), P(None, "tp")))(x)
        np.testing.assert_allclose(np.asarray(full), np.asarray(x))
        np.testing.assert_allclose(np.asarray(back), np.asarray(x))

    def test_reduce(self, tp_mesh):
        x = jnp.ones((TP, 3))  # one row per rank

        def f(x):
            return reduce_from_tensor_model_parallel_region(x, "tp")

        out = smap(tp_mesh, f, P("tp", None), P(None, None))(x)
        np.testing.assert_allclose(np.asarray(out), np.full((1, 3), TP))

    def test_sequence_gather_backward_is_reduce_scatter(self, tp_mesh):
        # fwd gathers seq; bwd reduce-scatters.  With a *replicated*
        # downstream loss every rank contributes the full gradient, so the
        # reduce-scatter sums TP identical copies — grad = TP * 2x.  (In the
        # real Megatron pattern each rank's branch differs and the sum
        # accumulates partials; see test_column_row_pair_sequence_parallel.)
        x = jnp.arange(8.0).reshape(8, 1)

        def loss(x):
            full = gather_from_sequence_parallel_region(x, "tp")
            return jnp.sum(full ** 2)

        g = smap(tp_mesh, jax.grad(loss), P("tp", None), P("tp", None))(x)
        np.testing.assert_allclose(np.asarray(g), TP * 2 * np.asarray(x))

    def test_reduce_scatter_sequence(self, tp_mesh):
        x = jnp.ones((8, 2))  # every rank contributes same full-seq tensor

        def f(x):
            return reduce_scatter_to_sequence_parallel_region(x, "tp")

        # input replicated over tp; output seq-sharded
        out = smap(tp_mesh, f, P(), P("tp", None))(x)
        np.testing.assert_allclose(np.asarray(out), np.full((8, 2), TP))


class TestParallelLinears:
    def setup_method(self, _):
        rng = np.random.RandomState(0)
        self.x = rng.randn(6, 16).astype(np.float32)
        self.w = rng.randn(24, 16).astype(np.float32)  # (out, in)
        self.b = rng.randn(24).astype(np.float32)

    def test_column_parallel_matches_dense(self, tp_mesh):
        x, w, b = map(jnp.asarray, (self.x, self.w, self.b))

        def f(x, w, b):
            return column_parallel_linear(x, w, b, gather_output=True, axis_name="tp")

        out = smap(tp_mesh, f, (P(), P("tp", None), P("tp")), P())(x, w, b)
        ref = self.x @ self.w.T + self.b
        np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-5, atol=1e-5)

    @pytest.mark.slow
    def test_column_parallel_grads_match_dense(self, tp_mesh):
        x, w, b = map(jnp.asarray, (self.x, self.w, self.b))

        def loss(x, w, b):
            y = column_parallel_linear(x, w, b, gather_output=True, axis_name="tp")
            return jnp.sum(jnp.sin(y)) / 100.0

        gx, gw, gb = smap(
            tp_mesh,
            jax.grad(loss, argnums=(0, 1, 2)),
            (P(), P("tp", None), P("tp")),
            (P(), P("tp", None), P("tp")),
        )(x, w, b)

        def ref_loss(x, w, b):
            return jnp.sum(jnp.sin(x @ w.T + b)) / 100.0

        rx, rw, rb = jax.grad(ref_loss, argnums=(0, 1, 2))(x, w, b)
        np.testing.assert_allclose(np.asarray(gx), np.asarray(rx), rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(gw), np.asarray(rw), rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(gb), np.asarray(rb), rtol=1e-5, atol=1e-5)

    def test_row_parallel_matches_dense(self, tp_mesh):
        rng = np.random.RandomState(1)
        x = rng.randn(6, 16).astype(np.float32)
        w = rng.randn(10, 16).astype(np.float32)  # (out, in) — in sharded
        b = rng.randn(10).astype(np.float32)
        xj, wj, bj = map(jnp.asarray, (x, w, b))

        def f(x, w, b):
            return row_parallel_linear(x, w, b, input_is_parallel=True, axis_name="tp")

        out = smap(tp_mesh, f, (P(None, "tp"), P(None, "tp"), P()), P())(xj, wj, bj)
        ref = x @ w.T + b
        np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-5, atol=1e-5)

    @pytest.mark.slow
    def test_column_row_pair_sequence_parallel(self, tp_mesh):
        # the Megatron block pattern: SP in → column (gather) → row (reduce-scatter) → SP out
        rng = np.random.RandomState(2)
        seq, hid, ffn = 8, 16, 32
        x = rng.randn(seq, hid).astype(np.float32)
        w1 = rng.randn(ffn, hid).astype(np.float32)
        w2 = rng.randn(hid, ffn).astype(np.float32)
        xj, w1j, w2j = map(jnp.asarray, (x, w1, w2))

        def f(x, w1, w2):
            h = column_parallel_linear(
                x, w1, None, gather_output=False, sequence_parallel_enabled=True, axis_name="tp"
            )
            h = jax.nn.gelu(h, approximate=False)
            return row_parallel_linear(
                h, w2, None, input_is_parallel=True, sequence_parallel_enabled=True, axis_name="tp"
            )

        out = smap(
            tp_mesh,
            f,
            (P("tp", None), P("tp", None), P(None, "tp")),
            P("tp", None),
        )(xj, w1j, w2j)
        ref = jax.nn.gelu(xj @ w1j.T, approximate=False) @ w2j.T
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-4)


class TestVocabParallel:
    def test_embedding_matches_dense(self, tp_mesh):
        rng = np.random.RandomState(3)
        vocab, hid = 32, 8
        w = rng.randn(vocab, hid).astype(np.float32)
        ids = rng.randint(0, vocab, size=(4, 6))
        wj, idsj = jnp.asarray(w), jnp.asarray(ids)

        def f(ids, w):
            return vocab_parallel_embedding(ids, w, axis_name="tp")

        out = smap(tp_mesh, f, (P(), P("tp", None)), P())(idsj, wj)
        np.testing.assert_allclose(np.asarray(out), w[ids], rtol=1e-6)

    @pytest.mark.parametrize("smoothing", [0.0])
    def test_cross_entropy_matches_dense(self, tp_mesh, smoothing):
        rng = np.random.RandomState(4)
        batch, vocab = 10, 32
        logits = (rng.randn(batch, vocab) * 3).astype(np.float32)
        target = rng.randint(0, vocab, size=(batch,))
        lj, tj = jnp.asarray(logits), jnp.asarray(target)

        def f(logits, target):
            return vocab_parallel_cross_entropy(logits, target, smoothing, "tp")

        out = smap(tp_mesh, f, (P(None, "tp"), P()), P())(lj, tj)

        # dense oracle
        lse = jax.scipy.special.logsumexp(lj, axis=-1)
        ref = lse - jnp.take_along_axis(lj, tj[:, None], axis=1)[:, 0]
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)

    @pytest.mark.slow
    def test_cross_entropy_grad_matches_dense(self, tp_mesh):
        rng = np.random.RandomState(5)
        batch, vocab = 6, 16
        logits = rng.randn(batch, vocab).astype(np.float32)
        target = rng.randint(0, vocab, size=(batch,))
        lj, tj = jnp.asarray(logits), jnp.asarray(target)

        def loss(logits, target):
            return jnp.mean(vocab_parallel_cross_entropy(logits, target, 0.0, "tp"))

        g = smap(tp_mesh, jax.grad(loss), (P(None, "tp"), P()), P(None, "tp"))(lj, tj)

        def ref_loss(logits):
            return jnp.mean(
                jax.scipy.special.logsumexp(logits, axis=-1)
                - jnp.take_along_axis(logits, tj[:, None], axis=1)[:, 0]
            )

        gr = jax.grad(ref_loss)(lj)
        np.testing.assert_allclose(np.asarray(g), np.asarray(gr), rtol=1e-5, atol=1e-5)


class TestParallelState:
    def test_initialize_and_getters(self, devices8):
        parallel_state.initialize_model_parallel(
            tensor_model_parallel_size_=2,
            pipeline_model_parallel_size_=2,
            devices=devices8,
        )
        assert parallel_state.model_parallel_is_initialized()
        assert parallel_state.get_tensor_model_parallel_world_size() == 2
        assert parallel_state.get_pipeline_model_parallel_world_size() == 2
        assert parallel_state.get_data_parallel_world_size() == 2
        assert parallel_state.get_context_parallel_world_size() == 1
        mesh = parallel_state.get_mesh()
        assert mesh.axis_names == ("dp", "pp", "cp", "tp")
        parallel_state.destroy_model_parallel()
        assert not parallel_state.model_parallel_is_initialized()

    def test_bad_sizes_raise(self, devices8):
        with pytest.raises(RuntimeError):
            parallel_state.initialize_model_parallel(
                tensor_model_parallel_size_=3, devices=devices8
            )

    def test_rank_getters_inside_shard_map(self, devices8):
        mesh = parallel_state.initialize_model_parallel(
            tensor_model_parallel_size_=4, devices=devices8
        )

        def f(x):
            r = parallel_state.get_tensor_model_parallel_rank()
            return x + r

        out = jax.shard_map(
            f, mesh=mesh, in_specs=P("tp"), out_specs=P("tp")
        )(jnp.zeros(4))
        np.testing.assert_allclose(np.asarray(out), [0, 1, 2, 3])
        parallel_state.destroy_model_parallel()


class TestTensorParallelAttributes:
    """Spec-tree analog of the reference's param attribute stamping
    (layers.py:70-107) and its consumer (calc_params_l2_norm dedup)."""

    def test_defaults_and_duplicate_rule(self):
        from apex_tpu.transformer.tensor_parallel import (
            TensorParallelAttributes,
            copy_tensor_model_parallel_attributes,
            param_is_not_tensor_parallel_duplicate,
            set_defaults_if_not_set_tensor_model_parallel_attributes,
            set_tensor_model_parallel_attributes,
        )

        d = set_defaults_if_not_set_tensor_model_parallel_attributes(None)
        assert d == TensorParallelAttributes(False, -1, 1)
        s = set_tensor_model_parallel_attributes(True, 0, 1)
        c = copy_tensor_model_parallel_attributes(s)
        assert c == s and c is not s
        # sharded params count on every rank; replicated only on rank 0
        assert param_is_not_tensor_parallel_duplicate(s, tp_rank=3)
        assert param_is_not_tensor_parallel_duplicate(None, tp_rank=0)
        assert not param_is_not_tensor_parallel_duplicate(None, tp_rank=1)

    def test_attributes_tree_and_l2norm_dedup(self):
        from apex_tpu.transformer.pipeline_parallel.utils import calc_params_l2_norm
        from apex_tpu.transformer.tensor_parallel import attributes_tree

        params = {"wq": jnp.full((4,), 2.0), "ln": jnp.full((9,), 2.0)}
        attrs = attributes_tree(
            params, lambda path, leaf: (0, 1) if "wq" in str(path) else None)
        assert attrs["wq"].tensor_model_parallel and not attrs["ln"].tensor_model_parallel

        # rank 0 counts both; rank 1 counts only the sharded leaf
        n0 = float(calc_params_l2_norm(params, attrs=attrs, tp_rank=0))
        n1 = float(calc_params_l2_norm(params, attrs=attrs, tp_rank=1))
        np.testing.assert_allclose(n0, np.sqrt(4 * 4 + 9 * 4), rtol=1e-6)
        np.testing.assert_allclose(n1, np.sqrt(4 * 4), rtol=1e-6)

    def test_l2norm_axis_name_psum(self, devices8):
        """With axis_name, per-rank sharded views psum norm² over the
        group (reference utils.py:234-238 all-reduces across mp)."""
        from jax.sharding import Mesh, PartitionSpec as P
        from jax import shard_map

        from apex_tpu.transformer.pipeline_parallel.utils import calc_params_l2_norm

        mesh = Mesh(np.array(devices8[:4]), ("tp",))
        w = jnp.arange(16.0, dtype=jnp.float32)

        def f(w_shard):
            return calc_params_l2_norm({"w": w_shard}, axis_name="tp")

        norm = shard_map(f, mesh=mesh, in_specs=P("tp"),
                         out_specs=P())(w)
        np.testing.assert_allclose(
            float(norm), np.linalg.norm(np.arange(16.0)), rtol=1e-6)

    def test_l2norm_axis_name_with_attrs_counts_replicated_once(self, devices8):
        """attrs × axis_name: replicated leaves count once across the
        group (traced axis_index-0 weighting), sharded leaves from every
        rank — matching reference utils.py:217-238 filter-then-allreduce."""
        from jax.sharding import Mesh, PartitionSpec as P
        from jax import shard_map

        from apex_tpu.transformer.pipeline_parallel.utils import calc_params_l2_norm
        from apex_tpu.transformer.tensor_parallel import attributes_tree

        mesh = Mesh(np.array(devices8[:4]), ("tp",))
        sharded = jnp.arange(16.0, dtype=jnp.float32)   # split over tp
        replicated = jnp.full((3,), 2.0)                # same on every rank
        attrs = attributes_tree(
            {"s": sharded, "r": replicated},
            lambda path, leaf: (0, 1) if "'s'" in str(path) else None)

        def f(s_shard, r):
            return calc_params_l2_norm({"s": s_shard, "r": r},
                                       attrs=attrs, axis_name="tp")

        norm = shard_map(f, mesh=mesh, in_specs=(P("tp"), P()),
                         out_specs=P())(sharded, replicated)
        expect = np.sqrt(np.sum(np.arange(16.0) ** 2) + 3 * 4.0)
        np.testing.assert_allclose(float(norm), expect, rtol=1e-6)

    def test_l2norm_tp_dedup_keeps_pp_distinct_leaves(self, devices8):
        """tp-replicated but pp-stage-sharded params (per-layer LN
        scales) are distinct per pp rank: the dedup weighting applies to
        the tp axis only, so every pp rank's slice counts (the reference
        filters TP duplicates then all-reduces over the full mp group)."""
        from jax.sharding import Mesh, PartitionSpec as P
        from jax import shard_map

        from apex_tpu.transformer.pipeline_parallel.utils import calc_params_l2_norm
        from apex_tpu.transformer.tensor_parallel import attributes_tree

        mesh = Mesh(np.array(devices8[:4]).reshape(2, 2), ("tp", "pp"))
        # the flagship layout: layer-stacked params shard over pp on the
        # leading axis; weights additionally shard over tp, LN params
        # are tp-replicated
        wq = jnp.arange(16.0, dtype=jnp.float32).reshape(4, 4)  # P(pp, tp)
        ln = jnp.arange(8.0, dtype=jnp.float32)                 # P(pp)
        attrs = attributes_tree(
            {"wq": wq, "ln": ln},
            lambda path, leaf: (1, 1) if "'wq'" in str(path) else None)

        def f(wq_shard, ln_shard):
            return calc_params_l2_norm(
                {"wq": wq_shard, "ln": ln_shard}, attrs=attrs,
                axis_name=("tp", "pp"), tp_axis_name="tp")

        norm = shard_map(f, mesh=mesh, in_specs=(P("pp", "tp"), P("pp")),
                         out_specs=P())(wq, ln)
        # wq: every (pp, tp) rank owns a distinct slice -> sumsq once;
        # ln: distinct per pp rank, tp-replicated -> counted on tp rank
        # 0 of EACH pp rank -> sumsq once.  A dedup over both axes would
        # have dropped pp rank 1's ln slice.
        expect = np.sqrt(np.sum(np.arange(16.0) ** 2) + np.sum(np.arange(8.0) ** 2))
        np.testing.assert_allclose(float(norm), expect, rtol=1e-6)
