"""Tensor-parallel layer semantics: sharded-vocab cross entropy and the
mp RNG tracker (reference fleet/layers/mpu/mp_layers.py:498,
c_softmax_with_cross_entropy_op.cu, mpu/random.py).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

import paddle_tpu as paddle
from paddle_tpu.distributed import mesh as pmesh
from paddle_tpu.parallel.mp_layers import (
    ParallelCrossEntropy,
    get_rng_state_tracker,
    parallel_softmax_cross_entropy,
)

from jax import shard_map


def _dense_ce(x, li):
    x = x.astype(np.float64)
    m = x.max(-1, keepdims=True)
    lse = np.log(np.exp(x - m).sum(-1)) + m[..., 0]
    safe = np.clip(li, 0, x.shape[-1] - 1)
    picked = np.take_along_axis(x, safe[..., None], -1)[..., 0]
    return lse - picked


class TestParallelCrossEntropy:
    def test_gspmd_form_matches_dense(self):
        rng = np.random.RandomState(0)
        x = rng.randn(6, 16).astype(np.float32)
        li = rng.randint(0, 16, (6,)).astype(np.int32)
        out = parallel_softmax_cross_entropy(
            paddle.to_tensor(x), paddle.to_tensor(li))
        np.testing.assert_allclose(np.asarray(out._value), _dense_ce(x, li),
                                   rtol=1e-5)

    def test_ignore_index(self):
        rng = np.random.RandomState(1)
        x = rng.randn(4, 8).astype(np.float32)
        li = np.array([1, -100, 3, -100], np.int32)
        out = parallel_softmax_cross_entropy(
            paddle.to_tensor(x), paddle.to_tensor(li), ignore_index=-100)
        ov = np.asarray(out._value)
        assert ov[1] == 0.0 and ov[3] == 0.0
        np.testing.assert_allclose(ov[[0, 2]],
                                   _dense_ce(x, li)[[0, 2]], rtol=1e-5)

    def test_per_shard_form_matches_dense_no_gather(self):
        """Run the shard_map form on a 4-way vocab sharding; every rank
        holds [N, V/4] and the loss must equal the dense oracle."""
        devs = jax.devices()[:4]
        mesh = Mesh(np.array(devs), ("mp",))
        rng = np.random.RandomState(2)
        N, V = 8, 32
        x = rng.randn(N, V).astype(np.float32)
        li = rng.randint(0, V, (N,)).astype(np.int32)

        def body(xs, ls):
            from paddle_tpu.core.tensor import Tensor

            out = parallel_softmax_cross_entropy(Tensor(xs), Tensor(ls))
            return out._value

        f = jax.jit(shard_map(body, mesh=mesh,
                              in_specs=(P(None, "mp"), P()),
                              out_specs=P(), check_vma=False))
        out = f(x, li)
        np.testing.assert_allclose(np.asarray(out), _dense_ce(x, li),
                                   rtol=1e-5)

    def test_per_shard_gradient_is_softmax_minus_onehot(self):
        devs = jax.devices()[:4]
        mesh = Mesh(np.array(devs), ("mp",))
        rng = np.random.RandomState(3)
        N, V = 4, 16
        x = rng.randn(N, V).astype(np.float32)
        li = rng.randint(0, V, (N,)).astype(np.int32)

        def loss(xs):
            def body(xx, ls):
                from paddle_tpu.core.tensor import Tensor

                return parallel_softmax_cross_entropy(
                    Tensor(xx), Tensor(ls))._value

            f = shard_map(body, mesh=mesh, in_specs=(P(None, "mp"), P()),
                          out_specs=P(), check_vma=False)
            return f(xs, li).sum()

        g = jax.jit(jax.grad(loss))(x)
        xs = np.exp(x - x.max(-1, keepdims=True))
        sm = xs / xs.sum(-1, keepdims=True)
        oh = np.eye(V, dtype=np.float32)[li]
        np.testing.assert_allclose(np.asarray(g), sm - oh, rtol=2e-4,
                                   atol=2e-5)

    def test_layer_wrapper(self):
        rng = np.random.RandomState(4)
        x = rng.randn(5, 12).astype(np.float32)
        li = rng.randint(0, 12, (5,)).astype(np.int32)
        layer = ParallelCrossEntropy()
        out = layer(paddle.to_tensor(x), paddle.to_tensor(li))
        np.testing.assert_allclose(np.asarray(out._value), _dense_ce(x, li),
                                   rtol=1e-5)

    def test_backward_through_layer(self):
        rng = np.random.RandomState(5)
        x = paddle.to_tensor(rng.randn(3, 10).astype(np.float32))
        x.stop_gradient = False
        li = paddle.to_tensor(rng.randint(0, 10, (3,)).astype(np.int32))
        loss = ParallelCrossEntropy()(x, li).sum()
        loss.backward()
        xs = np.exp(np.asarray(x._value) -
                    np.asarray(x._value).max(-1, keepdims=True))
        sm = xs / xs.sum(-1, keepdims=True)
        oh = np.eye(10, dtype=np.float32)[np.asarray(li._value)]
        np.testing.assert_allclose(np.asarray(x.grad._value), sm - oh,
                                   rtol=2e-4, atol=2e-5)


class TestRngTracker:
    def test_local_state_differs_across_mp_ranks(self):
        """Inside a per-shard program, 'local_seed' dropout masks must
        DIFFER across mp ranks; 'global_seed' masks must MATCH
        (reference mpu/random.py)."""
        import paddle_tpu.nn.functional as F

        devs = jax.devices()[:4]
        mesh = Mesh(np.array(devs), ("mp",))
        tracker = get_rng_state_tracker()
        tracker.reset()
        tracker.add("global_seed", 11)
        tracker.add("local_seed", 12)
        x = np.ones((4, 64, 32), np.float32)  # dim0 = one slab per rank

        def body(xs, state_name):
            from paddle_tpu.core.tensor import Tensor

            with tracker.rng_state(state_name):
                out = F.dropout(Tensor(xs[0]), p=0.5, training=True)
            return out._value[None]

        for name, want_equal in [("global_seed", True),
                                 ("local_seed", False)]:
            f = jax.jit(shard_map(
                lambda xs, n=name: body(xs, n), mesh=mesh,
                in_specs=(P("mp"),), out_specs=P("mp"), check_vma=False))
            out = np.asarray(f(x))
            masks = [out[r] != 0 for r in range(4)]
            equal = all((m == masks[0]).all() for m in masks[1:])
            assert equal == want_equal, (name, equal)

    def test_add_twice_raises(self):
        tracker = get_rng_state_tracker()
        tracker.reset()
        tracker.add("s", 1)
        with pytest.raises(ValueError):
            tracker.add("s", 2)
        tracker.reset()

    def test_process_level_mp_rank_folds_into_local_draws(self):
        """Eager multi-process mode (no bound 'mp' axis): set_mp_rank must
        differentiate rank-local dropout masks while leaving global_seed
        draws shared (reference mpu/random.py per-rank seeding)."""
        import paddle_tpu.nn.functional as F
        from paddle_tpu.core.tensor import Tensor

        tracker = get_rng_state_tracker()
        tracker.reset()
        paddle.seed(77)
        x = paddle.to_tensor(np.ones((64,), np.float32))

        def mask(state, rank):
            tracker.reset()  # fresh draw counters per simulated rank
            tracker.set_mp_rank(rank)
            paddle.seed(77)  # identical base state per simulated rank
            with tracker.rng_state(state):
                out = F.dropout(x, p=0.5, training=True)
            tracker.set_mp_rank(0)
            return np.asarray(out._value) != 0

        m0, m1 = mask("local_seed", 0), mask("local_seed", 1)
        assert (m0 != m1).any()
        g0, g1 = mask("global_seed", 0), mask("global_seed", 1)
        assert (g0 == g1).all()
        tracker.reset()
