"""Kernel tests: flash attention (interpret mode on CPU) + ring attention
on the 8-device mesh vs the dense reference."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.distributed import mesh as pmesh
from paddle_tpu.kernels.flash_attention import (
    FLASH_SAVED_NAMES,
    _reference_attention,
    flash_attention,
)
from paddle_tpu.kernels.ring_attention import sequence_parallel_attention

RNG = np.random.RandomState(21)
# the forward kernel sits in a jitted wrapper, so that a model's layers
# share one trace of it: a jaxpr prints the wrapper's body (and the
# ``name=flash_fwd`` in it) once, and every call of it by this name
FWD_CALL = "name=_flash_fwd_bhnd"


def _qkv(b, n, h, d, kv_n=None):
    kv_n = kv_n or n
    q = RNG.rand(b, n, h, d).astype(np.float32)
    k = RNG.rand(b, kv_n, h, d).astype(np.float32)
    v = RNG.rand(b, kv_n, h, d).astype(np.float32)
    return q, k, v


def _dense_ref(q, k, v, causal):
    b, n, h, d = q.shape
    qf = np.transpose(q, (0, 2, 1, 3)).reshape(b * h, n, d)
    kf = np.transpose(k, (0, 2, 1, 3)).reshape(b * h, k.shape[1], d)
    vf = np.transpose(v, (0, 2, 1, 3)).reshape(b * h, v.shape[1], d)
    out = np.asarray(_reference_attention(
        jnp.asarray(qf), jnp.asarray(kf), jnp.asarray(vf),
        1.0 / np.sqrt(d), causal))
    return np.transpose(out.reshape(b, h, n, d), (0, 2, 1, 3))


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_dense(self, causal):
        q, k, v = _qkv(2, 256, 2, 64)
        out = flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                              causal=causal, interpret=True)
        ref = _dense_ref(q, k, v, causal)
        np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-3, atol=2e-4)

    def test_cross_attention_lengths(self):
        q, k, v = _qkv(1, 128, 2, 64, kv_n=256)
        out = flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                              interpret=True)
        ref = _dense_ref(q, k, v, False)
        np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-3, atol=2e-4)

    def test_gradients_match_dense(self):
        q, k, v = _qkv(1, 128, 1, 64)

        def loss_flash(q_, k_, v_):
            return jnp.sum(flash_attention(q_, k_, v_, causal=True,
                                           interpret=True) ** 2)

        def loss_dense(q_, k_, v_):
            b, n, h, d = q_.shape
            qf = jnp.swapaxes(q_, 1, 2).reshape(b * h, n, d)
            kf = jnp.swapaxes(k_, 1, 2).reshape(b * h, n, d)
            vf = jnp.swapaxes(v_, 1, 2).reshape(b * h, n, d)
            o = _reference_attention(qf, kf, vf, 1.0 / np.sqrt(d), True)
            return jnp.sum(o ** 2)

        g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
        g2 = jax.grad(loss_dense, argnums=(0, 1, 2))(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
        for a, b_ in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       rtol=5e-3, atol=5e-4)

    @pytest.mark.parametrize("causal", [False, True])
    def test_bf16_fwd_bwd_matches_fp32_dense(self, causal):
        """The bf16 fast path (native-precision MXU dots, bf16 p/ds casts)
        must stay within bf16 tolerance of the fp32 dense reference — this
        is the dtype the TPU train step actually runs."""
        # zero-mean inputs (the real activation regime): uniform-positive
        # data drives softmax nearly flat, where true grads self-cancel and
        # any scale-relative metric explodes regardless of kernel precision
        b, n, h, d = 2, 256, 2, 128
        qb, kb, vb = (jnp.asarray(RNG.randn(b, n, h, d), jnp.bfloat16)
                      for _ in range(3))
        # the fp32 oracle consumes the SAME bf16-quantized values, so the
        # comparison isolates kernel arithmetic from input quantization
        q, k, v = (np.asarray(x, np.float32) for x in (qb, kb, vb))

        out = flash_attention(qb, kb, vb, causal=causal, interpret=True)
        assert out.dtype == jnp.bfloat16
        ref = _dense_ref(q, k, v, causal)
        np.testing.assert_allclose(np.asarray(out, np.float32), ref,
                                   rtol=2e-2, atol=2e-2)

        def loss_flash(q_, k_, v_):
            return jnp.sum(flash_attention(
                q_, k_, v_, causal=causal,
                interpret=True).astype(jnp.float32) ** 2)

        def loss_dense(q_, k_, v_):
            b, n, h, d = q_.shape
            qf = jnp.swapaxes(q_, 1, 2).reshape(b * h, n, d)
            kf = jnp.swapaxes(k_, 1, 2).reshape(b * h, n, d)
            vf = jnp.swapaxes(v_, 1, 2).reshape(b * h, n, d)
            o = _reference_attention(qf, kf, vf, 1.0 / np.sqrt(d), causal)
            return jnp.sum(o.astype(jnp.float32) ** 2)

        g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(qb, kb, vb)
        g2 = jax.grad(loss_dense, argnums=(0, 1, 2))(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
        for a, b_ in zip(g1, g2):
            a = np.asarray(a, np.float32)
            b_ = np.asarray(b_)
            # bf16 grads: compare scale-relative (elementwise rtol is
            # meaningless where the true grad crosses zero)
            denom = np.abs(b_).mean() + 1e-8
            assert np.abs(a - b_).mean() / denom < 2e-2

    def test_odd_shapes_fall_back(self):
        q, k, v = _qkv(1, 100, 2, 32)
        out = flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                              causal=True, interpret=True)
        ref = _dense_ref(q, k, v, True)
        np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-3, atol=2e-4)


class TestRingAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_dense(self, causal):
        pmesh.set_mesh(None)
        pmesh.build_hybrid_mesh(dp=1, mp=1, sep=8)
        q, k, v = _qkv(2, 64, 2, 16)  # 8 ranks x 8 tokens each
        out = sequence_parallel_attention(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), causal=causal)
        ref = _dense_ref(q, k, v, causal)
        np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-3,
                                   atol=2e-4)
        pmesh.set_mesh(None)

    def test_long_context_grad(self):
        pmesh.set_mesh(None)
        pmesh.build_hybrid_mesh(dp=1, mp=1, sep=8)
        q, k, v = _qkv(1, 128, 1, 16)

        def loss(q_, k_, v_):
            return jnp.sum(sequence_parallel_attention(
                q_, k_, v_, causal=True) ** 2)

        g = jax.grad(loss)(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
        assert np.isfinite(np.asarray(g)).all()
        pmesh.set_mesh(None)


class TestFlashAttentionRegressions:
    def test_causal_cross_length_fwd_bwd_agree(self):
        """Causal with kv_len != q_len: kernel forward, XLA fallback, and
        the VJP recompute must share start-aligned mask semantics."""
        q, k, v = _qkv(1, 128, 1, 32, kv_n=256)
        qj, kj, vj = map(jnp.asarray, (q, k, v))
        out_kernel = flash_attention(qj, kj, vj, causal=True)
        out_dense = _dense_ref(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out_kernel), out_dense,
                                   rtol=2e-4, atol=2e-5)

        def loss_kernel(q_, k_, v_):
            return flash_attention(q_, k_, v_, causal=True).sum()

        def loss_dense(q_, k_, v_):
            b, n, h, d = q_.shape
            fold = lambda x: jnp.swapaxes(x, 1, 2).reshape(
                b * h, x.shape[1], d)
            return _reference_attention(
                fold(q_), fold(k_), fold(v_), 1.0 / np.sqrt(d),
                True).sum()

        gk = jax.grad(loss_kernel, argnums=(0, 1, 2))(qj, kj, vj)
        gd = jax.grad(loss_dense, argnums=(0, 1, 2))(qj, kj, vj)
        for a, b_ in zip(gk, gd):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       rtol=2e-4, atol=2e-5)

    def test_unaligned_length_uses_fallback(self):
        """n=100 is not tileable (block_q would be 100, not a multiple of
        8 after min-clamp? it is 100%8!=0... ensure result matches dense)."""
        q, k, v = _qkv(1, 100, 2, 32)
        out = flash_attention(*map(jnp.asarray, (q, k, v)), causal=True)
        np.testing.assert_allclose(np.asarray(out),
                                   _dense_ref(q, k, v, True),
                                   rtol=2e-4, atol=2e-5)

    def test_long_context_kv_streams(self):
        """kv grid dimension: long kv with small blocks stays correct."""
        q, k, v = _qkv(1, 128, 1, 32, kv_n=1024)
        out = flash_attention(jnp.asarray(q), jnp.asarray(k),
                              jnp.asarray(v), causal=False)
        np.testing.assert_allclose(np.asarray(out),
                                   _dense_ref(q, k, v, False),
                                   rtol=2e-4, atol=2e-5)


class TestFlashPallasBackward:
    """The Pallas dq/dkv kernels (multi-block accumulation + causal block
    skipping) vs the dense VJP oracle."""

    @pytest.mark.parametrize("causal", [False, True])
    def test_multiblock_grads_match_dense(self, causal):
        from paddle_tpu.kernels.flash_attention import (
            _flash_core, _reference_attention)

        key = jax.random.PRNGKey(7)
        ks = jax.random.split(key, 4)
        bh, n, d = 2, 256, 64
        q, k, v, g = [jax.random.normal(kk, (bh, n, d), jnp.float32)
                      for kk in ks]
        sc = 1.0 / np.sqrt(d)
        # 4x4 blocks of 64 -> real multi-iteration accumulation paths
        out, vjp = jax.vjp(
            lambda a, b_, c: _flash_core(a, b_, c, None, sc, causal, 64, 128,
                                         True), q, k, v)
        ref_out, ref_vjp = jax.vjp(
            lambda a, b_, c: _reference_attention(a, b_, c, sc, causal),
            q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out),
                                   rtol=1e-4, atol=1e-5)
        for mine, ref in zip(vjp(g), ref_vjp(g)):
            np.testing.assert_allclose(np.asarray(mine), np.asarray(ref),
                                       rtol=5e-3, atol=5e-4)

    def test_cross_length_causal_grads(self):
        from paddle_tpu.kernels.flash_attention import (
            _flash_core, _reference_attention)

        ks = jax.random.split(jax.random.PRNGKey(8), 4)
        bh, n, kv_n, d = 2, 128, 256, 64
        q = jax.random.normal(ks[0], (bh, n, d), jnp.float32)
        k = jax.random.normal(ks[1], (bh, kv_n, d), jnp.float32)
        v = jax.random.normal(ks[2], (bh, kv_n, d), jnp.float32)
        g = jax.random.normal(ks[3], (bh, n, d), jnp.float32)
        sc = 1.0 / np.sqrt(d)
        _, vjp = jax.vjp(
            lambda a, b_, c: _flash_core(a, b_, c, None, sc, True, 64, 128, True),
            q, k, v)
        _, ref_vjp = jax.vjp(
            lambda a, b_, c: _reference_attention(a, b_, c, sc, True),
            q, k, v)
        for mine, ref in zip(vjp(g), ref_vjp(g)):
            np.testing.assert_allclose(np.asarray(mine), np.asarray(ref),
                                       rtol=5e-3, atol=5e-4)


class TestFlashValueHeadDimOfItsOwn:
    """q and k [.., D], v and the output [.., Dv] (latent attention's
    expanded heads): ``flash_fwd`` and the backward kernels against
    ``_reference_attention``, nothing padded."""

    @pytest.mark.parametrize("d,dv,causal", [(48, 32, True),
                                             (48, 32, False),
                                             (32, 64, True)])
    def test_forward_and_grads_match_the_reference(self, d, dv, causal):
        from paddle_tpu.kernels.flash_attention import (
            _flash_core, _reference_attention)

        ks = jax.random.split(jax.random.PRNGKey(d + dv), 4)
        bh, n = 2, 256
        q = jax.random.normal(ks[0], (bh, n, d), jnp.float32)
        k = jax.random.normal(ks[1], (bh, n, d), jnp.float32)
        v = jax.random.normal(ks[2], (bh, n, dv), jnp.float32)
        g = jax.random.normal(ks[3], (bh, n, dv), jnp.float32)
        sc = 1.0 / np.sqrt(d)
        out, vjp = jax.vjp(
            lambda a, b_, c: _flash_core(a, b_, c, None, sc, causal, 64, 128,
                                         True), q, k, v)
        ref_out, ref_vjp = jax.vjp(
            lambda a, b_, c: _reference_attention(a, b_, c, sc, causal),
            q, k, v)
        assert out.shape == (bh, n, dv)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out),
                                   rtol=1e-4, atol=1e-5)
        for mine, ref in zip(vjp(g), ref_vjp(g)):
            assert mine.shape == ref.shape
            np.testing.assert_allclose(np.asarray(mine), np.asarray(ref),
                                       rtol=5e-3, atol=5e-4)

    def test_public_entry_folds_each_operand_by_its_own_dim(self):
        from paddle_tpu.kernels.flash_attention import flash_attention

        ks = jax.random.split(jax.random.PRNGKey(5), 3)
        q = jax.random.normal(ks[0], (2, 128, 3, 24), jnp.float32)
        k = jax.random.normal(ks[1], (2, 128, 3, 24), jnp.float32)
        v = jax.random.normal(ks[2], (2, 128, 3, 16), jnp.float32)
        got = flash_attention(q, k, v, causal=True, scale=0.3,
                              interpret=True)
        # 100 rows is no multiple of a block: the fallback, same result
        short = flash_attention(q[:, :100], k[:, :100], v[:, :100],
                                causal=True, scale=0.3, interpret=True)
        assert got.shape == (2, 128, 3, 16)
        np.testing.assert_allclose(np.asarray(got[:, :100]),
                                   np.asarray(short), rtol=1e-4, atol=1e-5)


class TestForwardKernelOutsideTheVjp:
    """``_flash_core`` runs the forward kernel outside its custom_vjp
    and names ``out`` and ``lse`` (FLASH_SAVED_NAMES), so a checkpoint
    policy can keep them. Value and VJP are what they were: held to
    ``_reference_attention`` on each form the kernel takes."""

    @pytest.mark.parametrize("form", ["causal", "segmented",
                                      "kv_len_differs"])
    def test_value_and_vjp_match_the_reference(self, form):
        b, n, h, d = 1, 256, 2, 128
        kv_n = 384 if form == "kv_len_differs" else n
        q, k, v = map(jnp.asarray, _qkv(b, n, h, d, kv_n=kv_n))
        g = jnp.asarray(RNG.rand(b, n, h, d).astype(np.float32))
        segment_ids = None
        if form == "segmented":
            segment_ids = jnp.asarray(
                np.repeat([[0, 1, 2]], [100, 28, 128], axis=1), jnp.int32)

        def fold(x):
            return jnp.swapaxes(x, 1, 2).reshape(b * h, x.shape[1], d)

        def kernel(q_, k_, v_):
            return flash_attention(q_, k_, v_, causal=True, block_q=128,
                                   block_k=128, interpret=True,
                                   segment_ids=segment_ids)

        def reference(q_, k_, v_):
            segs = None
            if segment_ids is not None:
                segs = jnp.broadcast_to(segment_ids[:, None, :],
                                        (b, h, n)).reshape(b * h, n)
            out = _reference_attention(fold(q_), fold(k_), fold(v_),
                                       1.0 / np.sqrt(d), True, segs=segs)
            return jnp.swapaxes(out.reshape(b, h, n, d), 1, 2)

        out, vjp = jax.vjp(kernel, q, k, v)
        ref_out, ref_vjp = jax.vjp(reference, q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out),
                                   rtol=2e-4, atol=2e-5)
        for mine, ref in zip(vjp(g), ref_vjp(g)):
            np.testing.assert_allclose(np.asarray(mine), np.asarray(ref),
                                       rtol=5e-4, atol=5e-5)

    def test_no_gradient_is_one_forward_call(self):
        q, k, v = map(jnp.asarray, _qkv(1, 128, 2, 128))
        text = str(jax.make_jaxpr(lambda *a: flash_attention(
            *a, causal=True, interpret=True))(q, k, v))
        assert text.count(FWD_CALL) == 1
        assert "name=flash_dq" not in text

    def test_without_a_checkpoint_the_residuals_are_what_they_were(self):
        """q, k, v, out, lse and nothing else: one forward call in the
        gradient, its two outputs named."""
        q, k, v = map(jnp.asarray, _qkv(1, 128, 2, 128))
        text = str(jax.make_jaxpr(jax.grad(lambda *a: flash_attention(
            *a, causal=True, interpret=True).sum(), argnums=(0, 1, 2)))(
                q, k, v))
        assert text.count(FWD_CALL) == 1
        assert text.count("name=flash_dq") == 1
        assert text.count("name=flash_dkv") == 1
        for name in FLASH_SAVED_NAMES:
            assert "name=%s" % name in text


class TestRecomputedLayerKeepsAttentionOutput:
    """``models/llama.py`` ``_remat_layer``: a checkpointed decoder layer
    keeps its input and the flash kernel's ``out`` and ``lse``, so the
    backward pass holds one forward-kernel call a layer (two under a
    bare ``jax.checkpoint``), and the arithmetic is the same."""

    LAYERS = 2

    @pytest.fixture
    def grads(self, monkeypatch):
        """recompute -> (gradient function of (state values, ids), state
        values) of a two-layer llama with heads of 128, whose attention
        takes the Pallas path in the interpreter."""
        from paddle_tpu.core.dispatch import no_grad
        from paddle_tpu.core.tensor import Tensor
        from paddle_tpu.kernels import flash_attention as fa
        from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

        # the attention dispatch asks the backend before it takes the
        # kernel, and the kernel asks it again to pick Mosaic
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(fa, "resolve_interpret", lambda _: True)

        def build(recompute):
            paddle.seed(0)
            model = LlamaForCausalLM(LlamaConfig(
                vocab_size=256, hidden_size=256, intermediate_size=512,
                num_hidden_layers=self.LAYERS, num_attention_heads=2,
                num_key_value_heads=1, max_position_embeddings=256,
                use_parallel=False, recompute=recompute))
            names, values = model.functional_state()

            def loss(values, ids, labels):
                with model.bind_state(names, list(values)), no_grad():
                    return model(Tensor(ids), Tensor(labels))._value

            return jax.value_and_grad(loss), values

        return build

    def test_one_forward_kernel_a_layer_and_the_same_gradients(self, grads):
        ids = jnp.asarray(RNG.randint(0, 256, (2, 128)), jnp.int32)
        labels = jnp.roll(ids, -1, axis=1)
        results = {}
        for recompute in (False, True):
            fn, values = grads(recompute)
            text = str(jax.make_jaxpr(fn)(values, ids, labels))
            assert text.count(FWD_CALL) == self.LAYERS, recompute
            assert text.count("name=flash_dq") == self.LAYERS
            assert text.count("name=flash_dkv") == self.LAYERS
            assert ("prevent_cse=" in text) == recompute   # a remat
            # primitive by primitive: under jit XLA's CPU backend fuses
            # the recomputed forward differently (1e-9 apart in float32)
            with jax.disable_jit():
                results[recompute] = fn(values, ids, labels)
        (loss, grad), (loss_r, grad_r) = results[False], results[True]
        assert float(loss) == float(loss_r)
        assert len(grad) == len(grad_r) > 0
        for plain, remat in zip(grad, grad_r):
            np.testing.assert_array_equal(np.asarray(plain),
                                          np.asarray(remat))

    def test_a_bare_checkpoint_runs_the_kernel_twice(self, grads,
                                                     monkeypatch):
        """What the policy is for: with no names kept the same model's
        gradient holds two forward-kernel calls a layer."""
        monkeypatch.setattr(
            jax.checkpoint_policies, "save_only_these_names",
            lambda *names: jax.checkpoint_policies.nothing_saveable)
        fn, values = grads(True)
        ids = jnp.zeros((2, 128), jnp.int32)
        text = str(jax.make_jaxpr(fn)(values, ids, ids))
        assert text.count(FWD_CALL) == 2 * self.LAYERS


def _out_and_lse(q, k, v, scale, causal, segs=None):
    """``_reference_attention``'s output and, from the same masked
    logits, the log-sum-exp the forward kernel saves."""
    logits = jnp.einsum("bnd,bmd->bnm", q, k,
                        precision=jax.lax.Precision.HIGHEST) * scale
    keep = jnp.ones(logits.shape[1:], bool)
    if causal:
        keep = jnp.tril(keep)
    keep = keep[None]
    if segs is not None:
        keep = keep & (segs[:, :, None] == segs[:, None, :])
    lse = jax.scipy.special.logsumexp(jnp.where(keep, logits, -jnp.inf),
                                      axis=-1)
    return _reference_attention(q, k, v, scale, causal, segs=segs), lse


# (name, n, kv_len, d, dv, causal, segmented, block_q, block_k, block_kv)
FWD_CASES = [
    ("causal_one_tile", 128, 128, 64, 64, True, False, 128, 128, 128),
    ("causal_two_tiles", 256, 256, 64, 64, True, False, 128, 128, 128),
    ("causal_many_tiles", 1024, 1024, 64, 64, True, False, 128, 128, 128),
    ("causal_resident_block_walked", 1024, 1024, 64, 64, True, False, 128,
     128, 512),
    ("causal_whole_kv_resident", 1024, 1024, 64, 64, True, False, 256, 128,
     1024),
    ("causal_halved_diagonal_tile", 1024, 1024, 64, 64, True, False, 256,
     256, 1024),
    ("causal_halved_diagonal_one_tile", 512, 512, 64, 64, True, False, 512,
     512, 512),
    ("causal_q_tile_shorter", 512, 512, 64, 64, True, False, 64, 128, 256),
    ("causal_q_tile_taller", 512, 512, 64, 64, True, False, 256, 128, 256),
    ("causal_kv_tile_wider_than_q", 512, 512, 64, 64, True, False, 128, 256,
     512),
    ("causal_kv_longer", 256, 1024, 64, 64, True, False, 128, 128, 512),
    ("causal_kv_longer_grid_steps", 256, 1024, 64, 64, True, False, 128,
     128, 128),
    ("causal_kv_shorter", 1024, 256, 64, 64, True, False, 128, 128, 256),
    ("causal_kv_shorter_square_tiles", 1024, 512, 64, 64, True, False, 256,
     256, 256),
    ("full_one_block", 512, 512, 64, 64, False, False, 128, 128, 128),
    ("full_resident_block_walked", 512, 1024, 64, 64, False, False, 256,
     128, 512),
    ("segmented_causal", 512, 512, 64, 64, True, True, 128, 128, 128),
    ("segmented_causal_halved_diagonal", 512, 512, 64, 64, True, True, 256,
     256, 256),
    ("segmented_full", 512, 512, 64, 64, False, True, 128, 256, 256),
    ("d128", 512, 512, 128, 128, True, False, 256, 256, 512),
    ("d256", 512, 512, 256, 256, True, False, 256, 256, 512),
    ("qk192_v128", 512, 512, 192, 128, True, False, 256, 256, 512),
    ("qk192_v128_full", 256, 512, 192, 128, False, False, 128, 256, 512),
]


def _fwd_inputs(n, kv_len, d, dv, segmented, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    bh = 2
    q = jax.random.normal(ks[0], (bh, n, d), jnp.float32)
    k = jax.random.normal(ks[1], (bh, kv_len, d), jnp.float32)
    v = jax.random.normal(ks[2], (bh, kv_len, dv), jnp.float32)
    segs = None
    if segmented:
        # three packed sequences whose ends fall inside a tile
        ends = jnp.asarray([n // 3 + 5, 2 * n // 3 - 7])
        segs = jnp.broadcast_to(
            jnp.searchsorted(ends, jnp.arange(n), side="right")
            .astype(jnp.int32), (bh, n))
    return q, k, v, segs


class TestFlashForwardTileProgram:
    """The forward kernel's two tile programs, its clamped K/V index maps
    and the walk over a resident K/V block, in interpret mode against
    ``_reference_attention`` for ``out`` and against the masked logits'
    log-sum-exp for ``lse``."""

    @pytest.mark.parametrize(
        "n,kv_len,d,dv,causal,segmented,block_q,block_k,block_kv",
        [c[1:] for c in FWD_CASES], ids=[c[0] for c in FWD_CASES])
    def test_out_and_lse_match_the_reference(self, n, kv_len, d, dv, causal,
                                             segmented, block_q, block_k,
                                             block_kv):
        from paddle_tpu.kernels.flash_attention import _flash_fwd_bhnd

        q, k, v, segs = _fwd_inputs(n, kv_len, d, dv, segmented, seed=n + d)
        scale = 1.0 / np.sqrt(d)
        out, lse = _flash_fwd_bhnd(q, k, v, scale, causal, block_q, block_k,
                                   True, segs=segs, block_kv=block_kv)
        ref_out, ref_lse = _out_and_lse(q, k, v, scale, causal, segs)
        assert out.shape == (2, n, dv) and lse.shape == (2, 1, n)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out),
                                   rtol=1e-4, atol=2e-5)
        np.testing.assert_allclose(np.asarray(lse[:, 0]),
                                   np.asarray(ref_lse), rtol=1e-5,
                                   atol=2e-5)

    @pytest.mark.parametrize("block_kv", [128, 512])
    def test_keys_no_query_sees_never_reach_the_output(self, block_kv):
        """K/V rows past the last block a query can see are NaN: what a
        block that was not fetched, or a stale one, would hold."""
        from paddle_tpu.kernels.flash_attention import _flash_fwd_bhnd

        q, k, v, _ = _fwd_inputs(256, 1024, 64, 64, False, seed=3)
        k = k.at[:, 512:].set(jnp.nan)
        v = v.at[:, 512:].set(jnp.nan)
        scale = 0.125
        out, lse = _flash_fwd_bhnd(q, k, v, scale, True, 128, 128, True,
                                   block_kv=block_kv)
        ref_out, ref_lse = _out_and_lse(q, k[:, :256], v[:, :256], scale,
                                        True)
        assert np.isfinite(np.asarray(out)).all()
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out),
                                   rtol=1e-4, atol=2e-5)
        np.testing.assert_allclose(np.asarray(lse[:, 0]),
                                   np.asarray(ref_lse), rtol=1e-5,
                                   atol=2e-5)

    def test_a_scale_that_is_not_positive_is_not_folded(self):
        from paddle_tpu.kernels.flash_attention import _flash_fwd_bhnd

        q, k, v, _ = _fwd_inputs(256, 256, 64, 64, False, seed=4)
        for scale in (-0.125, 0.0):
            out, lse = _flash_fwd_bhnd(q, k, v, scale, True, 128, 128, True)
            ref_out, ref_lse = _out_and_lse(q, k, v, scale, True)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out),
                                       rtol=1e-4, atol=2e-5)
            np.testing.assert_allclose(np.asarray(lse[:, 0]),
                                       np.asarray(ref_lse), rtol=1e-5,
                                       atol=2e-5)

    @pytest.mark.parametrize("segmented", [False, True])
    def test_backward_takes_the_lse_of_a_forward_tile_of_its_own(
            self, segmented):
        """The forward at 256 x 256 over a resident 512, ``flash_dq`` and
        ``flash_dkv`` at 128 x 128: the gradients are the reference's."""
        from paddle_tpu.kernels.flash_attention import _flash_core

        q, k, v, segs = _fwd_inputs(512, 512, 64, 32, segmented, seed=9)
        g = jax.random.normal(jax.random.PRNGKey(10), (2, 512, 32),
                              jnp.float32)
        scale = 0.125
        fwd_tiles = (256, 256, 256 if segmented else 512)
        out, vjp = jax.vjp(
            lambda a, b_, c: _flash_core(a, b_, c, segs, scale, True, 128,
                                         128, True, fwd_tiles), q, k, v)
        ref_out, ref_vjp = jax.vjp(
            lambda a, b_, c: _reference_attention(a, b_, c, scale, True,
                                                  segs=segs), q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out),
                                   rtol=1e-4, atol=2e-5)
        for mine, ref in zip(vjp(g), ref_vjp(g)):
            np.testing.assert_allclose(np.asarray(mine), np.asarray(ref),
                                       rtol=5e-3, atol=5e-4)

    def test_public_entry_runs_the_chosen_tile(self):
        """No tile named: 2048 rows run at the chooser's 1024 x 1024 over
        the whole of K/V, gradients through the backward's 512 x 512."""
        ks = jax.random.split(jax.random.PRNGKey(11), 3)
        q, k, v = [jax.random.normal(kk, (1, 2048, 1, 64), jnp.float32)
                   for kk in ks]
        text = str(jax.make_jaxpr(lambda *a: flash_attention(
            *a, causal=True, interpret=True))(q, k, v))
        assert text.count(FWD_CALL) == 1
        # two q blocks of 1024, one resident block of all 2048 keys
        assert "grid=(1, 2, 1)" in text

        def loss(fn):
            return lambda q_, k_, v_: jnp.sum(fn(q_, k_, v_) ** 2)

        got, got_g = jax.value_and_grad(loss(lambda *a: flash_attention(
            *a, causal=True, interpret=True)), argnums=(0, 1, 2))(q, k, v)
        ref, ref_g = jax.value_and_grad(loss(lambda q_, k_, v_: jnp.swapaxes(
            _reference_attention(q_[0].swapaxes(0, 1), k_[0].swapaxes(0, 1),
                                 v_[0].swapaxes(0, 1), 0.125, True), 0,
            1)[None]), argnums=(0, 1, 2))(q, k, v)
        np.testing.assert_allclose(float(got), float(ref), rtol=1e-4)
        for mine, theirs in zip(got_g, ref_g):
            np.testing.assert_allclose(np.asarray(mine), np.asarray(theirs),
                                       rtol=5e-3, atol=5e-4)


# what the benchmark's four cells hand the forward kernel: (rows, q/k head
# dim, v head dim), bf16, causal, kv_len == rows
CELL_FWD_SHAPES = (
    [(n, 128, 128) for n in (128, 256, 512, 1024, 2048, 4096)]      # Mistral
    + [(n, 256, 256) for n in (512, 1024, 2048, 4096, 8192)]      # Qwen3-Next
    + [(n, 192, 128) for n in (512, 1024, 2048, 4096, 8192)]     # DeepSeek-V2
)


class TestFlashForwardTileChooser:
    """``_fwd_tiles`` as a pure function of a call's shapes."""

    @pytest.mark.parametrize("n,d,dv", CELL_FWD_SHAPES)
    def test_tiles_of_the_cells_shapes(self, n, d, dv):
        from paddle_tpu.kernels.flash_attention import (
            _FWD_VMEM_BUDGET, _fwd_tiles, _fwd_vmem_bytes)

        bq, bk, bkv = _fwd_tiles(n, n, d, dv, 2)
        assert n % bq == 0 and n % bkv == 0 and bkv % bk == 0
        # Mosaic: q rows on sublanes, kv rows on the score tile's lanes,
        # block_q on the lanes of the lse tile
        assert bq % 8 == 0 and (bq % 128 == 0 or bq == n)
        assert bk % 128 == 0
        assert _fwd_vmem_bytes(bq, bk, bkv, d, dv, 2) <= _FWD_VMEM_BUDGET
        # a side of 1024 wherever the rows divide by it, and the whole of
        # K/V resident at every length a cell runs
        assert bq == bk == (1024 if n % 1024 == 0 else min(n, 512))
        assert bkv == n

    def test_wider_operands_and_longer_contexts_stay_in_budget(self):
        from paddle_tpu.kernels.flash_attention import (
            _FWD_VMEM_BUDGET, _fwd_tiles, _fwd_vmem_bytes)

        for n, kv_len, d, dv, itemsize in ((8192, 8192, 256, 256, 4),
                                           (4096, 65536, 128, 128, 2),
                                           (2048, 2048, 512, 512, 4),
                                           (1536, 1536, 128, 128, 2),
                                           (128, 4096, 128, 128, 2)):
            bq, bk, bkv = _fwd_tiles(n, kv_len, d, dv, itemsize)
            assert n % bq == 0 and kv_len % bkv == 0 and bkv % bk == 0
            assert bq % 128 == 0 and bk % 128 == 0
            assert _fwd_vmem_bytes(bq, bk, bkv, d, dv,
                                   itemsize) <= _FWD_VMEM_BUDGET
        # 65,536 keys do not fit: the resident block is a part of them
        assert _fwd_tiles(4096, 65536, 128, 128, 2)[2] < 65536
        # a segmented call's kv segment ids are cut by the BlockSpec
        assert _fwd_tiles(2048, 2048, 128, 128, 2, segmented=True) == (
            1024, 1024, 1024)

    @pytest.mark.parametrize("named", [dict(block_q=128), dict(block_k=128),
                                       dict(block_q=256, block_k=128)])
    def test_a_named_tile_wins(self, named, monkeypatch):
        from paddle_tpu.kernels import flash_attention as fa

        seen = []
        real = fa._flash_fwd_bhnd

        def spy(*args, **kw):
            seen.append((args[5], args[6], kw["block_kv"]))
            return real(*args, **kw)

        monkeypatch.setattr(fa, "_flash_fwd_bhnd", spy)
        q = jnp.zeros((1, 1024, 1, 64), jnp.float32)
        jax.eval_shape(lambda: fa.flash_attention(q, q, q, causal=True,
                                                  interpret=True, **named))
        bq = named.get("block_q", 512)
        bk = named.get("block_k", 512)
        assert seen == [(bq, bk, bk)]
        seen.clear()
        jax.eval_shape(lambda: fa.flash_attention(q, q, q, causal=True,
                                                  interpret=True))
        assert seen == [(1024, 1024, 1024)]


class TestFlashMinHeadDimFlag:
    """FLAGS_flash_min_head_dim gates sdpa routing into the kernel:
    default 128 keeps the measured path; 64 is kernel-exact (the d=64
    parity tests above) and Mosaic-compiles (test_tpu_lowering.py); the
    default flips when a ledger row says so (ROADMAP D2)."""

    def test_default_is_128(self):
        from paddle_tpu.core import flags as fl

        assert fl.get_flags("FLAGS_flash_min_head_dim")[
            "FLAGS_flash_min_head_dim"] == 128

    def test_d64_grads_match_dense_multiblock(self):
        q, k, v = _qkv(2, 256, 4, 64)

        def f_kernel(q, k, v):
            return jnp.sum(flash_attention(q, k, v, causal=True,
                                           block_q=128, block_k=128,
                                           interpret=True))

        def f_ref(q, k, v):
            b, n, h, d = q.shape

            def fold(x):
                return jnp.swapaxes(x, 1, 2).reshape(b * h, x.shape[1], d)

            return jnp.sum(_reference_attention(
                fold(q), fold(k), fold(v), 1.0 / np.sqrt(d), True))

        args = tuple(jnp.asarray(x) for x in (q, k, v))
        g = jax.grad(f_kernel, argnums=(0, 1, 2))(*args)
        gr = jax.grad(f_ref, argnums=(0, 1, 2))(*args)
        for a, b2 in zip(g, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b2),
                                       rtol=5e-5, atol=5e-5)


class TestFusedCE:
    """Streaming lm_head+CE kernel (kernels/fused_ce.py): the
    [tokens, vocab] logits never materialize; interpret-mode exact vs
    the jnp logsumexp reference, including ignore_index and vocab sizes
    that need block padding (ERNIE's 40000)."""

    def _ref(self, h, w, labels):
        logits = (h @ w).astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        safe = jnp.where(labels != -100, labels, 0)
        gold = jnp.take_along_axis(logits, safe[:, None], axis=-1)[:, 0]
        return jnp.where(labels != -100, lse - gold, 0.0)

    @pytest.mark.parametrize("V", [2048, 2000])  # tileable + padded
    def test_fwd_bwd_match_reference(self, V):
        from paddle_tpu.kernels.fused_ce import fused_lm_head_ce

        rng = np.random.RandomState(0)
        T, H = 512, 64
        h = jnp.asarray(rng.randn(T, H) * 0.5, jnp.float32)
        w = jnp.asarray(rng.randn(H, V) * 0.1, jnp.float32)
        lbl = rng.randint(0, V, (T,)).astype(np.int32)
        lbl[::7] = -100
        lbl = jnp.asarray(lbl)

        out = fused_lm_head_ce(h, w, lbl, -100, 256, 1024, True)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(self._ref(h, w, lbl)),
                                   rtol=1e-5, atol=1e-5)

        def mean_valid(losses):
            v = (lbl != -100).astype(jnp.float32)
            return jnp.sum(losses) / jnp.maximum(jnp.sum(v), 1.0)

        g = jax.grad(lambda h, w: mean_valid(fused_lm_head_ce(
            h, w, lbl, -100, 256, 1024, True)), argnums=(0, 1))(h, w)
        gr = jax.grad(lambda h, w: mean_valid(self._ref(h, w, lbl)),
                      argnums=(0, 1))(h, w)
        for a, b in zip(g, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-7)
        assert g[1].shape == (H, V)

    def test_compiled_training_parity_with_flag(self):
        """FLAGS_fused_lm_head_ce routes the llama loss tail through the
        kernel on compiled steps; losses must match the unfused path."""
        import paddle_tpu.nn.functional as F
        from paddle_tpu.core import flags as fl
        from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
        from paddle_tpu.parallel.engine import CompiledTrainStep

        cfg = LlamaConfig.tiny(use_parallel=False)
        rng = np.random.RandomState(0)
        ids = rng.randint(0, cfg.vocab_size, (4, 64)).astype(np.int32)
        pmesh.build_hybrid_mesh(dp=1, devices=jax.devices()[:1])

        def run(fused):
            fl.set_flags({"FLAGS_fused_lm_head_ce": fused})
            try:
                paddle.seed(0)
                m = LlamaForCausalLM(cfg)
                opt = paddle.optimizer.AdamW(
                    learning_rate=1e-3, parameters=m.parameters())
                if fused:
                    step = CompiledTrainStep(m, None, opt,
                                             labels_to_model=True)
                else:
                    step = CompiledTrainStep(
                        m, lambda lg, lb: F.cross_entropy(
                            lg.reshape([-1, cfg.vocab_size]),
                            lb.reshape([-1])), opt)
                return [float(step(paddle.to_tensor(ids),
                                   paddle.to_tensor(ids)))
                        for _ in range(3)]
            finally:
                fl.set_flags({"FLAGS_fused_lm_head_ce": False})

        np.testing.assert_allclose(run(True), run(False), rtol=2e-4)

    def test_eager_with_flag_warns_loudly_once(self, monkeypatch):
        """A flag-enabled EAGER forward structurally cannot fuse (the
        eager tape never sees the custom_vjp): the gate must warn — once
        per process — so eager-vs-compiled A/Bs under the flag aren't
        silently comparing different loss tails."""
        import warnings

        from paddle_tpu.core import flags as fl
        from paddle_tpu.kernels import fused_ce

        monkeypatch.setattr(fused_ce, "_eager_unfused_warned", False)
        hv = jnp.zeros((2, 128, 8), jnp.float32)   # concrete = eager
        fl.set_flags({"FLAGS_fused_lm_head_ce": True})
        try:
            with pytest.warns(UserWarning, match="EAGER"):
                assert fused_ce.fused_ce_applies(hv, False) is False
            # once-latch: the second eager call stays quiet
            with warnings.catch_warnings():
                warnings.simplefilter("error")
                assert fused_ce.fused_ce_applies(hv, False) is False
        finally:
            fl.set_flags({"FLAGS_fused_lm_head_ce": False})

    def test_flag_off_or_traced_no_warning(self, monkeypatch):
        import warnings

        from paddle_tpu.core import flags as fl
        from paddle_tpu.kernels import fused_ce

        monkeypatch.setattr(fused_ce, "_eager_unfused_warned", False)
        hv = jnp.zeros((2, 128, 8), jnp.float32)
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            # flag off: eager fallback is the EXPECTED path, no warning
            assert fused_ce.fused_ce_applies(hv, False) is False
        fl.set_flags({"FLAGS_fused_lm_head_ce": True})
        try:
            with warnings.catch_warnings():
                warnings.simplefilter("error")
                # non-tiling token count: compiled would not fuse
                # either, so warning "use a compiled step" would be
                # false advice — and it must not burn the once-latch
                bad = jnp.zeros((3, 11, 8), jnp.float32)
                assert fused_ce.fused_ce_applies(bad, False) is False
            assert fused_ce._eager_unfused_warned is False
        finally:
            fl.set_flags({"FLAGS_fused_lm_head_ce": False})
        fl.set_flags({"FLAGS_fused_lm_head_ce": True})
        try:
            with warnings.catch_warnings():
                warnings.simplefilter("error")
                # traced value: the fused path applies, nothing to warn
                out = []
                jax.make_jaxpr(
                    lambda x: out.append(
                        fused_ce.fused_ce_applies(x, False)) or x)(hv)
                assert out == [True]
        finally:
            fl.set_flags({"FLAGS_fused_lm_head_ce": False})


class TestSsmDecodeKernel:
    """``ssm_decode`` (serving/kernels/ssm.py) in interpret mode against
    its ``jax.numpy`` twin: the state of the active slots updated in
    place, an idle slot's row bit for bit as it was."""

    @staticmethod
    def _inputs(s, h, p, g, n, dtype, seed):
        rng = np.random.RandomState(seed)
        x = jnp.asarray(rng.randn(s, h, p), dtype)
        dt = jnp.asarray(np.log1p(np.exp(rng.randn(s, h))), jnp.float32)
        a = -jnp.asarray(rng.uniform(1.0, 16.0, h), jnp.float32)
        d = jnp.asarray(rng.uniform(0.5, 1.5, h), jnp.float32)
        b = jnp.asarray(rng.randn(s, g, n), dtype)
        c = jnp.asarray(rng.randn(s, g, n), dtype)
        active = jnp.asarray(rng.rand(s) < 0.6)
        state = jnp.asarray(rng.randn(s, g, n, h // g * p), jnp.float32)
        return x, dt, a, d, b, c, active, state

    @pytest.mark.parametrize("s,h,p,g,n,dtype", [
        (5, 4, 8, 2, 16, jnp.float32),
        (6, 16, 16, 8, 24, jnp.bfloat16)])
    def test_kernel_matches_its_twin_and_idle_slots_stay(self, s, h, p, g,
                                                         n, dtype):
        from paddle_tpu.serving.kernels import ssm

        args = self._inputs(s, h, p, g, n, dtype, seed=s)
        active = np.asarray(args[6])
        assert active.any() and not active.all()
        y_t, s_t = ssm.ssm_decode_reference(*args)
        y_k, s_k = ssm.ssm_decode_kernel(*args, interpret=True)
        assert y_k.shape == (s, h, p) and y_k.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(y_k)[active],
                                   np.asarray(y_t)[active],
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_t),
                                   rtol=1e-6, atol=1e-6)
        for new in (s_k, s_t):
            assert np.array_equal(np.asarray(new)[~active],
                                  np.asarray(args[7])[~active])
        assert not np.array_equal(np.asarray(s_k)[active],
                                  np.asarray(args[7])[active])

    def test_off_the_tpu_the_dispatch_takes_the_twin(self):
        from paddle_tpu.serving.kernels import ssm

        args = self._inputs(3, 4, 8, 2, 16, jnp.float32, seed=1)
        text = str(jax.make_jaxpr(ssm.ssm_decode)(*args))
        assert "pallas_call" not in text
        y, new = ssm.ssm_decode(*args)
        y_t, s_t = ssm.ssm_decode_reference(*args)
        assert np.array_equal(np.asarray(y), np.asarray(y_t))
        assert np.array_equal(np.asarray(new), np.asarray(s_t))

    def test_all_layers_share_one_trace_of_the_kernel(self):
        """The kernel body sits in a jitted wrapper: two calls in one
        program are two calls of one ``_ssm_decode``."""
        from paddle_tpu.serving.kernels import ssm

        args = self._inputs(3, 4, 8, 2, 16, jnp.float32, seed=2)

        def two_layers(*a):
            y, state = ssm.ssm_decode_kernel(*a, interpret=True)
            return ssm.ssm_decode_kernel(*a[:7], state, interpret=True)

        text = str(jax.make_jaxpr(two_layers)(*args))
        assert text.count("name=_ssm_decode") == 2
        assert text.count("name=ssm_decode") == 1


class TestGdnChunkedKernel:
    """``gdn_chunked`` (kernels/gdn_chunked.py) in interpret mode against
    the token-by-token recurrence (``gated_delta_step``) and its jnp twin
    (``gated_delta_chunked``), from a state that is not zero."""

    @staticmethod
    def _inputs(b, t, hk, hv, d, seed):
        from paddle_tpu.models import qwen3_next as qn

        rng = np.random.RandomState(seed)
        q = qn.l2_normalise(jnp.asarray(rng.randn(b, t, hk, d),
                                        jnp.float32)) * d ** -0.5
        k = qn.l2_normalise(jnp.asarray(rng.randn(b, t, hk, d),
                                        jnp.float32))
        v = jnp.asarray(rng.randn(b, t, hv, d), jnp.float32)
        g = -jnp.asarray(rng.rand(b, t, hv), jnp.float32)
        beta = jnp.asarray(rng.rand(b, t, hv), jnp.float32)
        state = jnp.asarray(rng.randn(b, hv, d, d), jnp.float32)
        return q, k, v, g, beta, state

    @staticmethod
    def _recurrence(q, k, v, g, beta, state):
        from paddle_tpu.models import qwen3_next as qn

        outs = []
        for t in range(q.shape[1]):
            o, state = qn.gated_delta_step(q[:, t], k[:, t], v[:, t],
                                           g[:, t], beta[:, t], state)
            outs.append(o)
        return jnp.stack(outs, 1), state

    @pytest.mark.parametrize("tokens,hk", [
        (1, 2), (63, 2), (64, 2), (65, 2), (150, 2), (129, 2), (65, 4),
        (64, 1)], ids=["t1", "t63", "t64", "t65", "t150", "t129",
                       "t65_one_key_a_value_head", "t64_four_value_heads_a_key"])
    def test_kernel_matches_the_recurrence_and_its_twin(self, tokens, hk):
        """One chunk short, whole, one over, several with a remainder and
        one over two chunks; two value heads a key head (the published
        ratio), and a pair of value heads on two key heads or on one."""
        from paddle_tpu.kernels import gdn_chunked as gk

        args = self._inputs(2, tokens, hk, 4, 16, seed=tokens + hk)
        o_k, s_k = gk.gdn_chunked_kernel(*args, interpret=True)
        assert o_k.shape == (2, tokens, 4, 16) and o_k.dtype == jnp.float32
        assert s_k.dtype == jnp.float32
        o_r, s_r = self._recurrence(*args)
        o_t, s_t = gk.gated_delta_chunked(*args)
        for want_o, want_s in ((o_r, s_r), (o_t, s_t)):
            np.testing.assert_allclose(np.asarray(o_k), np.asarray(want_o),
                                       rtol=1e-4, atol=1e-5)
            np.testing.assert_allclose(np.asarray(s_k), np.asarray(want_s),
                                       rtol=1e-4, atol=1e-5)

    def test_padded_rows_leave_the_state_as_the_real_rows_do(self):
        """g = 0 and beta = 0 on the rows past the real ones: the state is
        what the real rows alone leave, and the real rows' outputs are
        theirs."""
        from paddle_tpu.kernels import gdn_chunked as gk

        q, k, v, g, beta, state = self._inputs(1, 128, 2, 4, 16, seed=5)
        live = (jnp.arange(128) < 75)[None, :, None]
        o_pad, padded = gk.gdn_chunked_kernel(
            q, k, v, jnp.where(live, g, 0.0), jnp.where(live, beta, 0.0),
            state, interpret=True)
        o_real, real = gk.gdn_chunked_kernel(
            q[:, :75], k[:, :75], v[:, :75], g[:, :75], beta[:, :75], state,
            interpret=True)
        np.testing.assert_allclose(np.asarray(padded), np.asarray(real),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(o_pad[:, :75]),
                                   np.asarray(o_real), rtol=1e-5, atol=1e-6)

    def test_gradients_through_the_kernel_are_the_twins(self):
        from paddle_tpu.kernels import gdn_chunked as gk

        args = self._inputs(1, 70, 2, 4, 16, seed=3)
        rng = np.random.RandomState(4)
        w_o = jnp.asarray(rng.randn(1, 70, 4, 16), jnp.float32)
        w_s = jnp.asarray(rng.randn(1, 4, 16, 16), jnp.float32)

        def loss(fn):
            def f(*a):
                o, s = fn(*a)
                return jnp.sum(o * w_o) + jnp.sum(s * w_s)
            return jax.grad(f, argnums=tuple(range(6)))

        got = loss(lambda *a: gk.gdn_chunked_kernel(*a, interpret=True))(
            *args)
        want = loss(gk.gated_delta_chunked)(*args)
        for x, y in zip(got, want):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       rtol=1e-4, atol=1e-5)

    def test_off_the_tpu_the_dispatch_takes_the_twin(self):
        from paddle_tpu.kernels import gdn_chunked as gk

        args = self._inputs(1, 20, 2, 4, 16, seed=1)
        text = str(jax.make_jaxpr(gk.gdn_chunked)(*args))
        assert "pallas_call" not in text
        o, s = gk.gdn_chunked(*args)
        o_t, s_t = gk.gated_delta_chunked(*args)
        assert np.array_equal(np.asarray(o), np.asarray(o_t))
        assert np.array_equal(np.asarray(s), np.asarray(s_t))

    def test_all_layers_share_one_trace_of_the_kernel(self):
        """The kernel body sits in a jitted wrapper: two calls in one
        program are two calls of one ``_gdn_forward``."""
        from paddle_tpu.kernels import gdn_chunked as gk

        args = self._inputs(1, 20, 2, 4, 16, seed=2)

        def two_layers(*a):
            o, state = gk.gdn_chunked_kernel(*a, interpret=True)
            return gk.gdn_chunked_kernel(*a[:5], state, interpret=True)

        text = str(jax.make_jaxpr(two_layers)(*args))
        assert text.count("name=_gdn_forward") == 2
        assert text.count("name=gdn_chunked") == 1
