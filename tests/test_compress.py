"""Parity suite for quantized gradient communication
(paddle_tpu.distributed.compress).

Pins, per ISSUE acceptance:
- flag OFF: compiled-step HLO free of quantized-sync artifacts and
  byte-stable, eager wire frames byte-identical to the legacy format;
- flag ON: int8 path within tolerance (4-proc dp=2 x sharding=2 run in
  tests/compress_worker.py, >=3x comm-byte reduction via the
  comm_bytes registry / flight-recorder payload sizes);
- error-feedback residual pins the compiled loss trajectory to fp32
  over 50 steps;
- bucketing pins "number of reductions issued" via the flight recorder.
"""
from __future__ import annotations

import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.core import flags as fl
from paddle_tpu.distributed import compress
from paddle_tpu.kernels import quant

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "compress_worker.py")


@pytest.fixture
def qsync_flag():
    """Flag hygiene: every test leaves the global flag off."""
    yield
    fl.set_flags({"FLAGS_quantized_grad_sync": False,
                  "FLAGS_quantized_grad_sync_stochastic": False,
                  "FLAGS_grad_sync_bucket_mb": 4.0})


class TestQuantPrimitives:
    def test_roundtrip_within_half_ulp_per_block(self):
        rng = np.random.RandomState(0)
        # wide dynamic range across blocks — what block scaling is FOR
        x = (rng.randn(8, 1024) * np.exp(rng.randn(8, 1))) \
            .astype(np.float32)
        q, s = quant.quantize_int8_block(jnp.asarray(x), 256)
        xr = np.asarray(quant.dequantize_int8_block(q, s, block=256))
        blocks = x.reshape(8, 4, 256)
        half_ulp = np.abs(blocks).max(axis=-1, keepdims=True) / 127 * .5
        err = np.abs((x - xr).reshape(8, 4, 256))
        assert (err <= half_ulp + 1e-7).all()

    def test_zero_blocks_exact(self):
        x = jnp.zeros((2, 512), jnp.float32)
        q, s = quant.quantize_int8_block(x)
        assert np.asarray(
            quant.dequantize_int8_block(q, s)).sum() == 0.0

    def test_stochastic_rounding_unbiased(self):
        rng = np.random.RandomState(1)
        x = jnp.asarray(rng.randn(1, 256).astype(np.float32))
        key = jax.random.PRNGKey(0)
        acc = np.zeros((1, 256), np.float64)
        n = 300
        for i in range(n):
            q, s = quant.quantize_int8_block(
                x, 256, stochastic=True, key=jax.random.fold_in(key, i))
            acc += np.asarray(quant.dequantize_int8_block(q, s))
        ulp = float(np.abs(np.asarray(x)).max()) / 127
        bias = np.abs(acc / n - np.asarray(x)).max()
        # the mean of n dithered roundings concentrates ~ulp/sqrt(n)
        assert bias < 0.25 * ulp, (bias, ulp)

    def test_nonfinite_blocks_propagate_nan_not_silent_zero(self):
        """Overflow detectability (review-found): an inf gradient used
        to smear finite garbage, and a NaN gradient silently became 0 —
        masking AMP overflow detection. Non-finite blocks now carry
        scale NaN and dequantize to NaN on every rank."""
        for poison in (np.inf, np.nan):
            flat = np.ones(512, np.float32)
            flat[5] = poison
            q, s = compress.quantize_np(flat, 256)
            assert np.isnan(s[0])  # poisoned block flagged via scale
            assert np.isfinite(s[1])  # healthy block untouched
            deq = compress.dequantize_np(q, s, 256)
            assert np.isnan(deq[:256]).all()
            np.testing.assert_allclose(deq[256:], 1.0)
        # wire round trip keeps the poison visible
        bad = np.ones(2048, np.float32)
        bad[0] = np.inf
        out, _ = compress.wire_decode(
            compress.wire_encode(bad, compressed=True))
        assert np.isnan(out[:256]).all() and np.isfinite(out[256:]).all()
        # and the traced twin agrees
        xb = jnp.asarray(np.where(np.isfinite(bad[:512]), 1.0,
                                  np.nan)).reshape(2, 256)
        qj, sj = quant.quantize_int8_block(xb, 256)
        assert np.isnan(np.asarray(sj)[0, 0])
        assert np.isnan(np.asarray(
            quant.dequantize_int8_block(qj, sj))[0]).all()

    def test_np_twins_match_traced(self):
        rng = np.random.RandomState(2)
        flat = rng.randn(5000).astype(np.float32)
        qn, sn = compress.quantize_np(flat, 256)
        pad = np.pad(flat, (0, 5120 - 5000)).reshape(20, 256)
        qj, sj = quant.quantize_int8_block(jnp.asarray(pad), 256)
        np.testing.assert_array_equal(
            qn, np.asarray(qj).reshape(-1)[:5000])
        np.testing.assert_allclose(sn, np.asarray(sj).reshape(-1))
        np.testing.assert_allclose(
            compress.dequantize_np(qn, sn, 256),
            np.asarray(quant.dequantize_int8_block(qj, sj))
            .reshape(-1)[:5000])


class TestWireFormat:
    def test_uncompressed_frame_byte_identical_to_legacy(self):
        """Flag-off wire pin: the frame layout predates compression and
        every byte must stay put (mixed-version worlds decode it)."""
        import struct

        rng = np.random.RandomState(3)
        for arr in (rng.randn(8, 3).astype(np.float32),
                    rng.randint(0, 9, (4,)).astype(np.int64)):
            head = json.dumps({"d": arr.dtype.name,
                               "s": list(arr.shape)}).encode()
            legacy = struct.pack(">I", len(head)) + head + arr.tobytes()
            assert compress.wire_encode(arr) == legacy

    def test_flag_off_never_compresses(self, qsync_flag):
        big = np.random.RandomState(0).randn(4096).astype(np.float32)
        assert not compress.should_compress(big)
        fl.set_flags({"FLAGS_quantized_grad_sync": True})
        assert compress.should_compress(big)
        # ints and small payloads stay exact even with the flag on
        assert not compress.should_compress(
            np.arange(4096, dtype=np.int32))
        assert not compress.should_compress(
            np.zeros(512, np.float32))

    def test_compressed_frame_ratio_and_roundtrip(self):
        rng = np.random.RandomState(4)
        arr = (rng.randn(256, 64) * np.exp(rng.randn(256, 1))) \
            .astype(np.float32)
        plain = compress.wire_encode(arr)
        packed = compress.wire_encode(arr, compressed=True)
        assert len(plain) >= 3 * len(packed)
        assert compress.wire_is_compressed(packed)
        assert not compress.wire_is_compressed(plain)
        out, meta = compress.wire_decode(packed)
        assert out.shape == arr.shape and out.dtype == arr.dtype
        scale = np.abs(arr).max()
        assert np.abs(out - arr).max() <= scale / 127 + 1e-6

    def test_bf16_roundtrip(self):
        import ml_dtypes

        arr = np.random.RandomState(5).randn(64, 32) \
            .astype(ml_dtypes.bfloat16)
        out, _ = compress.wire_decode(
            compress.wire_encode(arr, compressed=True))
        assert out.dtype == arr.dtype
        assert np.abs(out.astype(np.float32)
                      - arr.astype(np.float32)).max() < 0.1


class TestBucketPlan:
    def test_threshold_coalescing(self):
        items = [("a", 30), ("b", 30), ("c", 30), ("d", 100), ("e", 10)]
        assert compress.plan_buckets(items, 64) == \
            [["a", "b"], ["c"], ["d"], ["e"]]

    def test_oversized_item_gets_own_bucket(self):
        items = [("big", 1000), ("s1", 5), ("s2", 5)]
        assert compress.plan_buckets(items, 64) == \
            [["big"], ["s1", "s2"]]

    def test_analytic_ring_bytes_ratio(self):
        fp = compress.ring_allreduce_bytes(1 << 20, 4, False)
        q8 = compress.ring_allreduce_bytes(1 << 20, 4, True)
        assert fp >= 3 * q8


needs8 = pytest.mark.skipif(jax.device_count() < 8,
                            reason="needs 8 virtual devices")


def _build_step(seed=7, lr=1e-2, zero_stage=0):
    import paddle_tpu.nn as nn
    import paddle_tpu.nn.functional as F
    from paddle_tpu.parallel.engine import CompiledTrainStep

    paddle.seed(seed)
    m = nn.Sequential(nn.Linear(16, 64), nn.Tanh(), nn.Linear(64, 8))
    o = paddle.optimizer.AdamW(learning_rate=lr,
                               parameters=m.parameters())
    return m, CompiledTrainStep(
        m, lambda out, y: F.cross_entropy(out, y), o,
        zero_stage=zero_stage)


def _program_text(hlo):
    """The program without what records where it was built from: the
    tables in front of it (FileNames ... StackFrames, each a title and
    numbered rows) and every instruction's ``metadata`` hold the line
    each ``lowered_hlo`` call was made from, so two builds differ there."""
    titles = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")
    return "\n".join(re.sub(r", metadata=\{[^}]*\}", "", line)
                     for line in hlo.splitlines()
                     if line not in titles and not line[:1].isdigit())


def _batch(n=16):
    rng = np.random.RandomState(0)
    return (paddle.to_tensor(rng.rand(n, 16).astype(np.float32)),
            paddle.to_tensor(rng.randint(0, 8, n)))


@needs8
class TestCompiledQuantizedSync:
    @pytest.fixture(autouse=True)
    def mesh(self, qsync_flag):
        from paddle_tpu.distributed import mesh as pmesh

        pmesh.build_hybrid_mesh(dp=4, sharding=2)
        yield
        pmesh.set_mesh(None)

    def test_flag_off_hlo_has_no_quant_artifacts_and_is_stable(self):
        """The off-path pin: no all-to-all, no int8 payloads, and the
        HLO is build-to-build deterministic — the quantized machinery
        leaves zero residue when disabled."""
        x, y = _batch()
        _, s1 = _build_step()
        hlo1 = s1.lowered_hlo(x, y)
        assert "all-to-all" not in hlo1
        assert " s8[" not in hlo1
        _, s2 = _build_step()
        assert _program_text(s2.lowered_hlo(x, y)) == _program_text(hlo1)

    def test_flag_on_hlo_reduces_in_int8(self):
        fl.set_flags({"FLAGS_quantized_grad_sync": True})
        x, y = _batch()
        _, step = _build_step()
        hlo = step.lowered_hlo(x, y)
        assert "all-to-all" in hlo
        assert " s8[" in hlo
        assert step._qsync is not None
        axes, nranks, buckets = step._qsync
        assert nranks == 8 and set(axes) == {"dp", "sharding"}

    def test_error_feedback_pins_loss_trajectory_50_steps(self):
        x, y = _batch()
        _, ref = _build_step()
        ref_losses = [float(ref(x, y)) for _ in range(50)]
        fl.set_flags({"FLAGS_quantized_grad_sync": True})
        _, qs = _build_step()
        q_losses = [float(qs(x, y)) for _ in range(50)]
        np.testing.assert_allclose(q_losses, ref_losses, rtol=2e-2)
        # and it actually trained (not pinned by standing still)
        assert q_losses[-1] < 0.5 * q_losses[0]

    def test_bucketing_pins_reduction_count(self):
        # tiny threshold -> one bucket per param; big -> one bucket.
        # HLO all-to-all count is the compiled-path witness (the eager
        # witness — flight-recorder all_reduce count — is pinned by the
        # 4-proc worker)
        x, y = _batch()
        fl.set_flags({"FLAGS_quantized_grad_sync": True,
                      "FLAGS_grad_sync_bucket_mb": 1e-6})
        _, fine = _build_step()
        assert np.isfinite(float(fine(x, y)))  # triggers the build
        assert len(fine._qsync[2]) == 4  # W1, b1, W2, b2
        fl.set_flags({"FLAGS_grad_sync_bucket_mb": 4.0})
        _, fused = _build_step()
        assert float(fused(x, y)) > 0
        assert len(fused._qsync[2]) == 1

    def test_run_steps_quantized(self):
        fl.set_flags({"FLAGS_quantized_grad_sync": True})
        _, step = _build_step()
        rng = np.random.RandomState(1)
        xs = rng.rand(4, 16, 16).astype(np.float32)
        ys = rng.randint(0, 8, (4, 16))
        l1 = float(step.run_steps(paddle.to_tensor(xs),
                                  paddle.to_tensor(ys)))
        l2 = float(step.run_steps(paddle.to_tensor(xs),
                                  paddle.to_tensor(ys)))
        assert np.isfinite(l1) and np.isfinite(l2) and l2 < l1

    def test_sum_reduction_loss_declared_matches_exact(self):
        """Review-found: the quantized path combines PER-RANK losses,
        so a sum-reduction loss must be declared via loss_reduction
        ('mean' assumed otherwise) — psum replaces pmean and gradients
        keep their magnitude."""
        import paddle_tpu.nn as nn
        import paddle_tpu.nn.functional as F
        from paddle_tpu.parallel.engine import CompiledTrainStep

        def build(reduction_arg):
            paddle.seed(9)
            m = nn.Sequential(nn.Linear(16, 32), nn.Tanh(),
                              nn.Linear(32, 4))
            o = paddle.optimizer.SGD(learning_rate=1e-3,
                                     parameters=m.parameters())
            loss = lambda out, y: F.cross_entropy(out, y,
                                                  reduction="sum")
            return CompiledTrainStep(m, loss, o,
                                     loss_reduction=reduction_arg)

        x, y = _batch()
        ref = build("sum")          # flag off: exact path
        ref_losses = [float(ref(x, y)) for _ in range(10)]
        fl.set_flags({"FLAGS_quantized_grad_sync": True})
        qs = build("sum")
        q_losses = [float(qs(x, y)) for _ in range(10)]
        np.testing.assert_allclose(q_losses, ref_losses, rtol=2e-2)

    def test_stochastic_rounding_path(self):
        fl.set_flags({"FLAGS_quantized_grad_sync": True,
                      "FLAGS_quantized_grad_sync_stochastic": True})
        x, y = _batch()
        _, step = _build_step()
        l0 = float(step(x, y))
        for _ in range(5):
            l1 = float(step(x, y))
        assert np.isfinite(l1) and l1 < l0

    def test_zero2_quantized_matches_stage0(self):
        x, y = _batch()
        fl.set_flags({"FLAGS_quantized_grad_sync": True})
        _, s0 = _build_step(zero_stage=0)
        _, s2 = _build_step(zero_stage=2)
        l0 = [float(s0(x, y)) for _ in range(5)]
        l2 = [float(s2(x, y)) for _ in range(5)]
        np.testing.assert_allclose(l2, l0, rtol=1e-2)

    def test_unsupported_mesh_falls_back_with_warning(self):
        from paddle_tpu.distributed import mesh as pmesh

        pmesh.build_hybrid_mesh(dp=4, mp=2)
        fl.set_flags({"FLAGS_quantized_grad_sync": True})
        x, y = _batch()
        _, step = _build_step()
        with pytest.warns(UserWarning, match="unsupported"):
            hlo = step.lowered_hlo(x, y)
        assert "all-to-all" not in hlo
        assert step._qsync is None

    def test_comm_bytes_gauges_published(self):
        from paddle_tpu import monitor

        fl.set_flags({"FLAGS_quantized_grad_sync": True})
        x, y = _batch()
        _, step = _build_step()
        float(step(x, y))
        metrics = monitor.snapshot()["metrics"]
        series = {tuple(sorted(s["labels"].items())): s["value"]
                  for s in metrics["grad_sync_bytes_per_step"]["series"]}
        fp = series[(("compressed", "false"),)]
        q8 = series[(("compressed", "true"),)]
        assert fp >= 3 * q8 > 0
        assert metrics["grad_sync_buckets"]["series"][0]["value"] == 1


class TestHybridOptimizerRoute:
    def test_flag_routes_dp_grad_sync_through_compressed_path(
            self, qsync_flag, monkeypatch):
        """The fused_allreduce_gradients analog must take the bucketed
        EF sync when the flag is on (review-found: a bare compressed
        all_reduce would drop sub-ulp grad mass with no residual)."""
        import paddle_tpu.distributed.compress as compress_mod
        from paddle_tpu import nn, optimizer
        from paddle_tpu.parallel.hybrid_optimizer import (
            HybridParallelOptimizer,
        )

        class FakePg:
            world_size = 2

        class FakeGroup:
            nranks = 2
            pg = FakePg()

        class FakeHcg:
            def get_data_parallel_group(self):
                return FakeGroup()

        calls = []
        monkeypatch.setattr(
            compress_mod, "sync_gradients_compressed",
            lambda params, group, residuals=None, **kw:
            calls.append((len(list(params)), residuals)))
        fl.set_flags({"FLAGS_quantized_grad_sync": True})
        lin = nn.Linear(2, 2)
        opt = HybridParallelOptimizer(
            optimizer.SGD(learning_rate=0.1,
                          parameters=lin.parameters()),
            hcg=FakeHcg(), strategy=None)
        lin(paddle.to_tensor(np.ones((1, 2), np.float32))) \
            .sum().backward()
        opt.step()
        opt.step()
        assert len(calls) == 2
        # residuals dict persists across steps (error feedback state)
        assert calls[0][1] is calls[1][1] is not None


class TestCompressed4Proc:
    """The acceptance run: 4 processes, dp=2 x sharding=2, int8 within
    tolerance of fp32 and >=3x fewer gradient comm bytes."""

    @pytest.fixture(scope="class")
    def cluster(self):
        sys.path.insert(0, os.path.join(REPO, "tests"))
        from dist_utils import free_ports

        port = free_ports(1)
        procs = []
        for rank in range(4):
            env = dict(os.environ)
            env.update({
                "PYTHONPATH": REPO + os.pathsep
                + env.get("PYTHONPATH", ""),
                "JAX_PLATFORMS": "cpu",
                "PADDLE_TRAINER_ID": str(rank),
                "PADDLE_TRAINERS_NUM": "4",
                "PADDLE_MASTER": "127.0.0.1:%d" % port,
            })
            procs.append(subprocess.Popen(
                [sys.executable, WORKER], env=env,
                stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                text=True))
        results = {}
        for rank, p in enumerate(procs):
            try:
                out, err = p.communicate(timeout=300)
            except subprocess.TimeoutExpired:
                for q in procs:
                    q.kill()
                raise
            assert p.returncode == 0, (
                "rank %d rc=%d\nstdout:\n%s\nstderr:\n%s"
                % (rank, p.returncode, out[-2000:], err[-3000:]))
            line = [l for l in out.splitlines()
                    if l.startswith("COMPRESS_RESULT ")][0]
            results[rank] = json.loads(line[len("COMPRESS_RESULT "):])
        return results

    def test_int8_losses_within_tolerance(self, cluster):
        for rank, rec in cluster.items():
            fp = np.asarray(rec["fp32_losses"])
            q8 = np.asarray(rec["q8_losses"])
            np.testing.assert_allclose(q8, fp, rtol=5e-2,
                                       err_msg="rank %d" % rank)
            assert q8[-1] < q8[0], "rank %d did not train" % rank

    def test_all_ranks_identical_global_loss(self, cluster):
        base = cluster[0]["q8_losses"]
        for rank, rec in cluster.items():
            np.testing.assert_allclose(rec["q8_losses"], base,
                                       rtol=1e-9)

    def test_comm_bytes_at_least_3x_smaller(self, cluster):
        for rank, rec in cluster.items():
            fp_bytes = rec["fp32_bytes"]["false"]
            q8_bytes = rec["q8_bytes"]["true"]
            assert q8_bytes > 0, rank
            assert fp_bytes >= 3 * q8_bytes, (
                "rank %d: fp32 sync moved %d B but int8 moved %d B "
                "(< 3x reduction)" % (rank, fp_bytes, q8_bytes))

    def test_bucketing_pins_reductions_via_flight_recorder(self, cluster):
        for rank, rec in cluster.items():
            # 4 params -> 4 fp32 all_reduces; 2 buckets -> 2 compressed
            assert rec["fp32_allreduces_per_sync"] == 4, rank
            assert rec["q8_allreduces_per_sync"] == 2, rank
            assert rec["q8_wire_bytes_recorded"], rank

    def test_zero2_subgroup_training_within_tolerance(self, cluster):
        for rank, rec in cluster.items():
            fp = np.asarray(rec["zero2_fp32_losses"])
            q8 = np.asarray(rec["zero2_q8_losses"])
            assert np.isfinite(q8).all()
            np.testing.assert_allclose(q8, fp, rtol=5e-2,
                                       err_msg="rank %d" % rank)

    def test_max_reduction_stays_exact_under_flag(self, cluster):
        for rank, rec in cluster.items():
            assert rec.get("max_exact"), (
                "rank %d: op=max was lossy under the flag" % rank)

    def test_object_collectives_unaffected(self, cluster):
        for rank, rec in cluster.items():
            assert rec.get("object_collectives_ok"), rank

    def test_mismatch_validation_names_rank(self, cluster):
        for rank, rec in cluster.items():
            msg = rec["mismatch_error"]
            assert msg is not None, (
                "rank %d: strict all_gather let a shape mismatch "
                "through" % rank)
            assert "rank 1" in msg and "(3, 2)" in msg, msg
