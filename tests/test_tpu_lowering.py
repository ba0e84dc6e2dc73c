"""Every Pallas kernel, lowered and compiled for the TPU from the CPU.

Two layers, both at the chip_smoke.py geometry (llama1b widths), plus
the paged decode kernel at the benchmark's serving cell's own:

- ``TestCrossLowering``: ``jit(f).trace(...).lower(lowering_platforms=
  ("tpu",))`` with ``interpret=False``. This is Pallas's own TPU
  lowering — block-shape legality, supported primitives — and needs no
  TPU software at all. It is what refused the fused lm_head+CE kernel's
  ``(1, block_t)`` planes.
- ``TestMosaicCompile``: the same functions AOT-compiled against a
  ``v5e:1x1`` topology description. libtpu ships the real compiler, so
  this is Mosaic proper — layout inference, the scoped-VMEM limit — with
  no chip attached. Skipped where libtpu cannot describe the topology.

Neither layer executes anything: numerics on the chip are
chip_smoke.py's job.
"""
from __future__ import annotations

import importlib
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.analysis.graph.hlo import mosaic_kernels
from paddle_tpu.kernels import flash_attention as fa
from paddle_tpu.kernels import fused_ce as fc
from paddle_tpu.kernels import gdn_chunked as gdn
from paddle_tpu.kernels import moe_gmm as mg
from paddle_tpu.parallel import moe

# the package re-exports a function under the module's name
pa = importlib.import_module("paddle_tpu.serving.kernels.paged_attention")
mla = importlib.import_module("paddle_tpu.serving.kernels.mla_attention")
ssm = importlib.import_module("paddle_tpu.serving.kernels.ssm")
da = importlib.import_module("paddle_tpu.serving.kernels.diff_attention")
sc = importlib.import_module("paddle_tpu.serving.kernels.selective_scan")

BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32
# the forward kernel's place in an instruction's ``op_name``, below the
# scope that called it: its jitted wrapper (one trace for all layers),
# then the kernel's own name before ``pallas_call``
FWD_KERNEL_PATH = 'jit(_flash_fwd_bhnd)/flash_fwd/pallas_call"'

# smoke geometry: 8 x 1024 tokens, 16 heads x 128, vocab 32000;
# serving: 8 slots, 512 pages of 16, 128 pages per slot
B, N, H, D = 8, 1024, 16, 128
T, HID, V = B * N, 2048, 32000
S, NB, BS, MB, C = 8, 512, 16, 128, 16
# mistral7b-chat-backlog: 64 slots x 160 pages of a 10,000-page pool,
# 32 heads over 8 kv heads (benchmark/traffic/chat-backlog.json)
CELL = "paged_decode_bf16_gqa_cell"
CELL_S, CELL_NB, CELL_MB, CELL_H, CELL_HKV = 64, 10000, 160, 32, 8
# qwen3next-longdoc-backlog: one paged layer at head_dim 256 with 16
# query heads over 2 kv heads, 128 slots x 576 pages of a 72,000-page
# pool; prefill buckets up to 8192; 256 experts held of 2048 x 512, ten
# pairs a token (benchmark/traffic/longdoc-backlog.json)
QWEN_S, QWEN_NB, QWEN_MB, QWEN_H, QWEN_HKV, QWEN_D = 128, 72000, 576, 16, 2, 256
QWEN_EXPERTS, QWEN_HID, QWEN_WIDTH, QWEN_TOPK = 256, 2048, 512, 10
# deepseekv2-longctx-backlog: 128 heads over one latent row of 512 + 64
# values a token (640 lanes in the pool), 128 slots x 640 pages of a
# 60,000-page pool; prefill attends over expanded heads 192 wide in q/k
# and 128 in v (benchmark/traffic/longctx-backlog.json)
MLA_S, MLA_NB, MLA_MB, MLA_H, MLA_RANK, MLA_W = 128, 60000, 640, 128, 512, 640
MLA_QK, MLA_V = 192, 128
# nemotron3nano-longreason-backlog: 256 slots of Mamba-2 state, 64 heads
# x 64 with a state of 128 in 8 groups; 64 experts held of 2688 x 1856
# (a width that is no multiple of 128), six pairs a token
# (benchmark/traffic/longreason-backlog.json)
SSM_S, SSM_H, SSM_P, SSM_G, SSM_N = 256, 64, 64, 8, 128
NEMO_EXPERTS, NEMO_HID, NEMO_WIDTH, NEMO_TOPK = 64, 2688, 1856, 6
# gigachat35-longdoc-backlog: 64 latent heads over the same 576-value
# row, 192 slots x 640 pages of an 80,000-page pool; 8 experts held of
# 256 at 7168 x 2048, clamped SwiGLU, eight pairs a token
# (benchmark/traffic/longdoc-hybrid-backlog.json)
GIGA_S, GIGA_NB, GIGA_H = 192, 80000, 64
GIGA_EXPERTS, GIGA_ROUTER, GIGA_HID, GIGA_WIDTH, GIGA_TOPK = (
    8, 256, 7168, 2048, 8)
# the Gated DeltaNet prefill of both hybrid cells, heads of 128: key /
# value heads 16 / 32 (Qwen3-Next) and 32 / 64 (GigaChat3.5), the
# largest bucket and one chunk
GDN_SHAPES = (("qwen", 16, 32), ("gigachat", 32, 64))
GDN_D, GDN_TOKENS = 128, (8192, 64)
# phi4flash-mathreason-backlog: 160 slots; 40 query heads over 20 KV
# heads of 64, a page [16, 1280] (every head of a token on the lanes),
# 384 pages a slot of a 34,000-page pool, and a 512-row ring a slot of
# each window layer (32 pages); Mamba-1 over 5120 channels with a state
# of 16; prefill buckets up to 2048, window 512
# (benchmark/traffic/mathreason-backlog.json)
PHI_S, PHI_NB, PHI_MB, PHI_H, PHI_HKV, PHI_D = 160, 34000, 384, 40, 20, 64
PHI_WINDOW, PHI_C, PHI_N = 512, 5120, 16


def _flash(dtype, d, segmented=False):
    shape = ((B, N, H, d), dtype)
    args = [shape, shape, shape] + ([((B, N), I32)] if segmented else [])

    def fwd(q, k, v, seg=None):
        return fa.flash_attention(q, k, v, causal=True, interpret=False,
                                  segment_ids=seg)

    def bwd(q, k, v, seg=None):
        return jax.grad(
            lambda q, k, v: fwd(q, k, v, seg).astype(F32).sum(),
            argnums=(0, 1, 2))(q, k, v)

    return fwd, bwd, args


def _cases():
    """(name, fn, args, kernel names). A backward case lowers the
    forward kernel too, so only the main geometry has a forward-only
    case per kernel family."""
    cases = []
    for name, dtype, d, seg in (("flash_bf16_d128", BF16, 128, False),
                                ("flash_f32_d128", F32, 128, False),
                                ("flash_bf16_d64", BF16, 64, False),
                                ("flash_bf16_d128_segmented", BF16, 128,
                                 True)):
        fwd, bwd, args = _flash(dtype, d, seg)
        if name == "flash_bf16_d128":
            cases.append((name + "_fwd", fwd, args, {"flash_fwd"}))
        cases.append((name + "_bwd", bwd, args,
                      {"flash_fwd", "flash_dq", "flash_dkv"}))
    for name, dtype, h, hkv, s, nb, mb in (
            ("paged_decode_bf16_mha", BF16, 16, 16, S, NB, MB),
            ("paged_decode_f32_mha", F32, 16, 16, S, NB, MB),
            ("paged_decode_bf16_gqa", BF16, 32, 8, S, NB, MB),
            (CELL, BF16, CELL_H, CELL_HKV, CELL_S, CELL_NB, CELL_MB)):
        pool = ((nb, BS, hkv, D), dtype)
        cases.append((
            name,
            lambda q, k, v, bt, ln: pa.paged_attention_kernel(
                q, k, v, bt, ln, interpret=False),
            [((s, h, D), dtype), pool, pool, ((s, mb), I32), ((s,), I32)],
            {"paged_decode"}))
    pool = ((QWEN_NB, BS, QWEN_HKV, QWEN_D), BF16)
    cases.append((
        "paged_decode_bf16_d256_qwen_cell",
        lambda q, k, v, bt, ln: pa.paged_attention_kernel(
            q, k, v, bt, ln, interpret=False),
        [((QWEN_S, QWEN_H, QWEN_D), BF16), pool, pool,
         ((QWEN_S, QWEN_MB), I32), ((QWEN_S,), I32)],
        {"paged_decode"}))
    for tokens in (512, 8192):
        shape = ((1, tokens, QWEN_H, QWEN_D), BF16)
        cases.append((
            "flash_bf16_d256_fwd_%d" % tokens,
            lambda q, k, v: fa.flash_attention(q, k, v, causal=True,
                                               interpret=False),
            [shape, shape, shape], {"flash_fwd"}))

    cases.append((
        "mla_decode_bf16_deepseek_cell",
        lambda q, pool, bt, ln: mla.mla_attention_kernel(
            q, pool, bt, ln, scale=0.1147, rank=MLA_RANK, interpret=False),
        [((MLA_S, MLA_H, MLA_W), BF16), ((MLA_NB, BS, MLA_W), BF16),
         ((MLA_S, MLA_MB), I32), ((MLA_S,), I32)],
        {"mla_decode"}))
    cases.append((
        "mla_decode_bf16_gigachat_cell",
        lambda q, pool, bt, ln: mla.mla_attention_kernel(
            q, pool, bt, ln, scale=0.1053, rank=MLA_RANK, interpret=False),
        [((GIGA_S, GIGA_H, MLA_W), BF16), ((GIGA_NB, BS, MLA_W), BF16),
         ((GIGA_S, MLA_MB), I32), ((GIGA_S,), I32)],
        {"mla_decode"}))
    cases.append((
        "mla_decode_f32_tiny",
        lambda q, pool, bt, ln: mla.mla_attention_kernel(
            q, pool, bt, ln, scale=0.2, rank=128, interpret=False),
        [((S, 8, 256), F32), ((NB, BS, 256), F32), ((S, MB), I32),
         ((S,), I32)],
        {"mla_decode"}))
    # latent attention's expanded heads: v narrower than q and k
    wide = lambda n, h: ((1, n, h, MLA_QK), BF16)           # noqa: E731
    narrow = lambda n, h: ((1, n, h, MLA_V), BF16)          # noqa: E731
    cases.append((
        "flash_bf16_qk192_v128_fwd_8192",
        lambda q, k, v: fa.flash_attention(q, k, v, causal=True,
                                           scale=0.1147, interpret=False),
        [wide(8192, MLA_H), wide(8192, MLA_H), narrow(8192, MLA_H)],
        {"flash_fwd"}))
    cases.append((
        "flash_bf16_qk192_v128_bwd",
        lambda q, k, v: jax.grad(
            lambda q, k, v: fa.flash_attention(
                q, k, v, causal=True, interpret=False).astype(F32).sum(),
            argnums=(0, 1, 2))(q, k, v),
        [wide(N, H), wide(N, H), narrow(N, H)],
        {"flash_fwd", "flash_dq", "flash_dkv"}))

    # mistral7b-pretrain-4k: 2 x 4096 tokens, 32 heads of 128; and the
    # smallest prefill bucket a serving cell warms
    train = ((2, 4096, 32, D), BF16)
    cases.append(("flash_bf16_d128_train_4096_bwd", _flash(BF16, D)[1],
                  [train] * 3, {"flash_fwd", "flash_dq", "flash_dkv"}))
    small = ((1, 128, 32, D), BF16)
    cases.append(("flash_bf16_d128_fwd_128", _flash(BF16, D)[0],
                  [small] * 3, {"flash_fwd"}))

    def experts(x, w1, w2, sizes):
        # one expert layer's two calls: gate/up, then down
        h = mg.moe_gmm(x, w1, sizes, interpret=False)
        h = jax.nn.silu(h[:, :QWEN_WIDTH]) * h[:, QWEN_WIDTH:]
        return mg.moe_gmm(h, w2, sizes, interpret=False)

    for name, tokens in (("moe_gmm_bf16_decode", QWEN_S),
                         ("moe_gmm_bf16_prefill", 8192)):
        cases.append((
            name, experts,
            [((tokens * QWEN_TOPK, QWEN_HID), BF16),
             ((QWEN_EXPERTS, QWEN_HID, 2 * QWEN_WIDTH), BF16),
             ((QWEN_EXPERTS, QWEN_WIDTH, QWEN_HID), BF16),
             ((QWEN_EXPERTS,), I32)],
            {"moe_gmm"}))
    def clamped_experts(x, w1, w2, sizes):
        # the clamped SwiGLU between the two calls
        h = mg.moe_gmm(x, w1, sizes, interpret=False)
        h = moe.swiglu_clamped(h[:, :GIGA_WIDTH], h[:, GIGA_WIDTH:], 10.0)
        return mg.moe_gmm(h.astype(BF16), w2, sizes, interpret=False)

    for name, tokens in (("moe_gmm_bf16_k7168_decode", GIGA_S),
                         ("moe_gmm_bf16_k7168_prefill", 2048)):
        cases.append((
            name, clamped_experts,
            [((tokens * GIGA_TOPK * GIGA_EXPERTS // GIGA_ROUTER, GIGA_HID),
              BF16),
             ((GIGA_EXPERTS, GIGA_HID, 2 * GIGA_WIDTH), BF16),
             ((GIGA_EXPERTS, GIGA_WIDTH, GIGA_HID), BF16),
             ((GIGA_EXPERTS,), I32)],
            {"moe_gmm"}))

    def relu2_experts(x, w1, w2, sizes):
        # an ungated expert layer's two calls: up, then down
        h = jnp.square(jax.nn.relu(mg.moe_gmm(x, w1, sizes,
                                              interpret=False)))
        return mg.moe_gmm(h, w2, sizes, interpret=False)

    for name, tokens in (("moe_gmm_bf16_w1856_decode", SSM_S),
                         ("moe_gmm_bf16_w1856_prefill", 8192)):
        cases.append((
            name, relu2_experts,
            [((tokens * NEMO_TOPK, NEMO_HID), BF16),
             ((NEMO_EXPERTS, NEMO_HID, NEMO_WIDTH), BF16),
             ((NEMO_EXPERTS, NEMO_WIDTH, NEMO_HID), BF16),
             ((NEMO_EXPERTS,), I32)],
            {"moe_gmm"}))
    for name, s, dtype in (("ssm_decode_bf16_nemotron_cell", SSM_S, BF16),
                           ("ssm_decode_f32_8_slots", 8, F32)):
        cases.append((
            name,
            lambda x, dt, a, d, b, c, on, state: ssm.ssm_decode_kernel(
                x, dt, a, d, b, c, on, state, interpret=False),
            [((s, SSM_H, SSM_P), dtype), ((s, SSM_H), F32),
             ((SSM_H,), F32), ((SSM_H,), F32), ((s, SSM_G, SSM_N), dtype),
             ((s, SSM_G, SSM_N), dtype), ((s,), jnp.bool_),
             ((s, SSM_G, SSM_N, SSM_H // SSM_G * SSM_P), F32)],
            {"ssm_decode"}))
    for name, h, hkv in (("paged_mixed_bf16_mha", 16, 16),
                         ("paged_mixed_bf16_gqa", 32, 8)):
        pool = ((NB, BS, hkv, D), BF16)
        cases.append((
            name,
            lambda q, k, v, bt, hl, ql: pa.mixed_paged_attention_kernel(
                q, k, v, bt, hl, ql, interpret=False),
            [((S, C, h, D), BF16), pool, pool, ((S, MB), I32),
             ((S,), I32), ((S,), I32)],
            {"paged_mixed"}))
    for model, hk, hv in GDN_SHAPES:
        for tokens in GDN_TOKENS:
            cases.append((
                "gdn_chunked_%s_%d" % (model, tokens),
                lambda q, k, v, g, beta, state: gdn.gdn_chunked_kernel(
                    q, k, v, g, beta, state, interpret=False),
                [((1, tokens, hk, GDN_D), F32), ((1, tokens, hk, GDN_D), F32),
                 ((1, tokens, hv, GDN_D), F32), ((1, tokens, hv), F32),
                 ((1, tokens, hv), F32), ((1, hv, GDN_D, GDN_D), F32)],
                {"gdn_chunked"}))
    for name, nb, mb in (
            ("diff_decode_bf16_phi4flash_pages", PHI_NB, PHI_MB),
            ("diff_decode_bf16_phi4flash_rings",
             PHI_S * PHI_WINDOW // BS, PHI_WINDOW // BS)):
        pool = ((nb, BS, PHI_HKV * PHI_D), BF16)
        cases.append((
            name,
            lambda q, k, v, bt, ln, lam: da.diff_decode_kernel(
                q, k, v, bt, ln, lam, interpret=False),
            [((PHI_S, PHI_H, PHI_D), BF16), pool, pool,
             ((PHI_S, mb), I32), ((PHI_S,), I32), ((), F32)],
            {"diff_decode"}))
    for tokens in (2048, 64):
        rows = ((1, tokens, PHI_C), BF16)
        cases.append((
            "selective_scan_phi4flash_%d" % tokens,
            lambda x, dt, a, b, c, d: sc.selective_scan_kernel(
                x, dt, a, b, c, d, interpret=False),
            [rows, ((1, tokens, PHI_C), F32), ((PHI_N, PHI_C), F32),
             ((1, tokens, PHI_N), BF16), ((1, tokens, PHI_N), BF16),
             ((PHI_C,), BF16)],
            {"selective_scan"}))
    # a window layer's prefill: one of its two maps, 20 query pairs over
    # a 128-wide pair of values, banded to 512 keys
    qk = ((1, 2048, PHI_H // 2, PHI_D), BF16)
    cases.append((
        "flash_bf16_d64_v128_window_fwd_2048",
        lambda q, k, v: fa.flash_attention(q, k, v, causal=True,
                                           window=PHI_WINDOW,
                                           interpret=False),
        [qk, qk, ((1, 2048, PHI_H // 2, 2 * PHI_D), BF16)],
        {"flash_fwd"}))
    for name, dtype in (("fused_ce_bf16", BF16), ("fused_ce_f32", F32)):
        args = [((T, HID), dtype), ((HID, V), dtype), ((T,), I32)]

        def ce(h, w, lbl):
            return fc.fused_lm_head_ce(h, w, lbl, interpret=False)

        if dtype == BF16:
            cases.append((name + "_fwd", ce, args, {"fused_ce_fwd"}))
        cases.append((
            name + "_bwd",
            lambda h, w, lbl: jax.grad(
                lambda h, w: ce(h, w, lbl).sum(), argnums=(0, 1))(h, w),
            args, {"fused_ce_fwd", "fused_ce_dh", "fused_ce_dw"}))
    return cases


CASES = _cases()
IDS = [c[0] for c in CASES]


class TestCrossLowering:
    @pytest.mark.parametrize("name,fn,args,kernels", CASES, ids=IDS)
    def test_lowers_to_a_tpu_custom_call(self, name, fn, args, kernels):
        avals = [jax.ShapeDtypeStruct(s, d) for s, d in args]
        text = jax.jit(fn).trace(*avals).lower(
            lowering_platforms=("tpu",)).as_text()
        assert text.count("tpu_custom_call") >= len(kernels), name


@pytest.fixture(scope="module")
def v5e():
    """One abstract v5e device to compile for, or skip."""
    # libtpu asks the (absent) metadata server for these; naming them
    # only silences the warnings
    os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
    os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
    try:
        from jax.experimental import topologies

        topo = topologies.get_topology_desc(
            topology_name="v5e:1x1", platform="tpu",
            chips_per_host_bounds=(1, 1, 1))
    except Exception as e:     # no libtpu, or it cannot run here
        pytest.skip("no TPU topology description available: %r" % (e,))
    return jax.sharding.SingleDeviceSharding(topo.devices[0])


class TestMosaicCompile:
    def test_topology_is_the_chip_we_run_on(self, v5e):
        from paddle_tpu.distributed.auto_parallel.cost_model import \
            DEVICE_PEAKS

        (dev,) = v5e.device_set
        assert dev.platform == "tpu"
        # the string chip_smoke.py's device phase printed on the chip
        assert dev.device_kind == "TPU v5 lite"
        assert dev.device_kind in DEVICE_PEAKS

    @pytest.mark.parametrize("name,fn,args,kernels", CASES, ids=IDS)
    def test_compiles_under_mosaic(self, v5e, name, fn, args, kernels):
        avals = [jax.ShapeDtypeStruct(s, d, sharding=v5e)
                 for s, d in args]
        compiled = jax.jit(fn).lower(*avals).compile()
        found = mosaic_kernels(compiled.as_text())
        assert kernels <= set(found), name
        if name == CELL:
            # one call a layer by one name: no second kernel to combine
            # partial sums, and 32 pages a group inside the VMEM limit
            assert found == {"paged_decode": 1}
            assert pa._pages_per_group(BS, CELL_HKV, D, 2, CELL_MB) == 32
        if name == "mla_decode_bf16_deepseek_cell":
            # one call a layer by one name, 64 pages (1024 tokens) a
            # trip: a double buffer of 2.6 MB
            assert found == {"mla_decode": 1}
            assert mla._pages_per_group(BS, MLA_W, 2, MLA_MB) == 64
        if name.startswith("flash_"):
            # one forward kernel a call whatever tile it chose, and one
            # of each backward kernel where there is a gradient
            assert found == dict.fromkeys(kernels, 1)
        if name.startswith("moe_gmm"):
            # the kernel body is jitted: both calls are one kernel name
            assert found == {"moe_gmm": 2}
        if name.startswith("ssm_decode"):
            assert found == {"ssm_decode": 1}
        if name.startswith("gdn_chunked"):
            # one call for a prompt's whole recurrence, every head
            assert found == {"gdn_chunked": 1}


# deepseekv2-longctx-backlog's expert layer: 20 experts held of 160 at
# 5120 x 1536 gated, six pairs a token, group-limited
DSV2_EXPERTS, DSV2_ROUTER, DSV2_HID, DSV2_WIDTH, DSV2_TOPK = (
    20, 160, 5120, 1536, 6)


class TestExpertLayerPrefill:
    """``moe_forward`` on the 8192 rows of the four sparse cells'
    largest prefill, compiled for the v5e at each family's widths: two
    programs under one ``cond``, each with the grouped kernel twice
    (the jitted body is one kernel name), the first on a block's rows
    and the second on every pair's; no expert matrix is copied (PR 37's
    finding); the layer's temporaries are within a tenth of those of the
    layer that lays every pair out, which is what the parent compiled
    (the second program is that layer, and sets the peak)."""

    ROWS = 8192

    @pytest.mark.parametrize("held,router,hid,width,top_k,kw", [
        (DSV2_EXPERTS, DSV2_ROUTER, DSV2_HID, DSV2_WIDTH, DSV2_TOPK,
         dict(gated=True, activation="silu", norm_topk_prob=False,
              n_group=8, topk_group=3, routed_scaling_factor=16.0)),
        (QWEN_EXPERTS, 2 * QWEN_EXPERTS, QWEN_HID, QWEN_WIDTH, QWEN_TOPK,
         dict(gated=True, activation="silu", norm_topk_prob=True)),
        (NEMO_EXPERTS, 2 * NEMO_EXPERTS, NEMO_HID, NEMO_WIDTH, NEMO_TOPK,
         dict(activation="relu2", norm_topk_prob=True,
              routed_scaling_factor=2.5, select_bias=True)),
        (GIGA_EXPERTS, GIGA_ROUTER, GIGA_HID, GIGA_WIDTH, GIGA_TOPK,
         dict(gated=True, activation="silu", norm_topk_prob=True,
              routed_scaling_factor=2.5, select_bias=True,
              swiglu_limit=10.0)),
    ], ids=["deepseek-v2", "qwen3-next", "nemotron-h", "gigachat3.5"])
    def test_a_block_and_every_pair_compile_under_one_cond(
            self, v5e, monkeypatch, held, router, hid, width, top_k, kw):
        # the kernels' dispatch asks the backend; here it is the CPU
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        kw = dict(kw)
        wide = width * (2 if kw.get("gated") else 1)
        if kw.get("select_bias"):
            kw["select_bias"] = jnp.zeros((router,), F32)
        pairs = self.ROWS * top_k
        block = moe._pair_block(pairs, held, router)
        assert block < pairs

        def compiled_layer():
            # a new function a compile: a new trace
            def layer(x, gate_w, w1, w2):
                return moe.moe_forward(x, gate_w, w1, None, w2, None,
                                       top_k=top_k, **kw)

            avals = [jax.ShapeDtypeStruct(shape, BF16, sharding=v5e)
                     for shape in ((self.ROWS, hid), (hid, router),
                                   (held, hid, wide), (held, width, hid))]
            return jax.jit(layer).lower(*avals).compile()

        both = compiled_layer()
        text = both.as_text()
        assert mosaic_kernels(text) == {"moe_gmm": 4}
        assert " conditional(" in text
        matrices = ("bf16[%d,%d,%d]" % (held, hid, wide),
                    "bf16[%d,%d,%d]" % (held, wide, hid),
                    "bf16[%d,%d,%d]" % (held, width, hid),
                    "bf16[%d,%d,%d]" % (held, hid, width))
        copies = [line.strip()[:160] for line in text.splitlines()
                  if " copy(" in line and line.lstrip().startswith("%")
                  and any(m in line.split(" copy(")[0] for m in matrices)]
        assert copies == []
        # the kernel runs on a block's rows in one program and on every
        # pair's in the other
        for rows in (block, pairs):
            assert any("custom-call(" in line and "moe_gmm" in line
                       and "bf16[%d,%d]" % (rows, hid) in line
                       for line in text.splitlines()), rows
        monkeypatch.setattr(moe, "_pair_block", lambda *shapes: 10 ** 9)
        once = compiled_layer()
        assert mosaic_kernels(once.as_text()) == {"moe_gmm": 2}
        assert (both.memory_analysis().temp_size_in_bytes
                <= 1.1 * once.memory_analysis().temp_size_in_bytes)

class TestInterpretNeverOnTPU:
    def test_resolve_interpret(self, monkeypatch):
        assert fa.resolve_interpret(None) is True       # CPU: interpreter
        assert fa.resolve_interpret(False) is False     # cross-lowering
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert fa.resolve_interpret(None) is False
        with pytest.raises(ValueError, match="interpret=True on a TPU"):
            fa.resolve_interpret(True)

    def test_reference_branch_on_tpu_is_said_once_by_name(
            self, monkeypatch, capsys):
        from paddle_tpu.monitor import registry

        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        key = "serving.paged_attention.reference_on_tpu"
        with registry._warned_lock:
            registry._warned.discard(key)
        q = jnp.zeros((2, 4, 16), F32)          # head_dim 16: not tileable
        pool = jnp.zeros((4, 8, 4, 16), F32)
        bt = jnp.zeros((2, 2), I32)
        lens = jnp.ones((2,), I32)
        for _ in range(2):
            out = pa.paged_attention(q, pool, pool, bt, lens)
        assert out.shape == (2, 4, 16)
        err = capsys.readouterr().err
        assert err.count("paged_attention takes the jnp gather "
                         "reference on the TPU") == 1


class TestMosaicKernelsParser:
    def test_names_bare_wrapped_and_unnamed(self):
        text = "\n".join([
            '  %flash_fwd.1 = (bf16[8,1024,128]{2,1,0:T(8,128)(2,1)S(1)},'
            ' f32[8,1,1024]{2,1,0:T(1,128)}) custom-call(%a, %b, %c),'
            ' custom_call_target="tpu_custom_call", metadata={op_name='
            '"jit(step)/checkpoint/flash_fwd/pallas_call"}',
            '  %x.2 = bf16[8,1024,128]{2,1,0} custom-call(%a),'
            ' custom_call_target="tpu_custom_call", metadata={op_name='
            '"jit(step)/transpose(jvp(flash_dq))/pallas_call"}',
            '  %x.3 = bf16[8,1024,128]{2,1,0} custom-call(%a),'
            ' custom_call_target="tpu_custom_call", metadata={op_name='
            '"jit(step)/jvp(flash_fwd)/pallas_call"}',
            '  %anon.4 = f32[8]{0} custom-call(%a),'
            ' custom_call_target="tpu_custom_call"',
            '  %lapack.5 = f32[8]{0} custom-call(%a),'
            ' custom_call_target="lapack_sgetrf"',
        ])
        assert mosaic_kernels(text) == {
            "flash_fwd": 2, "flash_dq": 1, "anon.4": 1}


class TestFlashOnAMesh:
    """GSPMD refuses to partition a Mosaic kernel; on a multi-device
    mesh the flash call goes through shard_map (batch over dp/sharding,
    heads over mp). Found by AOT-compiling the dp=2 x mp=2 step."""

    def _mesh(self):
        from jax.sharding import Mesh

        return Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                    ("dp", "mp"))

    def test_unwrapped_kernel_cannot_be_partitioned(self):
        from jax.sharding import NamedSharding, PartitionSpec as P

        sh = NamedSharding(self._mesh(), P("dp", None, "mp", None))
        aval = jax.ShapeDtypeStruct((4, 256, 4, 128), BF16, sharding=sh)
        with pytest.raises(NotImplementedError,
                           match="cannot be automatically partitioned"):
            jax.jit(lambda q, k, v: fa.flash_attention(
                q, k, v, causal=True, interpret=False)).trace(
                    aval, aval, aval).lower(lowering_platforms=("tpu",))

    def test_dispatch_shards_batch_and_heads(self, monkeypatch):
        from jax.sharding import NamedSharding, PartitionSpec as P

        from paddle_tpu.distributed import mesh as pmesh
        from paddle_tpu.nn.functional import attention as att

        seen = []

        def fake_flash(q, k, v, causal=False, scale=None):
            seen.append(tuple(q.shape))
            return att._sdpa_reference(q, k, v, causal=causal,
                                       scale=scale)

        monkeypatch.setattr(fa, "flash_attention", fake_flash)
        mesh = self._mesh()
        rng = np.random.RandomState(0)
        q, k, v = (jnp.asarray(rng.randn(4, 16, 4, 8), F32)
                   for _ in range(3))
        want = att._sdpa_reference(q, k, v, causal=True)
        sh = NamedSharding(mesh, P("dp", None, "mp", None))
        with pmesh.scoped_mesh(mesh):
            got = jax.jit(
                lambda q, k, v: att._flash_on_mesh(q, k, v, True, None),
                in_shardings=(sh, sh, sh))(q, k, v)
        assert seen == [(2, 16, 2, 8)]      # batch / dp, heads / mp
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    def test_no_mesh_or_one_device_calls_the_kernel_directly(
            self, monkeypatch):
        from jax.sharding import Mesh

        from paddle_tpu.distributed import mesh as pmesh
        from paddle_tpu.nn.functional import attention as att

        seen = []

        def fake_flash(q, k, v, causal=False, scale=None):
            seen.append(tuple(q.shape))
            return q

        monkeypatch.setattr(fa, "flash_attention", fake_flash)
        q = jnp.zeros((4, 16, 4, 8), F32)
        one = Mesh(np.array(jax.devices()[:1]), ("dp",))
        for mesh in (None, one):
            with pmesh.scoped_mesh(mesh):
                jax.jit(lambda q: att._flash_on_mesh(q, q, q, True,
                                                     None))(q)
        assert seen == [(4, 16, 4, 8)] * 2
        # and "no mesh" did not conjure the all-devices default
        with pmesh.scoped_mesh(None):
            assert pmesh.current_mesh() is None


class TestBlocksAreNamed:
    """``models/llama.py`` wraps each decoder layer, its two halves and
    the head in ``jax.named_scope``: every instruction's ``op_name``
    says which block it belongs to, and a Mosaic kernel keeps its own
    name as the path element before ``pallas_call``."""

    SCOPES = ("layer_0/attn", "layer_0/mlp", "layer_1/attn",
              "layer_1/mlp", "lm_head")

    @pytest.fixture(scope="class")
    def forward(self):
        """(function of (state values, ids), state values) of a
        two-layer model with heads of 128."""
        import paddle_tpu as paddle
        from paddle_tpu.core.dispatch import no_grad
        from paddle_tpu.core.tensor import Tensor
        from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

        paddle.seed(0)
        model = LlamaForCausalLM(LlamaConfig(
            vocab_size=256, hidden_size=256, intermediate_size=512,
            num_hidden_layers=2, num_attention_heads=2,
            num_key_value_heads=2, max_position_embeddings=256,
            use_parallel=False))
        names, values = model.functional_state()

        def fwd(values, ids, labels):
            with model.bind_state(names, list(values)), no_grad():
                return model(Tensor(ids), Tensor(labels))._value

        return fwd, values

    def test_lowered_forward_carries_every_block(self, forward):
        fwd, values = forward
        ids = jnp.zeros((1, 128), I32)
        text = jax.jit(fwd).lower(values, ids, ids).as_text(
            debug_info=True)
        for scope in self.SCOPES:
            assert scope in text, scope

    def test_mosaic_kernels_keep_their_names_inside_a_block(
            self, v5e, forward, monkeypatch):
        fwd, values = forward
        # the attention dispatch asks the backend; here it is the CPU
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        avals = [jax.ShapeDtypeStruct(v.shape, BF16, sharding=v5e)
                 for v in values]
        ids = jax.ShapeDtypeStruct((1, 128), I32, sharding=v5e)
        text = jax.jit(fwd).lower(avals, ids, ids).compile().as_text()
        assert mosaic_kernels(text) == {"flash_fwd": 2}
        for layer in (0, 1):
            assert ('layer_%d/attn/' % layer + FWD_KERNEL_PATH in text)
        for scope in self.SCOPES:
            assert scope in text, scope

    def test_the_benchmarks_kernel_finder_agrees_on_a_compiled_step(
            self, v5e, forward, monkeypatch):
        """``benchmark/`` stands alone, so ``trace_reduce.py`` keeps a
        finder of its own beside ``mosaic_kernels``; what the benchmark
        books device time under and what chip_smoke.py asserts on must
        be the same names, on a forward and backward with the autodiff
        wrappers around them."""
        trace_reduce = _benchmark_trace_reduce()
        fwd, values = forward
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        avals = [jax.ShapeDtypeStruct(v.shape, BF16, sharding=v5e)
                 for v in values]
        ids = jax.ShapeDtypeStruct((1, 128), I32, sharding=v5e)
        text = jax.jit(jax.grad(
            lambda values, ids, labels: fwd(values, ids, labels)
            .astype(F32).sum())).lower(avals, ids, ids).compile().as_text()
        found = mosaic_kernels(text)
        assert found == {"flash_fwd": 2, "flash_dq": 2, "flash_dkv": 2}
        assert trace_reduce.kernel_counts(text) == found


def _benchmark_trace_reduce():
    """``benchmark/trace_reduce.py``, which stands alone (no package)."""
    spec = importlib.util.spec_from_file_location(
        "benchmark_trace_reduce", os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "benchmark", "trace_reduce.py"))
    trace_reduce = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(trace_reduce)
    return trace_reduce


def _bf16_zeros(self, shape, dtype=None, name=None):
    """``Initializer.create`` for a model that is only compiled: nothing
    runs, so every parameter is bf16 zeros and not a seeded draw."""
    from paddle_tpu.core.tensor import Parameter

    return Parameter(jnp.zeros(tuple(int(s) for s in shape), BF16),
                     name=name)


class TestRecomputedStepRunsFlashFwdOnce:
    """``mistral7b-pretrain-4k``'s forward and backward (the published
    widths, two layers, recompute on, 2 x 4096 tokens) compiled for the
    v5e: a checkpointed layer keeps the flash kernel's output
    (``models/llama.py`` ``_remat_layer``), so the program holds one
    ``flash_fwd`` a layer beside one ``flash_dq`` and one ``flash_dkv``;
    under a bare ``jax.checkpoint`` it held two. The same model with no
    gradient is one ``flash_fwd`` a layer, as every prefill is."""

    LAYERS, BATCH, SEQ = 2, 2, 4096

    @pytest.fixture(scope="class")
    def programs(self, v5e):
        """{"train" | "forward": compiled HLO text}."""
        from paddle_tpu.core.dispatch import no_grad
        from paddle_tpu.core.tensor import Tensor
        from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
        from paddle_tpu.nn import initializer

        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(initializer.Initializer, "create", _bf16_zeros)
            # the attention dispatch asks the backend; here it is the CPU
            patch.setattr(jax, "default_backend", lambda: "tpu")
            model = LlamaForCausalLM(LlamaConfig(
                vocab_size=32768, hidden_size=4096,
                intermediate_size=14336, num_hidden_layers=self.LAYERS,
                num_attention_heads=32, num_key_value_heads=8,
                max_position_embeddings=32768, rope_theta=1e6,
                use_parallel=False, dtype="bfloat16", recompute=True))
            names, values = model.functional_state()

            def loss(values, ids, labels):
                with model.bind_state(names, list(values)), no_grad():
                    return model(Tensor(ids), Tensor(labels))._value

            def logits(values, ids):
                with model.bind_state(names, list(values)), no_grad():
                    return model(Tensor(ids))._value

            avals = [jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=v5e)
                     for v in values]
            ids = jax.ShapeDtypeStruct((self.BATCH, self.SEQ), I32,
                                       sharding=v5e)
            return {
                "train": jax.jit(jax.value_and_grad(loss)).lower(
                    avals, ids, ids).compile().as_text(),
                "forward": jax.jit(logits).lower(
                    avals, ids).compile().as_text(),
            }

    def test_one_forward_kernel_a_layer_under_recompute(self, programs):
        assert mosaic_kernels(programs["train"]) == {
            "flash_fwd": self.LAYERS, "flash_dq": self.LAYERS,
            "flash_dkv": self.LAYERS}
        for layer in range(self.LAYERS):
            assert ('/jvp(layer_%d)/attn/' % layer + FWD_KERNEL_PATH
                    in programs["train"])
        # none of them is a backward pass's recomputation
        assert ('checkpoint/attn/' + FWD_KERNEL_PATH
                not in programs["train"])

    def test_no_gradient_is_one_forward_kernel_a_layer(self, programs):
        assert mosaic_kernels(programs["forward"]) == {
            "flash_fwd": self.LAYERS}


class TestPrefillHeadOnOneRow:
    """``mistral7b-chat-backlog``'s largest prefill (bucket 2048, the
    published widths, one layer) compiled for the v5e: the engine names
    the row it reads (``logits_at``), so the program holds no
    [2048, 32768] logits (134 MB), which the same prefill with the head
    on every row does. (``memory_analysis()`` does not show it: the
    compiler's peak of temporaries is its scheduler's choice, and with
    the logits gone it overlaps more; 162.0 -> 153.6 MB at one layer,
    153.8 -> 164.3 at two.)"""

    P, VOCAB = 2048, 32768

    def test_no_logits_of_the_whole_bucket(self, v5e, monkeypatch):
        from paddle_tpu import serving
        from paddle_tpu.core.dispatch import no_grad
        from paddle_tpu.core.tensor import Tensor
        from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
        from paddle_tpu.nn import initializer

        monkeypatch.setattr(initializer.Initializer, "create", _bf16_zeros)
        # the attention dispatch asks the backend; here it is the CPU
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        model = LlamaForCausalLM(LlamaConfig(
            vocab_size=self.VOCAB, hidden_size=4096,
            intermediate_size=14336, num_hidden_layers=1,
            num_attention_heads=32, num_key_value_heads=8,
            max_position_embeddings=32768, rope_theta=1e6,
            use_parallel=False, dtype="bfloat16"))
        eng = serving.Engine(model, max_slots=2, num_blocks=512,
                             block_size=16, max_model_len=2560)
        assert eng._bucket(1100) == self.P
        args = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(
                jnp.shape(a), jnp.result_type(a), sharding=v5e),
            (eng._state_vals, eng.cache.pools,
             jnp.zeros((1, self.P), I32),
             jnp.asarray(eng.cache.block_tables[0]),
             jnp.asarray(self.P, I32)))

        def every_row(state_vals, pools, ids, table_row, true_len):
            with model.bind_state(eng._names, list(state_vals)), \
                    no_grad():
                views = eng.cache.prefill_views(pools, table_row,
                                                true_len)
                logits, views = model.generate_step(Tensor(ids), views, 0)
            return (jnp.argmax(logits._value[0, true_len - 1].astype(F32)),
                    [v.pool for v in views])

        def compiled(fn):
            return eng._run_eval(
                jax.jit(fn, donate_argnums=(1,)).lower, *args).compile()

        one, every = compiled(eng._prefill_fn), compiled(every_row)
        logits = "bf16[%d,%d]" % (self.P, self.VOCAB)
        assert logits in every.as_text()
        assert "%d,%d]" % (self.P, self.VOCAB) not in one.as_text()
        assert mosaic_kernels(one.as_text()) == {"flash_fwd": 1}


class TestNemotronDecodeStep:
    """The decode step of ``nemotron3nano-longreason-backlog`` compiled
    for the v5e at the published widths (pattern ``MEM*``, 8 experts
    held, 64 slots: the cell's is ``MEMEM*EME``, 64 and 256): one
    ``ssm_decode`` a Mamba-2 layer, one ``paged_decode``, the expert
    layer's two ``moe_gmm``, and nothing but the kernel reads or writes
    a layer's [slots, 8, 128, 512] float32 state: it comes in as the
    step's (donated) argument, goes through the kernel aliased in place
    and out as the step's result. No select keeps the idle slots' rows
    and no second pass reads the state out."""

    SLOTS = 64

    def test_kernels_and_one_reader_of_the_state(self, v5e, monkeypatch):
        import re

        from paddle_tpu import serving
        from paddle_tpu.models.nemotron_h import (NemotronHConfig,
                                                  NemotronHForCausalLM)
        from paddle_tpu.nn import initializer

        monkeypatch.setattr(initializer.Initializer, "create", _bf16_zeros)
        # the kernels' dispatch asks the backend; here it is the CPU
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        model = NemotronHForCausalLM(NemotronHConfig(
            vocab_size=4096, hybrid_override_pattern="MEM*",
            experts_held=range(8), dtype="bfloat16"))
        model.eval()
        eng = serving.Engine(model, max_slots=self.SLOTS, num_blocks=512,
                             block_size=16, max_model_len=2048)
        _, _, fn, args = eng._hot_step()
        avals = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(
                jnp.shape(a), jnp.result_type(a), sharding=v5e), args)
        text = eng._run_eval(
            jax.jit(fn, donate_argnums=(1,)).lower, *avals).compile(
            ).as_text()
        assert mosaic_kernels(text) == {
            "ssm_decode": 2, "paged_decode": 1, "moe_gmm": 2}
        for layer, scope in enumerate(("ssm", "moe", "ssm", "attn")):
            assert "/layer_%d/%s/" % (layer, scope) in text
        state = "f32[%d,8,128,512]" % self.SLOTS
        touching = [line.strip() for line in text.splitlines()
                    if state in line
                    and line.lstrip().startswith(("%", "ROOT "))]
        allowed = re.compile(
            r" (parameter|get-tuple-element|tuple|bitcast)\(|"
            r" custom-call\(.*op_name=\"[^\"]*/ssm_decode/pallas_call")
        others = [line[:160] for line in touching
                  if not allowed.search(line)]
        assert others == []
        # in as an argument, through the kernel, out as a result: twice
        assert sum(" parameter(" in line for line in touching) == 2
        assert sum(" custom-call(" in line for line in touching) == 2


class TestGatedDeltaNetPrefill:
    """A whole-sequence forward of Qwen3-Next (two Gated DeltaNet layers)
    and of GigaChat3.5 (its published layer 4: GDN over the experts) at
    the published widths, 1024 rows, two experts held, compiled for the
    v5e: one ``gdn_chunked`` a GDN layer, by the name the benchmark books
    device time under, each under its layer's ``gdn`` scope, and none of
    XLA's triangular-solve custom calls, of which the jnp twin's program
    holds one a layer."""

    ROWS = 1024

    def _compiled(self, v5e, monkeypatch, model):
        from paddle_tpu.core.dispatch import no_grad
        from paddle_tpu.core.tensor import Tensor

        names, values = model.functional_state()

        def logits(values, ids):
            with model.bind_state(names, list(values)), no_grad():
                return model(Tensor(ids))._value

        avals = [jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=v5e)
                 for v in values]
        ids = jax.ShapeDtypeStruct((1, self.ROWS), I32, sharding=v5e)
        return jax.jit(logits).lower(avals, ids).compile().as_text()

    @pytest.mark.parametrize("family", ["qwen3-next", "gigachat3.5"])
    def test_one_kernel_a_gdn_layer_and_no_triangular_solve(
            self, v5e, monkeypatch, family):
        from paddle_tpu.nn import initializer

        monkeypatch.setattr(initializer.Initializer, "create", _bf16_zeros)
        # the kernels' dispatch asks the backend; here it is the CPU
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        if family == "qwen3-next":
            from paddle_tpu.models.qwen3_next import (Qwen3NextConfig,
                                                      Qwen3NextForCausalLM)

            model = Qwen3NextForCausalLM(Qwen3NextConfig(
                vocab_size=1024, num_hidden_layers=2,
                experts_held=range(2), dtype="bfloat16"))
            gdn_layers = (0, 1)
        else:
            from paddle_tpu.models.gigachat3_5 import (GigaChat35Config,
                                                       GigaChat35ForCausalLM)

            model = GigaChat35ForCausalLM(GigaChat35Config(
                vocab_size=1024, num_hidden_layers=1, layers_held=(4,),
                experts_held=range(2), dtype="bfloat16"))
            gdn_layers = (0,)
        text = self._compiled(v5e, monkeypatch, model)
        counts = _benchmark_trace_reduce().kernel_counts(text)
        assert counts["gdn_chunked"] == len(gdn_layers), counts
        assert mosaic_kernels(text)["gdn_chunked"] == len(gdn_layers)
        for layer in gdn_layers:
            assert ("layer_%d/gdn/jit(_gdn_forward)/gdn_chunked/pallas_call"
                    % layer) in text
        # the twin's solve: its op_name and its custom call's target
        assert '/triangular_solve"' not in text
        assert "InvertDiagBlocksLowerTriangular" not in text
