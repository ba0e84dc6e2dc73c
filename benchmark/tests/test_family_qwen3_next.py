"""The qwen3_next family file: its arithmetic against hand counts at the
published widths, its plain reference against the program's
``Qwen3NextForCausalLM`` at a tiny size on the CPU, the backlog runner
driven end to end on it, and the three expert-layer readers."""
import json
import os

import numpy as np
import pytest

import peaks
import run as bench
import tiny

CELL = "qwen3next-longdoc-backlog"
TINY = dict(
    family="qwen3_next", vocab_size=128, hidden_size=64,
    num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, partial_rotary_factor=0.25, rope_theta=1e7,
    rms_norm_eps=1e-6, full_attention_interval=4,
    linear_conv_kernel_dim=4, linear_key_head_dim=8,
    linear_num_key_heads=2, linear_num_value_heads=4,
    linear_value_head_dim=8, moe_intermediate_size=32,
    shared_expert_intermediate_size=32, num_experts=8,
    num_experts_published=16, num_experts_per_tok=4, norm_topk_prob=True,
    max_position_embeddings=512, tie_word_embeddings=False,
    torch_dtype="float32", full_attention_layers=1,
    linear_attention_layers=3)


@pytest.fixture(scope="module")
def family():
    return bench.load_module("families", "qwen3_next")


@pytest.fixture(scope="module")
def published():
    manifest = bench.load_json(os.path.join(bench.ROOT, "BENCHMARK.json"))
    entry = bench.find(manifest["configs"], "qwen3-next-80b-a3b-ep2-l4",
                       "config")
    return bench.load_json(os.path.join(bench.ROOT, entry["file"]))


def test_parameter_counts_by_hand(family, published):
    cfg = published
    lp = family.layer_params(cfg)
    # 256 experts of three 2048 x 512 matrices
    assert lp["experts"] == 256 * 3 * 2048 * 512 == 805306368
    # q+gate 2048 x 8192, k and v 2048 x 512, o 4096 x 2048, two norms
    assert lp["attention"] == (2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048
                               + 2 * 256) == 27263488
    # qkvz 2048 x 12288, ba 2048 x 64, conv 8192 x 4, A_log, dt_bias,
    # the head norm, out 4096 x 2048
    assert lp["gdn"] == (2048 * 12288 + 2048 * 64 + 8192 * 4 + 64 + 128
                         + 4096 * 2048) == 33718464
    # router 2048 x 512, shared expert 3 x 2048 x 512, its gate
    assert lp["moe_other"] == 2048 * 512 + 3 * 2048 * 512 + 2048 == 4196352
    assert family.layer_counts(cfg) == (1, 3) == (
        cfg["full_attention_layers"], cfg["linear_attention_layers"])
    total = (27263488 + 3 * 33718464 + 4 * (805306368 + 4196352 + 4096)
             + 2 * 75968 * 2048 + 2048)
    assert family.param_count(cfg) == total == 3677613120   # 7.36 GB bf16


def test_kernel_costs_by_hand(family, published):
    cfg = published
    # one paged layer: K and V of 2 KV heads x 256 in bf16
    assert family.kv_page_bytes(cfg, 16) == 2 * 1 * 16 * 2 * 256 * 2 == 32768
    # three layers of 32 x 128 x 128 float32 and a 3 x 8192 bf16 tail
    assert family.state_slot_bytes(cfg) == 3 * (32 * 128 * 128 * 4
                                                + 3 * 8192 * 2) == 6438912
    flops, moved = family.paged_decode_cost(cfg, context_tokens=400000,
                                            rows=128)
    assert flops == 2 * 2 * 400000 * 16 * 256
    assert moved == 2 * 400000 * 2 * 256 * 2 + 2 * 128 * 16 * 256 * 2
    flops, moved = family.moe_gmm_cost(cfg, rows=128, pairs=640,
                                       experts_touched=235)
    assert flops == 6 * 2048 * 512 * 640
    # 235 experts' three matrices once, 128 rows in and out
    assert moved == 235 * 3 * 2048 * 512 * 2 + 2 * 128 * 2048 * 2
    # bound by the weight stream, in a decode step and even in the
    # largest prefill (160 rows an expert; the v5e's ridge is at 240)
    v5e = peaks.peaks_for("TPU v5 lite")
    assert peaks.least_seconds(flops, moved, v5e)[1] == "bandwidth"
    assert peaks.least_seconds(*family.moe_gmm_cost(
        cfg, rows=8192, pairs=40960, experts_touched=256), v5e)[1] \
        == "bandwidth"


def test_the_config_file_keeps_every_published_number(published):
    """Every number of the catalog row's ``config`` under the same key;
    the three cut keys carry the held share with the published value
    beside it."""
    catalog = {
        "decoder_sparse_step": 1, "full_attention_interval": 4,
        "head_dim": 256, "hidden_size": 2048, "intermediate_size": 5120,
        "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
        "linear_num_key_heads": 16, "linear_num_value_heads": 32,
        "linear_value_head_dim": 128, "max_position_embeddings": 262144,
        "moe_intermediate_size": 512, "num_attention_heads": 16,
        "num_experts": 512, "num_experts_per_tok": 10,
        "num_hidden_layers": 48, "num_key_value_heads": 2,
        "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
        "rope_theta": 10000000, "shared_expert_intermediate_size": 512,
        "vocab_size": 151936}
    cut = {"num_hidden_layers": 4, "num_experts": 256, "vocab_size": 75968}
    assert sorted(published["reduced"]) == sorted(cut)
    for key, value in catalog.items():
        assert published[key] == cut.get(key, value), key
    assert published["num_experts_published"] == 512
    assert published["vocab_size_published"] == 151936
    assert published["norm_topk_prob"] is True
    assert published["mlp_only_layers"] == []


@pytest.fixture(scope="module")
def tiny_model(family):
    return family.build_model(TINY, seed=3000000019, training=False)


def test_seed_makes_the_weights(family, tiny_model):
    again = family.build_model(TINY, seed=3000000019, training=False)
    other = family.build_model(TINY, seed=7, training=False)
    name = "model.layers.1.mlp.experts.w1"
    w = np.asarray(family.weights_of(tiny_model)[name])
    assert np.array_equal(w, np.asarray(family.weights_of(again)[name]))
    assert not np.array_equal(w, np.asarray(family.weights_of(other)[name]))
    assert family.param_count(TINY) == sum(
        int(np.prod(v.shape))
        for v in family.weights_of(tiny_model).values())


def test_reference_logits_match_the_program(family, tiny_model):
    import paddle_tpu as paddle

    ids = np.random.default_rng(0).integers(
        0, TINY["vocab_size"], (2, 70)).astype(np.int32)
    got = np.asarray(tiny_model(paddle.to_tensor(ids))._value)
    weights = family.weights_of(tiny_model)
    for row, want in zip(ids, got):
        ref, routing = family.reference_forward(weights, TINY, row)
        # float32 on both sides: what differs is the order of sums
        np.testing.assert_allclose(np.asarray(ref), want, rtol=2e-4,
                                   atol=2e-4)
        assert len(routing) == 4 and routing[0].shape == (70, 4)
    assert family.reference_loss(weights, TINY, ids[:, :-1], ids[:, 1:]) > 0


@pytest.fixture(scope="module")
def backlog(family):
    runner = bench.load_module("runners", "serve_backlog")
    return runner.run_backlog(
        family, TINY, tiny.mix("longdoc-backlog", **tiny.BACKLOG),
        tiny.SEED, 1.0, tiny.quiet, on_chip=False)


def test_backlog_runs_and_checks_itself(backlog):
    assert {k: ok for k, (ok, _) in backlog["checks"].items()} == {
        "reference": True, "no_compile_in_window": True,
        "queue_never_empty": True}
    assert backlog["attempted"] > 0 and backlog["failed"] == 0
    counters = backlog["observations"]["counters"]
    assert counters["decode_compiles"] == 1
    moe, state = counters["moe"], counters["state"]
    assert moe["layers"] == 4 and moe["experts_held"] == 8
    # 4 slots x top-4 of 16 experts, half of them held here
    assert 0 < moe["pairs"] <= 16 and 0 < moe["experts_touched"] <= 8
    assert moe["load_max_over_mean"] >= 1.0
    assert state["slots"] == 4 and state["layers"] == 3
    assert state["pool_bytes"] == 4 * state["slot_bytes"]
    json.dumps(counters)        # what a result line can carry


def test_expert_layer_readers(backlog, family):
    manifest = bench.load_json(os.path.join(bench.ROOT, "BENCHMARK.json"))
    obs = backlog["observations"]
    steps = obs["steps"][-5:]
    programs = sum(1 + s["prefills"] for s in steps)
    trace = {"chips": 1, "window_s": 1.0, "busy_s": 0.5,
             "op_seconds": {"moe_gmm": 0.25},
             "op_calls": {"moe_gmm": programs * 4 * 2},
             "device_ops": [], "idle_gaps": []}
    logged = []
    full = dict(obs, traced_steps=steps, trace=trace, config=TINY,
                traffic={}, family=family, chips=1,
                peaks=peaks.peaks_for("TPU v5 lite"), log=logged.append,
                end_to_end=backlog["end_to_end"])
    values = bench.read_layer_metrics(manifest, CELL, full)
    assert {"moe_gmm_roofline", "moe.load_max_over_mean",
            "moe.experts_touched"} <= set(values)
    assert 0 < values["moe_gmm_roofline"] < 100
    assert values["moe.load_max_over_mean"] >= 1.0
    assert 0 < values["moe.experts_touched"] <= 100
    assert any("moe_gmm_roofline" in line for line in logged)
    # a program without the counters (the parent): nothing, no raise
    bare = dict(full, counters={k: v for k, v in obs["counters"].items()
                                if k != "moe"})
    for name in ("moe_gmm_roofline", "moe.load_max_over_mean",
                 "moe.experts_touched"):
        assert bench.load_module("layer_metrics", name).read(bare) is None
    # half the kernel calls outside the trace: the share halves
    trace["op_calls"]["moe_gmm"] //= 2
    halved = bench.load_module("layer_metrics", "moe_gmm_roofline").read(full)
    assert halved == pytest.approx(values["moe_gmm_roofline"] / 2, rel=0.02)
