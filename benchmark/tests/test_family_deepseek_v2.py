"""The deepseek_v2 family file: its arithmetic against hand counts at the
published widths, its plain reference against the program's
``DeepseekV2ForCausalLM`` at a tiny size on the CPU, the backlog runner
driven end to end on it, and the two latent-cache readers."""
import json
import os

import numpy as np
import pytest

import peaks
import run as bench
import tiny

CELL = "deepseekv2-longctx-backlog"
TINY = dict(
    family="deepseek_v2", vocab_size=128, hidden_size=64,
    intermediate_size=96, moe_intermediate_size=32, num_hidden_layers=3,
    num_attention_heads=4, q_lora_rank=48, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    n_routed_experts=4, n_routed_experts_published=16, n_shared_experts=2,
    num_experts_per_tok=3, n_group=4, topk_group=2,
    routed_scaling_factor=4.0, norm_topk_prob=False,
    first_k_dense_replace=1, rope_theta=10000,
    rope_scaling={"type": "yarn", "factor": 40, "beta_fast": 32,
                  "beta_slow": 1, "mscale": 0.707, "mscale_all_dim": 0.707,
                  "original_max_position_embeddings": 16},
    rms_norm_eps=1e-6, max_position_embeddings=512,
    tie_word_embeddings=False, torch_dtype="float32")


@pytest.fixture(scope="module")
def family():
    return bench.load_module("families", "deepseek_v2")


@pytest.fixture(scope="module")
def manifest():
    return bench.load_json(os.path.join(bench.ROOT, "BENCHMARK.json"))


@pytest.fixture(scope="module")
def published(manifest):
    entry = bench.find(manifest["configs"], "deepseek-v2-ep8-l5", "config")
    return bench.load_json(os.path.join(bench.ROOT, entry["file"]))


def test_parameter_counts_by_hand(family, published):
    cfg = published
    lp = family.layer_params(cfg)
    # W_dq 5120 x 1536, W_uq 1536 x 128 x 192, W_dkv 5120 x 576, W_ukv
    # 512 x 128 x 256, W_o 16384 x 5120, the two inner norms
    assert lp["attention"] == (7864320 + 37748736 + 2949120 + 16777216
                               + 83886080 + 1536 + 512) == 149227520
    assert lp["dense_mlp"] == 3 * 5120 * 12288 == 188743680
    # 20 experts of three 5120 x 1536 matrices
    assert lp["experts"] == 20 * 23592960 == 471859200
    # router 5120 x 160, the two shared experts as one 5120 x 3072 SwiGLU
    assert lp["moe_other"] == 819200 + 47185920 == 48005120
    assert family.layer_counts(cfg) == (1, 4)
    total = (5 * (149227520 + 2 * 5120) + 188743680
             + 4 * (471859200 + 48005120) + 2 * 12800 * 5120 + 5120)
    assert family.param_count(cfg) == total == 3145466880   # 6.29 GB bf16


def test_kernel_costs_by_hand(family, published):
    cfg = published
    assert family.latent_width(cfg) == 576
    # five latent planes, a row of 576 values tiled as 640 lanes, bf16
    assert family.kv_page_bytes(cfg, 16) == 5 * 16 * 640 * 2 == 102400
    flops, moved = family.mla_decode_cost(cfg, context_tokens=600000,
                                          rows=128)
    # every head: a 576-wide score dot and a 512-wide value dot a token
    assert flops == 2 * 600000 * 128 * (576 + 512)
    # a row once for all heads; q in and the latent output back
    assert moved == (600000 * 576 + 128 * 128 * (576 + 512)) * 2
    v5e = peaks.peaks_for("TPU v5 lite")
    t_flops, t_bytes = flops / v5e["flops_bf16"], moved / v5e["hbm_bytes_s"]
    # on the ridge: the two bounds within 5 % of each other
    assert abs(t_flops / t_bytes - 1) < 0.05
    flops, moved = family.moe_gmm_cost(cfg, rows=128, pairs=96,
                                       experts_touched=20)
    assert flops == 6 * 5120 * 1536 * 96
    assert moved == 20 * 3 * 5120 * 1536 * 2 + 2 * 128 * 5120 * 2
    assert peaks.least_seconds(flops, moved, v5e)[1] == "bandwidth"


def test_the_config_file_keeps_every_published_number(published):
    """Every number of the catalog row's ``config`` under the same key,
    the nested group whole; the three cut keys carry the held share with
    the published value beside it."""
    catalog = {
        "first_k_dense_replace": 1, "hidden_size": 5120,
        "intermediate_size": 12288, "kv_lora_rank": 512,
        "max_position_embeddings": 163840, "moe_intermediate_size": 1536,
        "moe_layer_freq": 1, "n_group": 8, "n_routed_experts": 160,
        "n_shared_experts": 2, "num_attention_heads": 128,
        "num_experts_per_tok": 6, "num_hidden_layers": 60,
        "num_key_value_heads": 128, "q_lora_rank": 1536,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-06, "rope_theta": 10000,
        "routed_scaling_factor": 16, "topk_group": 3, "v_head_dim": 128,
        "vocab_size": 102400}
    cut = {"num_hidden_layers": 5, "n_routed_experts": 20,
           "vocab_size": 12800}
    assert sorted(published["reduced"]) == sorted(cut)
    for key, value in catalog.items():
        assert published[key] == cut.get(key, value), key
    assert published["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 0.707,
        "mscale_all_dim": 0.707, "original_max_position_embeddings": 4096,
        "type": "yarn"}
    assert published["n_routed_experts_published"] == 160
    assert published["vocab_size_published"] == 102400
    assert published["norm_topk_prob"] is False
    assert published["attention_bias"] is False
    assert (published["scoring_func"], published["topk_method"]) == (
        "softmax", "group_limited_greedy")
    # the held experts are one whole routing group
    assert published["n_routed_experts"] * published["n_group"] == 160


def test_the_cell_is_as_the_issue_set_it(manifest):
    cell, config, mix, family, runner = bench.resolve(manifest, CELL)
    assert (cell["chips"], cell["config"], cell["traffic"]) == (
        1, "deepseek-v2-ep8-l5", "longctx-backlog")
    assert mix["runner"] == "serve_backlog"
    e = mix["engine"]
    assert (e["max_slots"], e["block_size"], e["max_model_len"]) == (
        128, 16, 10240)
    assert mix["kernels"] == {"mla_decode": "num_hidden_layers",
                              "moe_gmm": None}
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 4096,
                                    "sigma": 0.7, "min": 1024, "max": 8192}
    assert mix["output_tokens"] == {"dist": "lognormal", "median": 512,
                                    "sigma": 0.6, "min": 128, "max": 2048}
    assert (mix["queue_depth"], mix["pool"], mix["pair_seed"]) == (
        16, 32, 20260928)
    # the pool is memory the traffic can touch, and no request outgrows
    # its slot
    assert e["num_blocks"] < 128 * 10240 // 16
    import traffic_gen
    pool = traffic_gen.length_pool(mix)
    assert max(p + o for p, o in pool) <= e["max_model_len"]
    assert sum(p > 4096 for p, _ in pool) == 16      # half in bucket 8192
    reported = [m["name"] for m in manifest["end_to_end"]
                if bench.applies(m, CELL)]
    assert reported == ["itl_p95_ms", "setup_s"]


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
    with pytest.raises(ValueError, match="serving family"):
        family.build_model(TINY, seed=1, training=True)


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
        assert len(routing) == 2 and routing[0].shape == (70, 3)
    assert family.reference_loss(weights, TINY, ids[:, :-1], ids[:, 1:]) > 0


@pytest.fixture(scope="module")
def backlog(family):
    runner = bench.load_module("runners", "serve_backlog")
    return runner.run_backlog(
        family, TINY, tiny.mix("longctx-backlog", **tiny.BACKLOG),
        tiny.SEED, 1.0, tiny.quiet, on_chip=False)


def test_backlog_runs_and_checks_itself(backlog):
    assert {k: ok for k, (ok, _) in backlog["checks"].items()} == {
        "reference": True, "no_compile_in_window": True,
        "queue_never_empty": True}
    assert backlog["attempted"] > 0 and backlog["failed"] == 0
    counters = backlog["observations"]["counters"]
    assert counters["decode_compiles"] == 1
    moe, latent = counters["moe"], counters["latent"]
    assert moe["layers"] == 2 and moe["experts_held"] == 4
    # 4 slots x top-3 of 16 experts, a quarter of them held here
    assert 0 < moe["pairs"] <= 12 and 0 < moe["experts_touched"] <= 4
    assert latent["layers"] == 3
    # 40 values a token, a row of 128 float32 lanes in the pool
    assert latent["row_bytes"] == 128 * 4
    assert latent["pool_bytes"] == 3 * 64 * 8 * latent["row_bytes"]
    assert 0 < latent["cached_tokens"] <= 4 * 128
    assert counters["state"] is None
    json.dumps(counters)        # what a result line can carry


def test_latent_cache_readers(backlog, family, manifest):
    obs = backlog["observations"]
    steps = obs["steps"][-5:]
    trace = {"chips": 1, "window_s": 1.0, "busy_s": 0.5,
             "op_seconds": {"mla_decode": 0.25, "moe_gmm": 0.1},
             "op_calls": {"mla_decode": len(steps) * 3,
                          "moe_gmm": sum(1 + s["prefills"]
                                         for s in steps) * 2 * 2},
             "device_ops": [], "idle_gaps": []}
    logged = []
    mix = tiny.mix("longctx-backlog", **tiny.BACKLOG)
    full = dict(obs, traced_steps=steps, trace=trace, config=TINY,
                traffic=mix, family=family, chips=1,
                peaks=peaks.peaks_for("TPU v5 lite"), log=logged.append,
                end_to_end=backlog["end_to_end"])
    values = bench.read_layer_metrics(manifest, CELL, full)
    assert {"mla_decode_roofline", "latent.cached_tokens",
            "moe_gmm_roofline", "moe.experts_touched",
            "moe.load_max_over_mean", "serve.prefill_ms",
            "serve.prefill_engine_ms", "serve.itl_p95_engine_ms"} \
        <= set(values)
    assert 0 < values["mla_decode_roofline"] < 100
    assert values["latent.cached_tokens"] == \
        obs["counters"]["latent"]["cached_tokens"]
    assert any("mla_decode_roofline" in line and "3 calls" in line
               for line in logged)
    # the calls a step come from the mix's entry: a number serves too
    asked = dict(full, traffic=dict(mix, kernels={"mla_decode": 3}))
    reader = bench.load_module("layer_metrics", "mla_decode_roofline")
    assert reader.read(asked) == pytest.approx(
        values["mla_decode_roofline"])
    # a program without the kernel or the counter (the parent): nothing,
    # no raise
    bare = dict(full, counters={k: v for k, v in obs["counters"].items()
                                if k != "latent"},
                trace=dict(trace, op_seconds={}, op_calls={}))
    assert reader.read(bare) is None
    assert bench.load_module("layer_metrics",
                             "latent.cached_tokens").read(bare) is None
    # half the kernel calls outside the trace: the share halves
    trace["op_calls"]["mla_decode"] = len(steps) * 3 // 2
    assert reader.read(full) == pytest.approx(
        values["mla_decode_roofline"] * (len(steps) * 3 // 2)
        / (len(steps) * 3), rel=1e-6)
