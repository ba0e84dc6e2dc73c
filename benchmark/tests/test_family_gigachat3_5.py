"""The gigachat3_5 family file: its arithmetic against hand counts at the
published widths and against the program's leaves (shapes only), its
plain reference against the program's ``GigaChat35ForCausalLM`` at a
tiny size on the CPU, the backlog runner driven end to end on it, and
the readers of the cell: ``gdn_decode_roofline`` (new with it, and read
in the Qwen3-Next cell too) and ``mla_decode_roofline``; the cell is
off ``moe_gmm_roofline``'s list, whose reader scales a prefill's pairs
to its real rows where this model counts only those."""
import json
import os

import numpy as np
import pytest

import peaks
import run as bench
import scope_time
import tiny

CELL = "gigachat35-longdoc-backlog"
CONFIG = "gigachat3.5-432b-a28b-ep32-l5"
TINY = dict(
    family="gigachat3_5", vocab_size=128, hidden_size=64,
    intermediate_size=96, moe_intermediate_size=32, num_hidden_layers=5,
    layers_held=[0, 3, 4, 5, 6], full_attention_layers=[3, 7, 11],
    first_k_dense_replace=3, num_attention_heads=8, q_lora_rank=48,
    kv_lora_rank=128, qk_nope_head_dim=32, qk_rope_head_dim=16,
    v_head_dim=32, linear_num_key_heads=2, linear_num_value_heads=4,
    linear_key_head_dim=8, linear_value_head_dim=8,
    linear_conv_kernel_dim=4, linear_sigmoid_gate_scale=2,
    linear_attn_o_norm_eps=1e-6, layernorm_gating_weight=2,
    n_routed_experts=4, n_routed_experts_published=8, n_shared_experts=1,
    num_experts_per_tok=3, routed_scaling_factor=2.5, norm_topk_prob=True,
    swiglu_limit=10, rope_theta=100000,
    rope_scaling={"type": "yarn", "factor": 8, "beta_fast": 32,
                  "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
                  "original_max_position_embeddings": 16},
    rms_norm_eps=1e-6, max_position_embeddings=512,
    tie_word_embeddings=False, torch_dtype="float32",
    linear_attention_layers=4, latent_attention_layers=1)


@pytest.fixture(scope="module")
def family():
    return bench.load_module("families", "gigachat3_5")


@pytest.fixture(scope="module")
def manifest():
    return bench.load_json(os.path.join(bench.ROOT, "BENCHMARK.json"))


@pytest.fixture(scope="module")
def published(manifest):
    entry = bench.find(manifest["configs"], CONFIG, "config")
    return bench.load_json(os.path.join(bench.ROOT, entry["file"]))


def test_parameter_counts_by_hand(family, published):
    cfg = published
    lp = family.layer_params(cfg)
    # in_proj_qkvz 7168 x (16384 + 8192), in_proj_ba 7168 x 128, the
    # convolution 16384 x 4, A_log, dt_bias, the head norm, out_proj
    # 8192 x 7168
    assert lp["gdn"] == (7168 * 24576 + 7168 * 128 + 16384 * 4 + 128 + 128
                         + 8192 * 7168) == 235864320
    # W_dq 7168 x 1536, W_uq 1536 x 64 x 192, W_dkv 7168 x 576, W_ukv
    # 512 x 64 x 256, W_o and the gate 8192 x 7168 each, two inner norms
    assert lp["mla"] == (11010048 + 18874368 + 4128768 + 8388608
                         + 2 * 58720256 + 1536 + 512) == 159844352
    assert lp["dense_mlp"] == 3 * 7168 * 18432 == 396361728
    # 8 experts of three 7168 x 2048 matrices
    assert lp["experts"] == 8 * 44040192 == 352321536
    # router 7168 x 256 and its bias, the shared expert 7168 x 2048 x 3
    assert lp["moe_other"] == 1835008 + 256 + 44040192 == 45875456
    # four norms and two gates of 7168 a layer
    assert lp["norms"] == 6 * 7168
    assert family.layer_counts(cfg) == {"mla": 1, "gdn": 4, "dense": 1,
                                        "moe": 4}
    total = (159844352 + 4 * 235864320 + 396361728
             + 4 * (352321536 + 45875456) + 5 * 43008 + 2 * 16032 * 7168
             + 7168)
    assert family.param_count(cfg) == total == 3322508288   # 6.65 GB bf16


def test_whole_model_counts_what_was_published(family, published):
    """With every layer, expert and vocabulary row: 430.5 B, and the two
    multi-token-prediction modules (not built) make the published
    432 B."""
    whole = dict(published, num_hidden_layers=40,
                 layers_held=list(range(40)), n_routed_experts=256,
                 vocab_size=128256)
    assert family.layer_counts(whole) == {"mla": 10, "gdn": 30, "dense": 3,
                                          "moe": 37}
    assert round(family.param_count(whole) / 1e9, 1) == 430.5


def test_param_count_is_the_models_leaves_at_the_configurations_sizes(
        family, published):
    """Shapes only: nothing of 3.3 B parameters is allocated."""
    import jax

    from paddle_tpu.models.gigachat3_5 import GigaChat35ForCausalLM

    def leaves():
        return GigaChat35ForCausalLM(
            family.model_config(published)).functional_state()[1]

    shapes = jax.eval_shape(leaves)
    assert sum(int(np.prod(s.shape)) for s in shapes) \
        == family.param_count(published)


def test_kernel_costs_by_hand(family, published):
    cfg = published
    # one latent layer: a 576-value row tiled as 640 lanes, bf16
    assert family.kv_page_bytes(cfg, 16) == 1 * 16 * 640 * 2 == 20480
    # four layers of 64 x 128 x 128 float32 and a 3 x 16384 bf16 tail
    assert family.state_slot_bytes(cfg) == 4 * (4194304 + 98304) == 17170432
    flops, moved = family.mla_decode_cost(cfg, context_tokens=1000000,
                                          rows=192)
    assert flops == 2 * 1000000 * 64 * (576 + 512)
    assert moved == (1000000 * 576 + 192 * 64 * (576 + 512)) * 2
    flops, moved = family.moe_gmm_cost(cfg, rows=192, pairs=48,
                                       experts_touched=8)
    assert flops == 6 * 7168 * 2048 * 48
    assert moved == 8 * 3 * 7168 * 2048 * 2 + 2 * 192 * 7168 * 2
    v5e = peaks.peaks_for("TPU v5 lite")
    assert peaks.least_seconds(flops, moved, v5e)[1] == "bandwidth"
    # the largest prefill: 8192 rows, a quarter of 8 pairs each held here
    assert peaks.least_seconds(*family.moe_gmm_cost(
        cfg, rows=8192, pairs=16384, experts_touched=8), v5e)[1] == "compute"


def test_the_config_file_keeps_every_published_number(published):
    """Every key of the catalog row's ``config`` under the same key; the
    four cut keys carry the held share with the published value
    beside it."""
    catalog = {
        "vocab_size": 128256, "max_position_embeddings": 262144,
        "hidden_size": 7168, "intermediate_size": 18432,
        "moe_intermediate_size": 2048, "num_hidden_layers": 40,
        "nextn_is_sparse": False, "num_attention_heads": 64,
        "n_shared_experts": 1, "n_routed_experts": 256,
        "routed_scaling_factor": 2.5, "kv_lora_rank": 512,
        "q_lora_rank": 1536, "qk_rope_head_dim": 64, "v_head_dim": 128,
        "qk_nope_head_dim": 128, "qk_head_dim": 192, "n_group": 1,
        "topk_group": 1, "num_experts_per_tok": 8,
        "first_k_dense_replace": 3, "norm_topk_prob": True,
        "rope_interleave": True, "num_key_value_heads": 64,
        "hidden_act": "silu", "rms_norm_eps": 1e-06, "rope_theta": 100000,
        "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 8,
                         "mscale": 1, "mscale_all_dim": 1,
                         "original_max_position_embeddings": 32768,
                         "type": "yarn"},
        "attention_bias": False, "norm_type": "ZeroCenteredGatedNorm",
        "layernorm_type": "pre_post", "layernorm_gating_weight": 2,
        "gated_attention": True, "use_shared_expert_sigmoid": False,
        "use_mla_scaling_factor": True,
        "linear_attention_type": "GigaChat35GatedDeltaNet",
        "full_attention_layers": [3, 7, 11, 15, 19, 23, 27, 31, 35, 39],
        "linear_key_head_dim": 128, "linear_value_head_dim": 128,
        "linear_conv_kernel_dim": 4, "linear_num_key_heads": 32,
        "linear_num_value_heads": 64,
        "linear_gating_type": "gated_rmsnorm_sigmoid_zero_centered",
        "linear_sigmoid_gate_scale": 2, "linear_attn_o_norm_eps": 1e-06,
        "swiglu_limit": 10, "tie_word_embeddings": False,
        "num_nextn_predict_layers": 2, "model_type": "gigachat3_5",
        "tf_legacy_loss": False}
    cut = {"num_hidden_layers": 5, "n_routed_experts": 8,
           "vocab_size": 16032, "num_nextn_predict_layers": 0}
    assert sorted(published["reduced"]) == sorted(cut)
    for key, value in catalog.items():
        assert published[key] == cut.get(key, value), key
        if key in cut:
            assert published[key + "_published"] == value
    # layer 0 and one whole period, 3-6: every kind in its ratio
    assert published["layers_held"] == [0, 3, 4, 5, 6]
    assert (published["linear_attention_layers"],
            published["latent_attention_layers"]) == (4, 1)
    assert published["experts_held_from"] == 0
    assert published["torch_dtype"] == "bfloat16"
    assert "deployment" in published and len(published["assumed"]) >= 7


def test_the_mix_holds_the_cells_sizes():
    mix = bench.load_json(os.path.join(bench.HERE, "traffic",
                                       "longdoc-hybrid-backlog.json"))
    assert mix["runner"] == "serve_backlog"
    assert mix["engine"] == {"max_slots": 192, "num_blocks": 80000,
                             "block_size": 16, "max_model_len": 10240}
    assert mix["kernels"] == {"mla_decode": 1, "moe_gmm": None}
    assert (mix["reference_prompts"], mix["reference_tokens"],
            mix["trace_seconds"], mix["queue_depth"]) == ([48, 384], 8, 3, 16)
    import traffic_gen

    pool = traffic_gen.length_pool(mix)
    # half the prompts land in the 8192 bucket; a mean output of 1125
    assert sum(p > 4096 for p, _ in pool) == 16
    assert 1100 < sum(o for _, o in pool) / len(pool) < 1150


@pytest.fixture(scope="module")
def tiny_model(family):
    return family.build_model(TINY, seed=3000000019, training=False)


def test_seed_makes_the_weights(family, tiny_model):
    again = family.build_model(TINY, seed=3000000019, training=False)
    other = family.build_model(TINY, seed=7, training=False)
    weights = family.weights_of(tiny_model)
    for name in ("model.layers.1.mlp.experts.w1",
                 "model.layers.1.self_attn.gate_proj",
                 "model.layers.2.linear_attn.in_proj_qkvz",
                 "model.layers.2.mlp.e_score_correction_bias"):
        w = np.asarray(weights[name])
        assert np.array_equal(w, np.asarray(family.weights_of(again)[name]))
        assert not np.array_equal(
            w, np.asarray(family.weights_of(other)[name]))
    assert family.param_count(TINY) == sum(
        int(np.prod(v.shape)) for v in weights.values())


def test_a_switch_the_program_does_not_build_is_refused(family):
    with pytest.raises(ValueError, match="gated_attention"):
        family.model_config(dict(TINY, gated_attention=False))
    with pytest.raises(ValueError, match="num_nextn_predict_layers"):
        family.model_config(dict(TINY, num_nextn_predict_layers=2))


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
        assert len(routing) == 4 and routing[0].shape == (70, 3)
    assert family.reference_loss(weights, TINY, ids[:, :-1], ids[:, 1:]) > 0


def test_the_reference_imports_nothing_of_the_programs_models(family):
    with open(family.__file__) as f:
        source = f.read()
    body = source.split("# -- the plain reference")[1].split(
        "# -- arithmetic")[0]
    assert "paddle_tpu" not in body and "import" in body


@pytest.fixture(scope="module")
def backlog(family):
    runner = bench.load_module("runners", "serve_backlog")
    return runner.run_backlog(
        family, TINY, tiny.mix("longdoc-hybrid-backlog", **tiny.BACKLOG),
        tiny.SEED, 1.0, tiny.quiet, on_chip=False)


def test_backlog_runs_and_checks_itself(backlog, family):
    assert {k: ok for k, (ok, _) in backlog["checks"].items()} == {
        "reference": True, "no_compile_in_window": True,
        "queue_never_empty": True}
    assert backlog["attempted"] > 0 and backlog["failed"] == 0
    counters = backlog["observations"]["counters"]
    assert counters["decode_compiles"] == 1
    moe, state, latent = counters["moe"], counters["state"], \
        counters["latent"]
    assert moe["layers"] == 4 and moe["experts_held"] == 4
    # 4 slots x top-3 of 8 experts, half of them held here
    assert 0 < moe["pairs"] <= 12 and 0 < moe["experts_touched"] <= 4
    assert state["slots"] == 4 and state["layers"] == 4
    assert state["slot_bytes"] == family.state_slot_bytes(TINY)
    assert latent["layers"] == 1 and latent["cached_tokens"] > 0
    assert counters["ssm"] is None
    json.dumps(counters)        # what a result line can carry


def _trace_of_steps(tmp_path, monkeypatch, steps, gdn_s, programs):
    """A reduced trace and a device trace (``scope_time.load``'s form,
    a file beside it) in which every decode program spends ``gdn_s``
    seconds under the ``gdn`` scope."""
    path = tmp_path / "cell" / "plugins" / "profile" / "t"
    path.mkdir(parents=True)
    (path / "host.xplane.pb").write_bytes(b"")
    monkeypatch.setattr(scope_time, "TRACE_ROOT", str(tmp_path))
    ops = {1: {"module": "jit__decode_fn", "ops": {
        "fusion.1": "jit(_decode_fn)/layer_0/gdn/dot_general",
        "fusion.2": "jit(_decode_fn)/layer_1/mla/dot_general"}},
        2: {"module": "jit__prefill_fn", "ops": {
            "fusion.1": "jit(_prefill_fn)/layer_0/gdn/dot_general"}}}
    events, runs = [], []
    for i in range(programs):
        at = i * 0.1
        events += [(0, 1, "jit__decode_fn", "fusion.1", at, gdn_s),
                   (0, 1, "jit__decode_fn", "fusion.2", at + gdn_s, 1e-3),
                   (0, 2, "jit__prefill_fn", "fusion.1", at + 0.05, 0.02)]
        runs += [(0, 1, "jit__decode_fn", at, gdn_s + 1e-3),
                 (0, 2, "jit__prefill_fn", at + 0.05, 0.02)]
    monkeypatch.setattr(scope_time, "load", lambda p: {
        "events": events, "runs": runs, "programs": ops,
        "spans": [("bench.engine_step", 0.0, 0.1 * len(steps))]})
    return {"chips": 1, "window_s": 1.0, "busy_s": 0.5,
            "op_seconds": {"mla_decode": 1e-4, "moe_gmm": 0.25},
            "op_calls": {"mla_decode": len(steps),
                         "moe_gmm": sum(1 + s["prefills"] for s in steps)
                         * 4 * 2},
            "device_ops": [], "idle_gaps": []}


def test_the_cells_readers(backlog, family, manifest, tmp_path, monkeypatch):
    obs = backlog["observations"]
    steps = obs["steps"][-5:]
    trace = _trace_of_steps(tmp_path, monkeypatch, steps, 2e-3, len(steps))
    mix = bench.load_json(os.path.join(bench.HERE, "traffic",
                                       "longdoc-hybrid-backlog.json"))
    logged = []
    v5e = peaks.peaks_for("TPU v5 lite")
    full = dict(obs, traced_steps=steps, trace=trace, config=TINY,
                traffic=mix, family=family, chips=1, peaks=v5e,
                log=logged.append, end_to_end=backlog["end_to_end"])
    values = bench.read_layer_metrics(manifest, CELL, full)
    assert {"gdn_decode_roofline", "mla_decode_roofline",
            "moe.load_max_over_mean", "moe.experts_touched",
            "latent.cached_tokens", "serve.prefill_ms",
            "serve.prefill_engine_ms", "serve.itl_p95_engine_ms"} \
        <= set(values)
    assert "moe_gmm_roofline" not in values
    # the least time of the steps' live slots over 2 ms a decode program
    reader = bench.load_module("layer_metrics", "gdn_decode_roofline")
    least = sum(reader.least_seconds(TINY, s["rows"], v5e)[0]
                for s in steps)
    assert values["gdn_decode_roofline"] == pytest.approx(
        100 * least / (len(steps) * 2e-3), rel=1e-6)
    assert any("gdn_decode_roofline" in line for line in logged)
    assert 0 < values["mla_decode_roofline"]
    # half the decode programs fell outside the trace: the time under
    # the scope and the work counted against it shrink alike
    monkeypatch.undo()
    trace = _trace_of_steps(tmp_path / "half", monkeypatch, steps, 2e-3,
                            (len(steps) + 1) // 2)
    assert reader.read(dict(full, trace=trace)) == pytest.approx(
        values["gdn_decode_roofline"], rel=1e-6)
    # and twice the time a program: half the share
    monkeypatch.undo()
    trace = _trace_of_steps(tmp_path / "slow", monkeypatch, steps, 4e-3,
                            len(steps))
    assert reader.read(dict(full, trace=trace)) == pytest.approx(
        values["gdn_decode_roofline"] / 2, rel=1e-6)


def test_gdn_decode_roofline_by_hand_at_the_published_widths(published):
    """192 live slots of four layers: 1.41 GB of weights once, 8.4 MB of
    state read and written a slot a layer; bound by the memory."""
    reader = bench.load_module("layer_metrics", "gdn_decode_roofline")
    v5e = peaks.peaks_for("TPU v5 lite")
    seconds, bound = reader.least_seconds(published, 192, v5e)
    weights = 7168 * 24576 + 7168 * 128 + 8192 * 7168
    moved = 4 * ((weights + 16384 * 4) * 2 + 192 * (
        2 * 64 * 128 * 128 * 4 + 2 * 3 * 16384 * 2 + 2 * 7168 * 2))
    assert bound == "bandwidth"
    assert seconds == pytest.approx(moved / 819e9)
    assert 9.5e-3 < seconds < 10.5e-3


def test_gdn_decode_roofline_finds_nothing_where_there_is_nothing(
        backlog, tmp_path, monkeypatch):
    """No Gated DeltaNet layers, no trace, no file to read or no decode
    program under the scope: nothing, one line, no raise."""
    reader = bench.load_module("layer_metrics", "gdn_decode_roofline")
    steps = backlog["observations"]["steps"][-3:]
    lines = []
    base = dict(traced_steps=steps, config=TINY, log=lines.append,
                peaks=peaks.peaks_for("TPU v5 lite"))
    assert reader.read(dict(base, trace=None)) is None
    assert reader.read(dict(base, trace={}, config=dict(
        TINY, linear_attention_layers=0))) is None
    monkeypatch.setattr(scope_time, "TRACE_ROOT", str(tmp_path))
    assert reader.read(dict(base, trace={"chips": 1})) is None
    assert "FileNotFoundError" in lines[-1]
    trace = _trace_of_steps(tmp_path, monkeypatch, steps, 0.0, len(steps))
    assert reader.read(dict(base, trace=trace)) is None
    assert "no gdn time" in lines[-1]
