"""The nemotron_h family file: its arithmetic against hand counts at the
published widths and against the program's leaves (shapes only), its
plain reference against the program's ``NemotronHForCausalLM`` at a tiny
size on the CPU, the backlog runner driven end to end on it, and the two
state-space readers."""
import json
import os

import numpy as np
import pytest

import peaks
import run as bench
import tiny

CELL = "nemotron3nano-longreason-backlog"
CONFIG = "nemotron-3-nano-30b-a3b-ep2-l9"
TINY = dict(
    family="nemotron_h", vocab_size=128, hidden_size=64,
    hybrid_override_pattern="MEM*E", num_hidden_layers=5,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    mamba_num_heads=4, mamba_head_dim=8, ssm_state_size=16, n_groups=2,
    conv_kernel=4, chunk_size=8, time_step_min=0.001, time_step_max=0.1,
    time_step_floor=1e-4, n_routed_experts=4,
    n_routed_experts_published=8, num_experts_per_tok=3,
    moe_intermediate_size=32, moe_shared_expert_intermediate_size=48,
    routed_scaling_factor=2.5, norm_topk_prob=True,
    layer_norm_epsilon=1e-5, max_position_embeddings=512,
    tie_word_embeddings=False, torch_dtype="float32", mamba_layers=2,
    moe_layers=2, attention_layers=1)
PUBLISHED_PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


@pytest.fixture(scope="module")
def family():
    return bench.load_module("families", "nemotron_h")


@pytest.fixture(scope="module")
def published():
    manifest = bench.load_json(os.path.join(bench.ROOT, "BENCHMARK.json"))
    entry = bench.find(manifest["configs"], CONFIG, "config")
    return bench.load_json(os.path.join(bench.ROOT, entry["file"]))


def test_parameter_counts_by_hand(family, published):
    cfg = published
    lp = family.layer_params(cfg)
    # in_proj 2688 x (4096 + 6144 + 64), conv 6144 x 4 and its bias,
    # A_log, dt_bias, D, the gate norm, out_proj 4096 x 2688, the norm
    assert lp["M"] == (2688 * 10304 + 6144 * 5 + 3 * 64 + 4096
                       + 4096 * 2688 + 2688) == 38744896
    # q 2688 x 4096, k and v 2688 x 256, o 4096 x 2688, the norm
    assert lp["*"] == (2 * 2688 * 4096 + 2 * 2688 * 256 + 2688) == 23399040
    # 64 experts of two 2688 x 1856 matrices, the shared expert at 3712,
    # the router 2688 x 128 and its bias, the norm
    assert lp["E"] == (64 * 2 * 2688 * 1856 + 2 * 2688 * 3712 + 2688 * 128
                       + 128 + 2688) == 658885376
    assert family.layer_counts(cfg) == {"M": 4, "E": 4, "*": 1}
    assert (cfg["mamba_layers"], cfg["moe_layers"],
            cfg["attention_layers"]) == (4, 4, 1)
    total = (4 * 38744896 + 4 * 658885376 + 23399040 + 2 * 65536 * 2688
             + 2688)
    assert family.param_count(cfg) == total == 3166244352   # 6.33 GB bf16


def test_whole_model_counts_what_was_published(family, published):
    """With every layer, expert and vocabulary row: the published
    31.6 B."""
    whole = dict(published, hybrid_override_pattern=PUBLISHED_PATTERN,
                 n_routed_experts=128, vocab_size=131072)
    assert family.layer_counts(whole) == {"M": 23, "E": 23, "*": 6}
    assert round(family.param_count(whole) / 1e9, 1) == 31.6


def test_param_count_is_the_models_leaves_at_the_configurations_sizes(
        family, published):
    """Shapes only: nothing of 3.2 B parameters is allocated."""
    import jax

    from paddle_tpu.models.nemotron_h import NemotronHForCausalLM

    def leaves():
        return NemotronHForCausalLM(
            family.model_config(published)).functional_state()[1]

    shapes = jax.eval_shape(leaves)
    assert sum(int(np.prod(s.shape)) for s in shapes) \
        == family.param_count(published)


def test_kernel_costs_by_hand(family, published):
    cfg = published
    # one paged layer: K and V of 2 KV heads x 128 in bf16
    assert family.kv_page_bytes(cfg, 16) == 1 * 16 * 2 * 2 * 128 * 2 == 16384
    # four layers of 64 x 64 x 128 float32 and a 3 x 6144 bf16 tail
    assert family.state_slot_bytes(cfg) == 4 * (2097152 + 36864) == 8536064
    flops, moved = family.paged_decode_cost(cfg, context_tokens=1000000,
                                            rows=256)
    assert flops == 2 * 2 * 1000000 * 32 * 128
    assert moved == 2 * 1000000 * 2 * 128 * 2 + 2 * 256 * 32 * 128 * 2
    flops, moved = family.moe_gmm_cost(cfg, rows=256, pairs=768,
                                       experts_touched=64)
    # no gate: two matrices an expert, 4 FLOPs a weight a pair
    assert flops == 4 * 2688 * 1856 * 768
    assert moved == 64 * 2 * 2688 * 1856 * 2 + 2 * 256 * 2688 * 2
    v5e = peaks.peaks_for("TPU v5 lite")
    assert peaks.least_seconds(flops, moved, v5e)[1] == "bandwidth"
    flops, moved = family.ssm_decode_cost(cfg, rows=256)
    assert flops == 6 * 64 * 64 * 128 * 256
    # the state in and out, x, B, C in bf16, dt and y in float32
    assert moved == 256 * (2 * 2097152 + (4096 + 2048) * 2 + 64 * 4
                           + 4096 * 4)
    seconds, bound = peaks.least_seconds(flops, moved, v5e)
    assert bound == "bandwidth" and 1.2e-3 < seconds < 1.4e-3


def test_the_config_file_keeps_every_published_number(published):
    """Every key of the catalog row's ``config`` under the same key; the
    four cut keys carry the held share with the published value beside
    it."""
    catalog = {
        "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
        "expand": 2, "head_dim": 128, "hidden_size": 2688,
        "hybrid_override_pattern": PUBLISHED_PATTERN,
        "intermediate_size": 1856, "layer_norm_epsilon": 1e-05,
        "mamba_head_dim": 64, "mamba_hidden_act": "silu",
        "mamba_num_heads": 64, "mamba_proj_bias": False,
        "max_position_embeddings": 262144, "mlp_bias": False,
        "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
        "moe_intermediate_size": 1856,
        "moe_shared_expert_intermediate_size": 3712, "n_group": 1,
        "n_groups": 8, "n_routed_experts": 128, "n_shared_experts": 1,
        "norm_eps": 1e-05, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts_per_tok": 6,
        "num_hidden_layers": 52, "num_key_value_heads": 2,
        "num_logits_to_keep": 1, "partial_rotary_factor": 1,
        "rescale_prenorm_residual": True, "residual_in_fp32": False,
        "rope_theta": 10000, "routed_scaling_factor": 2.5,
        "sliding_window": None, "ssm_state_size": 128,
        "tie_word_embeddings": False, "time_step_floor": 0.0001,
        "time_step_max": 0.1, "time_step_min": 0.001, "topk_group": 1,
        "use_bias": False, "use_conv_bias": True,
        "use_mamba_kernels": True, "vocab_size": 131072}
    cut = {"num_hidden_layers": 9, "hybrid_override_pattern": "MEMEM*EME",
           "n_routed_experts": 64, "vocab_size": 65536}
    assert sorted(published["reduced"]) == sorted(cut)
    for key, value in catalog.items():
        assert published[key] == cut.get(key, value), key
        if key in cut:
            assert published[key + "_published"] == value
    assert PUBLISHED_PATTERN.startswith(published["hybrid_override_pattern"])
    assert published["experts_held_from"] == 0
    assert published["torch_dtype"] == "bfloat16"
    assert "deployment" in published and len(published["assumed"]) >= 5


def test_the_mix_is_the_issues(published):
    mix = bench.load_json(os.path.join(bench.HERE, "traffic",
                                       "longreason-backlog.json"))
    assert mix["runner"] == "serve_backlog"
    assert mix["engine"] == {"max_slots": 256, "num_blocks": 120000,
                             "block_size": 16, "max_model_len": 10240}
    assert mix["kernels"] == {"paged_decode": "attention_layers",
                              "ssm_decode": "mamba_layers", "moe_gmm": None}
    import traffic_gen

    pool = traffic_gen.length_pool(mix)
    assert sum(p > 4096 for p, _ in pool) == 12
    assert 1100 < sum(o for _, o in pool) / len(pool) < 1150


@pytest.fixture(scope="module")
def tiny_model(family):
    return family.build_model(TINY, seed=3000000019, training=False)


def test_seed_makes_the_weights(family, tiny_model):
    again = family.build_model(TINY, seed=3000000019, training=False)
    other = family.build_model(TINY, seed=7, training=False)
    weights = family.weights_of(tiny_model)
    for name in ("backbone.layers.1.mixer.experts.w1",
                 "backbone.layers.0.mixer.A_log",
                 "backbone.layers.0.mixer.dt_bias"):
        w = np.asarray(weights[name])
        assert np.array_equal(w, np.asarray(family.weights_of(again)[name]))
        assert not np.array_equal(
            w, np.asarray(family.weights_of(other)[name]))
    # the family's init: A in [1, 16], the time step in [1e-3, 1e-1]
    a = np.exp(np.asarray(weights["backbone.layers.0.mixer.A_log"]))
    assert (a >= 1.0).all() and (a <= 16.0).all()
    step = np.log1p(np.exp(np.asarray(
        weights["backbone.layers.0.mixer.dt_bias"])))
    assert (step > 0.9e-3).all() and (step < 0.11).all()
    for name in ("backbone.layers.0.mixer.conv_bias",
                 "backbone.layers.1.mixer.e_score_correction_bias"):
        assert np.abs(np.asarray(weights[name])).min() > 0
    assert family.param_count(TINY) == sum(
        int(np.prod(v.shape)) for v in weights.values())


def test_the_router_biases_are_balanced_when_a_model_is_built(family):
    """At a size where there is something to balance (32 experts, top-4,
    16 held): the built model's late tokens land on every held expert
    and about half their pairs land here; the same weights with the
    drawn biases left as they were pick a few experts for everybody."""
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.models.nemotron_h import NemotronHForCausalLM
    from paddle_tpu.parallel.moe import route

    cfg = dict(TINY, hybrid_override_pattern="MEME",
               n_routed_experts=16, n_routed_experts_published=32,
               num_experts_per_tok=4)
    built = family.build_model(cfg, seed=5, training=False)
    paddle.seed(5)
    drawn = NemotronHForCausalLM(family.model_config(cfg))
    drawn.eval()
    ids = np.random.default_rng(9).integers(0, 128, (4, 256)).astype(
        np.int32)

    def loads(model):
        caught = []
        mixer = model.backbone.layers[3].mixer
        hook = mixer.register_forward_pre_hook(
            lambda _layer, inputs: caught.append(inputs[0]))
        model(paddle.to_tensor(ids))
        hook.remove()
        x = caught[0][:, -64:].reshape(-1, 64)
        _, chosen, _ = route(
            jnp.asarray(x), mixer.experts.gate_weight._value, 4, True,
            select_bias=mixer.e_score_correction_bias._value)
        return np.bincount(np.asarray(chosen).reshape(-1), minlength=32)

    even, skewed = loads(built), loads(drawn)
    assert (even[:16] > 0).all() and 0.4 < even[:16].sum() / even.sum() < 0.6
    assert even.max() < 3.0 * even.mean()
    assert skewed.max() > 2 * even.max()
    w = family.weights_of(built)
    assert np.array_equal(
        np.asarray(w["backbone.layers.1.mixer.experts.gate_weight"]),
        np.asarray(family.weights_of(drawn)[
            "backbone.layers.1.mixer.experts.gate_weight"]))


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
        family, TINY, tiny.mix("longreason-backlog", **tiny.BACKLOG),
        tiny.SEED, 1.0, tiny.quiet, on_chip=False)


def test_backlog_runs_and_checks_itself(backlog, family):
    assert {k: ok for k, (ok, _) in backlog["checks"].items()} == {
        "reference": True, "no_compile_in_window": True,
        "queue_never_empty": True}
    assert backlog["attempted"] > 0 and backlog["failed"] == 0
    counters = backlog["observations"]["counters"]
    assert counters["decode_compiles"] == 1
    moe, state, ssm = counters["moe"], counters["state"], counters["ssm"]
    assert moe["layers"] == 2 and moe["experts_held"] == 4
    # 4 slots x top-3 of 8 experts, half of them held here
    assert 0 < moe["pairs"] <= 12 and 0 < moe["experts_touched"] <= 4
    assert state["slots"] == 4 and state["layers"] == 2
    assert ssm["layers"] == 2
    assert ssm["state_bytes_slot"] == family.state_slot_bytes(TINY)
    assert 0 < ssm["active_slots"] <= 4
    assert counters["latent"] is None
    json.dumps(counters)        # what a result line can carry


def test_state_space_readers(backlog, family):
    manifest = bench.load_json(os.path.join(bench.ROOT, "BENCHMARK.json"))
    obs = backlog["observations"]
    steps = obs["steps"][-5:]
    programs = sum(1 + s["prefills"] for s in steps)
    trace = {"chips": 1, "window_s": 1.0, "busy_s": 0.5,
             "op_seconds": {"ssm_decode": 1e-6, "moe_gmm": 0.25},
             "op_calls": {"ssm_decode": len(steps) * 2,
                          "moe_gmm": programs * 2 * 2},
             "device_ops": [], "idle_gaps": []}
    logged = []
    mix = bench.load_json(os.path.join(bench.HERE, "traffic",
                                       "longreason-backlog.json"))
    full = dict(obs, traced_steps=steps, trace=trace, config=TINY,
                traffic=mix, family=family, chips=1,
                peaks=peaks.peaks_for("TPU v5 lite"), log=logged.append,
                end_to_end=backlog["end_to_end"])
    values = bench.read_layer_metrics(manifest, CELL, full)
    assert {"ssm_decode_roofline", "ssm.active_slots", "moe_gmm_roofline",
            "moe.load_max_over_mean", "moe.experts_touched",
            "serve.prefill_ms", "serve.prefill_engine_ms",
            "serve.itl_p95_engine_ms"} == set(values)
    assert values["ssm.active_slots"] == obs["counters"]["ssm"][
        "active_slots"]
    # the least time of the steps' rows x two layers over the time given
    rows = sum(s["rows"] for s in steps)
    least = 2 * family.ssm_decode_cost(TINY, rows)[1] / peaks.peaks_for(
        "TPU v5 lite")["hbm_bytes_s"]
    assert values["ssm_decode_roofline"] == pytest.approx(
        100 * least / 1e-6, rel=1e-6)
    assert any("ssm_decode_roofline" in line for line in logged)
    # a program without the kernel or the counter (the parent): nothing,
    # no raise
    bare = dict(full, counters={k: v for k, v in obs["counters"].items()
                                if k != "ssm"},
                trace=dict(trace, op_seconds={}, op_calls={}))
    for name in ("ssm_decode_roofline", "ssm.active_slots"):
        assert bench.load_module("layer_metrics", name).read(bare) is None
    # half the kernel calls outside the trace: the share halves
    trace["op_calls"]["ssm_decode"] //= 2
    halved = bench.load_module("layer_metrics",
                               "ssm_decode_roofline").read(full)
    assert halved == pytest.approx(values["ssm_decode_roofline"] / 2,
                                   rel=0.02)
