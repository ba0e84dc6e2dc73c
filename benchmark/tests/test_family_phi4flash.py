"""The phi4flash family file: its arithmetic against hand counts at the
published widths and against the program's leaves (shapes only), the
configuration against the catalog row, the mix's stated sizes, its
plain reference against the program's ``Phi4FlashForCausalLM`` at a tiny
size on the CPU, the backlog runner driven end to end on it, and the
three readers this cell brings."""
import json
import os

import numpy as np
import pytest

import peaks
import run as bench
import tiny

CELL = "phi4flash-mathreason-backlog"
CONFIG = "phi-4-mini-flash-reasoning-l32"
TINY = dict(
    family="phi4flash", vocab_size=128, hidden_size=64,
    intermediate_size=128, num_hidden_layers=8, num_attention_heads=8,
    num_key_value_heads=4, sliding_window=8, mb_per_layer=2,
    mamba_d_state=16, mamba_d_conv=4, mamba_expand=2, mamba_dt_rank=4,
    layer_norm_eps=1e-5, max_position_embeddings=512, time_step_min=0.001,
    time_step_max=0.1, time_step_floor=1e-4, lambda_std=0.1,
    tie_word_embeddings=True, mlp_bias=False, lm_head_bias=False,
    torch_dtype="float32", diff_attention_layers=4)


@pytest.fixture(scope="module")
def family():
    return bench.load_module("families", "phi4flash")


@pytest.fixture(scope="module")
def published():
    manifest = bench.load_json(os.path.join(bench.ROOT, "BENCHMARK.json"))
    entry = bench.find(manifest["configs"], CONFIG, "config")
    return bench.load_json(os.path.join(bench.ROOT, entry["file"]))


def test_parameter_counts_by_hand(family, published):
    cfg = published
    lp = family.layer_params(cfg)
    # two LayerNorms and the MLP, in every layer
    common = 4 * 2560 + 3 * 2560 * 10240
    # in_proj 2560 x 10240, conv 5120 x 4 and its bias, x_proj 5120 x
    # 192, dt_proj 160 x 5120 and its bias, A_log 5120 x 16, D, out_proj
    assert lp["mamba"] - common == (2560 * 10240 + 5120 * 5 + 5120 * 192
                                    + 160 * 5120 + 5120 + 5120 * 16 + 5120
                                    + 5120 * 2560) == 41241600
    # q and o 2560 x 2560, k and v 2560 x 1280, four lambda vectors of 64
    # and the 128-wide sub-norm
    assert lp["window"] - common == (2 * 2560 * 2560 + 2 * 2560 * 1280
                                     + 4 * 64 + 128) == 19661184
    assert lp["full"] == lp["window"]
    assert lp["cross"] - common == 2 * 2560 * 2560 + 4 * 64 + 128
    assert lp["gmu"] - common == 2 * 2560 * 5120
    assert family.layer_counts(cfg) == {"mamba": 9, "window": 8, "full": 1,
                                        "gmu": 7, "cross": 7}
    # every parameter counted, the published 3.8 B (7.70 GB in bf16)
    assert family.param_count(cfg) == 3852457984


def test_param_count_is_the_models_leaves_at_the_configurations_sizes(
        family, published):
    """Shapes only: nothing of 3.85 B parameters is allocated."""
    import jax

    from paddle_tpu.models.phi4flash import Phi4FlashForCausalLM

    def leaves():
        return Phi4FlashForCausalLM(
            family.model_config(published)).functional_state()[1]

    shapes = jax.eval_shape(leaves)
    assert sum(int(np.prod(s.shape)) for s in shapes) \
        == family.param_count(published)


def test_sizes_and_costs_by_hand(family, published):
    cfg = published
    # the one full-attention layer: K and V of 20 heads x 64 in bf16
    assert family.kv_page_bytes(cfg, 16) == 16 * 2 * 20 * 64 * 2 == 81920
    # 8 window layers x K and V x 512 rows x 1280 x 2 B
    assert family.ring_slot_bytes(cfg) == 8 * 2 * 512 * 1280 * 2 == 20971520
    # 9 layers of a 5120 x 16 float32 state and a 3 x 5120 bf16 tail
    assert family.state_slot_bytes(cfg) == 9 * (327680 + 30720) == 3225600
    v5e = peaks.peaks_for("TPU v5 lite")
    flops, moved = family.diff_decode_step_cost(cfg, rows=160,
                                                context_tokens=160 * 2600)
    weights = 9 * (2 * 2560 * 2560 + 2 * 2560 * 1280) + 7 * 2 * 2560 * 2560
    keys = 160 * (8 * 2600 + 8 * 512)
    assert moved == (weights * 2 + keys * 5120 + 160 * 9 * 5120
                     + 160 * 16 * 2 * 2560 * 2)
    assert flops == 2 * weights * 160 + 4 * 64 * 40 * keys
    seconds, bound = peaks.least_seconds(flops, moved, v5e)
    assert bound == "bandwidth" and 0.025 < seconds < 0.026
    # a short context counts only the rows a ring holds
    assert family.diff_decode_step_cost(cfg, 1, 100)[1] \
        < family.diff_decode_step_cost(cfg, 1, 1000)[1]
    flops, moved = family.selective_scan_prefill_cost(cfg, rows=1536)
    matmul = 2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560
    assert flops == 9 * 1536 * (2 * matmul + 6 * 5120 * 16)
    assert peaks.least_seconds(flops, moved, v5e)[1] == "compute"


def test_the_config_file_keeps_every_published_number(published):
    """Every key of the catalog row's ``config`` under the same key, and
    nothing cut."""
    catalog = {
        "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
        "intermediate_size": 10240, "layer_norm_eps": 1e-05,
        "max_position_embeddings": 262144, "mb_per_layer": 2,
        "model_type": "phi4flash", "num_attention_heads": 40,
        "num_hidden_layers": 32, "num_key_value_heads": 20,
        "resid_pdrop": 0, "sliding_window": 512,
        "tie_word_embeddings": True, "mlp_bias": False,
        "lm_head_bias": False, "vocab_size": 200064}
    for key, value in catalog.items():
        assert published[key] == value, key
    assert published["reduced"] == {}
    assert published["torch_dtype"] == "bfloat16"
    assert published["deployment"] == ("one chip serves the whole model; "
                                       "replicas are data-parallel")
    assert len(published["assumed"]) >= 7
    assert published["diff_attention_layers"] == 16


def test_the_mix_is_as_stated():
    mix = bench.load_json(os.path.join(bench.HERE, "traffic",
                                       "mathreason-backlog.json"))
    assert mix["runner"] == "serve_backlog"
    assert mix["engine"] == {"max_slots": 160, "num_blocks": 34000,
                             "block_size": 16, "max_model_len": 6144}
    assert mix["kernels"] == {"diff_decode": "diff_attention_layers"}
    assert mix["reference_prompts"] == [48, 1536]
    import traffic_gen

    pool = traffic_gen.length_pool(mix)
    # every prompt in the 2048 bucket; outputs 512-4096
    assert all(1025 <= p <= 2048 for p, _ in pool)
    assert all(512 <= o <= 4096 for _, o in pool)
    assert max(p for p, _ in pool) + max(o for _, o in pool) \
        <= mix["engine"]["max_model_len"]


@pytest.fixture(scope="module")
def tiny_model(family):
    return family.build_model(TINY, seed=3000000019, training=False)


def test_seed_makes_the_weights(family, tiny_model):
    again = family.build_model(TINY, seed=3000000019, training=False)
    other = family.build_model(TINY, seed=7, training=False)
    weights = family.weights_of(tiny_model)
    for name in ("model.layers.0.mixer.in_proj",
                 "model.layers.0.mixer.dt_bias",
                 "model.layers.1.mixer.lambda_q1"):
        w = np.asarray(weights[name])
        assert np.array_equal(w, np.asarray(family.weights_of(again)[name]))
        assert not np.array_equal(
            w, np.asarray(family.weights_of(other)[name]))
    # the family's init: A_log = log(1 .. 16) for every channel, the time
    # step in [1e-3, 1e-1], D = 1, lambda vectors of spread 0.1
    a = np.exp(np.asarray(weights["model.layers.0.mixer.A_log"]))
    np.testing.assert_allclose(a, np.tile(np.arange(1, 17), (128, 1)),
                               rtol=1e-6)
    step = np.log1p(np.exp(np.asarray(
        weights["model.layers.0.mixer.dt_bias"])))
    assert (step > 0.9e-3).all() and (step < 0.11).all()
    assert (np.asarray(weights["model.layers.0.mixer.D"]) == 1).all()
    lam = np.concatenate([np.asarray(weights["model.layers.%d.mixer.%s"
                                             % (i, k)])
                          for i in (1, 3, 5, 7)
                          for k in ("lambda_q1", "lambda_k2")])
    assert 0.05 < lam.std() < 0.15
    assert np.abs(np.asarray(
        weights["model.layers.0.mixer.conv_bias"])).min() > 0


def test_reference_logits_match_the_program(family, tiny_model):
    import paddle_tpu as paddle

    ids = np.random.default_rng(0).integers(
        0, TINY["vocab_size"], (2, 40)).astype(np.int32)
    got = np.asarray(tiny_model(paddle.to_tensor(ids))._value)
    weights = family.weights_of(tiny_model)
    for row, want in zip(ids, got):
        # float32 on both sides: what differs is the order of sums
        np.testing.assert_allclose(
            family.reference_logits(weights, TINY, row), want, rtol=2e-4,
            atol=2e-5)


def test_the_reference_imports_nothing_of_the_program(family):
    with open(family.__file__) as f:
        source = f.read()
    body = source.split("# -- the plain reference")[1].split(
        "# -- arithmetic")[0]
    assert "paddle_tpu" not in body and "import" in body


def test_build_model_refuses_a_part_left_out(family):
    for change in ({"diff_lambda": False}, {"diff_subnorm": False},
                   {"memory_layer": 2}, {"sliding_window": None}):
        with pytest.raises(ValueError, match="published model only"):
            family.model_config(dict(TINY, **change))


@pytest.fixture(scope="module")
def backlog(family):
    runner = bench.load_module("runners", "serve_backlog")
    return runner.run_backlog(
        family, TINY, tiny.mix("mathreason-backlog", **tiny.BACKLOG),
        tiny.SEED, 1.0, tiny.quiet, on_chip=False)


def test_backlog_runs_and_checks_itself(backlog, family):
    assert {k: ok for k, (ok, _) in backlog["checks"].items()} == {
        "reference": True, "no_compile_in_window": True,
        "queue_never_empty": True}
    assert backlog["attempted"] > 0 and backlog["failed"] == 0
    counters = backlog["observations"]["counters"]
    assert counters["decode_compiles"] == 1
    assert counters["ssm"]["layers"] == 3
    assert counters["state"]["slot_bytes"] == family.state_slot_bytes(TINY)
    window = counters["window"]
    assert window["layers"] == 2 and window["window"] == 8
    assert 0 < window["held_bytes"] <= 4 * window["slot_bytes"]
    assert counters["yoco"]["cross_rows_per_prefill"] == 1.0
    assert counters["yoco"]["prompt_rows_per_prefill"] > 1
    assert counters["moe"] is None and counters["latent"] is None
    json.dumps(counters)        # what a result line can carry


def test_the_cells_readers(backlog, family):
    manifest = bench.load_json(os.path.join(bench.ROOT, "BENCHMARK.json"))
    obs = backlog["observations"]
    steps = obs["steps"][-5:]
    trace = {"chips": 1, "window_s": 1.0, "busy_s": 0.5, "op_seconds": {},
             "op_calls": {}, "device_ops": [], "idle_gaps": []}
    logged = []
    mix = bench.load_json(os.path.join(bench.HERE, "traffic",
                                       "mathreason-backlog.json"))
    full = dict(obs, traced_steps=steps, trace=trace, config=TINY,
                traffic=mix, family=family, chips=1,
                peaks=peaks.peaks_for("TPU v5 lite"), log=logged.append,
                end_to_end=backlog["end_to_end"])
    values = bench.read_layer_metrics(manifest, CELL, full)
    # no trace file on the CPU: the two device readers say so and give
    # nothing; the counter reads the skip
    assert values["yoco.cross_rows_per_prefill"] == 1.0
    assert "diff_decode_roofline" not in values
    assert "selective_scan_roofline" not in values
    assert any(line.startswith("diff_decode_roofline: nothing to read")
               for line in logged)
    # a program without the counters (the parent): nothing, no raise
    bare = dict(full, counters={}, family=None)
    for name in ("yoco.cross_rows_per_prefill", "diff_decode_roofline",
                 "selective_scan_roofline"):
        assert bench.load_module("layer_metrics", name).read(bare) is None
