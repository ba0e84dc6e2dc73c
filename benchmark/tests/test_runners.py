"""Both runners driven end to end on the CPU at a tiny size, by calling
their functions with the sizes as arguments (``run.py`` itself has no
CPU mode). What comes back is fed to every layer metric's reader with a
hand-built reduced trace. Times read here are CPU times and are asserted
on only for their shape, never for their size."""
import json
import math
import os

import pytest

import peaks
import run as bench
import tiny
import traffic_gen


@pytest.fixture(scope="module")
def manifest():
    return bench.load_json(os.path.join(bench.ROOT, "BENCHMARK.json"))


@pytest.fixture(scope="module")
def family():
    return bench.load_module("families", "mistral")


@pytest.fixture(scope="module")
def backlog(family):
    runner = bench.load_module("runners", "serve_backlog")
    return runner.run_backlog(
        family, tiny.CONFIG, tiny.mix("chat-backlog", **tiny.BACKLOG),
        tiny.SEED, 1.0, tiny.quiet, on_chip=False)


@pytest.fixture(scope="module")
def train(family):
    import jax

    runner = bench.load_module("runners", "train_steps")
    return runner.run_steps(
        family, tiny.CONFIG, tiny.mix("pretrain-4k", **tiny.TRAIN),
        tiny.SEED, 1.0, tiny.quiet, jax.devices()[:1], on_chip=False)


def test_backlog_runs_and_checks_itself(backlog):
    assert {k: ok for k, (ok, _) in backlog["checks"].items()} == {
        "reference": True, "no_compile_in_window": True,
        "queue_never_empty": True}
    assert backlog["window_s"] >= 1.0
    assert backlog["attempted"] > 0 and backlog["failed"] == 0
    assert set(backlog["end_to_end"]) == {"serve_out_tok_s", "itl_p95_ms"}
    assert all(v > 0 for v in backlog["end_to_end"].values())
    obs = backlog["observations"]
    assert obs["counters"]["decode_compiles"] == 1
    assert len(obs["steps"]) > 10 and obs["traced_steps"] == []
    assert backlog["trace"] is None
    step = obs["steps"][0]
    assert 0 < step["active_slots"] <= obs["max_slots"]
    assert 0 <= step["free_blocks"] <= obs["usable_blocks"]
    assert step["context_tokens"] >= step["rows"] > 0


def test_train_runs_and_checks_itself(train):
    assert {k: ok for k, (ok, _) in train["checks"].items()} == {
        "reference": True, "loss_falls": True,
        "no_compile_in_window": True, "losses_finite": True}
    assert train["attempted"] == len(train["observations"]["step_s"]) > 3
    assert train["failed"] == 0
    assert train["end_to_end"]["train_tok_s_chip"] > 0
    assert train["observations"]["tokens_per_step"] == 64


def test_every_seed_gets_the_same_sizes_in_another_order():
    mix = tiny.mix("chat-backlog")
    pool = traffic_gen.length_pool(mix)
    assert len(pool) == mix["pool"]
    prompts = sorted(p for p, _ in pool)
    assert prompts[0] >= 64 and prompts[-1] == 2048
    assert 480 <= prompts[len(prompts) // 2] <= 560
    outputs = sorted(o for _, o in pool)
    assert outputs[0] >= 16 and outputs[-1] == 512
    a = traffic_gen.RequestStream(mix, 32768, tiny.SEED)
    b = traffic_gen.RequestStream(mix, 32768, tiny.SEED)
    c = traffic_gen.RequestStream(mix, 32768, 7)
    first_a = [a.next() for _ in range(len(pool))]
    first_b = [b.next() for _ in range(len(pool))]
    first_c = [c.next() for _ in range(len(pool))]
    assert first_a == first_b
    sizes = lambda reqs: [(len(p), o) for p, o in reqs]     # noqa: E731
    assert sizes(first_a) != sizes(first_c)
    assert sorted(sizes(first_a)) == sorted(sizes(first_c)) == sorted(pool)
    assert all(0 <= t < 32768 for p, _ in first_a for t in p)


def fake_trace(op_seconds, op_calls):
    return {"window_s": 2.0, "busy_s": 1.5, "chips": 1,
            "op_seconds": op_seconds, "op_calls": op_calls,
            "device_ops": [], "idle_gaps": []}


def layer_values(manifest, cell, result, family, traffic, trace, extra):
    obs = dict(result["observations"], trace=trace, config=tiny.CONFIG,
               traffic=traffic, family=family, chips=1,
               peaks=peaks.peaks_for("TPU v5 lite"), log=tiny.quiet,
               end_to_end=result["end_to_end"], **extra)
    return bench.read_layer_metrics(manifest, cell, obs)


def test_serving_layer_metrics_read_what_the_runner_saw(manifest, backlog,
                                                        family):
    steps = backlog["observations"]["steps"]
    layers = tiny.CONFIG["num_hidden_layers"]
    trace = fake_trace({"paged_decode": 0.5},
                       {"paged_decode": len(steps[-5:]) * layers})
    values = layer_values(manifest, "mistral7b-chat-backlog", backlog,
                          family, tiny.BACKLOG, trace,
                          {"traced_steps": steps[-5:]})
    named = {m["name"] for m in manifest["per_layer"]
             if "mistral7b-chat-backlog" in m["workloads"]}
    assert set(values) == named
    assert 0 < values["serve.slot_occupancy"] <= 100
    assert 0 < values["kv.page_occupancy"] < 100
    assert values["device_idle.serve"] == pytest.approx(25.0)
    assert values["serve.decode_step_ms"] > 0
    least = sum(
        peaks.least_seconds(*family.paged_decode_cost(
            tiny.CONFIG, s["context_tokens"], s["rows"]),
            peaks.peaks_for("TPU v5 lite"))[0] * layers
        for s in steps[-5:])
    assert values["paged_decode_roofline"] == pytest.approx(
        100 * least / 0.5)
    # no trace: the trace's metrics are left out, the others stay
    values = layer_values(manifest, "mistral7b-chat-backlog", backlog,
                          family, tiny.BACKLOG, None, {})
    assert "paged_decode_roofline" not in values
    assert "device_idle.serve" not in values
    assert "serve.decode_step_ms" in values


def test_training_layer_metrics_read_what_the_runner_saw(manifest, train,
                                                         family):
    calls = {"flash_fwd": 4, "flash_dq": 2, "flash_dkv": 2}
    trace = fake_trace({k: 0.01 for k in calls}, calls)
    values = layer_values(manifest, "mistral7b-pretrain-4k", train, family,
                          tiny.TRAIN, trace, {})
    named = {m["name"] for m in manifest["per_layer"]
             if "mistral7b-pretrain-4k" in m["workloads"]}
    assert set(values) == named
    rate = train["end_to_end"]["train_tok_s_chip"]
    assert values["train.mfu"] == pytest.approx(
        100 * family.train_flops_per_token(tiny.CONFIG, 32) * rate / 197e12)
    assert values["train.step_ms"] > 0
    pk = peaks.peaks_for("TPU v5 lite")
    least = sum(n * peaks.least_seconds(
        *family.flash_cost(tiny.CONFIG, k, 2, 32), pk)[0]
        for k, n in calls.items())
    assert values["flash_roofline"] == pytest.approx(100 * least / 0.03)


def test_result_lines_keep_to_the_contract(manifest, backlog):
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
              "memory_peak_bytes": 1}
    line = bench.result_line(manifest, "mistral7b-chat-backlog", False,
                             backlog, 12.5, {}, device, None)
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert set(line["metrics"]) == {"serve_out_tok_s", "itl_p95_ms",
                                    "setup_s"}
    assert line["metrics"]["setup_s"] == {"value": 12.5, "unit": "s"}
    assert line["correct"] is True
    json.dumps(line)
    reduced = {"busy_s": 1.5, "window_s": 2.0,
               "device_ops": [["paged_decode", 1.0]],
               "idle_gaps": [["bench.engine_step", 0.5]]}
    line = bench.result_line(manifest, "mistral7b-chat-backlog", True,
                             backlog, 12.5, {"serve.slot_occupancy": 99.0,
                                             "train.mfu": 1.0},
                             device, reduced)
    assert set(line["metrics"]) == {"serve.slot_occupancy"}
    assert line["device"]["busy_s"] == 1.5
    assert line["breakdown"]["device_ops"] == [["paged_decode", 1.0]]
    failing = dict(backlog, checks={"reference": (False, "off")})
    assert bench.result_line(manifest, "mistral7b-chat-backlog", False,
                             failing, 1.0, {}, device, None
                             )["correct"] is False


def test_no_accelerator_no_result(manifest):
    """Here JAX is held to the CPU: the command exits non-zero and prints
    no result line."""
    import subprocess
    import sys

    done = subprocess.run(
        [sys.executable] + manifest["command"][1:]
        + ["--workload", "mistral7b-chat-backlog", "--seed", "3000000019",
           "--seconds", "1", "--trace", "0"],
        cwd=bench.ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert done.returncode != 0
    assert "{" not in done.stdout
    assert "chip only" in done.stderr


def test_percentile_and_buckets():
    runner = bench.load_module("runners", "serve_backlog")
    assert runner.percentile(range(1, 101), 0.95) == 95
    assert runner.percentile([5.0], 0.95) == 5.0
    assert runner.prefill_buckets(74, 2048) == [128, 256, 512, 1024, 2048]
    assert runner.prefill_buckets(64, 2048)[0] == 64
    assert math.isclose(peaks.least_seconds(197e12, 0, peaks.PEAKS[
        "TPU v5 lite"])[0], 1.0)
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")
