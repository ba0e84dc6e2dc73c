"""The five layer metrics that read the engine's own account
(``Engine.stats()``: ``host_ms``, ``prefill_ms``, ``itl_ms``) and the
idle that no ``serving.*`` span explains: each reader on hand-built
observations, then on the tiny backlog run. A program that keeps no such
account (the parent of the PR that added them) gives every counter's
reader nothing to read, and none raises."""
import os

import pytest

import run as bench
import tiny

CELL = "mistral7b-chat-backlog"
HOST_MS = {"schedule": 0.25, "upload": 0.5, "dispatch": 1.0,
           "readback": 38.0, "accept": 0.75}
COUNTERS = {"host_ms": HOST_MS, "recent_steps": 300, "prefill_ms": 31.0,
            "itl_ms": {"p50": 41.0, "p95": 120.0}}
COUNTER_METRICS = ("serve.host_ms", "serve.prefill_engine_ms",
                   "scheduler.schedule_ms", "serve.itl_p95_engine_ms")
UNATTRIBUTED = "device_idle.serve_unattributed"


def read(name, **obs):
    obs.setdefault("log", tiny.quiet)
    return bench.load_module("layer_metrics", name).read(obs)


def trace(idle_gaps, window_s=2.0, busy_s=1.5):
    return {"window_s": window_s, "busy_s": busy_s, "chips": 1,
            "op_seconds": {}, "op_calls": {}, "device_ops": [],
            "idle_gaps": idle_gaps}


@pytest.mark.parametrize("name,want", [
    ("serve.host_ms", 0.25 + 0.5 + 1.0 + 0.75),
    ("serve.prefill_engine_ms", 31.0),
    ("scheduler.schedule_ms", 0.25),
    ("serve.itl_p95_engine_ms", 120.0)])
def test_counter_readers_read_the_engines_account(name, want):
    said = []
    steps = [{"wall_s": 0.0405, "prefills": 0},
             {"wall_s": 0.0415, "prefills": 0},
             {"wall_s": 0.110, "prefills": 2}]
    assert read(name, counters=COUNTERS, steps=steps, log=said.append,
                end_to_end={"itl_p95_ms": 123.9}) == pytest.approx(want)
    # the two that close the account say what they are to be held against
    if name == "serve.host_ms":
        assert "40.5000 ms" in said[0] and "41.0000" in said[0]
    if name == "serve.itl_p95_engine_ms":
        assert "123.9000" in said[0]
    # without the benchmark's own readings they still read
    assert read(name, counters=COUNTERS) == pytest.approx(want)


@pytest.mark.parametrize("name", COUNTER_METRICS)
@pytest.mark.parametrize("counters", [
    {},                                                 # the parent's
    {"host_ms": None, "prefill_ms": None, "itl_ms": None,
     "recent_steps": 0}])                               # empty rings
def test_counter_readers_find_nothing_without_the_account(name, counters):
    assert read(name, counters=dict(counters, decode_steps=7)) is None
    assert read(name) is None


@pytest.mark.parametrize("idle_gaps,want", [
    ([], 100.0),
    ([["serving.upload", 0.2], ["serving.dispatch", 0.2],
      ["serving.accept", 0.1]], 0.0),
    ([["bench.engine_step", 0.3], ["serving.prefill", 0.1],
      ["bench.between_steps", 0.05], ["(no span open)", 0.05]], 80.0),
    # rows the reduction lumped together count as unattributed, whatever
    # they held
    ([["serving.schedule", 0.25], ["(all others)", 0.25]], 50.0)])
def test_unattributed_idle(idle_gaps, want):
    assert read(UNATTRIBUTED, trace=trace(idle_gaps)) == pytest.approx(want)


def test_unattributed_idle_without_a_trace_or_without_idle():
    assert read(UNATTRIBUTED) is None
    assert read(UNATTRIBUTED, trace=None, counters=COUNTERS) is None
    assert read(UNATTRIBUTED, trace=trace([], 2.0, 2.0)) == 0.0


@pytest.fixture(scope="module")
def backlog():
    runner = bench.load_module("runners", "serve_backlog")
    return runner.run_backlog(
        bench.load_module("families", "mistral"), tiny.CONFIG,
        tiny.mix("chat-backlog", **tiny.BACKLOG), tiny.SEED, 1.0,
        tiny.quiet, on_chip=False)


def test_on_the_tiny_backlog_run(backlog):
    """CPU times: held to each other, never to a size."""
    manifest = bench.load_json(os.path.join(bench.ROOT, "BENCHMARK.json"))
    obs = dict(backlog["observations"], trace=trace([]), log=tiny.quiet,
               end_to_end=backlog["end_to_end"])
    counters = obs["counters"]
    assert counters["recent_steps"] > 0
    values = {}
    for metric in manifest["per_layer"][-5:]:
        assert metric["workloads"] == [CELL]
        values[metric["name"]] = bench.load_module(
            "layer_metrics", metric["name"]).read(obs)
    assert set(values) == set(COUNTER_METRICS) | {UNATTRIBUTED}
    assert values[UNATTRIBUTED] == 100.0
    host = counters["host_ms"]
    assert values["serve.host_ms"] == pytest.approx(
        sum(host.values()) - host["readback"])
    assert 0 <= values["scheduler.schedule_ms"] < values["serve.host_ms"]
    assert values["serve.prefill_engine_ms"] > 0
    assert values["serve.itl_p95_engine_ms"] >= counters["itl_ms"]["p50"] > 0
    # the engine's account of a decode step against the runner's stamps
    # around the same calls: inside the call, and most of it
    decode = bench.load_module("layer_metrics", "serve.decode_step_ms")
    wall_ms = decode.read(obs)
    assert 0.25 * wall_ms < sum(host.values()) < 2 * wall_ms
    # with no trace the four counters still read
    obs["trace"] = None
    assert bench.load_module("layer_metrics", UNATTRIBUTED).read(obs) is None
    assert all(bench.load_module("layer_metrics", name).read(obs) is not None
               for name in COUNTER_METRICS)
