"""The engine's account of its own time: the ``serving.*`` spans it
writes into the profiler's trace, and the stamps it keeps at the same
boundaries for ``Engine.stats()``.

One profiler session for the whole file (``traced``): a warmed split
engine (``serving.prefill`` + ``serving.decode_step``) and a warmed
chunked-prefill engine (``serving.mixed_step``) each serve a few
requests under a span of the test's own, and the trace is read back with
``jax.profiler.ProfileData``. Times are CPU times: asserted on for their
order and their shares, never for their size.
"""
import glob
import os
import statistics
import time

import numpy as np
import pytest

import jax

import paddle_tpu as paddle
from paddle_tpu import serving
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving import metrics as smetrics

STEP_SPANS = {"serving.prefill", "serving.decode_step",
              "serving.mixed_step"}
CHILD_SPANS = {"serving.upload", "serving.dispatch", "serving.readback"}
ALL_SPANS = STEP_SPANS | CHILD_SPANS | {"serving.schedule",
                                        "serving.accept"}


@pytest.fixture(scope="module")
def llama():
    paddle.seed(0)
    cfg = LlamaConfig(vocab_size=64, hidden_size=32, intermediate_size=64,
                      num_hidden_layers=2, num_attention_heads=4,
                      max_position_embeddings=128, use_parallel=False)
    return LlamaForCausalLM(cfg)


def _engine(model, chunked=False, **kw):
    paddle.set_flags({"FLAGS_serving_chunked_prefill": chunked})
    try:
        return serving.Engine(model, **dict(
            dict(max_slots=4, num_blocks=64, block_size=4), **kw))
    finally:
        paddle.set_flags({"FLAGS_serving_chunked_prefill": False})


def _submit(eng, n, seed, new_tokens=6):
    rng = np.random.RandomState(seed)
    return [eng.add_request(rng.randint(0, 64, (int(rng.randint(3, 12)),))
                            .tolist(), max_new_tokens=new_tokens)
            for _ in range(n)]


@pytest.fixture(scope="module")
def traced(llama, tmp_path_factory):
    """{"split" | "chunked": [(name, start_ns, end_ns, stats)] of the
    ``serving.*`` spans of that engine's traced run, in start order}."""
    engines = {"split": _engine(llama), "chunked": _engine(llama, True)}
    for eng in engines.values():        # every shape compiled beforehand
        _submit(eng, 6, seed=1)
        eng.run()
    trace_dir = str(tmp_path_factory.mktemp("serving_spans"))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        for kind, eng in engines.items():
            _submit(eng, 6, seed=2)
            with jax.profiler.TraceAnnotation("test." + kind):
                eng.run()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    events = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(("serving.", "test.")):
                    events.append((ev.name, ev.start_ns,
                                   ev.start_ns + ev.duration_ns,
                                   dict(ev.stats)))
    events.sort(key=lambda e: (e[1], -e[2]))
    out = {}
    for kind in engines:
        (_, lo, hi, _), = [e for e in events if e[0] == "test." + kind]
        out[kind] = [e for e in events if e[0].startswith("serving.")
                     and lo <= e[1] and e[2] <= hi]
    return out


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


@pytest.mark.parametrize("kind,steps", [
    ("split", {"serving.prefill", "serving.decode_step"}),
    ("chunked", {"serving.mixed_step"})])
def test_only_the_tables_names_appear(traced, kind, steps):
    names = {e[0] for e in traced[kind]}
    assert names <= ALL_SPANS
    assert names == (ALL_SPANS - STEP_SPANS) | steps


def _program(event):
    """What names the program of a span: a decode step's number or a
    prefill's request."""
    stats = event[3]
    return ("step", stats["step"]) if "step" in stats \
        else ("request", stats["request"])


@pytest.mark.parametrize("kind", ["split", "chunked"])
def test_children_lie_inside_a_step_that_ends_after_its_readback(
        traced, kind):
    """The mixed step holds its upload, dispatch and readback. A split
    engine runs a step ahead: a prefill or decode step holds its upload
    and dispatch, and its tokens are read back once, after it ended,
    under a ``serving.readback`` that names the same program."""
    spans = traced[kind]
    parents = [e for e in spans if e[0] in STEP_SPANS]
    children = [e for e in spans if e[0] in CHILD_SPANS]
    inner = ["serving.upload", "serving.dispatch"]
    if kind == "chunked":
        inner.append("serving.readback")
    nested = [c for c in children if any(_inside(c, p) for p in parents)]
    assert parents and len(nested) == len(inner) * len(parents)
    for child in nested:
        assert sum(_inside(child, p) for p in parents) == 1, child
    for parent in parents:
        mine = [c for c in children if _inside(c, parent)]
        assert [c[0] for c in mine] == inner
        assert parent[2] >= mine[-1][2]
    readbacks = [c for c in children if c not in nested]
    if kind == "chunked":
        assert not readbacks
    else:
        assert {c[0] for c in readbacks} == {"serving.readback"}
        ended = {_program(p): p[2] for p in parents}
        assert sorted(map(_program, readbacks)) == sorted(ended)
        for c in readbacks:
            assert c[1] >= ended[_program(c)], c
    # scheduling and accepting are a step's siblings, not its children
    for e in spans:
        if e[0] in ("serving.schedule", "serving.accept"):
            assert not any(_inside(e, p) for p in parents), e


def test_metadata_names_the_request_and_the_step(traced):
    prefills = [e for e in traced["split"] if e[0] == "serving.prefill"]
    assert len(prefills) == 6
    assert len({e[3]["request"] for e in prefills}) == 6
    assert all(3 <= e[3]["tokens"] <= e[3]["bucket"] for e in prefills)
    steps = [e[3] for e in traced["split"]
             if e[0] == "serving.decode_step"]
    assert [s["step"] for s in steps] == list(
        range(steps[0]["step"], steps[0]["step"] + len(steps)))
    assert all(1 <= s["rows"] <= 4 for s in steps)
    assert all("step" in e[3] and "rows" in e[3]
               for e in traced["chunked"] if e[0] == "serving.mixed_step")


@pytest.mark.parametrize("kind", ["split", "chunked"])
def test_spans_cover_the_engines_time(traced, kind):
    spans = traced[kind]
    lo, hi = spans[0][1], max(e[2] for e in spans)
    covered, upto = 0, lo
    for _, start, end, _ in spans:      # in start order
        if end > upto:
            covered += end - max(start, upto)
            upto = end
    assert covered >= 0.8 * (hi - lo)


def _timed_run(eng):
    """Run the engine dry; -> the walls of its decode-only steps: the
    calls that read back a decode step and no prefill."""
    walls = []
    while eng.has_work():
        before = eng.metrics.prefill_runs, eng.metrics.decode_steps
        t0 = time.monotonic()
        eng.step()
        wall = time.monotonic() - t0
        if eng.metrics.prefill_runs == before[0] \
                and eng.metrics.decode_steps > before[1]:
            walls.append(wall)
    return walls


def test_stats_account_for_a_decode_step(llama):
    eng = _engine(llama)
    empty = eng.stats()
    assert empty["host_ms"] is None and empty["prefill_ms"] is None
    assert empty["itl_ms"] is None and empty["recent_steps"] == 0
    _submit(eng, 20, seed=3, new_tokens=12)
    walls = _timed_run(eng)
    stats = eng.stats()
    assert stats["requests_finished"] == 20
    host = stats["host_ms"]
    assert tuple(host) == smetrics.HOST_PHASES == (
        "schedule", "upload", "dispatch", "readback", "accept")
    assert all(v >= 0 for v in host.values())
    assert stats["recent_steps"] == len(walls) > 20
    # the phases are disjoint parts of the call the test timed: step by
    # step they add up to no more than its wall, and to most of it
    rows = [r for r in eng.metrics.steps if r[-1] and not r[-2]]
    assert len(rows) == len(walls)
    shares = [sum(r[:5]) / wall for r, wall in zip(rows, walls)]
    assert max(shares) <= 1.0
    assert statistics.median(shares) >= 0.5
    for i, phase in enumerate(smetrics.HOST_PHASES):
        assert host[phase] == pytest.approx(
            1e3 * statistics.median(r[i] for r in rows))
    assert stats["prefill_ms"] > 0
    assert len(eng.metrics.prefills) == stats["prefill_runs"] == 20
    assert all(3 <= tokens <= bucket
               for _, tokens, bucket in eng.metrics.prefills)
    itl = stats["itl_ms"]
    assert itl["p95"] >= itl["p50"] > 0
    # every token but a request's first closes a gap; a step leaves one
    # row of (gap, tokens that had it)
    rows = eng.metrics.gap_rows()
    assert sum(n for _, n in rows) == stats["output_tokens"] - 20
    assert len(eng.metrics.token_gaps) <= len(eng.metrics.steps)
    # a prefill's token and the same step's first decode token are two
    # stamps: no gap is zero
    assert min(gap for gap, _ in rows) > 0


def test_chunked_prefill_fills_the_same_keys(llama):
    eng = _engine(llama, chunked=True)
    _submit(eng, 8, seed=4, new_tokens=10)
    eng.run()
    stats = eng.stats()
    assert stats["prefill_chunks"] > 0
    assert tuple(stats["host_ms"]) == smetrics.HOST_PHASES
    assert all(v >= 0 for v in stats["host_ms"].values())
    assert stats["host_ms"]["readback"] > 0
    assert stats["recent_steps"] > 0
    assert stats["itl_ms"]["p95"] >= stats["itl_ms"]["p50"] > 0
    assert sum(n for _, n in eng.metrics.gap_rows()) \
        == stats["output_tokens"] - 8
    # no prefill of its own: the prompt rides the mixed step
    assert stats["prefill_ms"] is None


def test_rings_stay_at_their_caps(llama):
    eng = _engine(llama, max_slots=1)
    _submit(eng, 14, seed=5, new_tokens=40)
    eng.run()
    assert eng.metrics.decode_steps > smetrics.STEP_RING
    assert len(eng.metrics.steps) == smetrics.STEP_RING
    assert eng.stats()["recent_steps"] <= smetrics.STEP_RING
    # an idle engine's polls leave no rows
    last = eng.metrics.steps[-1]
    for _ in range(3):
        eng.step()
    assert eng.metrics.steps[-1] is last
    # the other two rings, fed as the engine feeds them
    em = smetrics.EngineMetrics(max_slots=1)
    for i in range(smetrics.GAP_RING + 100):
        em.on_step_begin()
        em.on_output_token(1e-3 * (i % 7 + 1))
        em.on_step_end()
    for i in range(smetrics.PREFILL_RING + 100):
        em.on_prefill_done(0.01, 8, 8)
    assert len(em.token_gaps) == smetrics.GAP_RING
    assert len(em.prefills) == smetrics.PREFILL_RING
    assert em.output_tokens == smetrics.GAP_RING + 100
    assert em.to_dict()["itl_ms"] == {"p50": 4.0, "p95": 7.0}


def _fed(slots, steps, prefill_every, step_s=0.025, prefill_s=0.1):
    """An EngineMetrics fed as the engine feeds it: ``steps`` decode
    steps of ``slots`` tokens each, every ``prefill_every``-th one
    behind a prefill that lengthens every slot's gap."""
    em = smetrics.EngineMetrics(max_slots=slots)
    for i in range(steps):
        em.on_step_begin()
        gap = step_s + (prefill_s if i % prefill_every == 0 else 0.0)
        for _ in range(slots):
            em.on_output_token(gap)
        em.on_step_end()
    return em


@pytest.mark.parametrize("slots", [64, 256])
def test_the_gap_ring_is_bounded_by_steps_whatever_the_slots(slots):
    """One row a step: 256 slots fill the ring no sooner than 64, so
    ``itl_ms`` covers the same stretch of time at both (a ring of
    16,384 tokens was 64 steps at 256 slots, and whether one long
    prefill fell inside it decided the p95)."""
    em = _fed(slots, smetrics.GAP_RING + 50, prefill_every=12)
    assert len(em.token_gaps) == smetrics.GAP_RING
    assert all(row == ((pytest.approx(row[0][0]), slots),)
               for row in em.token_gaps)
    assert sum(n for _, n in em.gap_rows()) == slots * smetrics.GAP_RING
    # a prefill every 12th step is a twelfth of the tokens: inside the
    # last twentieth, so the p95 is the long gap, the median the short
    itl = em.to_dict()["itl_ms"]
    assert itl["p50"] == pytest.approx(25.0)
    assert itl["p95"] == pytest.approx(125.0)


def test_gap_percentiles_are_weighted_by_tokens():
    """A step's gaps are merged: (gap, tokens that had it). 256 tokens
    at 20 ms and one step with 9 tokens at 100 ms, 1 at 300 ms."""
    em = smetrics.EngineMetrics(max_slots=256)
    em.on_step_begin()
    for _ in range(256):
        em.on_output_token(0.020)
    em.on_step_end()
    em.on_step_begin()
    for gap in [0.100] * 9 + [0.300]:
        em.on_output_token(gap)
    em.on_step_end()
    assert list(em.token_gaps) == [((0.020, 256),),
                                   ((0.100, 9), (0.300, 1))]
    itl = em.to_dict()["itl_ms"]
    # 266 tokens: rank 133 is a 20 ms gap, rank ceil(0.95 * 266) = 253
    # too; unweighted over the three distinct gaps it would read 300
    assert itl == {"p50": pytest.approx(20.0), "p95": pytest.approx(20.0)}
    assert smetrics._weighted_percentile(
        sorted(em.gap_rows()), 0.97) == pytest.approx(0.100)
    assert smetrics._weighted_percentile(
        sorted(em.gap_rows()), 1.0) == pytest.approx(0.300)


def test_a_failed_steps_gaps_are_kept_by_the_next_step():
    em = smetrics.EngineMetrics(max_slots=2)
    em.on_step_begin()
    em.on_output_token(0.030)       # the step raised before its end
    em.on_step_begin()
    em.on_output_token(0.010)
    em.on_step_end()
    assert list(em.token_gaps) == [((0.030, 1),), ((0.010, 1),)]


def test_a_dense_model_has_no_moe_and_no_state_block(llama):
    eng = _engine(llama)
    _submit(eng, 3, seed=6)
    eng.run()
    stats = eng.stats()
    assert stats["moe"] is None and stats["state"] is None
    assert len(eng.metrics.moe_calls) == 0


@pytest.fixture(scope="module")
def qwen():
    from paddle_tpu.models.qwen3_next import (Qwen3NextConfig,
                                              Qwen3NextForCausalLM)

    paddle.seed(0)
    return Qwen3NextForCausalLM(Qwen3NextConfig.tiny(vocab_size=64))


def test_stats_moe_block_counts_what_the_steps_routed(qwen):
    """Every compiled step hands back, a layer at a time, the pairs
    routed to the experts held here, the experts touched and the largest
    load; ``stats()["moe"]`` reduces the recent decode steps."""
    eng = _engine(qwen, max_slots=4, max_model_len=64)
    assert eng.stats()["moe"] is None               # empty ring
    _submit(eng, 6, seed=7, new_tokens=8)
    eng.run()
    stats = eng.stats()
    moe = stats["moe"]
    assert moe["layers"] == 4 and moe["experts_held"] == 8
    assert moe["recent_steps"] == stats["decode_steps"]
    # 4 slots x top-4 of 16 experts: at most 16 pairs a layer, on at most
    # 8 experts held here; the largest load is at least the mean
    assert 0 < moe["pairs"] <= 16
    assert 0 < moe["experts_touched"] <= min(8, moe["pairs"])
    assert 1 <= moe["load_max"] <= 4
    assert moe["load_max_over_mean"] >= 1.0
    # one row a program, prefills included, newest last
    calls = moe["calls"]
    assert len(calls) == stats["decode_steps"] + stats["prefill_runs"]
    prefills = [c for c in calls if not c[2]]
    assert len(prefills) == stats["prefill_runs"] == 6
    for rows, rows_run, _, pairs, touched in prefills:
        assert 3 <= rows <= rows_run and rows_run in (8, 16)
        assert len(pairs) == len(touched) == 4
        assert all(0 <= t <= 8 and t <= p <= rows_run * 4
                   for p, t in zip(pairs, touched))
    for rows, rows_run, decode, pairs, _ in calls:
        if decode:
            assert 1 <= rows <= rows_run == 4 and max(pairs) <= 16
    # five fields a program, as the kernel's roofline reader unpacks
    # them; the fourth counter is reduced apart. This tiny model's
    # prefills are one block at most, so each layer laid out every pair
    # of the bucket: rows_run x top-4 over the pairs routed here
    assert all(len(c) == 5 for c in calls)
    want = np.mean([rows_run * 4 / max(p, 1)
                    for _, rows_run, _, pairs, _ in prefills
                    for p in pairs])
    assert moe["prefill_rows_over_pairs"] == pytest.approx(want)
    assert moe["prefill_rows_over_pairs"] >= 1.0


def test_stats_state_block_sizes_the_slot_state(qwen):
    eng = _engine(qwen, max_slots=4, max_model_len=64)
    state = eng.stats()["state"]
    # per Gated DeltaNet layer: 4 heads x 8 x 8 float32 and a 3-row tail
    # of 2 * 2 * 8 + 4 * 8 = 64 channels in float32
    assert state == {"slots": 4, "layers": 3,
                     "slot_bytes": 3 * (4 * 8 * 8 * 4 + 3 * 64 * 4),
                     "pool_bytes": 4 * 3 * (4 * 8 * 8 * 4 + 3 * 64 * 4)}


def test_moe_ring_stays_at_its_cap():
    em = smetrics.EngineMetrics(max_slots=2)
    em.moe_experts_held = 4
    for i in range(smetrics.MOE_RING + 10):
        em.on_moe_call(np.asarray([2, 2, 1, 8, 2, 1, 2, 8]), 2, 2,
                       decode=True)
    assert len(em.moe_calls) == smetrics.MOE_RING
    moe = em.to_dict()["moe"]
    assert moe["layers"] == 2 and moe["pairs"] == 2.0
    assert moe["experts_touched"] == 1.5 and moe["load_max"] == 1.5
    # (1 / (2 / 4) + 2 / (2 / 4)) / 2
    assert moe["load_max_over_mean"] == 3.0
    # the ring holds decode steps alone: nothing to say of a prefill
    assert moe["prefill_rows_over_pairs"] is None


def test_prefill_rows_over_pairs_reads_the_prefills_alone():
    """The mean, over the ring's prefills and their layers, of the rows
    handed to the grouped matmuls over the pairs routed here; a decode
    step's fourth counter is not in it, and ``calls`` keeps five
    fields."""
    em = smetrics.EngineMetrics(max_slots=2)
    em.moe_experts_held = 4
    em.on_moe_call(np.asarray([100, 4, 40, 128, 50, 3, 30, 256]), 60, 64,
                   decode=False)
    em.on_moe_call(np.asarray([0, 0, 0, 0, 64, 4, 20, 64]), 16, 16,
                   decode=False)
    assert em.to_dict()["moe"] is None          # no decode step yet
    em.on_moe_call(np.asarray([2, 2, 1, 8, 2, 1, 2, 8]), 2, 2, decode=True)
    moe = em.to_dict()["moe"]
    # a layer that nothing was routed to laid nothing out: 0 / 1
    assert moe["prefill_rows_over_pairs"] == pytest.approx(
        (128 / 100 + 256 / 50 + 0.0 + 64 / 64) / 4)
    assert moe["calls"] == [[60, 64, 0, [100, 50], [4, 3]],
                            [16, 16, 0, [0, 64], [0, 4]],
                            [2, 2, 1, [2, 2], [2, 1]]]


def test_a_span_without_a_session_touches_no_native_code(monkeypatch):
    from paddle_tpu.core import native

    assert smetrics.span is jax.profiler.TraceAnnotation
    monkeypatch.setattr(
        native, "get_lib",
        lambda: pytest.fail("a span touched the native library"))
    with smetrics.span("serving.prefill", request=1, tokens=5, bucket=8):
        with smetrics.span("serving.upload"):
            pass
