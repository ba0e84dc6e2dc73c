"""The trace reduction on hand-built traces: plain lists in the shape
``trace_reduce.load`` returns."""
import pytest

import trace_reduce as tr

KERNEL_TEXT = ('%%paged_decode.%d = bf16[32,32,128]{2,1,0} custom-call('
               's32[32,160]{1,0} %%copy-done.5), custom_call_target='
               '"tpu_custom_call", operand_layout_constraints={}')


def test_short_names():
    assert tr.short_name("%fusion.77 = f32[8]{0} fusion(f32[8]{0} %p), "
                         "kind=kLoop") == "fusion.77"
    assert tr.short_name("fusion.77") == "fusion.77"
    assert tr.short_name(KERNEL_TEXT % 12) == "paged_decode.12"
    assert tr.strip_suffix("paged_decode.12") == "paged_decode"
    assert tr.strip_suffix("flash_fwd") == "flash_fwd"


def test_kernel_names_merge_suffixes_and_calls():
    # two kernels of one name with different suffixes go under one name;
    # an instruction the compiled HLO maps to a kernel does too, even
    # when the trace carries only its short name
    assert tr.op_name(KERNEL_TEXT % 12) == ("paged_decode", True)
    assert tr.op_name(KERNEL_TEXT % 3) == ("paged_decode", True)
    assert tr.op_name("fusion.77") == ("fusion.77", False)
    kernels = {"custom-call.9": "flash_dq"}
    assert tr.op_name("custom-call.9", kernels) == ("flash_dq", True)
    assert tr.op_name("flash_dq.2", kernels) == ("flash_dq", True)


def test_kernel_instructions_from_hlo():
    hlo = "\n".join([
        '  %flash_fwd.3 = bf16[2]{0} custom-call(bf16[2]{0} %a), '
        'custom_call_target="tpu_custom_call", metadata={op_name='
        '"jit(step)/checkpoint/flash_fwd/pallas_call"}',
        '  %custom-call.9 = bf16[2]{0} custom-call(bf16[2]{0} %a), '
        'custom_call_target="tpu_custom_call", metadata={op_name='
        '"jit(step)/transpose(jvp(flash_dq))/pallas_call"}',
        '  %flash_fwd.4 = bf16[2]{0} custom-call(bf16[2]{0} %a), '
        'custom_call_target="tpu_custom_call"',
        '  %fusion.1 = bf16[2]{0} fusion(bf16[2]{0} %a), kind=kLoop',
    ])
    assert tr.kernel_instructions(hlo) == {
        "flash_fwd.3": "flash_fwd", "custom-call.9": "flash_dq",
        "flash_fwd.4": "flash_fwd"}
    assert tr.kernel_counts(hlo) == {"flash_fwd": 2, "flash_dq": 1}


def hand_built():
    """One chip, a window of 10 s made of two host spans.

        host   bench.step      [0, 6)    with serving.decode_step [1, 2)
               bench.between   [6, 10)
        device fusion.1        [1, 3)
               paged_decode.12 [2, 4)    overlaps fusion.1 by 1 s
               paged_decode.3  [5, 6)
               while.1         [7, 9)    holds fusion.2 [7.5, 8.5)
               fusion.9        [20, 21)  outside the window
    busy = [1, 4) + [5, 6) + [7, 9) = 6 s; idle 4 s: [0, 1) and [4, 5)
    under bench.step, [6, 7) and [9, 10) under bench.between."""
    return {
        "host": [("bench.step", 0.0, 6.0),
                 ("serving.decode_step", 1.0, 1.0),
                 ("bench.between", 6.0, 4.0)],
        "devices": {0: [
            ("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)", 1.0, 2.0),
            (KERNEL_TEXT % 12, 2.0, 2.0),
            (KERNEL_TEXT % 3, 5.0, 1.0),
            ("%while.1 = (s32[]) while((s32[]) %t)", 7.0, 2.0),
            ("%fusion.2 = f32[8]{0} fusion(f32[8]{0} %p)", 7.5, 1.0),
            ("%fusion.9 = f32[8]{0} fusion(f32[8]{0} %p)", 20.0, 1.0),
        ]},
    }


def test_busy_is_a_union_and_names_are_short():
    out = tr.reduce(hand_built(), ("bench.step", "bench.between"))
    assert out["window_s"] == pytest.approx(10.0)
    assert out["busy_s"] == pytest.approx(6.0)
    assert out["chips"] == 1
    assert out["op_seconds"]["paged_decode"] == pytest.approx(3.0)
    assert out["op_calls"]["paged_decode"] == 2
    # a while's time is its own, less the body's
    assert out["op_seconds"]["while.1"] == pytest.approx(1.0)
    assert out["op_seconds"]["fusion.2"] == pytest.approx(1.0)
    assert "fusion.9" not in out["op_seconds"]
    names = [name for name, _ in out["device_ops"]]
    assert names[0] == "paged_decode"
    assert all(len(name) < 32 and " " not in name for name in names)
    # more names than places: the last place is the sum of the rest
    short = tr.reduce(hand_built(), ("bench.step", "bench.between"), top=3)
    assert [n for n, _ in short["device_ops"]] == [
        "paged_decode", "fusion.1", tr.OTHERS]
    assert sum(s for _, s in short["device_ops"]) == pytest.approx(
        sum(out["op_seconds"].values()))


def test_idle_gaps_go_to_the_innermost_open_span():
    out = tr.reduce(hand_built(), ("bench.step", "bench.between"))
    idle = dict(out["idle_gaps"])
    assert idle["bench.step"] == pytest.approx(2.0)
    assert idle["bench.between"] == pytest.approx(2.0)
    assert "serving.decode_step" not in idle    # the device ran under it
    assert sum(idle.values()) == pytest.approx(
        out["window_s"] - out["busy_s"])
    trace = hand_built()
    trace["devices"][0] = [e for e in trace["devices"][0]
                           if not e[0].startswith("%fusion.1 ")]
    idle = dict(tr.reduce(trace, ("bench.step",
                                  "bench.between"))["idle_gaps"])
    # now [1, 2) is idle too, and the program's span is the innermost
    assert idle["serving.decode_step"] == pytest.approx(1.0)
    assert idle["bench.step"] == pytest.approx(2.0)


def test_two_chips_average_and_empty_traces_return_nothing():
    trace = hand_built()
    trace["devices"][1] = [("%fusion.1 = f32[8]{0} fusion()", 0.0, 10.0)]
    out = tr.reduce(trace, ("bench.step", "bench.between"))
    assert out["chips"] == 2
    assert out["busy_s"] == pytest.approx((6.0 + 10.0) / 2)
    assert tr.reduce({"host": [], "devices": trace["devices"]},
                     ("bench.step",)) is None
    assert tr.reduce({"host": trace["host"], "devices": {0: []}},
                     ("bench.step",)) is None


def test_load_reads_a_recorded_trace(tmp_path):
    """A small trace recorded here, on the CPU: no device plane, but the
    benchmark's spans come back by name on one clock."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    tr.start(str(tmp_path))
    for _ in range(3):
        with jax.profiler.TraceAnnotation("bench.step"):
            f(x).block_until_ready()
    with jax.profiler.TraceAnnotation("other.span"):
        pass
    tr.stop()
    loaded = tr.load(tr.find_xplane(str(tmp_path)))
    names = [name for name, _, _ in loaded["host"]]
    assert names.count("bench.step") == 3 and "other.span" not in names
    starts = [start for _, start, _ in loaded["host"]]
    assert starts == sorted(starts)
    assert loaded["devices"] == {}
    assert tr.reduce(loaded, ("bench.step",)) is None
