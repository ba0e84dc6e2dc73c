"""Device time gets the model's names: every instruction of a compiled
step carries a scope.

``jax.named_scope`` is metadata (no primitive is added), and XLA carries
the scope path as each instruction's ``op_name``. ``hlo.scope_of`` is
the one grammar that reads it; ``benchmark/scope_time.py`` books a
traced window's device time by it (its own copy of the grammar is held
to this one in ``tests/test_measurement_story.py``). The guard below
compiles each family's tiny steps on the CPU and fails when a program
leaves named work outside every scope: it is what keeps the next
model's scopes whole.
"""
from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import serving
from paddle_tpu.analysis.graph import hlo


# -- the grammar --------------------------------------------------------------

@pytest.mark.parametrize("op_name, want", [
    ("jit(_decode_fn)/layer_3/attn/dot_general", (3, "attn")),
    ("jit(_prefill_fn)/layer_12/mla/jit(_flash_fwd_bhnd)/flash_fwd/"
     "pallas_call", (12, "mla")),
    # autodiff wraps one path element, or a whole path
    ("jit(step)/jvp(layer_0)/attn/cond/branch_1_fun/sin", (0, "attn")),
    ("jit(step)/transpose(jvp(layer_2/mlp))/dot_general", (2, "mlp")),
    ("jit(step)/layer_1/transpose(jvp(gdn))/mul", (1, "gdn")),
    # remat stands between the layer and its kind
    ("jit(step)/transpose(jvp(layer_0))/jvp(layer_0)/checkpoint/"
     "rematted_computation/attn/pow", (0, "attn")),
    ("jit(step)/transpose(jvp(jvp()))/checkpoint/layer_1/moe/add_any",
     (1, "moe")),
    # control flow inside a scope
    ("jit(f)/jvp(layer_0)/ssm/closed_call/while/body/closed_call/tanh",
     (0, "ssm")),
    ("jit(_prefill_fn)/layer_4/moe/cond/branch_1_fun/jit(argsort)/sort",
     (4, "moe")),
    # the first kind after the layer wins
    ("jit(f)/layer_5/moe/mlp/dot_general", (5, "moe")),
    # model-level names, bare and wrapped
    ("jit(_decode_fn)/lm_head/reduce", (None, "lm_head")),
    ("jit(step)/transpose(jvp(lm_head))/dot_general", (None, "lm_head")),
    ("jit(_decode_fn)/embed/jit(_take)/gather", (None, "embed")),
    ("jit(step)/optimizer/jit(_clip)/mul", (None, "optimizer")),
    # a layer without a kind, a look-alike, a parameter, nothing
    ("jit(step)/transpose(jvp(layer_1))/jvp(layer_1)/remat2", (None, None)),
    ("jit(f)/layer_3/mul", (None, None)),
    ("jit(f)/embed_tokens/mlp_out/player_1/attn", (None, None)),
    ("state_vals[26]", (None, None)),
    ("", (None, None)),
])
def test_scope_of(op_name, want):
    assert hlo.scope_of(op_name) == want


_HLO = """HloModule jit_f, is_scheduled=true

%fused_computation (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %tanh.1 = f32[8]{0} tanh(%p), metadata={op_name="jit(f)/layer_0/mlp/tanh"}
}

%body (arg: (s32[], f32[8])) -> (s32[], f32[8]) {
  %arg = (s32[], f32[8]{0}) parameter(0)
  %gte = f32[8]{0} get-tuple-element(%arg), index=1
  %fusion.7 = f32[8]{0:T(8,128)(2,1)S(1)} fusion(%gte), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(f)/layer_0/mlp/while/body/tanh"}
  ROOT %tuple.1 = (s32[], f32[8]{0}) tuple(%gte, %fusion.7)
}

%cond (arg.1: (s32[], f32[8])) -> pred[] {
  %arg.1 = (s32[], f32[8]{0}) parameter(0)
  ROOT %lt = pred[] constant(true), metadata={op_name="jit(f)/layer_0/mlp/while/cond/lt"}
}

%branch_a (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0)
  ROOT %copy.3 = f32[8]{0} copy(%x)
}

%branch_b (y: f32[8]) -> f32[8] {
  %y = f32[8]{0} parameter(0)
  ROOT %sort.2 = f32[8]{0} sort(%y), dimensions={0}, to_apply=%cmp, metadata={op_name="jit(f)/layer_1/moe/cond/branch_1_fun/jit(argsort)/sort"}
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0), metadata={op_name="a"}
  %t = (s32[], f32[8]{0}) tuple(%a, %a)
  %while.1 = (s32[], f32[8]{0:T(8,128)(2,1)}) while(%t), condition=%cond, body=%body, metadata={op_name="jit(f)/layer_0/mlp/while"}
  %g = f32[8]{0} get-tuple-element(%while.1), index=1
  %conditional.1 = f32[8]{0} conditional(%p, %g, %g), branch_computations={%branch_a, %branch_b}, metadata={op_name="jit(f)/layer_1/moe/cond"}
  ROOT %custom-call.4 = f32[8]{0} custom-call(%conditional.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/lm_head/argmax_kernel/pallas_call"}
}
"""


def test_instruction_scopes_reads_every_line_with_metadata():
    scopes = hlo.instruction_scopes(_HLO)
    assert scopes["fusion.7"] == (0, "mlp")
    assert scopes["tanh.1"] == (0, "mlp")        # inside the fusion too
    assert scopes["sort.2"] == (1, "moe")
    assert scopes["custom-call.4"] == (None, "lm_head")
    assert scopes["copy.3"] == (None, None)      # no metadata at all
    assert scopes["a"] == (None, None)


def test_executed_instructions_walks_bodies_and_branches_not_fusions():
    rows = hlo.executed_instructions(_HLO)
    by_name = {name: (opcode, op_name) for name, opcode, op_name in rows}
    # the entry, the while's body and condition, both branches
    assert by_name["while.1"][0] == "while"
    assert by_name["fusion.7"] == (
        "fusion", "jit(f)/layer_0/mlp/while/body/tanh")
    assert by_name["lt"][0] == "constant"
    assert by_name["copy.3"] == ("copy", "")
    assert by_name["sort.2"][0] == "sort"
    assert by_name["custom-call.4"][0] == "custom-call"
    # the inside of a fusion runs as its fusion, a sort's comparator as
    # its sort
    assert "tanh.1" not in by_name and "p" not in by_name


# -- the coverage guard -------------------------------------------------------

# what the device runs as an instruction of its own and the trace times
GUARDED = ("fusion", "convolution", "dot", "custom-call", "sort",
           "scatter", "gather", "while", "copy")
# named work that belongs to no scope, and why. Short, and printed.
ALLOWED = (
    # a parameter's layout change is named after the argument
    (re.compile(r"^[\w.]+(\[\d+\])+$"), "a parameter's layout copy"),
    # the copy at a checkpointed layer's boundary belongs to the layer,
    # not to a block of it
    (re.compile(r"jvp\(layer_\d+\)/remat2$"), "a recomputed layer's input"),
)


def _engine_programs(model, bucket=32):
    """{"decode": HLO text, "prefill": HLO text} of a tiny engine."""
    eng = serving.Engine(model, max_slots=2, num_blocks=64, block_size=4,
                         max_model_len=128)
    _, decode, _, args = eng._hot_step()
    ids = jnp.zeros((1, bucket), jnp.int32)
    row = jnp.asarray(eng.cache.block_tables[0])
    return {
        "decode": eng._run_eval(decode.lower, *args).compile().as_text(),
        # the prefill as the engine runs it: it also writes its first
        # token into the slot tokens
        "prefill": eng._run_eval(
            eng._prefill.lower, eng._state_vals, eng.cache.pools,
            eng._slot_tokens, jnp.asarray(0, jnp.int32), ids, row,
            jnp.asarray(bucket, jnp.int32)).compile().as_text(),
    }


def _llama_programs():
    from paddle_tpu.models import llama

    model = llama.LlamaForCausalLM(
        llama.LlamaConfig.tiny(use_parallel=False))
    return _engine_programs(model)


def _llama_train_program():
    from jax.sharding import Mesh

    from paddle_tpu.models import llama
    from paddle_tpu.parallel.engine import CompiledTrainStep

    model = llama.LlamaForCausalLM(
        llama.LlamaConfig.tiny(use_parallel=False, recompute=True))
    opt = paddle.optimizer.AdamW(
        learning_rate=1e-3, parameters=model.parameters(),
        grad_clip=paddle.nn.ClipGradByGlobalNorm(1.0))
    step = CompiledTrainStep(
        model, None, opt, mesh=Mesh(np.array(jax.devices()[:1]), ("dp",)),
        labels_to_model=True)
    ids = np.zeros((2, 32), np.int32)
    return {"train": step.lowered_hlo(ids, ids)}


def _qwen3_next_programs():
    from paddle_tpu.models import qwen3_next as m

    return _engine_programs(m.Qwen3NextForCausalLM(m.Qwen3NextConfig.tiny()))


def _deepseek_v2_programs():
    from paddle_tpu.models import deepseek_v2 as m

    return _engine_programs(
        m.DeepseekV2ForCausalLM(m.DeepseekV2Config.tiny()))


def _nemotron_h_programs():
    from paddle_tpu.models import nemotron_h as m

    return _engine_programs(m.NemotronHForCausalLM(m.NemotronHConfig.tiny()))


def _gigachat3_5_programs():
    from paddle_tpu.models import gigachat3_5 as m

    return _engine_programs(
        m.GigaChat35ForCausalLM(m.GigaChat35Config.tiny()))


def _phi4flash_programs():
    from paddle_tpu.models import phi4flash as m

    return _engine_programs(m.Phi4FlashForCausalLM(m.Phi4FlashConfig.tiny()))


FAMILIES = {
    "llama": (_llama_programs, {"embed", "attn", "mlp", "lm_head"}),
    "llama_train": (_llama_train_program,
                    {"embed", "attn", "mlp", "lm_head", "optimizer"}),
    "qwen3_next": (_qwen3_next_programs,
                   {"embed", "attn", "gdn", "moe", "lm_head"}),
    "deepseek_v2": (_deepseek_v2_programs,
                    {"embed", "mla", "mlp", "moe", "lm_head"}),
    "nemotron_h": (_nemotron_h_programs,
                   {"embed", "attn", "ssm", "moe", "lm_head"}),
    "gigachat3_5": (_gigachat3_5_programs,
                    {"embed", "gdn", "mla", "mlp", "moe", "lm_head"}),
    "phi4flash": (_phi4flash_programs,
                  {"embed", "attn", "ssm", "mlp", "lm_head"}),
}
_compiled = {}


def _program(family, program):
    if family not in _compiled:
        _compiled[family] = FAMILIES[family][0]()
    return _compiled[family][program]


@pytest.mark.parametrize("family, program", [
    ("llama", "decode"), ("llama", "prefill"), ("llama_train", "train"),
    ("qwen3_next", "decode"), ("qwen3_next", "prefill"),
    ("deepseek_v2", "decode"), ("deepseek_v2", "prefill"),
    ("nemotron_h", "decode"), ("nemotron_h", "prefill"),
    ("gigachat3_5", "decode"), ("gigachat3_5", "prefill"),
    ("phi4flash", "decode"), ("phi4flash", "prefill"),
])
def test_every_named_instruction_of_a_compiled_step_has_a_scope(
        family, program):
    """Every fusion, convolution, dot, custom call, sort, scatter,
    gather, while and copy of the entry computation and of the bodies it
    calls, if the program gave it a name at all, reads a kind. What the
    CPU compiler made itself (an empty ``op_name``: its copies, its tree
    reductions, the dots it rewrote) is counted and printed, not held
    against the program: it cannot name them."""
    rows = [(name, opcode, op_name) for name, opcode, op_name
            in hlo.executed_instructions(_program(family, program))
            if opcode in GUARDED]
    kinds, allowed, compilers, bare = set(), [], [], []
    for name, opcode, op_name in rows:
        kind = hlo.scope_of(op_name)[1]
        if kind is not None:
            kinds.add(kind)
        elif not op_name:
            compilers.append(name)
        else:
            why = [reason for pattern, reason in ALLOWED
                   if pattern.search(op_name)]
            (allowed if why else bare).append((name, op_name, why))
    print("%s %s: %d instructions, %d scoped, %d the compiler's own, "
          "allowed: %s" % (family, program, len(rows),
                           len(rows) - len(compilers) - len(allowed)
                           - len(bare), len(compilers), allowed))
    assert bare == [], "named work outside every scope"
    assert FAMILIES[family][1] <= kinds, sorted(kinds)
    assert len(rows) - len(compilers) - len(allowed) > len(rows) // 2
