"""Text-level parsers for XLA HLO and StableHLO dumps (stdlib-only).

The graph passes work on the two texts the AOT pipeline already
produces — ``lowered.as_text()`` (StableHLO: the program jax GAVE XLA,
with per-argument donation/aliasing attributes) and
``lowered.compile().as_text()`` (optimized HLO: what XLA actually
scheduled, with the ``input_output_alias`` header, the collective ops
and their replica groups). Parsing text instead of binding the C++
HLO API keeps the analyzer importable everywhere the repo's jax build
runs, and makes every extraction unit-testable on literal fixtures.

Nothing here imports jax: the parsers see strings only.
"""
from __future__ import annotations

import re

# bytes per element, HLO dtype spellings
HLO_DTYPE_BYTES = {
    "pred": 1, "s2": 1, "u2": 1, "s4": 1, "u4": 1,
    "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "token": 0, "opaque": 0,
}

# bytes per element, StableHLO/MLIR dtype spellings
MLIR_DTYPE_BYTES = {
    "i1": 1, "i2": 1, "i4": 1, "i8": 1, "ui8": 1,
    "i16": 2, "ui16": 2, "f16": 2, "bf16": 2,
    "i32": 4, "ui32": 4, "f32": 4,
    "i64": 8, "ui64": 8, "f64": 8,
    "f8E4M3FN": 1, "f8E5M2": 1,
}

# one HLO shape: dtype[dims]{layout}  (layout/braces optional)
_SHAPE_RE = re.compile(
    r"(?P<dtype>[a-z][a-z0-9]*)\[(?P<dims>[0-9,]*)\](?:\{[^}]*\})?")

# one instruction line:  [ROOT] %name = TYPE op(...), attrs
_INSTR_RE = re.compile(
    r"^\s*(?P<root>ROOT\s+)?%?(?P<name>[\w.-]+)\s*=\s*"
    r"(?P<type>\([^)]*\)|\S+)\s+"
    r"(?P<op>[a-z][a-z0-9-]*)\((?P<rest>.*)$")

# a computation header:  [ENTRY] %comp_name (params...) -> type {
_COMP_RE = re.compile(
    r"^(?P<entry>ENTRY\s+)?%?(?P<name>[\w.-]+)\s+\([^)]*")

_OPERAND_RE = re.compile(r"%([\w.-]+)")


class Instr:
    """One parsed HLO instruction."""

    __slots__ = ("name", "op", "shapes", "bytes", "operands",
                 "computation", "root", "line", "raw")

    def __init__(self, name, op, shapes, nbytes, operands, computation,
                 root, line, raw):
        self.name = name
        self.op = op
        self.shapes = shapes        # [(dtype, (dims...)), ...]
        self.bytes = nbytes         # total result bytes
        self.operands = operands    # referenced %names (incl. to_apply)
        self.computation = computation
        self.root = root
        self.line = line
        self.raw = raw

    def __repr__(self):
        return "Instr(%s %s %dB)" % (self.op, self.name, self.bytes)


def shape_bytes(dtype, dims):
    n = HLO_DTYPE_BYTES.get(dtype)
    if n is None:
        return 0
    total = n
    for d in dims:
        total *= d
    return total


def _parse_type(type_str):
    """[(dtype, dims)] for a single or tuple HLO result type."""
    out = []
    for m in _SHAPE_RE.finditer(type_str):
        dims = tuple(int(d) for d in m.group("dims").split(",")
                     if d != "")
        out.append((m.group("dtype"), dims))
    return out


def parse_instructions(hlo_text):
    """Every instruction in an HLO module dump, tagged with its
    computation. Lines that are not instructions (headers, braces,
    comments) are skipped; operand names are every ``%ref`` on the
    line after the ``=`` (instruction operands plus ``to_apply``-style
    computation refs — the latter never collide with instruction names
    inside one computation, so depth walks can ignore them)."""
    out = []
    comp = None
    for i, line in enumerate(hlo_text.splitlines(), 1):
        stripped = line.strip()
        if not stripped or stripped.startswith("//"):
            continue
        m = _INSTR_RE.match(line)
        if m:
            shapes = _parse_type(m.group("type"))
            nbytes = sum(shape_bytes(dt, dims) for dt, dims in shapes)
            operands = _OPERAND_RE.findall(m.group("rest"))
            out.append(Instr(m.group("name"), m.group("op"), shapes,
                             nbytes, operands, comp,
                             bool(m.group("root")), i, stripped))
            continue
        if stripped.endswith("{") and "(" in stripped and \
                "->" in stripped:
            cm = _COMP_RE.match(stripped)
            if cm:
                comp = cm.group("name")
    return out


# collective op spellings, async -start forms normalized onto the base
# op (the matching -done carries no payload of its own)
COLLECTIVE_OPS = ("all-reduce", "all-gather", "all-to-all",
                  "reduce-scatter", "collective-permute",
                  "collective-broadcast")


def collective_kind(op):
    """Base collective kind for an op name, None for non-collectives."""
    if op.endswith("-start"):
        op = op[:-len("-start")]
    if op in COLLECTIVE_OPS:
        return op
    return None


def collective_schedule(instrs):
    """Extract the collective schedule from parsed instructions.

    Returns ``(ops, depth)`` where ``ops`` is a list of dicts (kind,
    name, bytes, computation, depth) — one per collective, ``-done``
    halves skipped — and ``depth`` is the length of the LONGEST chain
    of collectives that depend on each other through dataflow. A chain
    of K collectives serializes K network round-trips; count - depth is
    the overlappable slack the T3/ROADMAP-4 work can reclaim.

    Depth is computed per computation over the textual order (HLO dumps
    are topologically ordered within a computation; scheduled modules
    are execution-ordered), with unknown operands contributing zero.
    """
    ops = []
    # name -> max collective-chain depth at that instruction's output,
    # scoped per computation (names are unique module-wide in practice)
    depth_at = {}
    for ins in instrs:
        d_in = 0
        for ref in ins.operands:
            d_in = max(d_in, depth_at.get((ins.computation, ref), 0))
        kind = collective_kind(ins.op)
        if ins.op.endswith("-done"):
            kind = None     # payload already counted at the -start
            # but the chain flows through: keep d_in
        d_out = d_in + (1 if kind else 0)
        depth_at[(ins.computation, ins.name)] = d_out
        if kind:
            ops.append({"kind": kind, "name": ins.name,
                        "bytes": ins.bytes, "computation":
                        ins.computation, "depth": d_out})
    return ops, max((o["depth"] for o in ops), default=0)


# -- module header: input/output aliasing ------------------------------------

_ALIAS_ENTRY_RE = re.compile(
    r"\{(?P<out>[0-9, ]*)\}:\s*\((?P<param>\d+),\s*\{[0-9, ]*\},?\s*"
    r"(?P<kind>may-alias|must-alias)?\)")


def parse_alias_header(hlo_text):
    """{param_index: output_tuple_index} from the compiled module's
    ``input_output_alias`` header ({} when nothing aliases). The header
    value nests braces (``{ {0}: (1, {}, may-alias) }``) so the body is
    cut with a balanced-brace scan, not a regex."""
    head = hlo_text.split("\n", 1)[0]
    key = "input_output_alias={"
    start = head.find(key)
    if start < 0:
        return {}
    i = start + len(key)
    depth = 1
    j = i
    while j < len(head) and depth > 0:
        if head[j] == "{":
            depth += 1
        elif head[j] == "}":
            depth -= 1
        j += 1
    body = head[i:j - 1]
    out = {}
    for e in _ALIAS_ENTRY_RE.finditer(body):
        idx = e.group("out").replace(" ", "")
        out[int(e.group("param"))] = \
            int(idx.split(",")[0]) if idx else 0
    return out


# -- StableHLO main signature ------------------------------------------------

_MAIN_RE = re.compile(r"func\.func\s+(?:public\s+)?@main\((?P<args>.*?)\)"
                      r"\s*->", re.S)
_ARG_RE = re.compile(
    r"%arg(?P<idx>\d+):\s*tensor<(?P<spec>[^>]*)>"
    # attr dict; values may be quoted strings carrying braces
    # (mhlo.sharding = "{devices=[2,1]0,1}")
    r"(?:\s*(?:loc\([^)]*\))?\s*"
    r"\{(?P<attrs>(?:[^{}\"]|\"[^\"]*\")*)\})?")


def _mlir_tensor(spec):
    """(dtype, dims, bytes) for an MLIR tensor<...> spec body."""
    parts = spec.split("x")
    dims = []
    for p in parts[:-1]:
        try:
            dims.append(int(p))
        except ValueError:
            dims.append(0)      # dynamic dim: size unknown
    dtype = parts[-1]
    n = MLIR_DTYPE_BYTES.get(dtype, 0)
    total = n
    for d in dims:
        total *= d
    return dtype, tuple(dims), total


def parse_main_args(stablehlo_text):
    """The lowered module's entry arguments: a list of dicts
    ``{index, dtype, dims, bytes, aliased (tf.aliasing_output present),
    donor (jax.buffer_donor present), sharding}`` in argument order.
    This is where jax records which donations it could actually use —
    a donated-but-unaliased buffer simply lacks both attributes."""
    m = _MAIN_RE.search(stablehlo_text)
    if not m:
        return []
    out = []
    for am in _ARG_RE.finditer(m.group("args")):
        attrs = am.group("attrs") or ""
        dtype, dims, nbytes = _mlir_tensor(am.group("spec"))
        sharding = None
        sm = re.search(r'mhlo\.sharding\s*=\s*"([^"]*)"', attrs)
        if sm:
            sharding = sm.group(1)
        out.append({
            "index": int(am.group("idx")),
            "dtype": dtype,
            "dims": dims,
            "bytes": nbytes,
            "aliased": "tf.aliasing_output" in attrs,
            "donor": "jax.buffer_donor" in attrs,
            "sharding": sharding,
        })
    out.sort(key=lambda a: a["index"])
    return out


def find_f64_ops(instrs):
    """Instructions producing an f64 result — the accidental-upcast
    lint's raw material (s64/u64 index math is deliberately NOT
    flagged; the TPU path's hazard is double-precision FLOPs)."""
    out = []
    for ins in instrs:
        if any(dt == "f64" for dt, _ in ins.shapes):
            out.append(ins)
    return out


# custom-call targets that move data to/from the host (vs. compute
# custom-calls like LAPACK kernels on the CPU backend, which are fine)
_HOST_TARGET_RE = re.compile(
    r"callback|host|infeed|outfeed|xla_ffi_python|SendToHost|"
    r"RecvFromHost", re.I)


def find_host_transfers(instrs):
    """Instructions that cross the device boundary inside the step:
    infeed/outfeed/send/recv plus custom-calls whose target names a
    host callback."""
    out = []
    for ins in instrs:
        if ins.op in ("infeed", "outfeed", "send", "recv", "send-done",
                      "recv-done"):
            out.append((ins, ins.op))
            continue
        if ins.op == "custom-call":
            tm = re.search(r'custom_call_target="([^"]*)"', ins.raw)
            if tm and _HOST_TARGET_RE.search(tm.group(1)):
                out.append((ins, tm.group(1)))
    return out


def find_gathers(instrs, min_bytes=0):
    """gather instructions at or above ``min_bytes`` of output — the
    GSPMD full-remat embedding-gather shape report."""
    return [ins for ins in instrs
            if ins.op == "gather" and ins.bytes >= min_bytes]


MOSAIC_TARGET = "tpu_custom_call"
_PALLAS_SCOPE_RE = re.compile(r'op_name="(?:[^"]*/)?([^/"]+)/pallas_call')
_LHS_NAME_RE = re.compile(r"\s*(?:ROOT\s+)?%?([\w.-]+)\s*=")


def mosaic_kernels(hlo_text):
    """{kernel name: count} of the Pallas/Mosaic kernels in a compiled
    HLO module: every ``custom-call`` whose target is
    ``tpu_custom_call``. The name is the ``pallas_call``'s ``name=``
    argument, which XLA carries as the path element before
    ``pallas_call`` in the instruction's ``op_name`` metadata, bare
    (".../flash_fwd/pallas_call") or inside autodiff wrappers
    (".../transpose(jvp(flash_dq))/pallas_call"); a kernel without it
    counts under the instruction's own name. chip_smoke.py asserts on this
    that the step the chip ran holds the kernel and not a reference
    path."""
    # line scan, not parse_instructions: TPU tuple results carry tiled
    # layouts with nested parentheses, which _INSTR_RE's type group
    # does not span
    out = {}
    marker = 'custom_call_target="%s"' % MOSAIC_TARGET
    for line in hlo_text.splitlines():
        if marker not in line:
            continue
        scope = _PALLAS_SCOPE_RE.search(line)
        inner = re.findall(r"[\w.-]+", scope.group(1)) if scope else []
        if inner:
            name = inner[-1]
        else:
            lhs = _LHS_NAME_RE.match(line)
            name = lhs.group(1) if lhs else "?"
        out[name] = out.get(name, 0) + 1
    return out


# -- model scopes -------------------------------------------------------------
# The models open ``jax.named_scope("layer_<i>")`` around a decoder layer
# and one of SCOPE_KINDS inside it; what belongs to no layer is under one
# of MODEL_SCOPES. XLA carries the scope path as every instruction's
# ``op_name``, which is how device time gets the model's names
# (benchmark/scope_time.py keeps its own copy of this grammar).

SCOPE_KINDS = ("attn", "mla", "mlp", "moe", "gdn", "ssm")
MODEL_SCOPES = ("lm_head", "embed", "optimizer")
# autodiff, remat and control flow wrap a path element or a whole path,
# and may stand between the layer and its kind: jvp(layer_0)/attn,
# transpose(jvp(layer_2/mlp)),
# transpose(jvp(layer_0))/jvp(layer_0)/checkpoint/rematted_computation/attn
_LAYER_SCOPE_RE = re.compile(r"(?<![\w.-])layer_(\d+)(?![\w.-])")
_KIND_SCOPE_RE = re.compile(
    r"(?<![\w.-])(%s)(?![\w.-])" % "|".join(SCOPE_KINDS))
_MODEL_SCOPE_RE = re.compile(
    r"(?<![\w.-])(%s)(?![\w.-])" % "|".join(MODEL_SCOPES))
_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')
_OPCODE_RE = re.compile(r"=.*?[\s)}\]]([a-z][a-z0-9-]*)\(")
_CALLED_RE = re.compile(
    r"(?:body|condition|to_apply|calls|true_computation|"
    r"false_computation)=%?([\w.-]+)|branch_computations=\{([^}]*)\}")


def scope_of(op_name):
    """(layer index or None, kind or None) of an instruction's
    ``op_name``. With a ``layer_<i>`` anywhere in the string, the kind
    is the first path element after it that is one of SCOPE_KINDS,
    inside ``jvp``, ``transpose``, ``checkpoint``, ``while/body`` and
    ``cond/branch_1_fun`` wrappers too; without a layer and its kind,
    the first of MODEL_SCOPES that is a path element; else
    (None, None)."""
    layer = _LAYER_SCOPE_RE.search(op_name)
    if layer:
        kind = _KIND_SCOPE_RE.search(op_name, layer.end())
        if kind:
            return int(layer.group(1)), kind.group(1)
    m = _MODEL_SCOPE_RE.search(op_name)
    return None, (m.group(1) if m else None)


def instruction_scopes(hlo_text):
    """{instruction short name: (layer, kind)} for every instruction of
    a compiled HLO module's text, fused computations' too; an
    instruction without ``op_name`` metadata reads (None, None)."""
    out = {}
    for line in hlo_text.splitlines():
        lhs = _LHS_NAME_RE.match(line)
        if not lhs or "(" not in line:
            continue
        name = _OP_NAME_RE.search(line)
        out[lhs.group(1)] = scope_of(name.group(1)) if name \
            else (None, None)
    return out


def executed_instructions(hlo_text):
    """[(instruction short name, opcode, op_name)] of the instructions
    the device runs one by one: those of the entry computation and of
    the computations it reaches through ``while``, ``conditional``,
    ``call`` and async wrappers, not the inside of a fusion or of a
    reducer. A line scan like ``mosaic_kernels`` (TPU layouts nest
    parentheses inside a result type)."""
    comps, entry, comp = {}, None, None
    for line in hlo_text.splitlines():
        stripped = line.strip()
        if stripped.endswith("{") and "->" in stripped:
            cm = _COMP_RE.match(stripped)
            if cm:
                comp = cm.group("name")
                comps[comp] = []
                if cm.group("entry"):
                    entry = comp
            continue
        lhs = _LHS_NAME_RE.match(line)
        opcode = _OPCODE_RE.search(line)
        if comp is None or not lhs or not opcode:
            continue
        name = _OP_NAME_RE.search(line)
        called = []
        if opcode.group(1) in ("while", "conditional", "call",
                               "async-start"):
            for one, many in _CALLED_RE.findall(line):
                called += [one] if one else re.findall(r"[\w.-]+", many)
        comps[comp].append((lhs.group(1), opcode.group(1),
                            name.group(1) if name else "", called))
    out, seen, todo = [], set(), [entry]
    while todo:
        comp = todo.pop()
        if comp in seen or comp not in comps:
            continue
        seen.add(comp)
        for name, opcode, op_name, called in comps[comp]:
            out.append((name, opcode, op_name))
            todo += called
    return out
