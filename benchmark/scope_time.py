"""Device time by the model's scopes: of a traced window, how much ran
under attention, FFN, state, head, optimizer, and how much under no name.

The program opens ``jax.named_scope("layer_<i>")`` and a kind inside it
(``attn``, ``mla``, ``mlp``, ``moe``, ``gdn``, ``ssm``), and ``lm_head``,
``embed`` and ``optimizer`` beside the layers; XLA carries the path as
every instruction's ``op_name``. The profiler's ``.xplane.pb`` holds, in
its plane ``/host:metadata``, the HLO module of every program that ran,
and every device event says which program and instruction it is. This
module joins the two, so an event is keyed by (program, instruction) and
two programs' ``fusion.77`` are not confused.

``load`` reads a trace, ``by_scope`` is pure and works on plain lists
(the tests drive it with hand-built ones), ``for_obs`` / ``per_step_ms``
are what the ``*.scope.*_ms`` layer metrics call. Like the rest of
``benchmark/`` this stands alone: the grammar below is a copy of
``paddle_tpu/analysis/graph/hlo.py`` ``scope_of``, held to it by
``tests/test_measurement_story.py``. Nothing here raises out of
``for_obs``: ``run.py`` calls a reader bare, so a failure is one log
line and "nothing to read".
"""
from __future__ import annotations

import glob
import os
import re
import time

import trace_reduce

# run.py writes a cell's trace under here, clears the cell's directory
# before and after a run, and hands a reader no path
TRACE_ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          ".trace")
WINDOW_SPANS = "bench."

# -- the grammar (a copy of hlo.scope_of) -------------------------------------

SCOPE_KINDS = ("attn", "mla", "mlp", "moe", "gdn", "ssm")
MODEL_SCOPES = ("lm_head", "embed", "optimizer")
_LAYER_RE = re.compile(r"(?<![\w.-])layer_(\d+)(?![\w.-])")
_KIND_RE = re.compile(r"(?<![\w.-])(%s)(?![\w.-])" % "|".join(SCOPE_KINDS))
_MODEL_RE = re.compile(r"(?<![\w.-])(%s)(?![\w.-])" % "|".join(MODEL_SCOPES))

UNNAMED = "unnamed"
CLASSES = ("attention", "ffn", "state", "head", "optimizer", UNNAMED)
CLASS_OF_KIND = {"attn": "attention", "mla": "attention",
                 "mlp": "ffn", "moe": "ffn",
                 "gdn": "state", "ssm": "state",
                 "lm_head": "head", "embed": "head",
                 "optimizer": "optimizer"}
# a Mosaic kernel that is traced once for all layers may carry no scope
# of its caller: its name says what it is
CLASS_OF_KERNEL = {"flash_fwd": "attention", "flash_dq": "attention",
                   "flash_dkv": "attention", "paged_decode": "attention",
                   "paged_mixed": "attention", "mla_decode": "attention",
                   "moe_gmm": "ffn", "ssm_decode": "state"}
_KERNEL_RE = re.compile(r"(?:^|/)(?:\w+\()*([\w.-]+?)\)*/pallas_call")
TOLERANCE = 0.01


def scope_of(op_name):
    """(layer index or None, kind or None) of an ``op_name``: the first
    of SCOPE_KINDS after a ``layer_<i>``, else the first of
    MODEL_SCOPES, else (None, None)."""
    layer = _LAYER_RE.search(op_name)
    if layer:
        kind = _KIND_RE.search(op_name, layer.end())
        if kind:
            return int(layer.group(1)), kind.group(1)
    m = _MODEL_RE.search(op_name)
    return None, (m.group(1) if m else None)


def kernel_of(op_name):
    """The ``pallas_call`` name in an ``op_name`` if it is a kernel of
    the table, else None."""
    m = _KERNEL_RE.search(op_name)
    return m.group(1) if m and m.group(1) in CLASS_OF_KERNEL else None


# -- the wire format ----------------------------------------------------------
# Field numbers of tsl/profiler/protobuf/xplane.proto and
# xla/service/hlo.proto, xla/xla_data.proto, read off the descriptors of
# tensorflow 2.21.0 (benchmark/tests/test_scope_time.py compares this
# walker with those classes on a trace made on the CPU). Importing them
# here would take a minute and bring a second runtime into the process
# that holds the chip.

XSPACE_PLANES = 1
XPLANE_NAME, XPLANE_EVENT_METADATA, XPLANE_STAT_METADATA = 2, 4, 5
MAP_KEY, MAP_VALUE = 1, 2
XEVENTMETADATA_STATS = 5
XSTATMETADATA_NAME = 2
XSTAT_METADATA_ID, XSTAT_BYTES_VALUE = 1, 6
HLOPROTO_MODULE = 1
HLOMODULE_NAME, HLOMODULE_COMPUTATIONS = 1, 3
HLOCOMPUTATION_INSTRUCTIONS, HLOCOMPUTATION_ID = 2, 5
HLOINSTRUCTION_NAME, HLOINSTRUCTION_METADATA = 1, 7
HLOINSTRUCTION_CALLED = 38
OPMETADATA_OP_NAME = 2
METADATA_PLANE = "/host:metadata"
HLO_PROTO_STAT = "Hlo Proto"
INSIDE = "(inside) "


def _varint(buf, pos):
    value = shift = 0
    while True:
        byte = buf[pos]
        pos += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, pos
        shift += 7


def fields(buf, lo=0, hi=None):
    """(field number, value) of every field of the message in
    ``buf[lo:hi]``: an int for a varint or a fixed-width field, the
    (lo, hi) of its bytes for a length-delimited one, never a copy."""
    hi = len(buf) if hi is None else hi
    pos = lo
    while pos < hi:
        tag, pos = _varint(buf, pos)
        number, wire = tag >> 3, tag & 7
        if wire == 0:
            value, pos = _varint(buf, pos)
        elif wire == 2:
            size, pos = _varint(buf, pos)
            value = (pos, pos + size)
            pos += size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value = int.from_bytes(buf[pos:pos + size], "little")
            pos += size
        else:
            raise ValueError("wire type %d at byte %d" % (wire, pos))
        yield number, value
    if pos != hi:
        raise ValueError("message ends at byte %d, not %d" % (pos, hi))


def _text(buf, span):
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _first(buf, span, number, default=None):
    for n, value in fields(buf, *span):
        if n == number:
            return value
    return default


def _map_entries(buf, span, number):
    """{key: the value's (lo, hi)} of a map<int64, message> field."""
    out = {}
    for n, entry in fields(buf, *span):
        if n == number:
            key = _first(buf, entry, MAP_KEY, 0)
            value = _first(buf, entry, MAP_VALUE)
            if value is not None:
                out[key] = value
    return out


def _ints(buf, value):
    """A repeated int64 field's values: packed, or one at a time."""
    if not isinstance(value, tuple):
        return [value]
    out, pos = [], value[0]
    while pos < value[1]:
        one, pos = _varint(buf, pos)
        out.append(one)
    return out


def hlo_op_names(buf, module):
    """{instruction: op_name} of an ``HloProto`` at ``buf[module]``, and
    the module's name. A fusion, ``while`` or ``conditional`` that XLA
    left without an ``op_name`` of its own (a multi-output fusion's root
    is a tuple) reads ``INSIDE`` + the first ``op_name`` with a scope
    among the instructions of the computations it calls: it is booked by
    what it holds."""
    hlo_module = _first(buf, module, HLOPROTO_MODULE)
    if hlo_module is None:
        return "", {}
    name, ops, inside, calls = "", {}, {}, {}
    for n, value in fields(buf, *hlo_module):
        if n == HLOMODULE_NAME:
            name = _text(buf, value)
        elif n == HLOMODULE_COMPUTATIONS:
            computation, scoped = None, None
            for m, instruction in fields(buf, *value):
                if m == HLOCOMPUTATION_ID:
                    computation = instruction
                if m != HLOCOMPUTATION_INSTRUCTIONS:
                    continue
                instr, op_name, called = "", "", []
                for k, v in fields(buf, *instruction):
                    if k == HLOINSTRUCTION_NAME:
                        instr = _text(buf, v)
                    elif k == HLOINSTRUCTION_METADATA:
                        found = _first(buf, v, OPMETADATA_OP_NAME)
                        if found is not None:
                            op_name = _text(buf, found)
                    elif k == HLOINSTRUCTION_CALLED:
                        called += _ints(buf, v)
                ops[instr] = op_name
                if not op_name and called:
                    calls[instr] = called
                if scoped is None and scope_of(op_name)[1] is not None:
                    scoped = op_name
            inside[computation] = scoped
    for instr, called in calls.items():
        found = [inside[c] for c in called if inside.get(c)]
        if found:
            ops[instr] = INSIDE + found[0]
    return name, ops


def programs_of(buf):
    """{program id: {"module": name, "ops": {instruction: op_name}}}
    from the ``/host:metadata`` plane of an XSpace's bytes ({} when the
    trace has no such plane)."""
    buf = memoryview(buf)
    out = {}
    for n, plane in fields(buf):
        if n != XSPACE_PLANES:
            continue
        name = _first(buf, plane, XPLANE_NAME)
        if name is None or _text(buf, name) != METADATA_PLANE:
            continue
        stat_names = {
            key: _text(buf, _first(buf, value, XSTATMETADATA_NAME, (0, 0)))
            for key, value in _map_entries(
                buf, plane, XPLANE_STAT_METADATA).items()}
        for program, meta in _map_entries(
                buf, plane, XPLANE_EVENT_METADATA).items():
            for k, stat in fields(buf, *meta):
                if k != XEVENTMETADATA_STATS:
                    continue
                which = _first(buf, stat, XSTAT_METADATA_ID, 0)
                proto = _first(buf, stat, XSTAT_BYTES_VALUE)
                if proto is None or stat_names.get(which) != HLO_PROTO_STAT:
                    continue
                module, ops = hlo_op_names(buf, proto)
                out[program] = {"module": module, "ops": ops}
    return out


# -- from a trace to rows -----------------------------------------------------

MODULES_LINE = "XLA Modules"
_PROGRAM_RE = re.compile(r"^(.*)\((\d+)\)$")


def load(xplane_path):
    """-> {"events": [(chip, program id, module, instruction, start_s,
    dur_s)] of every device plane's ``XLA Ops`` line, "runs": [(chip,
    program id, module, start_s, dur_s)] of its ``XLA Modules`` line (an
    event an execution, named ``<module>(<program id>)``), "programs":
    ``programs_of`` the file, "spans": [(name, start_s, dur_s)] of the
    host events whose name starts with WINDOW_SPANS}. A TPU trace's
    op events carry no stat that says which program they are of: an
    event's program is the execution it lies in, its instruction the
    head of its name (the instruction's whole text)."""
    import jax

    data = jax.profiler.ProfileData.from_file(xplane_path)
    events, runs, spans = [], [], []
    for plane in data.planes:
        m = trace_reduce.DEVICE_PLANE_RE.match(plane.name)
        if m:
            chip = int(m.group(2))
            modules, ops = [], []
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    for ev in line.events:
                        named = _PROGRAM_RE.match(ev.name)
                        if named:
                            modules.append((
                                ev.start_ns * 1e-9,
                                (ev.start_ns + ev.duration_ns) * 1e-9,
                                int(named.group(2)), named.group(1)))
                elif line.name == trace_reduce.OPS_LINE:
                    ops = line.events
            modules.sort()
            runs += [(chip, program, module, start, end - start)
                     for start, end, program, module in modules]
            at = 0
            for ev in sorted(ops, key=lambda e: e.start_ns):
                start = ev.start_ns * 1e-9
                while at + 1 < len(modules) and modules[at + 1][0] <= start:
                    at += 1
                program, module = None, ""
                if modules and modules[at][0] <= start < modules[at][1]:
                    program, module = modules[at][2:]
                events.append((chip, program, module,
                               trace_reduce.short_name(ev.name), start,
                               ev.duration_ns * 1e-9))
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(WINDOW_SPANS):
                        spans.append((ev.name, ev.start_ns * 1e-9,
                                      ev.duration_ns * 1e-9))
    with open(xplane_path, "rb") as f:
        programs = programs_of(f.read())
    return {"events": events, "runs": runs, "programs": programs,
            "spans": spans}


def by_scope(loaded, lo, hi):
    """Self-times of the device events inside [lo, hi] (a ``while`` holds
    its body's operations: they are taken out of it, as
    ``trace_reduce.reduce`` does), a mean over chips, summed three ways:

    -> {"scopes": {(module, layer, kind): s},
        "programs": {(module, program id): {"runs": n, "seconds": s,
                                            "classes": {class: s}}},
        "classes": {class: s}, "kernels": {kernel: s},
        "by_kernel_name": s (booked by CLASS_OF_KERNEL alone),
        "by_inside": s (booked by what an unnamed fusion holds),
        "unnamed": [(s, module, instruction, op_name)] largest first}

    An instruction's class is its scope's; a Mosaic kernel without a
    scope takes the class of its kernel name; the rest is ``unnamed``.
    ``runs`` counts a program's executions inside the window, over all
    chips."""
    programs = loaded["programs"]
    chips = {}
    for chip, program, module, instr, start, dur in loaded["events"]:
        if start >= lo and start + dur <= hi:
            chips.setdefault(chip, []).append(
                ((program, module, instr), start, dur))
    scopes, per_program, kernels, unnamed = {}, {}, {}, {}
    classes = dict.fromkeys(CLASSES, 0.0)
    named_by_kernel = named_by_inside = 0.0
    n = max(len(chips), 1)
    for events in chips.values():
        for (program, module, instr), self_s in \
                trace_reduce._self_times(events):
            self_s /= n
            op_name = programs.get(program, {}).get("ops", {}).get(instr, "")
            layer, kind = scope_of(op_name)
            kernel = kernel_of(op_name)
            cls = CLASS_OF_KIND.get(kind)
            if cls is None and kernel is not None:
                cls = CLASS_OF_KERNEL[kernel]
                named_by_kernel += self_s
            if cls is None:
                cls = UNNAMED
                key = (module, instr, op_name)
                unnamed[key] = unnamed.get(key, 0.0) + self_s
            elif op_name.startswith(INSIDE):
                named_by_inside += self_s
            if kernel is not None:
                kernels[kernel] = kernels.get(kernel, 0.0) + self_s
            classes[cls] += self_s
            key = (module, layer, kind)
            scopes[key] = scopes.get(key, 0.0) + self_s
            row = per_program.setdefault(
                (module, program),
                {"runs": 0, "seconds": 0.0,
                 "classes": dict.fromkeys(CLASSES, 0.0)})
            row["seconds"] += self_s
            row["classes"][cls] += self_s
    for _, program, module, start, dur in loaded["runs"]:
        if start >= lo and start + dur <= hi \
                and (module, program) in per_program:
            per_program[(module, program)]["runs"] += 1
    return {
        "scopes": scopes, "programs": per_program, "classes": classes,
        "kernels": kernels, "by_kernel_name": named_by_kernel,
        "by_inside": named_by_inside,
        "unnamed": sorted(((s,) + key for key, s in unnamed.items()),
                          reverse=True),
    }


# -- what the layer metrics call ----------------------------------------------

def find_xplane():
    """The newest ``.xplane.pb`` under ``TRACE_ROOT/*/`` (a crashed run
    of another cell may have left one)."""
    paths = glob.glob(os.path.join(TRACE_ROOT, "*", "plugins", "profile",
                                   "*", "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError("no .xplane.pb under %s" % TRACE_ROOT)
    return max(paths, key=os.path.getmtime)


def _agree(ours, theirs):
    return abs(ours - theirs) <= TOLERANCE * max(abs(theirs), 1e-12)


def _table(obs):
    log = obs["log"]
    reduced = obs.get("trace")
    if not reduced:
        raise ValueError("the run has no reduced trace")
    t0 = time.monotonic()
    path = find_xplane()
    loaded = load(path)
    if not loaded["programs"]:
        raise ValueError("%s has no %s plane with HLO modules"
                         % (os.path.basename(path), METADATA_PLANE))
    if not loaded["spans"]:
        raise ValueError("the trace holds no bench.* span")
    lo = min(s for _, s, _ in loaded["spans"])
    hi = max(s + d for _, s, d in loaded["spans"])
    table = by_scope(loaded, lo, hi)
    steps = len(obs.get("traced_steps") or obs.get("traced_step_s") or ())
    total = sum(table["classes"].values())
    theirs = sum(reduced["op_seconds"].values())
    log("scope_time: %s, %d bytes, %d device events, %d programs' HLO, "
        "read in %.2f s; window %.6f s, %d steps"
        % (os.path.basename(path), os.path.getsize(path),
           len(loaded["events"]), len(loaded["programs"]),
           time.monotonic() - t0, hi - lo, steps))
    ok = _agree(total, theirs)
    log("scope_time: check classes' sum %.6f s against the reduced "
        "trace's operations %.6f s: %s"
        % (total, theirs, "ok" if ok else "FAILED"))
    for kernel, seconds in sorted(table["kernels"].items()):
        other = reduced["op_seconds"].get(kernel)
        good = other is not None and _agree(seconds, other)
        ok = ok and good
        log("scope_time: check kernel %s %.6f s against %s: %s"
            % (kernel, seconds,
               "nothing" if other is None else "%.6f s" % other,
               "ok" if good else "FAILED"))
    for (module, program), row in sorted(
            table["programs"].items(), key=lambda kv: -kv[1]["seconds"]):
        runs = max(row["runs"], 1)
        log("scope_time: program %s(%s) x %d, %.3f ms a run: %s"
            % (module, program, row["runs"], 1e3 * row["seconds"] / runs,
               ", ".join("%s %.3f" % (c, 1e3 * row["classes"][c] / runs)
                         for c in CLASSES if row["classes"][c])))
    by_kind = {}
    for (_, layer, kind), seconds in table["scopes"].items():
        by_kind[(layer, kind)] = by_kind.get((layer, kind), 0.0) + seconds
    for (layer, kind), seconds in sorted(
            by_kind.items(), key=lambda kv: (kv[0][0] is None,
                                             kv[0][0] or 0, str(kv[0][1]))):
        log("scope_time: scope %-18s %.6f s"
            % ("%s%s" % ("" if layer is None else "layer_%d/" % layer,
                         kind or "(none)"), seconds))
    for seconds, module, instr, op_name in table["unnamed"][:10]:
        log("scope_time: unnamed %.6f s %s %s op_name=%r"
            % (seconds, module, instr, op_name))
    log("scope_time: %.3f ms booked by kernel name alone, %.3f ms by what "
        "a fusion without a name holds; classes (s): %s"
        % (1e3 * table["by_kernel_name"], 1e3 * table["by_inside"],
           ", ".join("%s %.6f" % (c, table["classes"][c])
                     for c in CLASSES)))
    if not ok or not steps:
        return None
    return {"classes": table["classes"], "steps": steps}


def for_obs(obs):
    """The run's table, computed once and kept in ``obs`` for the other
    readers: {"classes": {class: seconds in the traced window}, "steps":
    traced steps}, or None (one log line says why) when there is nothing
    to read or a cross-check fails."""
    if "_scope_time" not in obs:
        try:
            obs["_scope_time"] = _table(obs)
        except Exception as exc:        # never out of a reader: run.py
            obs["_scope_time"] = None   # calls read() bare
            try:
                obs["log"]("scope_time: nothing to read (%s: %s)"
                           % (type(exc).__name__, exc))
            except Exception:
                pass
    return obs["_scope_time"]


def per_step_ms(obs, cls):
    """Device milliseconds a traced step under ``cls``, or None."""
    table = for_obs(obs)
    if table is None:
        return None
    return 1e3 * table["classes"][cls] / table["steps"]
