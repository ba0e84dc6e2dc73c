"""``scope_time``: the join of device events to the programs' HLO
``op_name``s, on hand-built lists; the wire-format walker against the
protobuf classes on a trace made on the CPU; and the promise that a
reader never raises."""
import glob
import os

import pytest

import scope_time

MS = 1e-3


def event(program, instr, start_ms, dur_ms, chip=0, module=None):
    module = module or {1: "jit__decode_fn", 2: "jit__prefill_fn"}[program]
    return (chip, program, module, instr, start_ms * MS, dur_ms * MS)


PROGRAMS = {
    1: {"module": "jit__decode_fn", "ops": {
        "fusion.77": "jit(_decode_fn)/layer_0/attn/dot_general",
        "while.3": "jit(_decode_fn)/layer_1/gdn/while",
        "fusion.5": "jit(_decode_fn)/layer_1/gdn/while/body/mul",
        "copy.9": "",
        "paged_decode.2": "jit(_decode_fn)/jit(paged_attention)/"
                          "paged_decode/pallas_call",
        "fusion.9": "jit(_decode_fn)/lm_head/reduce",
    }},
    2: {"module": "jit__prefill_fn", "ops": {
        # the same short name as in program 1, another scope
        "fusion.77": "jit(_prefill_fn)/layer_2/moe/cond/branch_1_fun/"
                     "jit(argsort)/sort",
        "moe_gmm.4": "jit(_prefill_fn)/layer_2/moe/jit(grouped_matmul)/"
                     "moe_gmm/pallas_call",
        "fusion.1": "jit(_prefill_fn)/embed/jit(_take)/gather",
        "fusion.2": "state_vals[26]",
    }},
}


def loaded(events, runs=()):
    return {"events": events, "runs": list(runs), "programs": PROGRAMS,
            "spans": []}


def test_by_scope_takes_a_while_body_out_of_its_while():
    table = scope_time.by_scope(loaded([
        event(1, "fusion.77", 0.0, 1.0),
        event(1, "while.3", 1.0, 3.0),       # holds three body steps
        event(1, "fusion.5", 1.1, 0.8),
        event(1, "fusion.5", 2.0, 0.8),
        event(1, "copy.9", 2.8, 0.1),        # XLA's own, inside the loop
        event(1, "fusion.9", 4.0, 0.5),
    ]), 0.0, 10 * MS)
    scopes = table["scopes"]
    assert scopes[("jit__decode_fn", 0, "attn")] == pytest.approx(1.0 * MS)
    # the while keeps 3.0 - 0.8 - 0.8 - 0.1 for itself; the body's 1.6
    # is the same scope; the copy has none
    assert scopes[("jit__decode_fn", 1, "gdn")] == pytest.approx(2.9 * MS)
    assert scopes[("jit__decode_fn", None, None)] == pytest.approx(0.1 * MS)
    classes = table["classes"]
    assert classes["attention"] == pytest.approx(1.0 * MS)
    assert classes["state"] == pytest.approx(2.9 * MS)
    assert classes["head"] == pytest.approx(0.5 * MS)
    assert classes["unnamed"] == pytest.approx(0.1 * MS)
    assert classes["ffn"] == classes["optimizer"] == 0.0
    # self-times add up to the busy time
    assert sum(classes.values()) == pytest.approx(4.5 * MS)
    assert table["unnamed"] == [
        (pytest.approx(0.1 * MS), "jit__decode_fn", "copy.9", "")]


def test_two_programs_that_share_an_instruction_name_stand_apart():
    runs = [(0, 1, "jit__decode_fn", 0.0, 1.0 * MS),
            (0, 1, "jit__decode_fn", 5 * MS, 1.0 * MS),
            (0, 2, "jit__prefill_fn", 2 * MS, 2.5 * MS),
            (0, 2, "jit__prefill_fn", 50 * MS, 2.5 * MS)]  # past the window
    table = scope_time.by_scope(loaded([
        event(1, "fusion.77", 0.0, 1.0),
        event(2, "fusion.77", 2.0, 0.5),
        event(2, "moe_gmm.4", 2.5, 1.5),
        event(2, "fusion.1", 4.0, 0.25),
        event(2, "fusion.2", 4.25, 0.25),
        event(1, "fusion.77", 5.0, 1.0),
        event(2, "fusion.77", 50.0, 0.5),       # past the window
    ], runs), 0.0, 10 * MS)
    assert table["scopes"] == {
        ("jit__decode_fn", 0, "attn"): pytest.approx(2.0 * MS),
        ("jit__prefill_fn", 2, "moe"): pytest.approx(2.0 * MS),
        ("jit__prefill_fn", None, "embed"): pytest.approx(0.25 * MS),
        ("jit__prefill_fn", None, None): pytest.approx(0.25 * MS),
    }
    decode = table["programs"][("jit__decode_fn", 1)]
    prefill = table["programs"][("jit__prefill_fn", 2)]
    assert decode["runs"] == 2 and prefill["runs"] == 1
    assert decode["classes"]["attention"] == pytest.approx(2.0 * MS)
    assert prefill["classes"]["ffn"] == pytest.approx(2.0 * MS)
    assert prefill["classes"]["head"] == pytest.approx(0.25 * MS)
    assert prefill["seconds"] == pytest.approx(2.5 * MS)
    # a kernel inside a scope is booked by the scope, and still listed
    assert table["kernels"] == {"moe_gmm": pytest.approx(1.5 * MS)}
    assert table["by_kernel_name"] == 0.0
    assert [row[2:] for row in table["unnamed"]] == [
        ("fusion.2", "state_vals[26]")]


def test_an_unscoped_kernel_goes_by_its_kernels_name():
    table = scope_time.by_scope(loaded([
        event(1, "paged_decode.2", 0.0, 3.0),
        event(1, "paged_decode.2", 4.0, 3.0, chip=1),
        event(1, "copy.9", 3.0, 1.0),
        event(1, "copy.9", 7.0, 1.0, chip=1),
    ]), 0.0, 10 * MS)
    # a mean over the two chips
    assert table["classes"]["attention"] == pytest.approx(3.0 * MS)
    assert table["classes"]["unnamed"] == pytest.approx(1.0 * MS)
    assert table["by_kernel_name"] == pytest.approx(3.0 * MS)
    assert table["kernels"] == {"paged_decode": pytest.approx(3.0 * MS)}
    assert table["scopes"] == {
        ("jit__decode_fn", None, None): pytest.approx(4.0 * MS)}


def test_an_event_of_an_unknown_program_is_unnamed_not_an_error():
    table = scope_time.by_scope(loaded([
        event(9, "fusion.1", 0.0, 1.0, module="jit_other"),
        (0, None, "", "fusion.3", 2 * MS, 1 * MS)]), 0.0, 10 * MS)
    assert table["classes"]["unnamed"] == pytest.approx(2.0 * MS)


# -- the wire format ----------------------------------------------------------

@pytest.fixture(scope="module")
def cpu_trace(tmp_path_factory):
    """A trace of a jitted function with scopes and a loop, on the CPU."""
    import jax
    import jax.numpy as jnp

    def f(x, w):
        with jax.named_scope("embed"):
            x = x + 1.0
        for i in range(2):
            with jax.named_scope("layer_%d" % i):
                with jax.named_scope("mlp"):
                    x = jnp.tanh(x @ w)
                with jax.named_scope("attn"):
                    x = jax.lax.fori_loop(
                        0, 3, lambda _, c: jnp.tanh(c @ w), x)
        with jax.named_scope("lm_head"):
            return (x @ w).sum()

    g = jax.jit(f)
    x = jnp.ones((64, 64))
    g(x, x).block_until_ready()
    root = tmp_path_factory.mktemp("trace")
    trace_dir = str(root / "cell")
    import trace_reduce

    trace_reduce.start(trace_dir)
    with jax.profiler.TraceAnnotation("bench.step"):
        for _ in range(3):
            g(x, x).block_until_ready()
    trace_reduce.stop()
    return str(root), trace_reduce.find_xplane(trace_dir)


def test_the_walker_reads_what_the_protobuf_classes_read(cpu_trace):
    """Every field number in ``scope_time`` against the descriptors the
    machine has (tensorflow's copies of xplane.proto and hlo.proto):
    program ids, module names and every instruction's ``op_name``."""
    pytest.importorskip("tensorflow")
    from tensorflow.compiler.xla.service import hlo_pb2
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    with open(cpu_trace[1], "rb") as f:
        raw = f.read()
    ours = scope_time.programs_of(raw)
    space = xplane_pb2.XSpace()
    space.ParseFromString(raw)
    theirs = {}
    for plane in space.planes:
        if plane.name != scope_time.METADATA_PLANE:
            continue
        names = {k: m.name for k, m in plane.stat_metadata.items()}
        for program, meta in plane.event_metadata.items():
            for stat in meta.stats:
                if names[stat.metadata_id] != scope_time.HLO_PROTO_STAT:
                    continue
                proto = hlo_pb2.HloProto()
                proto.ParseFromString(stat.bytes_value)
                comps = {c.id: c for c in proto.hlo_module.computations}
                ops = {}
                for c in comps.values():
                    for i in c.instructions:
                        ops[i.name] = i.metadata.op_name
                        if i.metadata.op_name:
                            continue
                        # the rule of hlo_op_names, written again
                        held = [j.metadata.op_name
                                for called in i.called_computation_ids
                                for j in comps[called].instructions
                                if scope_time.scope_of(
                                    j.metadata.op_name)[1]]
                        if held:
                            ops[i.name] = scope_time.INSIDE + held[0]
                theirs[program] = {"module": proto.hlo_module.name,
                                   "ops": ops}
    assert theirs and ours == theirs
    for message, field, number in (
            (xplane_pb2.XSpace, "planes", scope_time.XSPACE_PLANES),
            (xplane_pb2.XPlane, "name", scope_time.XPLANE_NAME),
            (xplane_pb2.XPlane, "event_metadata",
             scope_time.XPLANE_EVENT_METADATA),
            (xplane_pb2.XPlane, "stat_metadata",
             scope_time.XPLANE_STAT_METADATA),
            (xplane_pb2.XEventMetadata, "stats",
             scope_time.XEVENTMETADATA_STATS),
            (xplane_pb2.XStatMetadata, "name",
             scope_time.XSTATMETADATA_NAME),
            (xplane_pb2.XStat, "metadata_id", scope_time.XSTAT_METADATA_ID),
            (xplane_pb2.XStat, "bytes_value", scope_time.XSTAT_BYTES_VALUE),
            (hlo_pb2.HloProto, "hlo_module", scope_time.HLOPROTO_MODULE),
            (hlo_pb2.HloModuleProto, "name", scope_time.HLOMODULE_NAME),
            (hlo_pb2.HloModuleProto, "computations",
             scope_time.HLOMODULE_COMPUTATIONS),
            (hlo_pb2.HloComputationProto, "instructions",
             scope_time.HLOCOMPUTATION_INSTRUCTIONS),
            (hlo_pb2.HloInstructionProto, "name",
             scope_time.HLOINSTRUCTION_NAME),
            (hlo_pb2.HloInstructionProto, "metadata",
             scope_time.HLOINSTRUCTION_METADATA),
            (hlo_pb2.HloInstructionProto, "called_computation_ids",
             scope_time.HLOINSTRUCTION_CALLED),
            (hlo_pb2.HloComputationProto, "id",
             scope_time.HLOCOMPUTATION_ID)):
        assert message.DESCRIPTOR.fields_by_name[field].number == number


def test_the_walker_finds_the_scopes_in_a_cpu_trace(cpu_trace):
    with open(cpu_trace[1], "rb") as f:
        programs = scope_time.programs_of(f.read())
    (ops,) = [p["ops"] for p in programs.values() if p["module"] == "jit_f"]
    found = {scope_time.scope_of(op_name) for op_name in ops.values()}
    assert {(None, "embed"), (0, "mlp"), (0, "attn"), (1, "mlp"),
            (1, "attn"), (None, "lm_head")} <= found


def test_load_reads_spans_and_programs_of_a_cpu_trace(cpu_trace):
    data = scope_time.load(cpu_trace[1])
    assert [name for name, _, _ in data["spans"]] == ["bench.step"]
    assert "jit_f" in {p["module"] for p in data["programs"].values()}
    # the CPU has no /device: plane
    assert data["events"] == [] and data["runs"] == []


def _field(number, value):
    """One field on the wire: a varint, or bytes with their length."""
    def varint(n):
        out = bytearray()
        while n >= 0x80:
            out.append(n & 0x7F | 0x80)
            n >>= 7
        return bytes(out + bytes([n]))

    if isinstance(value, int):
        return varint(number << 3) + varint(value)
    return varint(number << 3 | 2) + varint(len(value)) + value


def test_a_fusion_without_a_name_is_booked_by_what_it_holds():
    """XLA leaves a multi-output fusion (its root is a tuple) and a
    ``conditional`` without an ``op_name``: the first scoped name among
    the instructions of the computations they call stands in, marked."""
    def instruction(name, op_name="", called=()):
        out = _field(scope_time.HLOINSTRUCTION_NAME, name.encode())
        if op_name:
            out += _field(scope_time.HLOINSTRUCTION_METADATA, _field(
                scope_time.OPMETADATA_OP_NAME, op_name.encode()))
        if called:      # packed, as the serializer writes it
            out += _field(scope_time.HLOINSTRUCTION_CALLED,
                          b"".join(bytes([c]) for c in called))
        return _field(scope_time.HLOCOMPUTATION_INSTRUCTIONS, out)

    def computation(ident, *instructions):
        return _field(scope_time.HLOMODULE_COMPUTATIONS,
                      b"".join(instructions)
                      + _field(scope_time.HLOCOMPUTATION_ID, ident))

    held = computation(
        7, instruction("p.1"), instruction("bitcast.1", "squeeze"),
        instruction("mul.3", "jit(_prefill_fn)/layer_1/mla/while/body/mul"),
        instruction("add.4", "jit(_prefill_fn)/layer_1/mla/add"))
    bare = computation(8, instruction("p.2"), instruction("copy.5"))
    entry = computation(
        9, instruction("fusion.756", called=(7,)),
        instruction("fusion.9", called=(8,)),
        instruction("conditional.8", called=(8, 7)),
        instruction("fusion.1", "jit(_prefill_fn)/embed/gather",
                    called=(7,)),
        instruction("copy.528"))
    proto = _field(scope_time.HLOPROTO_MODULE, _field(
        scope_time.HLOMODULE_NAME, b"jit__prefill_fn")
        + held + bare + entry)
    name, ops = scope_time.hlo_op_names(memoryview(proto), (0, len(proto)))
    inside = scope_time.INSIDE \
        + "jit(_prefill_fn)/layer_1/mla/while/body/mul"
    assert name == "jit__prefill_fn"
    assert ops["fusion.756"] == ops["conditional.8"] == inside
    assert ops["fusion.9"] == "" and ops["copy.528"] == ""
    assert ops["fusion.1"] == "jit(_prefill_fn)/embed/gather"
    assert scope_time.scope_of(inside) == (1, "mla")
    table = scope_time.by_scope({
        "events": [(0, 2, name, "fusion.756", 0.0, 2 * MS),
                   (0, 2, name, "copy.528", 2 * MS, 1 * MS)],
        "runs": [], "spans": [],
        "programs": {2: {"module": name, "ops": ops}}}, 0.0, 10 * MS)
    assert table["classes"]["attention"] == pytest.approx(2 * MS)
    assert table["by_inside"] == pytest.approx(2 * MS)
    assert table["classes"]["unnamed"] == pytest.approx(1 * MS)


def test_fields_refuses_a_message_cut_short():
    with pytest.raises((ValueError, IndexError)):
        list(scope_time.fields(bytes([0x0A, 0x05, 0x01])))
    with pytest.raises(ValueError):
        list(scope_time.fields(bytes([0x0B])))      # a group: not read


# -- never out of a reader ----------------------------------------------------

@pytest.fixture(autouse=True)
def trace_root(tmp_path, monkeypatch):
    """No test reads benchmark/.trace: the root is the test's own."""
    monkeypatch.setattr(scope_time, "TRACE_ROOT", str(tmp_path))

def obs_with(trace, **more):
    lines = []
    return dict({"trace": trace, "log": lines.append,
                 "traced_steps": [{}] * 4}, **more), lines


REDUCED = {"op_seconds": {"fusion.1": 1.0}, "op_calls": {"fusion.1": 1},
           "chips": 1}


def test_nothing_to_read_on_an_empty_directory():
    obs, lines = obs_with(REDUCED)
    assert scope_time.per_step_ms(obs, "attention") is None
    assert scope_time.for_obs(obs) is None
    # one line says why, once: the table is computed once for ten readers
    assert len(lines) == 1 and "nothing to read" in lines[0]
    assert "FileNotFoundError" in lines[0]


def test_nothing_to_read_without_a_reduced_trace():
    obs, lines = obs_with(None)
    assert scope_time.per_step_ms(obs, "ffn") is None
    assert len(lines) == 1 and "no reduced trace" in lines[0]


def test_nothing_to_read_when_the_log_itself_raises():
    def broken(_msg):
        raise RuntimeError("a log that raises")

    obs = {"trace": REDUCED, "log": broken}
    assert scope_time.per_step_ms(obs, "head") is None


def test_nothing_to_read_without_the_metadata_plane(tmp_path, monkeypatch):
    """A trace whose file holds no HLO modules: one line, no number."""
    path = tmp_path / "cell" / "plugins" / "profile" / "t"
    path.mkdir(parents=True)
    (path / "host.xplane.pb").write_bytes(b"")
    monkeypatch.setattr(
        scope_time, "load",
        lambda p: {"events": [], "runs": [], "spans": [],
                   "programs": scope_time.programs_of(
                       open(p, "rb").read())})
    obs, lines = obs_with(REDUCED)
    assert scope_time.per_step_ms(obs, "unnamed") is None
    assert len(lines) == 1 and scope_time.METADATA_PLANE in lines[0]


def test_nothing_to_read_when_a_cross_check_fails(tmp_path, monkeypatch):
    path = tmp_path / "cell" / "plugins" / "profile" / "t"
    path.mkdir(parents=True)
    (path / "host.xplane.pb").write_bytes(b"")
    events = [event(1, "fusion.77", 1.0, 2.0),
              event(1, "paged_decode.2", 3.0, 1.0)]
    monkeypatch.setattr(
        scope_time, "load",
        lambda p: {"events": events, "runs": [], "programs": PROGRAMS,
                   "spans": [("bench.engine_step", 0.0, 10 * MS)]})
    # the reduced trace saw the same 3 ms and the same kernel: numbers
    good = {"op_seconds": {"fusion.77": 2 * MS, "paged_decode": 1 * MS}}
    obs, lines = obs_with(good)
    assert scope_time.per_step_ms(obs, "attention") \
        == pytest.approx(3.0 / 4)
    assert scope_time.per_step_ms(obs, "unnamed") == 0.0
    assert any("booked by kernel name alone" in line for line in lines)
    assert not any("FAILED" in line for line in lines)
    # it saw twice the time: the classes' sum is off, every metric None
    obs, lines = obs_with({"op_seconds": {"fusion.77": 5 * MS,
                                          "paged_decode": 1 * MS}})
    assert scope_time.per_step_ms(obs, "attention") is None
    assert any("classes' sum" in line and "FAILED" in line
               for line in lines)
    # the sum agrees, a kernel's own seconds do not
    obs, lines = obs_with({"op_seconds": {"fusion.77": 1 * MS,
                                          "paged_decode": 2 * MS}})
    assert scope_time.per_step_ms(obs, "attention") is None
    assert any("kernel paged_decode" in line and "FAILED" in line
               for line in lines)
    # no traced step to divide by
    obs, lines = obs_with(good, traced_steps=[])
    assert scope_time.per_step_ms(obs, "attention") is None


def test_the_newest_trace_is_read_when_a_crashed_run_left_another(tmp_path):
    for cell, age in (("old-cell", 100), ("new-cell", 0)):
        path = tmp_path / cell / "plugins" / "profile" / "t"
        path.mkdir(parents=True)
        file = path / "host.xplane.pb"
        file.write_bytes(b"")
        stamp = os.path.getmtime(str(file)) - age
        os.utime(str(file), (stamp, stamp))
    assert "new-cell" in scope_time.find_xplane()


def test_every_scope_metric_has_a_reader_and_a_workloads_list():
    import run as bench

    manifest = bench.load_json(os.path.join(bench.ROOT, "BENCHMARK.json"))
    metrics = [m for m in manifest["per_layer"] if ".scope." in m["name"]]
    assert len(metrics) == 10
    serving = {w["name"] for w in manifest["workloads"]
               if w["traffic"].endswith("backlog")}
    for metric in metrics:
        cls = metric["name"].split(".scope.")[1][:-len("_ms")]
        assert cls in scope_time.CLASSES
        assert (metric["unit"], metric["better"], metric["source"],
                metric["layer"]) == ("ms", "lower", "device_trace", "model")
        cells = set(metric["workloads"])
        if metric["name"].startswith("serve."):
            assert metric["moves"] == "itl_p95_ms" and cells <= serving
        else:
            assert metric["moves"] == "train_tok_s_chip"
            assert not cells & serving
        obs, lines = obs_with(None)
        reader = bench.load_module("layer_metrics", metric["name"])
        assert reader.read(obs) is None and len(lines) == 1
    files = glob.glob(os.path.join(bench.HERE, "layer_metrics",
                                   "*.scope.*_ms.py"))
    assert len(files) == 10
