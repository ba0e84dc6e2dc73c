"""From a profiler trace to numbers: device busy time, time by kernel or
operation name, and idle gaps by what the host had open.

The input is the ``.xplane.pb`` the JAX profiler writes. ``load`` turns
it into plain lists with ``jax.profiler.ProfileData``; ``reduce`` works
on those lists alone, so the tests drive it with hand-built traces.

What a TPU trace looks like (TPU v5 lite, jax 0.9.0): one plane per chip
named ``/device:TPU:<n>`` whose line ``XLA Ops`` holds one event per
executed HLO instruction, named by the instruction's whole text
(``%paged_decode.12 = bf16[...] custom-call(...)``); the host plane
``/host:CPU`` holds a line per thread, and ``TraceAnnotation`` spans are
events on the thread that opened them. Both are on one clock.

Names. An event's name is cut to the instruction's own short name
(``fusion.77``). A Mosaic kernel — an instruction whose text carries
``tpu_custom_call``, or whose short name the caller maps to a kernel —
goes under its ``pallas_call`` name with every call and every ``.N``
suffix merged (``paged_decode``).
"""
from __future__ import annotations

import bisect
import glob
import os
import re

DEVICE_PLANE_RE = re.compile(r"^/device:(TPU|GPU):(\d+)")
OPS_LINE = "XLA Ops"
MOSAIC_TARGET = "tpu_custom_call"
_PALLAS_SCOPE_RE = re.compile(r'op_name="(?:[^"]*/)?([^/"]+)/pallas_call')
_LHS_NAME_RE = re.compile(r"\s*(?:ROOT\s+)?%?([\w.-]+)\s*=")
_SUFFIX_RE = re.compile(r"^(.*?)(?:\.\d+)+$")
NO_SPAN = "(no span open)"
OTHERS = "(all others)"


def start(trace_dir):
    """Start the JAX profiler writing under ``trace_dir``, without the
    Python tracer: the spans the reduction reads are TraceAnnotations,
    and a call stack per Python frame would bury them."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)


def stop():
    import jax

    jax.profiler.stop_trace()


def short_name(event_name):
    """``%fusion.77 = f32[..] fusion(...)`` -> ``fusion.77``; a name that
    is already short stays as it is."""
    m = _LHS_NAME_RE.match(event_name)
    if m:
        return m.group(1)
    return event_name.strip().lstrip("%").split(" ", 1)[0]


def strip_suffix(name):
    m = _SUFFIX_RE.match(name)
    return m.group(1) if m else name


def kernel_instructions(hlo_text):
    """{instruction short name: pallas_call name} for every Mosaic custom
    call of a compiled HLO module. The kernel's name is the path element
    before ``pallas_call`` in the instruction's ``op_name`` metadata, bare
    or inside autodiff wrappers (``transpose(jvp(flash_dq))``); without
    it, the instruction's own name less its ``.N`` suffix."""
    out = {}
    marker = 'custom_call_target="%s"' % MOSAIC_TARGET
    for line in hlo_text.splitlines():
        if marker not in line:
            continue
        lhs = _LHS_NAME_RE.match(line)
        instr = lhs.group(1) if lhs else "?"
        scope = _PALLAS_SCOPE_RE.search(line)
        inner = re.findall(r"[\w.-]+", scope.group(1)) if scope else []
        out[instr] = inner[-1] if inner else strip_suffix(instr)
    return out


def kernel_counts(hlo_text):
    """{kernel name: number of calls in the module}."""
    counts = {}
    for kernel in kernel_instructions(hlo_text).values():
        counts[kernel] = counts.get(kernel, 0) + 1
    return counts


def check_kernels(hlo_text, wanted, cfg, what):
    """The kernels-present check behind ``correct``. ``wanted`` is a mix's
    ``kernels``: {kernel name: calls the compiled step must hold}, where
    a string is a key of the configuration (one call a layer) and None
    asks only that the kernel be there. -> ({instruction: kernel} for the
    reduction, (ok, detail))."""
    found = kernel_counts(hlo_text)
    wanted = {k: (cfg[n] if isinstance(n, str) else n)
              for k, n in wanted.items()}
    ok = all(found.get(k, 0) > 0 and n in (None, found.get(k))
             for k, n in wanted.items())
    return kernel_instructions(hlo_text), (
        ok, "%s holds %s, wanted %s" % (what, found, wanted))


def op_name(event_name, kernels=None):
    """The name an event is booked under: (name, is_kernel)."""
    short = short_name(event_name)
    if kernels:
        if short in kernels:
            return kernels[short], True
        base = strip_suffix(short)
        if base in kernels.values():
            return base, True
    if MOSAIC_TARGET in event_name:
        return strip_suffix(short), True
    return short, False


def find_xplane(trace_dir):
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError("no .xplane.pb under %s" % trace_dir)
    return paths[-1]


def load(xplane_path, span_prefixes=("bench.", "serving.")):
    """-> {"devices": {chip: [(name, start_s, dur_s), ...]},
           "host": [(name, start_s, dur_s), ...]}: the device planes'
    ``XLA Ops`` events and the host spans whose names start with one of
    ``span_prefixes``."""
    import jax

    data = jax.profiler.ProfileData.from_file(xplane_path)
    devices, host = {}, []
    for plane in data.planes:
        m = DEVICE_PLANE_RE.match(plane.name)
        if m:
            events = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    events.append((ev.name, ev.start_ns * 1e-9,
                                   ev.duration_ns * 1e-9))
            devices[int(m.group(2))] = events
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(span_prefixes):
                        host.append((ev.name, ev.start_ns * 1e-9,
                                     ev.duration_ns * 1e-9))
    return {"devices": devices, "host": host}


def _union(intervals):
    """Sorted, merged [start, end] lists of possibly overlapping
    intervals."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1][1] = end
        else:
            merged.append([start, end])
    return merged


def _self_times(events):
    """[(name, self seconds)]: an event's duration less the part its
    nested events on the same line cover (a ``while`` holds its body's
    operations), so that times by name add up to busy time."""
    out = []
    stack = []          # [name, end, self]
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        end = start + dur
        while stack and start >= stack[-1][1]:
            done = stack.pop()
            out.append((done[0], done[2]))
        if stack:
            stack[-1][2] -= min(end, stack[-1][1]) - start
        stack.append([name, end, dur])
    while stack:
        done = stack.pop()
        out.append((done[0], done[2]))
    return out


def _segments(spans):
    """The host timeline cut at every span boundary: sorted
    (start, end, innermost span's name) with no overlap. Innermost is the
    open span that started last."""
    points = sorted({p for _, s, d in spans for p in (s, s + d)})
    by_start = sorted(spans, key=lambda s: s[1])
    segs, open_spans, i = [], [], 0
    for left, right in zip(points, points[1:]):
        while i < len(by_start) and by_start[i][1] <= left:
            open_spans.append(by_start[i])
            i += 1
        open_spans = [s for s in open_spans if s[1] + s[2] > left]
        if open_spans:
            inner = max(open_spans, key=lambda s: (s[1], -s[2]))
            segs.append((left, right, inner[0]))
    return segs


def _clip(intervals, lo, hi):
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if e > lo and s < hi]


def reduce(loaded, window_spans, kernels=None, top=10):
    """The reduced trace every layer metric reads.

    ``window_spans``: names of the benchmark's own host spans; the traced
    window runs from the first one's start to the last one's end, and
    only device time inside it counts. ``kernels``: {instruction short
    name: kernel name} from ``kernel_instructions`` of the compiled step.

    -> {"window_s", "busy_s" (mean over chips), "chips",
        "op_seconds": {name: s, mean over chips}, "op_calls": {name: n,
        over all chips}, "device_ops": top list,
        "idle_gaps": top list of [innermost host span, idle seconds]}
    or None when the trace holds no window span or no device event."""
    marks = [(s, s + d) for n, s, d in loaded["host"] if n in window_spans]
    devices = {c: ev for c, ev in loaded["devices"].items() if ev}
    if not marks or not devices:
        return None
    lo = min(m[0] for m in marks)
    hi = max(m[1] for m in marks)
    window = hi - lo
    op_seconds, op_calls, idle = {}, {}, {}
    busy_total = 0.0
    segs = _segments([s for s in loaded["host"]
                      if s[1] < hi and s[1] + s[2] > lo])
    seg_starts = [s[0] for s in segs]
    for events in devices.values():
        inside = [(n, s, d) for n, s, d in events
                  if s >= lo and s + d <= hi]
        busy = _clip(_union([(s, s + d) for _, s, d in inside]), lo, hi)
        busy_total += sum(e - s for s, e in busy)
        for raw, self_s in _self_times(inside):
            name, _ = op_name(raw, kernels)
            op_seconds[name] = op_seconds.get(name, 0.0) + self_s
            op_calls[name] = op_calls.get(name, 0) + 1
        edges = [lo] + [p for iv in busy for p in iv] + [hi]
        for gap_lo, gap_hi in zip(edges[0::2], edges[1::2]):
            if gap_hi <= gap_lo:
                continue
            covered = 0.0
            j = max(bisect.bisect_right(seg_starts, gap_lo) - 1, 0)
            while j < len(segs) and segs[j][0] < gap_hi:
                part = min(segs[j][1], gap_hi) - max(segs[j][0], gap_lo)
                if part > 0:
                    idle[segs[j][2]] = idle.get(segs[j][2], 0.0) + part
                    covered += part
                j += 1
            rest = (gap_hi - gap_lo) - covered
            if rest > 0:
                idle[NO_SPAN] = idle.get(NO_SPAN, 0.0) + rest
    chips = len(devices)
    op_seconds = {n: s / chips for n, s in op_seconds.items()}
    idle = {n: s / chips for n, s in idle.items()}
    def top_list(table):
        """The ``top`` largest rows; when there are more, the last place
        goes to what all the others add up to."""
        rows = sorted(table.items(), key=lambda kv: -kv[1])
        if len(rows) > top:
            rest = sum(seconds for _, seconds in rows[top - 1:])
            rows = rows[:top - 1] + [(OTHERS, rest)]
        return [[name, seconds] for name, seconds in rows]

    return {
        "window_s": window,
        "busy_s": busy_total / chips,
        "chips": chips,
        "op_seconds": op_seconds,
        "op_calls": op_calls,
        "device_ops": top_list(op_seconds),
        "idle_gaps": top_list(idle),
    }
