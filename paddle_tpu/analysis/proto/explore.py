"""ptcheck exploration engine: bounded DFS + seeded random walk.

Exploration is **stateless replay**: a schedule is a list of transition
tokens, and every run re-executes the fixture from scratch under a
prefix of choices — so any state the explorer ever reaches is
reproducible from its token string alone (the replay contract:
``tools/ptcheck.py --replay "<fixture>:<tok,tok,...>"``).

DFS walks the tree of schedules: a run follows its prefix, then
extends with the first enabled transition at every choice point,
queueing each unexplored sibling as a new prefix. State-fingerprint
dedup (store state + per-task op/result history + budgets — exact
tuples, not hashes) prunes converging interleavings, which is what
makes 3-rank × 2-generation protocols exhaustible in seconds.

The random-walk mode drives the same runner with a seeded RNG picking
among enabled transitions — depth the DFS budget cannot reach, still
perfectly replayable (the failing walk's concrete schedule is printed,
and the seed re-derives it).
"""
from __future__ import annotations

import random
import time

from .sched import ReplayDivergence, Scheduler, VirtualClock
from .simstore import SimClient

_REAL_MONOTONIC = time.monotonic

# engine-level property ids (fixtures add their own)
DEADLOCK = "deadlock"           # blocked forever, no timeout to unwind
SCHEDULE_BUDGET = "schedule-budget"  # a run never terminated: a
#                                      protocol loop unbounded in sim
#                                      steps (a hot spin in real life)
REGRESSION_POWER = "regression-power"  # an expected-finding fixture
#                                        came back clean


class ProtoFinding:
    """One property violation on one explored schedule."""

    __slots__ = ("fixture", "prop", "message", "schedule", "mode",
                 "seed")

    def __init__(self, fixture, prop, message, schedule, mode="dfs",
                 seed=None):
        self.fixture = fixture
        self.prop = prop
        self.message = message
        self.schedule = schedule    # comma-joined token string
        self.mode = mode
        self.seed = seed

    @property
    def replay(self):
        return "%s:%s" % (self.fixture, self.schedule)

    def to_dict(self):
        out = {"fixture": self.fixture, "property": self.prop,
               "message": self.message, "schedule": self.schedule,
               "mode": self.mode, "replay": self.replay}
        if self.seed is not None:
            out["seed"] = self.seed
        return out

    def __repr__(self):
        return "ProtoFinding(%s/%s)" % (self.fixture, self.prop)


class Scenario:
    """One buildable system-under-test: a scheduler + store + tasks.
    Fixtures construct a FRESH one per run (stateless replay)."""

    def __init__(self, store, max_crashes=0, max_lost_acks=0,
                 patch_time=False, clock_start=0.0):
        self.store = store
        self.sched = Scheduler(clock=VirtualClock(clock_start),
                               max_crashes=max_crashes,
                               max_lost_acks=max_lost_acks,
                               patch_time=patch_time)
        self.sched.store = store
        self.log = self.sched.log

    def client(self, name, timeout_s=None, is_master=False):
        return SimClient(self.store, self.sched, name,
                         timeout_s=timeout_s, is_master=is_master)

    def task(self, name, fn, crashable=False):
        return self.sched.spawn(name, fn, crashable=crashable)


class RunResult:
    """What one explored schedule produced — the verdicts' input."""

    def __init__(self, scenario):
        sched = scenario.sched
        self.schedule = list(sched.schedule)
        self.events = list(sched.events)
        self.log = list(sched.log)
        self.store = scenario.store
        self.truncated = sched.truncated
        self.tasks = {
            name: {"status": t.status, "killed": t.killed,
                   "error": t.error, "result": t.result,
                   "op_count": t.op_count}
            for name, t in sched.tasks.items()}
        self.crashes = sorted(
            name for name, t in sched.tasks.items()
            if t.status == "crashed" and not t.killed)
        self.lost_acks = sum(1 for tok in self.schedule
                             if tok.startswith("a:"))

    @property
    def schedule_str(self):
        return ",".join(self.schedule)

    @property
    def hangs(self):
        return [p for k, p in self.events if k == "hang"]

    @property
    def deadlocks(self):
        return [p for k, p in self.events if k == "deadlock"]

    def errors(self):
        return {name: t["error"] for name, t in self.tasks.items()
                if t["error"] is not None}

    @property
    def fault_free(self):
        return not self.crashes and self.lost_acks == 0


def run_once(fixture, prefix, visited=None, collect=False,
             max_steps=None, require_full_prefix=False):
    """Execute one schedule: follow ``prefix``, then default-extend
    (first enabled token). With ``collect``, unexplored siblings of
    every new state past the prefix come back as fresh prefixes.
    ``require_full_prefix`` (the replay contract) refuses a run that
    terminated before consuming every prefix token — a schedule the
    current code no longer reaches must DIVERGE, never be judged as a
    different, shorter run."""
    scenario = fixture.build()
    sched = scenario.sched
    steps = max_steps if max_steps is not None else fixture.max_steps
    branches = []
    pos = [0]

    def chooser(tokens, fp):
        if pos[0] < len(prefix):
            tok = prefix[pos[0]]
            pos[0] += 1
            return tok
        if collect and visited is not None:
            if fp not in visited:
                visited.add(fp)
                base = list(sched.schedule)
                for tok in tokens[1:]:
                    branches.append(base + [tok])
        return tokens[0]

    sched.run(chooser, max_steps=steps)
    if require_full_prefix and pos[0] < len(prefix):
        raise ReplayDivergence(
            "run terminated after %d of %d schedule token(s) — the "
            "remaining %s were never reachable (the schedule does not "
            "belong to this fixture/build)"
            % (pos[0], len(prefix), ",".join(prefix[pos[0]:])))
    result = RunResult(scenario)
    return result, branches


def _engine_findings(fixture, result):
    out = []
    for d in result.deadlocks:
        out.append((DEADLOCK,
                    "hard deadlock: tasks %s blocked with no timeout "
                    "and no enabled transition" % ",".join(d["blocked"])))
    if result.truncated:
        out.append((SCHEDULE_BUDGET,
                    "run never terminated within %d scheduler steps — "
                    "an unbounded protocol loop (a hot spin in real "
                    "time)" % fixture.max_steps))
    return out


def _judge(fixture, result, mode, seed):
    """Fixture verdict + engine properties -> ProtoFindings."""
    out = []
    props = _engine_findings(fixture, result)
    if not result.truncated:
        props += list(fixture.verdict(result))
    for prop, message in props:
        out.append(ProtoFinding(fixture.name, prop, message,
                                result.schedule_str, mode=mode,
                                seed=seed))
    return out


def dfs_explore(fixture, max_schedules=None, wall_s=None):
    """Bounded exhaustive DFS with state dedup. Returns
    (findings, stats)."""
    budget = max_schedules if max_schedules is not None \
        else fixture.max_schedules
    wall = wall_s if wall_s is not None else fixture.wall_s
    t0 = _REAL_MONOTONIC()
    visited = set()
    pending = [[]]
    findings = {}
    stats = {"schedules": 0, "truncated": 0, "hangs": 0,
             "exhausted": False}
    while pending:
        if stats["schedules"] >= budget \
                or _REAL_MONOTONIC() - t0 > wall:
            break
        prefix = pending.pop()
        result, branches = run_once(fixture, prefix, visited=visited,
                                    collect=True)
        stats["schedules"] += 1
        stats["truncated"] += int(result.truncated)
        stats["hangs"] += len(result.hangs)
        for f in _judge(fixture, result, "dfs", None):
            findings.setdefault((f.prop, f.message), f)
        pending.extend(branches)
    stats["exhausted"] = not pending
    stats["states"] = len(visited)
    stats["wall_s"] = round(_REAL_MONOTONIC() - t0, 3)
    return list(findings.values()), stats


def random_walk(fixture, seed, walks=None, wall_s=None):
    """Seeded random exploration for schedules deeper than the DFS
    budget. Each walk's concrete schedule is recorded, so a finding
    replays from either the seed or the token string."""
    n = walks if walks is not None else fixture.walks
    wall = wall_s if wall_s is not None else fixture.wall_s
    t0 = _REAL_MONOTONIC()
    findings = {}
    stats = {"schedules": 0, "truncated": 0, "hangs": 0, "seed": seed}
    for walk in range(n):
        if _REAL_MONOTONIC() - t0 > wall:
            break
        rng = random.Random("%s:%s:%s" % (fixture.name, seed, walk))
        scenario = fixture.build()

        def chooser(tokens, fp, rng=rng):
            return rng.choice(tokens)

        scenario.sched.run(chooser, max_steps=fixture.max_steps)
        result = RunResult(scenario)
        stats["schedules"] += 1
        stats["truncated"] += int(result.truncated)
        stats["hangs"] += len(result.hangs)
        for f in _judge(fixture, result, "walk", seed):
            findings.setdefault((f.prop, f.message), f)
    stats["wall_s"] = round(_REAL_MONOTONIC() - t0, 3)
    return list(findings.values()), stats


def replay_schedule(fixture, schedule_str):
    """Re-run one schedule exactly (the ``--replay`` contract).
    Raises ReplayDivergence when a token is not enabled — the
    schedule does not belong to this fixture/build."""
    tokens = [t for t in schedule_str.split(",") if t]
    result, _ = run_once(fixture, tokens, require_full_prefix=True)
    findings = _judge(fixture, result, "replay", None)
    return result, findings


def run_fixtures(registry, names=None, mode="dfs", seed=0,
                 config=None):
    """Run the registered fixtures; returns (report, gate_findings).

    Live fixtures gate on zero findings. ``expect_finding`` fixtures
    are regression power checks: the historical bug must be FOUND
    (its findings are reported but do not gate); a clean run of one
    is itself a gate finding (the checker lost the power that
    justifies trusting its zeros).
    """
    cfg = dict(config or {})
    chosen = sorted(registry) if names is None else list(names)
    report = {"kind": "ptcheck_report", "version": 1, "mode": mode,
              "fixtures": {}}
    if mode == "walk":
        report["seed"] = seed
    gate = []
    for name in chosen:
        fixture = registry[name]
        kwargs = {"wall_s": cfg.get("wall_s")}
        if mode == "walk":
            findings, stats = random_walk(
                fixture, seed, walks=cfg.get("walks"), **kwargs)
        else:
            findings, stats = dfs_explore(
                fixture, max_schedules=cfg.get("max_schedules"),
                **kwargs)
        row = {"doc": fixture.doc,
               "expect_finding": fixture.expect_finding,
               "findings": [f.to_dict() for f in findings]}
        row.update(stats)
        if fixture.expect_finding:
            # the HISTORICAL property must be re-found — an engine
            # schedule-budget finding (truncated runs after some
            # refactor) is not evidence of power, it is noise that
            # would otherwise keep this gate green forever
            expected = set(fixture.expected_props) or None
            hits = [f for f in findings
                    if expected is None or f.prop in expected]
            row["found_expected"] = bool(hits)
            if not hits:
                gate.append(ProtoFinding(
                    name, REGRESSION_POWER,
                    "expected-finding fixture came back clean (no "
                    "finding in %s): the checker no longer finds the "
                    "known historical bug within its budget"
                    % (sorted(expected) if expected
                       else "any property"), "", mode=mode,
                    seed=seed if mode == "walk" else None))
        else:
            gate.extend(findings)
        report["fixtures"][name] = row
    report["findings"] = [f.to_dict() for f in gate]
    report["clean"] = not gate
    return report, gate


def render_proto_text(report):
    lines = []
    for name in sorted(report["fixtures"]):
        row = report["fixtures"][name]
        verdict = "clean"
        if row.get("expect_finding"):
            verdict = ("found expected bug"
                       if row.get("found_expected")
                       else "MISSED EXPECTED BUG")
        elif row["findings"]:
            verdict = "%d finding(s)" % len(row["findings"])
        lines.append(
            "%-16s %-22s schedules=%-5d states=%-6s hangs=%-4d %gs"
            % (name, verdict, row.get("schedules", 0),
               row.get("states", "-"), row.get("hangs", 0),
               row.get("wall_s", 0)))
        for f in row["findings"]:
            mark = ("  [expected] " if row.get("expect_finding")
                    else "  FINDING ")
            lines.append("%s%s: %s" % (mark, f["property"],
                                       f["message"]))
            if f.get("schedule"):
                lines.append("    replay: --replay %r" % f["replay"])
    n = len(report.get("findings", ()))
    lines.append("ptcheck: %d gate finding(s) across %d fixture(s)"
                 % (n, len(report["fixtures"])))
    return "\n".join(lines)


def render_proto_json(report, meta=None):
    out = dict(report)
    if meta:
        out["meta"] = dict(meta)
    return out
