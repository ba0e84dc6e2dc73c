"""ptcheck fixtures: the registered protocol properties.

Each fixture builds a fresh scenario per explored schedule (tasks =
ranks running the REAL protocol code over a ``SimStore``) and judges
one run's outcome against its machine-checked properties. Live
fixtures (``barrier``, ``election``, ``elastic``, ``bundle``,
``idempotence``) must come back clean on every explored schedule —
they gate CI. ``expect_finding`` fixtures reintroduce known historical
bugs (the pre-PR-7 count+go barrier, the non-idempotent retried
``add``) and must be FOUND within the default budget: they are the
proof the checker has the power its zeros claim.

Protocol code under test, unmodified:

- ``TCPStore.barrier`` (distributed/store.py) — invoked unbound on the
  sim client: round-based, (name, world_size)-namespaced counters;
- ``resilience.protocol.rebuild_membership`` — leader election +
  newest-common-snapshot agreement + generation barrier;
- ``ElasticManager`` (distributed/elastic.py) — TTL membership aging
  on an injected clock;
- ``monitor.watchdog`` bundle helpers — request/answer/gather with
  nonce matching and stale-bundle supersede.
"""
from __future__ import annotations

import json

from .explore import Scenario
from .simstore import SimStore

PROTO_FIXTURES = {}


def register(cls):
    fixture = cls()
    PROTO_FIXTURES[fixture.name] = fixture
    return cls


class ProtoFixture:
    """Base: budgets + the verdict helpers fixtures share."""

    name = None
    doc = ""
    expect_finding = False
    # expect_finding fixtures name WHICH property ids count as the
    # historical bug being re-found: an engine-level schedule-budget
    # finding (a truncated run after some refactor) must not satisfy
    # the regression-power gate by accident
    expected_props = ()
    max_schedules = 400
    max_steps = 300
    wall_s = 25.0
    walks = 80

    def build(self):
        raise NotImplementedError

    def verdict(self, result):
        raise NotImplementedError

    # -- shared property checks ------------------------------------------

    @staticmethod
    def _liveness(result, prop, fault_free_only=True, hangs=True):
        """Errors/hangs are findings (on fault-free schedules when
        ``fault_free_only``: with explored crashes, a clean raise is
        the documented contract, not a bug). ``hangs=False`` for
        protocols whose NORMAL operation waits out a bounded timeout
        window (the watchdog gather) — there, liveness is completion
        plus a bounded schedule, not the absence of blocked states."""
        out = []
        if fault_free_only and not result.fault_free:
            return out
        for name, err in sorted(result.errors().items()):
            out.append((prop, "task %r failed on a fault-free "
                        "schedule: %r" % (name, err)))
        if hangs:
            for hang in result.hangs:
                out.append((prop, "all live tasks blocked (hang) on "
                            "a fault-free schedule: %s" % json.dumps(
                                hang["blocked"], sort_keys=True)))
        return out

    @staticmethod
    def _clean_failures(result, prop,
                        allowed=(RuntimeError, TimeoutError)):
        """Whatever happens, a task may only fail by RAISING one of
        the protocol's contractual error types — never by wedging or
        by dying with an unrelated exception."""
        out = []
        for name, err in sorted(result.errors().items()):
            if not isinstance(err, allowed):
                out.append((prop, "task %r failed with a "
                            "non-contractual error type: %r"
                            % (name, err)))
        for name, t in sorted(result.tasks.items()):
            if t["killed"]:
                out.append((prop, "task %r never terminated (killed "
                            "at run end)" % name))
        return out


# -- barrier round-safety ----------------------------------------------------

def _barrier_round_safety(result, plan, prop="barrier-round-safety"):
    """No rank is released from a generation before every planned
    participant of that generation has arrived. Tasks log ("arrive",
    rank, gen) / ("release", rank, gen); the scheduler appends in
    schedule order, so the log IS the happens-before ordering."""
    out = []
    arrived = {}
    for ev in result.log:
        if ev[0] == "arrive":
            arrived.setdefault(ev[2], set()).add(ev[1])
        elif ev[0] == "release":
            _, rank, gen = ev
            missing = plan[gen] - arrived.get(gen, set())
            if missing:
                out.append((prop,
                            "rank %d released from generation %d "
                            "before rank(s) %s arrived — the barrier "
                            "leaked a round" % (
                                rank, gen,
                                ",".join(map(str, sorted(missing))))))
    return out


@register
class BarrierFixture(ProtoFixture):
    """The live round-based barrier: reuse across generations,
    including a SHRUNK world on the same name (the elastic-restart
    shape), under every interleaving and a retried arrival."""

    name = "barrier"
    doc = ("round-based store barrier: name reuse across same-size "
           "and shrunk generations; no hang, no timeout, no round "
           "leak; arrival retry (lost ack) stays exact; the master "
           "(r0) waits for every peer's confirmation")
    max_schedules = 500
    max_steps = 250
    # gen -> planned participants; gen 3 is the shrunk restart world
    plan = {1: {0, 1, 2}, 2: {0, 1, 2}, 3: {0, 1}}

    def build(self):
        scenario = Scenario(SimStore(), max_lost_acks=1)
        log = scenario.log

        def mk(rank):
            client = scenario.client("r%d" % rank, is_master=(rank == 0))

            def fn():
                for gen, world in ((1, 3), (2, 3), (3, 2)):
                    if rank not in self.plan[gen]:
                        return
                    log.append(("arrive", rank, gen))
                    client.barrier("x", world, timeout_s=5.0)
                    log.append(("release", rank, gen))

            return fn

        for rank in range(3):
            scenario.task("r%d" % rank, mk(rank))
        return scenario

    def verdict(self, result):
        out = self._liveness(result, "barrier-liveness",
                             fault_free_only=False)
        out += _barrier_round_safety(result, self.plan)
        return out


def _legacy_count_go_barrier(store, name, world_size, timeout_s=None):
    """The pre-PR-7 barrier, verbatim shape: one forever-lived count
    counter + one go key. Kept ONLY as the historical-bug regression
    fixture — the checker must find its name-reuse hang."""
    n = store.add("__legacy/%s/count" % name, 1)
    if n == world_size:
        store.set("__legacy/%s/go" % name, b"1")
    if store.get("__legacy/%s/go" % name, timeout_s) is None:
        raise TimeoutError("legacy barrier %r timed out (%d arrived)"
                           % (name, n))


@register
class LegacyBarrierFixture(ProtoFixture):
    """Reintroduces the historical count+go barrier: a rank dies
    before arriving, the survivors time out and retry the SAME name
    with the shrunk world — and the stale count strands them forever
    (counts 3,4 can never equal world_size 2). The checker must
    surface the hang + the timeout deterministically."""

    name = "barrier_legacy"
    doc = ("HISTORICAL BUG (pre-PR-7 count+go barrier): name reuse "
           "after a shrunk restart hangs — the checker must find it")
    expect_finding = True
    expected_props = ("barrier-liveness", "barrier-round-safety",
                      "deadlock")
    max_schedules = 300
    max_steps = 200
    plan = {1: {0, 1, 2}, 2: {0, 1}}

    def build(self):
        scenario = Scenario(SimStore())
        log = scenario.log

        def mk(rank):
            client = scenario.client("r%d" % rank)

            def fn():
                if rank == 2:
                    return          # died before arriving (gen 1)
                log.append(("arrive", rank, 1))
                try:
                    _legacy_count_go_barrier(client, "x", 3,
                                             timeout_s=2.0)
                    log.append(("release", rank, 1))
                except TimeoutError:
                    pass            # detected the death; restart:
                log.append(("arrive", rank, 2))
                _legacy_count_go_barrier(client, "x", 2,
                                         timeout_s=2.0)
                log.append(("release", rank, 2))

            return fn

        for rank in range(3):
            scenario.task("r%d" % rank, mk(rank))
        return scenario

    def verdict(self, result):
        out = self._liveness(result, "barrier-liveness",
                             fault_free_only=False)
        out += _barrier_round_safety(result, self.plan)
        return out


# -- leader election + snapshot agreement ------------------------------------

@register
class ElectionFixture(ProtoFixture):
    """The REAL recovery agreement (resilience.protocol.
    rebuild_membership) under crash-at-any-op-boundary and a retried
    leader claim: exactly one generation leader, survivors agree on
    members + the newest COMMON snapshot step, failures are clean
    raises."""

    name = "election"
    doc = ("rebuild_membership: leader uniqueness under any 1-rank "
           "crash at any op boundary + retried (lost-ack) claims; "
           "snapshot-step agreement among completers; clean failures")
    max_schedules = 600
    max_steps = 300
    base = "job/resilience/gen1"
    snapshots = {0: [10, 20], 1: [10, 20], 2: [10]}

    def build(self):
        from ...resilience.protocol import rebuild_membership

        scenario = Scenario(SimStore(), max_crashes=1,
                            max_lost_acks=1)
        log = scenario.log

        def mk(rank):
            client = scenario.client("r%d" % rank)

            def fn():
                info = rebuild_membership(
                    client, self.base, rank, [0, 1, 2], [3],
                    self.snapshots[rank], 1, timeout_s=5.0)
                log.append(("done", rank, tuple(info["members"]),
                            int(info["resume_step"])))
                return info

            return fn

        for rank in range(3):
            scenario.task("r%d" % rank, mk(rank), crashable=True)
        return scenario

    def verdict(self, result):
        out = []
        winners = [cid for cid, value in
                   result.store.observed_adds(self.base + "/leader")
                   if value == 1]
        if len(winners) > 1:
            out.append(("leader-unique",
                        "more than one rank observed leader claim "
                        "value 1: %s" % ",".join(sorted(winners))))
        done = [ev for ev in result.log if ev[0] == "done"]
        agreed = {(members, resume) for _, _, members, resume in done}
        if len(agreed) > 1:
            out.append(("snapshot-agreement",
                        "completing ranks disagree on (members, "
                        "resume_step): %s" % sorted(agreed)))
        if result.fault_free:
            if len(done) != 3 or agreed != {((0, 1, 2), 10)}:
                out.append(("election-liveness",
                            "fault-free schedule did not complete "
                            "with members=(0,1,2) resume=10 on all "
                            "ranks: done=%s errors=%s"
                            % (sorted(done),
                               sorted(result.errors().items()))))
        out += self._liveness(result, "election-liveness")
        out += self._clean_failures(result, "election-clean-failure")
        return out


# -- retried-add idempotence -------------------------------------------------

class _AddScenarioMixin:
    """Two clients, two adds each on one counter, plus one leader
    claim each — the exact shapes election and the barrier count on."""

    def _build(self, idempotent):
        scenario = Scenario(SimStore(idempotent_add=idempotent),
                            max_lost_acks=2)
        log = scenario.log

        def mk(rank):
            client = scenario.client("c%d" % rank)

            def fn():
                for _ in range(2):
                    log.append(("saw", rank, client.add("ctr", 1)))
                log.append(("claim", rank, client.add("leader", 1)))

            return fn

        for rank in range(2):
            scenario.task("c%d" % rank, mk(rank))
        return scenario

    def _verdict(self, result):
        out = []
        final = result.store.counters.get("ctr", 0)
        if all(t["status"] == "done" and t["error"] is None
               for t in result.tasks.values()) and final != 4:
            out.append(("retry-idempotence",
                        "4 logical adds left the counter at %d — a "
                        "retried add double-applied (or vanished)"
                        % final))
        for rank in (0, 1):
            seen = [v for kind, r, v in result.log
                    if kind == "saw" and r == rank]
            if any(b <= a for a, b in zip(seen, seen[1:])):
                out.append(("retry-idempotence",
                            "client %d observed non-increasing add "
                            "results %s" % (rank, seen)))
        claims = [v for kind, _, v in result.log if kind == "claim"]
        if len(claims) == 2 and sorted(claims) != [1, 2]:
            out.append(("claim-unique",
                        "leader claims on a fresh counter observed "
                        "%s — exactly one rank must observe the "
                        "first-claimant value 1" % sorted(claims)))
        out += self._liveness(result, "idempotence-liveness",
                              fault_free_only=False)
        return out


@register
class IdempotenceFixture(_AddScenarioMixin, ProtoFixture):
    """The SHIPPED add semantics (client nonce + server dedup) under
    lost-ack retries at every boundary: counts stay exact, the
    first-claimant property holds."""

    name = "idempotence"
    doc = ("nonce-idempotent add: retried ops after a lost ack never "
           "double-apply; counter exact, first-claim unique")
    max_schedules = 300
    max_steps = 120

    def build(self):
        return self._build(idempotent=True)

    def verdict(self, result):
        return self._verdict(result)


@register
class LegacyAddFixture(_AddScenarioMixin, ProtoFixture):
    """HISTORICAL BUG: the pre-fix server re-applies a retried add.
    The checker must find the double-apply within budget."""

    name = "add_legacy"
    doc = ("HISTORICAL BUG (non-idempotent retried add): a lost ack "
           "double-applies — the checker must find it")
    expect_finding = True
    expected_props = ("retry-idempotence", "claim-unique",
                      "idempotence-liveness")
    max_schedules = 200
    max_steps = 120

    def build(self):
        return self._build(idempotent=False)

    def verdict(self, result):
        return self._verdict(result)


# -- elastic TTL membership --------------------------------------------------

@register
class ElasticFixture(ProtoFixture):
    """The REAL ElasticManager liveness math on an injected virtual
    clock: an exited rank (counter deleted) is dead immediately; a
    silent rank is dead once its counter stops advancing for > ttl on
    the watcher's clock; a rank whose counter advanced since the last
    check is never dead."""

    name = "elastic"
    doc = ("ElasticManager TTL membership: exit→immediate dead, "
           "silence→dead after ttl, advance→never dead; explored "
           "against beat/watch/clock-tick interleavings + a crash")
    max_schedules = 500
    max_steps = 300
    ttl = 2.0

    def build(self):
        from ...distributed.elastic import ElasticManager

        store = SimStore()
        store.counters["j/beat/0"] = 1      # register() happened
        store.counters["j/beat/1"] = 1
        scenario = Scenario(store, max_crashes=1)
        sched = scenario.sched
        log = scenario.log
        watcher_client = scenario.client("w")
        beater_client = scenario.client("b")
        manager = ElasticManager(
            store=watcher_client, job_id="j", rank=0, np=2,
            heartbeat_interval=1.0, ttl=self.ttl,
            clock=lambda: sched.clock.now)
        # prime the once-per-change dead-set log: the EXPECTED death
        # ([1]) would otherwise stderr-print once per explored
        # schedule (hundreds of identical lines per ptcheck run); an
        # unexpected dead set still logs
        manager._logged_dead = [1]

        def beater():
            for i in range(3):
                beater_client.add("j/beat/1", 1)
                log.append(("beat", i))
            beater_client.delete("j/beat/1")
            log.append(("exit",))

        def watcher():
            for _ in range(4):
                watcher_client.add("j/beat/0", 1)   # own heartbeat
                now = sched.clock.now   # == alive_nodes' clock read:
                #                         no boundary between here and
                #                         it (watch's first op yields
                #                         AFTER the clock is taken)
                verdict = manager.watch()
                log.append(("watch", verdict,
                            tuple(manager.last_dead), now))

        def ticker():
            for _ in range(3):
                sched.tick(1.25)

        scenario.task("beater", beater, crashable=True)
        scenario.task("watcher", watcher)
        scenario.task("ticker", ticker)
        return scenario

    def verdict(self, result):
        out = []
        count = 1
        exited = False
        watches = []        # [(count_at_read, now)]
        for ev in result.log:
            if ev[0] == "beat":
                count += 1
            elif ev[0] == "exit":
                exited = True
                count = 0
            elif ev[0] == "watch":
                _, _, dead, now = ev
                if exited and 1 not in dead:
                    out.append(("elastic-exit-dead",
                                "watch after the rank's exit (beat "
                                "counter deleted) did not report it "
                                "dead: dead=%s" % (dead,)))
                if not exited:
                    if watches and count > watches[-1][0] \
                            and 1 in dead:
                        out.append(("elastic-fresh-alive",
                                    "beat counter advanced since the "
                                    "previous watch but the rank was "
                                    "reported dead"))
                    first = next((w for w in watches
                                  if w[0] == count), None)
                    if first is not None \
                            and now - first[1] > self.ttl + 1e-9 \
                            and 1 not in dead:
                        out.append(("elastic-ttl-dead",
                                    "beat counter unchanged for %.2fs "
                                    "> ttl=%.1fs on the watcher clock "
                                    "but the rank was not reported "
                                    "dead" % (now - first[1],
                                              self.ttl)))
                watches.append((count, now))
        out += self._liveness(result, "elastic-liveness")
        out += self._clean_failures(result, "elastic-clean-failure")
        return out


# -- watchdog bundle request/response ----------------------------------------

@register
class BundleFixture(ProtoFixture):
    """The watchdog bundle protocol, unmodified (monitor/watchdog.py
    module functions): a firing rank publishes a nonce'd request and
    gathers; responders answer; a stale bundle left by a previous
    incident must be superseded, never locked in; a crashed responder
    must not stall the gather past its grace window (bounded
    schedule = no hot spin)."""

    name = "bundle"
    doc = ("watchdog bundle request/gather: liveness under a crashed "
           "responder, stale-bundle supersede, nonce matching, "
           "bounded gather loop")
    max_schedules = 400
    max_steps = 300
    nonce = 42.5

    def build(self):
        from ...monitor import watchdog as wd

        store = SimStore()
        # leftover from a "previous incident" on the same store: rank
        # 1's old bundle with an old nonce — supersede, don't trust
        store.kv["__wd/bundle/rank1"] = json.dumps(
            {"kind": "watchdog_bundle", "rank": 1,
             "answering": 13.0}).encode()
        scenario = Scenario(store, max_crashes=1, patch_time=True)
        log = scenario.log
        fire_client = scenario.client("fire")

        def fire():
            wd._publish_bundle(fire_client, 0,
                               {"kind": "watchdog_bundle", "rank": 0},
                               answering=self.nonce)
            wd._publish_request(fire_client, 0, self.nonce)
            got = wd.gather_bundles(fire_client, 3, grace_s=0.6,
                                    expect_nonce=self.nonce)
            log.append(("gathered",
                        tuple(sorted(got)),
                        tuple(sorted((r, b.get("answering"))
                                     for r, b in got.items()))))

        def mk_responder(rank):
            client = scenario.client("r%d" % rank)

            def fn():
                req = None
                for _ in range(6):
                    req = wd._read_request(client)
                    if req is not None:
                        break
                if req is not None:
                    wd._publish_bundle(
                        client, rank,
                        {"kind": "watchdog_bundle", "rank": rank},
                        answering=req["t"])

            return fn

        scenario.task("fire", fire)
        scenario.task("r1", mk_responder(1))
        scenario.task("r2", mk_responder(2), crashable=True)
        return scenario

    def verdict(self, result):
        out = []
        gathered = [ev for ev in result.log if ev[0] == "gathered"]
        if not gathered:
            if not result.truncated:    # truncation is its own finding
                out.append(("bundle-liveness",
                            "the firing rank never returned from "
                            "gather_bundles"))
            return out
        _, ranks, answers = gathered[-1]
        answers = dict(answers)
        expected = {0, 1} if "r2" in result.crashes else {0, 1, 2}
        missing = expected - set(ranks)
        if missing:
            out.append(("bundle-liveness",
                        "live rank(s) %s missing from the gathered "
                        "bundles %s" % (sorted(missing),
                                        sorted(ranks))))
        for rank in expected & set(ranks):
            if answers.get(rank) != self.nonce:
                out.append(("bundle-stale-supersede",
                            "rank %d's gathered bundle answers %r, "
                            "not this incident's nonce %r — a stale "
                            "leftover was locked in"
                            % (rank, answers.get(rank), self.nonce)))
        # the gather's bounded waits (poll timeouts, the pacing sleep)
        # are its normal operation — liveness here is "gather returned
        # with the right bundles within a bounded schedule", not the
        # absence of blocked states
        out += self._liveness(result, "bundle-liveness", hangs=False)
        return out


# -- serving-fleet router membership -----------------------------------------

class _RouterScenarioMixin:
    """The serving-fleet register/renew/evict/dispatch protocol
    (serving/fleet/membership.py module functions, unmodified) under
    crash + lost-ack interleavings, judged by three properties:

    - ``register-exact``: one registration claims exactly one
      generation — the final generation counter never exceeds the
      attempted registrations and no client ever observes a phantom
      generation (the retried-register-without-nonce double-register).
    - ``dispatch-evicted``: the router never dispatches (or re-routes)
      a request to a replica after evicting it.
    - ``request-lost``: at the router's final pump every accepted
      request is either dispatched to a non-evicted replica or still
      queued; an assignment left on an evicted replica while live
      candidates existed is a lost request.
    """

    ttl = 2.0

    def _replica_task(self, scenario, rank, renews=2):
        from ...serving.fleet import membership

        client = scenario.client("r%d" % rank)
        log = scenario.log

        def fn():
            log.append(("register_attempt", rank))
            gen = membership.register_replica(
                client, rank, "sim://r%d" % rank)
            log.append(("registered", rank, gen))
            for _ in range(renews):
                membership.renew_lease(client, rank)

        return fn

    def _router_task(self, scenario, world_size=2, pumps=4, reqs=2):
        from ...serving.fleet import membership

        client = scenario.client("router")
        sched = scenario.sched
        log = scenario.log
        view = membership.ReplicaView(
            client, world_size, ttl_s=self.ttl,
            clock=lambda: sched.clock.now)

        def fn():
            assigned = {}           # request -> rank
            queued = ["q%d" % i for i in range(reqs)]
            evicted = set()
            candidates = []
            for _ in range(pumps):
                alive = set(view.alive())
                dead = [r for r in range(world_size)
                        if r not in alive]
                for r in dead:
                    # evict only ranks that actually registered: a
                    # never-seen rank has no lease to revoke and can
                    # hold no work or affinity entries
                    if r not in evicted and (client.counter_get(
                            membership.gen_key(r), default=0) or 0) > 0:
                        evicted.add(r)
                        membership.evict_replica(client, r)
                        log.append(("evict", r))
                # reroute before dispatch: work assigned to a replica
                # evicted this pump goes back in the queue
                for q, r in sorted(assigned.items()):
                    if r in evicted:
                        del assigned[q]
                        queued.append(q)
                        log.append(("reroute", q, r))
                candidates = sorted(alive - evicted)
                still = []
                for q in queued:
                    rank, _ = membership.pick_replica(candidates)
                    if rank is None:
                        still.append(q)
                    else:
                        assigned[q] = rank
                        log.append(("dispatch", q, rank))
                queued = still
            log.append(("final",
                        tuple(sorted(assigned.items())),
                        tuple(sorted(queued)),
                        tuple(sorted(evicted)),
                        tuple(candidates)))

        return fn

    def _membership_verdict(self, result, world_size=2):
        from ...serving.fleet import membership

        out = []
        attempts = {}
        for ev in result.log:
            if ev[0] == "register_attempt":
                attempts[ev[1]] = attempts.get(ev[1], 0) + 1
        for rank in range(world_size):
            gk = membership.gen_key(rank)
            n = attempts.get(rank, 0)
            final = result.store.counters.get(gk, 0)
            if final > n:
                out.append(("register-exact",
                            "rank %d attempted %d registration(s) but "
                            "the generation counter reads %d — a "
                            "retried register burned a generation "
                            "(double-register)" % (rank, n, final)))
            for _, seen in result.store.observed_adds(gk):
                if seen > n:
                    out.append(("register-exact",
                                "rank %d observed generation %d from "
                                "%d attempted registration(s) — the "
                                "client saw a phantom prior "
                                "incarnation" % (rank, seen, n)))
        evicted = set()
        for ev in result.log:
            if ev[0] == "evict":
                evicted.add(ev[1])
            elif ev[0] == "dispatch" and ev[2] in evicted:
                # a "reroute" names the rank the work LEFT — only a
                # dispatch TO an evicted rank violates the discipline
                out.append(("dispatch-evicted",
                            "request %r dispatched to rank %d AFTER "
                            "its eviction" % (ev[1], ev[2])))
        finals = [ev for ev in result.log if ev[0] == "final"]
        if finals:
            _, assigned, queued, evicted_final, candidates = finals[-1]
            reqs = {q for q, _ in assigned} | set(queued)
            expected = {"q0", "q1"}
            missing = expected - reqs
            if missing:
                out.append(("request-lost",
                            "accepted request(s) %s neither assigned "
                            "nor queued at the final pump"
                            % sorted(missing)))
            for q, r in assigned:
                if r in evicted_final and candidates:
                    out.append(("request-lost",
                                "request %r left assigned to evicted "
                                "rank %d while live candidates %s "
                                "existed" % (q, r, list(candidates))))
        out += self._liveness(result, "router-liveness")
        out += self._clean_failures(result, "router-clean-failure")
        return out


@register
class RouterMembershipFixture(_RouterScenarioMixin, ProtoFixture):
    """The SHIPPED fleet membership + dispatch discipline: replicas
    register/renew over the nonce-idempotent store, a router evicts on
    the elastic TTL view (the REAL ``ReplicaView`` math on the virtual
    clock), reroutes before dispatch, and never loses an accepted
    request — explored against a replica crash, a lost ack, and TTL
    time passing."""

    name = "router_membership"
    doc = ("serving-fleet membership/dispatch: register claims exactly "
           "one generation, no dispatch to an evicted replica, no "
           "accepted request lost; explored with crash + lost ack + "
           "TTL ticks")
    max_schedules = 400
    max_steps = 300

    def build(self):
        scenario = Scenario(SimStore(), max_crashes=1, max_lost_acks=1)
        sched = scenario.sched

        def ticker():
            for _ in range(2):
                sched.tick(1.25)

        scenario.task("r0", self._replica_task(scenario, 0),
                      crashable=True)
        scenario.task("r1", self._replica_task(scenario, 1),
                      crashable=True)
        scenario.task("router", self._router_task(scenario))
        scenario.task("ticker", ticker)
        return scenario

    def verdict(self, result):
        return self._membership_verdict(result)


@register
class RouterRegisterLegacyFixture(_RouterScenarioMixin, ProtoFixture):
    """HISTORICAL BUG: registration retried over a NON-idempotent add
    (no request nonce) burns a generation per retry — a lost ack
    double-registers the replica, so its record claims a phantom prior
    incarnation and every peer's generation-fencing is off by one. The
    checker must find the ``register-exact`` violation within budget."""

    name = "router_register_legacy"
    doc = ("HISTORICAL BUG (non-idempotent retried register): a lost "
           "ack burns a generation, the record claims a phantom prior "
           "incarnation — the checker must find it")
    expect_finding = True
    expected_props = ("register-exact",)
    max_schedules = 150
    max_steps = 80

    def build(self):
        scenario = Scenario(SimStore(idempotent_add=False),
                            max_lost_acks=1)
        scenario.task("r0", self._replica_task(scenario, 0, renews=1))
        return scenario

    def verdict(self, result):
        return self._membership_verdict(result, world_size=1)
