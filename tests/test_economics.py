"""Preemption economics: priced multi-victim revoke, free admission-level
eviction, and priced width migration (``PreemptionPolicy.max_victims`` /
``evict_admitted`` / ``migration``).

Two layers of coverage:

* **Deterministic scenario twins** — each economics move is driven through
  a pinned tenant mix run twice (move off / move on) on the same machine,
  so the assertions are about the *economics*: the move fires, it is
  priced (traced gain strictly exceeds traced cost), it helps the overdue
  tenant, and the usual pool invariants (exactly-once completion, no core
  oversubscription, exact service accounting) survive it.
* **Stub-adapter unit regressions** — core rules the pool mixes cannot pin
  deterministically (victim tie-breaks, the hyper-lane clamp re-predict,
  the quadrant fallback's next-biggest retry) are exercised against a
  table-driven ``StrategyAdapter``.

The armed-but-untriggered twin (economics knobs ON, no deadlines anywhere
-> bitwise the single-victim pool) plus the ``check_parity`` pool-preempt
leg are the behavior lock: the whole economics surface must be inert
unless armed AND triggered.
"""

import math

import pytest

from repro.core import (GraphBuilder, Op, OpPlan, PreemptionPolicy,
                        RuntimeConfig, SimMachine)
from repro.core.placement import REL_CROSS
from repro.core.strategy import (ScheduledOp, StrategyAdapter, StrategyConfig,
                                 StrategyCore)
from repro.multitenant import (Job, JobQueue, PoolConfig, PoolResult,
                               RuntimePool, compare_timelines, timeline_rows)
from repro.obs import RecordingSink


@pytest.fixture(scope="module")
def machine():
    return SimMachine()


# ---------------------------------------------------------------------------
# scenario graphs (widths pinned by the profiler: the comments give the
# frozen plan each shape profiles to on the default SimMachine)
# ---------------------------------------------------------------------------

def _chain(name, cls, shape, flops, bm, ws, pf, n):
    b = GraphBuilder(name)
    prev = None
    for _ in range(n):
        prev = b.add(cls, shape, flops=flops, bytes_moved=bm,
                     working_set=ws, parallel_fraction=pf,
                     deps=[prev] if prev is not None else [])
    return b.build()


def _narrow_runner(n=2, flops=8e11):
    """Profiles to 17 threads, ~2.7s per op at flops=8e11 — four of these
    tile the 68-core machine exactly, leaving zero idle cores."""
    return _chain("runner", "RunnerOp", (48, 96, 64), flops, 4e7, 4e7,
                  0.96, n)


def _wide_chain(n=2, flops=4e11):
    """Profiles to the full 68 threads, ~0.28s per op — the wide deadlined
    tenant whose preferred width no single narrow victim can seat."""
    return _chain("wide", "WideStep", (256, 256, 64), flops, 5e7, 5e7,
                  0.99, n)


def _giant_op():
    """One 68-thread ~2.8s op: long enough that a squeezed launch is still
    running when the narrow runners drain — the migration window."""
    return _chain("giant", "GiantStep", (256, 256, 64), 4e12, 5e7, 5e7,
                  0.99, 1)


def _blocker(n=2):
    """~66-thread ~2.9s ops — fills the machine so a co-admitted narrow
    tenant sits idle (the admission-eviction victim)."""
    return _chain("blocker", "Huge", (512, 512, 64), 1e12, 1e9, 1e9,
                  0.9, n)


def _assert_exactly_once(res, jobs):
    for job in jobs:
        recs = res.records[job.jid]
        assert len(recs) == job.graph.n_ops
        assert len({r.op.uid for r in recs}) == job.graph.n_ops
        assert job.done


def _assert_no_oversubscription(machine, res):
    spans = [(r.start, r.finish, r.threads)
             for recs in res.records.values() for r in recs if not r.hyper]
    spans += [(p.start, p.finish, p.threads)
              for precs in res.preempted.values() for p in precs
              if not p.hyper]
    for t in sorted({t for s in spans for t in s[:2]}):
        used = sum(th for s0, s1, th in spans if s0 <= t < s1)
        assert used <= machine.spec.cores


def _assert_service_accounting(machine, res, jobs):
    eff = machine.spec.hyper_thread_efficiency
    waste = machine.spec.restart_waste
    for job in jobs:
        granted = sum(r.threads * r.duration * (eff if r.hyper else 1.0)
                      for r in res.records[job.jid])
        wasted = sum(
            p.threads * (p.finish - p.start) * (eff if p.hyper else 1.0)
            * waste for p in res.preempted[job.jid])
        assert job.service == pytest.approx(granted + wasted, rel=1e-9)


# ---------------------------------------------------------------------------
# multi-victim revoke
# ---------------------------------------------------------------------------

def _run_multivictim(machine, policy):
    sink = RecordingSink()
    pool = RuntimePool(machine=machine,
                       config=PoolConfig(max_active=6, sink=sink,
                                         preemption=policy))
    runners = [pool.submit(_narrow_runner(), name=f"r{i}") for i in range(4)]
    # cp ~0.56s, budget 0.1s: overdue the instant it arrives, while the
    # four 17-thread runners hold all 68 cores
    wide = pool.submit(_wide_chain(), name="wide", submit_time=0.05,
                       deadline=0.05 + 0.1)
    res = pool.run()
    ev = [e for e in sink.events if e.family == "preemption"]
    return res, wide, runners, ev


@pytest.fixture(scope="module")
def multivictim_runs(machine):
    single = _run_multivictim(machine, PreemptionPolicy(enabled=True))
    multi = _run_multivictim(
        machine, PreemptionPolicy(enabled=True, max_victims=4))
    return single, multi


class TestMultiVictim:
    def test_seats_preferred_width_and_cuts_latency(self, multivictim_runs):
        (res_s, wide_s, _, ev_s), (res_m, wide_m, _, ev_m) = multivictim_runs
        # single-victim can only free 17 cores at a time: the wide op gets
        # squeezed; the victim set seats the full preferred width
        assert any(e.kind == "squeeze" for e in ev_s)
        mrs = [e for e in ev_m if e.kind == "multi_revoke"]
        assert mrs, "victim-set path never fired"
        assert all(e.data["prefer_threads"] > 17 for e in mrs)
        sets = [e for e in ev_m if e.kind == "revoke"
                and e.data["set_size"] >= 2]
        assert len(sets) >= 2, "a victim SET (>= 2 revokes) was expected"
        assert wide_m.latency < wide_s.latency

    def test_priced_gain_strictly_exceeds_summed_waste(self, machine,
                                                       multivictim_runs):
        _, (res_m, _, _, ev_m) = multivictim_runs
        waste_rate = machine.spec.restart_waste
        for mr in [e for e in ev_m if e.kind == "multi_revoke"]:
            assert mr.data["gain"] > mr.data["waste"]
            # the traced waste is exactly the summed re-billed restart
            # cost of the set revoked at the same instant
            summed = sum(
                e.data["victim_threads"] * e.data["victim_elapsed"]
                * waste_rate
                for e in ev_m if e.kind == "revoke" and e.ts == mr.ts)
            assert mr.data["waste"] == pytest.approx(summed, rel=1e-9)

    def test_single_victim_policy_never_revokes_sets(self, multivictim_runs):
        (res_s, _, _, ev_s), _ = multivictim_runs
        assert all(e.data["set_size"] == 1
                   for e in ev_s if e.kind == "revoke")
        assert not [e for e in ev_s if e.kind == "multi_revoke"]

    def test_pool_invariants_survive_victim_sets(self, machine,
                                                 multivictim_runs):
        _, (res_m, wide_m, runners_m, _) = multivictim_runs
        jobs = runners_m + [wide_m]
        _assert_exactly_once(res_m, jobs)
        _assert_no_oversubscription(machine, res_m)
        _assert_service_accounting(machine, res_m, jobs)


# ---------------------------------------------------------------------------
# admission-level eviction
# ---------------------------------------------------------------------------

def _run_eviction(machine, policy):
    sink = RecordingSink()
    pool = RuntimePool(
        machine=machine,
        config=PoolConfig(max_active=2, sink=sink, preemption=policy,
                          # S4 off: the bystander must stay at ZERO
                          # launches (the hyper lane would seat its ops)
                          runtime=RuntimeConfig(enable_s4=False)))
    blocker = pool.submit(_blocker(), name="blocker")
    bystander = pool.submit(_narrow_runner(n=1), name="bystander",
                            submit_time=0.001)
    urgent = pool.submit(_wide_chain(n=1), name="urgent", submit_time=0.01,
                         deadline=0.02)     # overdue on arrival, queued
    res = pool.run()
    ev = [e for e in sink.events if e.family == "preemption"]
    return res, blocker, bystander, urgent, ev


@pytest.fixture(scope="module")
def eviction_runs(machine):
    off = _run_eviction(machine, PreemptionPolicy(enabled=True))
    on = _run_eviction(
        machine, PreemptionPolicy(enabled=True, evict_admitted=True))
    return off, on


class TestEviction:
    def test_unblocks_overdue_queued_waiter(self, eviction_runs):
        (res_off, *_, u_off, ev_off), (res_on, _, b_on, u_on, ev_on) = \
            eviction_runs
        assert res_off.n_evictions == 0
        assert not [e for e in ev_off if e.kind == "evict"]
        assert res_on.n_evictions == 1
        assert b_on.evictions == 1
        evs = [e for e in ev_on if e.kind == "evict"]
        assert len(evs) == 1
        assert evs[0].key == b_on.jid
        assert evs[0].data["waiter_jid"] == u_on.jid
        assert evs[0].data["waiter_slack"] <= 0.0
        # without the free move the urgent tenant waits out a whole
        # admitted generation; with it, admission happens at its expiry
        assert u_on.latency < u_off.latency / 5

    def test_eviction_is_free(self, machine, eviction_runs):
        _, (res_on, blocker, bystander, urgent, _) = eviction_runs
        # zero restart waste for the evicted tenant: nothing had launched,
        # so nothing was discarded or re-billed
        assert res_on.preempted[bystander.jid] == []
        assert bystander.preemptions == 0
        granted = sum(
            r.threads * r.duration
            * (machine.spec.hyper_thread_efficiency if r.hyper else 1.0)
            for r in res_on.records[bystander.jid])
        assert bystander.service == pytest.approx(granted, rel=1e-9)
        assert res_on.metrics["pool.evictions"] == 1.0

    def test_evicted_job_still_completes(self, machine, eviction_runs):
        _, (res_on, blocker, bystander, urgent, _) = eviction_runs
        jobs = [blocker, bystander, urgent]
        _assert_exactly_once(res_on, jobs)
        _assert_no_oversubscription(machine, res_on)
        _assert_service_accounting(machine, res_on, jobs)


def test_readmit_preserves_original_submit_order():
    g = GraphBuilder("g")
    g.add("X", (4, 4), flops=1e6, bytes_moved=1e4)
    graph = g.build()
    q = JobQueue(max_active=4)
    a = Job(jid=0, name="a", graph=graph)
    b = Job(jid=1, name="b", graph=graph)
    q.submit(a)
    q.submit(b)
    assert q.pop_admissible([], 0.0) is a
    q.readmit(a)
    assert len(q.submitted) == 2      # same submission, not re-counted
    # identical priority/deadline/submit_time: only the queue-seq ticket
    # distinguishes them, and a keeps its original one
    assert q.pop_admissible([], 0.0) is a
    assert q.pop_admissible([], 0.0) is b


def _conv_dag(n, deps):
    """Conv2D ops as ``tests/test_property.py::op_graphs`` draws them."""
    b = GraphBuilder("rand")
    elems = 32 * 8 * 8 * 64
    for i in range(n):
        b.add("Conv2D", (32, 8, 8, 64), flops=elems * 660.0,
              bytes_moved=elems * 200.0, parallel_fraction=0.96,
              deps=deps.get(i, []))
    return b.build()


def test_eviction_victim_requeued_ahead_of_waiter_is_not_evicted():
    """Shrunk hypothesis draw of
    ``test_economics_every_op_completes_exactly_once`` that never ended.

    Deadlines are set after submission, so both tenants were queued as
    best-effort.  An evicted victim re-enters under its real deadline,
    ahead of the waiter it was evicted for; the next admission handed the
    victim its slot back and the same eviction repeated at the same
    instant forever.  The pool must now finish, every op exactly once."""
    blocker = GraphBuilder("blocker")
    prev = None
    for _ in range(3):
        prev = blocker.add("Huge", (512, 512, 64), flops=5e12,
                           bytes_moved=1e9, working_set=1e9,
                           deps=[prev] if prev is not None else [])
    machine = SimMachine()
    pool = RuntimePool(machine=machine, config=PoolConfig(
        max_active=2, preemption=PreemptionPolicy(
            enabled=True, max_victims=4, evict_admitted=True,
            migration=True)))
    jobs = [pool.submit(blocker.build(), name="blocker")]
    for i, g in enumerate([_conv_dag(4, {1: [0]}), _conv_dag(2, {})],
                          start=1):
        t = 1e-4 * i
        job = pool.submit(g, name=f"j{i}", submit_time=t)
        job.deadline = t + max(job.cp.values(), default=0.0) * 1.5
        jobs.append(job)
    res = pool.run()
    _assert_exactly_once(res, jobs)
    _assert_no_oversubscription(machine, res)


# ---------------------------------------------------------------------------
# width migration
# ---------------------------------------------------------------------------

def _run_migration(machine, policy):
    sink = RecordingSink()
    pool = RuntimePool(machine=machine,
                       config=PoolConfig(max_active=6, sink=sink,
                                         preemption=policy))
    # two 17-thread runners (~0.67s) hold 34 cores; the giant arrives
    # overdue and is squeezed into the other 34 by the deadline claim;
    # when the runners drain, only migration can re-seat it at 68
    runners = [pool.submit(_narrow_runner(n=1, flops=2e11), name=f"r{i}")
               for i in range(2)]
    urgent = pool.submit(_giant_op(), name="urgent", submit_time=0.05,
                         deadline=0.05 + 0.1)
    res = pool.run()
    ev = [e for e in sink.events if e.family == "preemption"]
    return res, urgent, runners, ev


@pytest.fixture(scope="module")
def migration_runs(machine):
    off = _run_migration(machine, PreemptionPolicy(enabled=True))
    on = _run_migration(
        machine, PreemptionPolicy(enabled=True, migration=True))
    return off, on


class TestMigration:
    def test_reseats_squeezed_op_wider(self, migration_runs):
        (res_off, u_off, _, ev_off), (res_on, u_on, _, ev_on) = \
            migration_runs
        assert res_off.n_migrations == 0
        assert not [e for e in ev_off if e.kind == "migrate"]
        migs = [e for e in ev_on if e.kind == "migrate"]
        assert migs and res_on.n_migrations == len(migs)
        assert u_on.migrations >= 1
        # the squeezed 34-thread launch is re-seated at a wider width
        assert all(e.data["to_threads"] > e.data["from_threads"]
                   for e in migs)
        assert u_on.latency < u_off.latency

    def test_every_migration_is_priced(self, migration_runs):
        _, (_, _, _, ev_on) = migration_runs
        for e in [e for e in ev_on if e.kind == "migrate"]:
            assert e.data["gain"] > e.data["cost"]
            # the gain is remaining-time improvement, the cost the
            # re-billed partial run — both strictly positive here
            assert e.data["remaining"] > 0.0
            assert e.data["elapsed"] > 0.0

    def test_pool_invariants_survive_migration(self, machine,
                                               migration_runs):
        _, (res_on, urgent, runners, _) = migration_runs
        jobs = runners + [urgent]
        _assert_exactly_once(res_on, jobs)
        _assert_no_oversubscription(machine, res_on)
        _assert_service_accounting(machine, res_on, jobs)


# ---------------------------------------------------------------------------
# armed-but-untriggered economics must be inert (the behavior lock)
# ---------------------------------------------------------------------------

def test_armed_economics_without_deadlines_is_bitwise_inert(machine):
    """No deadline anywhere means no overdue waiter, so multi-victim and
    eviction can never trigger: a pool with those knobs armed must be
    bit-for-bit the single-victim (PR-6) pool on the same mix.  Migration
    is deliberately NOT armed here — it prices moves without deadlines by
    design, so its lock is the off-default (covered by check_parity's
    pool-preempt leg)."""
    def run(policy):
        pool = RuntimePool(machine=machine,
                           config=PoolConfig(max_active=4,
                                             preemption=policy))
        jobs = [pool.submit(_narrow_runner(), name=f"r{i}")
                for i in range(3)]
        jobs.append(pool.submit(_wide_chain(), name="wide",
                                submit_time=0.01))
        return pool.run(), jobs

    base, jobs_b = run(PreemptionPolicy(enabled=True))
    armed, jobs_a = run(PreemptionPolicy(enabled=True, max_victims=4,
                                         evict_admitted=True))
    assert base.makespan == armed.makespan
    assert armed.n_evictions == 0 and armed.n_migrations == 0
    for jb, ja in zip(jobs_b, jobs_a):
        divs = compare_timelines(
            timeline_rows(base.per_job_schedule(jb.jid)),
            timeline_rows(armed.per_job_schedule(ja.jid)),
            label_a="single-victim", label_b="economics-armed")
        assert not divs, divs[:5]


# ---------------------------------------------------------------------------
# stub-adapter unit regressions (satellite fixes)
# ---------------------------------------------------------------------------

class _StubAdapter(StrategyAdapter):
    """Table-driven adapter: hand-built running set and ready frontier,
    dict-backed plans/predictions — pins core rules (tie-breaks, clamp
    re-prediction, placement retries) that pool mixes cannot reach
    deterministically."""

    def __init__(self, clock=1.0):
        self._clock = clock
        self._running: dict = {}
        self.ops: dict = {}
        self.plans: dict = {}
        self.cands: dict = {}
        self.preds: dict = {}          # (key, threads) -> predicted time
        self.slacks: dict = {}
        self.ready: list = []
        self.launched: list[ScheduledOp] = []
        self.revoked: list = []

    @property
    def clock(self):
        return self._clock

    @property
    def running(self):
        return self._running

    def ready_groups(self):
        return [list(self.ready)] if self.ready else []

    def op(self, key):
        return self.ops[key]

    def instance_plan(self, key):
        return self.plans[key]

    def candidates_for(self, key, k):
        return self.cands.get(key, [self.plans[key]])[:k]

    def clamp(self, key, proposal):
        return proposal

    def predict(self, key, threads, variant):
        return self.preds.get((key, threads),
                              self.plans[key].predicted_time)

    def commit(self, key, sched):
        if key in self.ready:
            self.ready.remove(key)
        self._running[key] = sched
        self.launched.append(sched)

    def deadline_slack(self, key):
        return self.slacks.get(key)

    def revoke(self, key):
        sched = self._running.pop(key)
        self.ready.append(key)
        self.revoked.append(key)
        return sched


def _mk_op(uid, cls):
    return Op(uid=uid, name=f"{cls}{uid}", op_class=cls,
              input_shape=(8, 8, 8, 8), flops=1e9, bytes_moved=1e6,
              working_set=1e6, parallel_fraction=0.9)


def _mk_running(uid, cls, threads, start, finish, cores=()):
    return ScheduledOp(op=_mk_op(uid, cls), threads=threads, variant=False,
                       hyper=False, start=start, finish=finish,
                       predicted=finish - start, cores=cores)


def test_hyper_clamp_repredicts_at_clamped_width():
    """Satellite: a hyper-lane launch clamped to the machine width must
    carry the CLAMPED width's prediction, not the unclamped plan's."""
    machine = SimMachine()
    core = StrategyCore(machine, StrategyConfig(), total_cores=8)
    ad = _StubAdapter(clock=1.0)
    ad.ops["w"] = _mk_op(0, "X")
    ad.plans["w"] = OpPlan(16, False, 0.123)      # wider than the machine
    ad.preds[("w", 8)] = 0.456
    ad.preds[("w", 1)] = 1.0                      # serial_time ordering
    ad.ready = ["w"]
    ad._running["r"] = _mk_running(1, "Y", 8, 0.0, 5.0)   # free == 0
    assert core.try_hyper(ad)
    sched = ad.launched[0]
    assert sched.hyper and sched.threads == 8
    assert sched.predicted == 0.456               # re-predicted, not 0.123


def test_victim_tiebreak_prefers_fewest_threads():
    """Satellite: equal remaining time must break on the cheapest revoke
    (fewest threads), not on the opaque node key."""
    machine = SimMachine()
    core = StrategyCore(
        machine,
        StrategyConfig(preemption=PreemptionPolicy(enabled=True)))
    ad = _StubAdapter(clock=1.0)
    ad.ops["w"] = _mk_op(0, "U")
    ad.plans["w"] = OpPlan(32, False, 0.5)
    ad.cands["w"] = [OpPlan(32, False, 0.5)]
    ad.preds[("w", 28)] = 0.6
    ad.slacks["w"] = -1.0
    ad.ready = ["w"]
    ad._running["v_wide"] = _mk_running(1, "A", 40, 0.0, 11.0)
    ad._running["v_narrow"] = _mk_running(2, "B", 28, 0.2, 11.0)
    assert core.try_preempt(ad)
    assert ad.revoked == ["v_narrow"]


def test_victim_tiebreak_equal_threads_prefers_earliest_launched():
    machine = SimMachine()
    core = StrategyCore(
        machine,
        StrategyConfig(preemption=PreemptionPolicy(enabled=True)))
    ad = _StubAdapter(clock=1.0)
    ad.ops["w"] = _mk_op(0, "U")
    ad.plans["w"] = OpPlan(32, False, 0.5)
    ad.cands["w"] = [OpPlan(32, False, 0.5)]
    ad.preds[("w", 34)] = 0.6
    ad.slacks["w"] = -1.0
    ad.ready = ["w"]
    ad._running["v_first"] = _mk_running(1, "A", 34, 0.0, 11.0)
    ad._running["v_second"] = _mk_running(2, "B", 34, 0.2, 11.0)
    assert core.try_preempt(ad)
    assert ad.revoked == ["v_first"]


def test_run_biggest_tries_next_biggest_on_placement_failure():
    """Satellite: under quadrant topology a placement failure of the
    biggest ready op must fall through to the next-biggest op in the SAME
    group, not skip the whole group and idle the cores."""
    machine = SimMachine()
    core = StrategyCore(machine, StrategyConfig(topology="quadrant"))
    spec = machine.spec
    # cross-blacklist (A, C): A must avoid C's quadrant, where the only
    # free cores are — so A's placement fails, and D must launch instead
    core.recorder.record("A", "C", 1.0, 10.0, relation=REL_CROSS)
    core.begin_run()
    q012 = tuple(c for q in (0, 1, 2) for c in spec.quadrant_cores(q))
    q3 = tuple(spec.quadrant_cores(3))
    ad = _StubAdapter(clock=1.0)
    ad._running["rB"] = _mk_running(1, "B", len(q012), 0.0, 101.0,
                                    cores=q012)
    ad._running["rC"] = _mk_running(2, "C", 8, 0.0, 101.0, cores=q3[:8])
    ad.ops["a"] = _mk_op(3, "A")
    ad.ops["d"] = _mk_op(4, "D")
    ad.plans["a"] = OpPlan(18, False, 5.0)        # the biggest
    ad.plans["d"] = OpPlan(8, False, 1.0)         # the next-biggest
    ad.preds[("a", 8)] = 4.0                      # clamped re-prediction
    ad.ready = ["a", "d"]
    assert core.run_biggest(ad)
    assert [s.op.op_class for s in ad.launched] == ["D"]
    assert set(ad.launched[0].cores) <= set(q3)


def test_mean_latency_nan_when_nothing_finished():
    """Satellite: a run where no job finished must not report the same
    0.0 as a perfect run — NaN poisons any aggregate built from it."""
    g = GraphBuilder("g")
    g.add("X", (4, 4), flops=1e6, bytes_moved=1e4)
    job = Job(jid=0, name="j", graph=g.build(), submit_time=0.25)
    res = PoolResult(makespan=0.0, jobs=[job], records={0: []}, events=[],
                     cache_stats={})
    assert math.isnan(res.mean_latency)
    job.finish_time = 1.0
    assert res.mean_latency == pytest.approx(0.75)
