"""Hypothesis property tests on system invariants."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="hypothesis not installed")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core import (ConcurrencyRuntime, CurveModel, GraphBuilder,
                        HillClimbProfiler, Op, OpPlan, Placement,
                        PreemptionPolicy, RuntimeConfig, SimMachine,
                        paper_case_lists, pick_admissible)
from repro.hw.hlo import parse_collectives, shape_bytes
from repro.multitenant import (JobQueue, PoolConfig, RuntimePool,
                               compare_timelines, corun_timeline,
                               pool_timeline, timeline_rows)

SETTINGS = dict(max_examples=25, deadline=None)


# ---------------------------------------------------------------------------
# perf model invariants
# ---------------------------------------------------------------------------

@settings(**SETTINGS)
@given(flops=st.floats(1e6, 1e11), byts=st.floats(1e4, 1e9),
       f=st.floats(0.5, 0.99), seed=st.integers(0, 100))
def test_hillclimb_best_never_worse_than_probes(flops, byts, f, seed):
    machine = SimMachine(seed=seed)
    op = Op(uid=0, name="t", op_class="X", input_shape=(32, 8, 8, 64),
            flops=flops, bytes_moved=byts, working_set=byts,
            parallel_fraction=f)

    def measure(op_, t, v):
        return machine.op_time(op_, Placement(t, cache_sharing=v))

    curve = HillClimbProfiler(measure, paper_case_lists(),
                              interval=4).profile(op)
    t, v, y = curve.measured_best()
    for variant, pts in curve.samples.items():
        for tt, yy in pts:
            assert y <= yy + 1e-15


@settings(**SETTINGS)
@given(f=st.floats(0.5, 0.99), seed=st.integers(0, 50))
def test_interpolation_between_sample_bounds(f, seed):
    """Predictions between two samples lie between those samples
    (piecewise-linear)."""
    machine = SimMachine(seed=seed, jitter=0.0)
    op = Op(uid=0, name="t", op_class="X", input_shape=(16, 16, 16, 64),
            flops=2e9, bytes_moved=1e7, working_set=1e7,
            parallel_fraction=f)

    def measure(op_, t, v):
        return machine.op_time(op_, Placement(t, cache_sharing=v))

    curve = HillClimbProfiler(measure, paper_case_lists(),
                              interval=4).profile(op)
    for v, pts in curve.samples.items():
        for (t1, y1), (t2, y2) in zip(pts, pts[1:]):
            mid = (t1 + t2) // 2
            pred = curve.predict(mid, v)
            lo, hi = min(y1, y2), max(y1, y2)
            assert lo - 1e-12 <= pred <= hi + 1e-12


@settings(**SETTINGS)
@given(threads=st.integers(1, 68), f=st.floats(0.5, 0.99))
def test_machine_time_positive_monotone_work(threads, f):
    machine = SimMachine(jitter=0.0)
    small = Op(uid=0, name="a", op_class="X", input_shape=(8, 8, 8, 8),
               flops=1e8, bytes_moved=1e6, working_set=1e6,
               parallel_fraction=f)
    big = Op(uid=1, name="b", op_class="X", input_shape=(8, 8, 8, 8),
             flops=2e8, bytes_moved=2e6, working_set=2e6,
             parallel_fraction=f)
    pl = Placement(threads)
    assert machine.op_time(small, pl) > 0
    assert machine.op_time(big, pl) > machine.op_time(small, pl)


# ---------------------------------------------------------------------------
# StrategyCore invariants over random op-graph DAGs
# ---------------------------------------------------------------------------

# per-class cost factors: cost must be a FUNCTION of (op_class, shape) —
# the paper's premise (and the profile-store key), so the generator never
# builds two ops sharing a size_key with different analytic cost
_DAG_CLASSES = {
    # op_class: (flops/elem, bytes/elem, parallel_fraction)
    "Conv2D": (660.0, 200.0, 0.96),
    "MatMul": (400.0, 60.0, 0.96),
    "FusedBatchNorm": (8.0, 12.0, 0.80),
    "Mul": (1.0, 12.0, 0.60),
    "Sum": (1.0, 8.0, 0.65),
}
_DAG_SHAPES = [(32, 8, 8, 64), (16, 16, 16, 32), (64, 4, 4, 128), (8, 8, 8, 8)]


@st.composite
def op_graphs(draw):
    """Random DAGs: each op depends on a subset of earlier ops, so the
    graph is acyclic by construction."""
    n = draw(st.integers(2, 12))
    b = GraphBuilder("rand")
    for i in range(n):
        cls = draw(st.sampled_from(sorted(_DAG_CLASSES)))
        shape = draw(st.sampled_from(_DAG_SHAPES))
        deps = (draw(st.lists(st.sampled_from(range(i)), unique=True,
                              max_size=min(i, 3))) if i else [])
        elems = float(np.prod(shape))
        fpe, bpe, pf = _DAG_CLASSES[cls]
        b.add(cls, shape, flops=elems * fpe, bytes_moved=elems * bpe,
              parallel_fraction=pf, deps=deps)
    return b.build()


DAG_SETTINGS = dict(max_examples=10, deadline=None)


@settings(**DAG_SETTINGS)
@given(graph=op_graphs())
def test_strategy_core_schedule_invariants(graph):
    """Every op exactly once, deps respected, cores never oversubscribed."""
    machine = SimMachine()
    rt = ConcurrencyRuntime(machine=machine)
    res = rt.execute_step(graph)
    assert len(res.records) == graph.n_ops
    assert len({r.op.uid for r in res.records}) == graph.n_ops
    start = {r.op.uid: r.start for r in res.records}
    finish = {r.op.uid: r.finish for r in res.records}
    for op in graph.ops.values():
        for d in op.deps:
            assert finish[d] <= start[op.uid] + 1e-12
    times = sorted(start.values()) + sorted(finish.values())
    for t in times:
        used = sum(r.threads for r in res.records
                   if not r.hyper and r.start <= t < r.finish)
        assert used <= machine.spec.cores


@settings(**DAG_SETTINGS)
@given(graph=op_graphs())
def test_single_job_pool_matches_corun_on_random_dags(graph):
    """The differential property: 1-job pool == CorunScheduler, bitwise,
    on arbitrary DAGs — not just the zoo models."""
    single = corun_timeline(graph, SimMachine(seed=0))
    pooled = pool_timeline(graph, SimMachine(seed=0))
    assert single.makespan == pooled.makespan
    assert not compare_timelines(timeline_rows(single), timeline_rows(pooled))


@settings(**DAG_SETTINGS)
@given(graphs=st.lists(op_graphs(), min_size=2, max_size=3),
       priorities=st.lists(st.floats(0.5, 4.0), min_size=3, max_size=3))
def test_pool_service_accounting_sums(graphs, priorities):
    """Fair-share service charged at launch must equal the core-seconds
    actually granted (threads x duration, hyper lanes at HT efficiency)."""
    machine = SimMachine()
    pool = RuntimePool(machine=machine, config=PoolConfig(max_active=3))
    jobs = [pool.submit(g, priority=p, name=f"j{i}")
            for i, (g, p) in enumerate(zip(graphs, priorities))]
    res = pool.run()
    eff = machine.spec.hyper_thread_efficiency
    for job in jobs:
        granted = sum(r.threads * r.duration * (eff if r.hyper else 1.0)
                      for r in res.records[job.jid])
        assert job.service == pytest.approx(granted, rel=1e-9)


@settings(**DAG_SETTINGS)
@given(graph=op_graphs(), a=st.sampled_from(sorted(_DAG_CLASSES)),
       b=st.sampled_from(sorted(_DAG_CLASSES)))
def test_blacklisted_pair_never_overlaps_on_random_dags(graph, a, b):
    """A pair blacklisted before the step starts is never co-launched,
    whatever the DAG shape — on any launch path (S3, fallback, S4)."""
    rt = ConcurrencyRuntime(machine=SimMachine())
    rt.profile(graph)
    rt.recorder.record(a, b, 1.0, 10.0)      # far above the 1.35 threshold
    res = rt.execute_step(graph)
    ra = [r for r in res.records if r.op.op_class == a]
    rb = [r for r in res.records if r.op.op_class == b]
    for x in ra:
        for y in rb:
            if x.op.uid == y.op.uid:
                continue
            assert not (x.start < y.finish - 1e-15
                        and y.start < x.finish - 1e-15), \
                f"blacklisted pair ({a}, {b}) co-launched"


# ---------------------------------------------------------------------------
# preemption invariants (deadline-driven revocation, random DAG mixes)
# ---------------------------------------------------------------------------

def _blocker_graph():
    """Chain of very long wide ops — guarantees the random tenants behind
    it actually experience head-of-line blocking, so the deadline path
    (including revocation) is exercised, not just defined."""
    b = GraphBuilder("blocker")
    prev = None
    for _ in range(3):
        prev = b.add("Huge", (512, 512, 64), flops=5e12, bytes_moved=1e9,
                     working_set=1e9, deps=[prev] if prev is not None else [])
    return b.build()


def _preempting_pool(graphs, deadline_scale, topology=None, feedback=None):
    """A long-op blocker tenant plus random DAG tenants arriving staggered
    with deadlines tight enough (a fraction of each job's own critical
    path) that slack pressure — and usually preemption — occurs."""
    machine = SimMachine()
    pool = RuntimePool(machine=machine,
                       config=PoolConfig(
                           max_active=4, topology=topology,
                           feedback=feedback,
                           preemption=PreemptionPolicy(enabled=True)))
    jobs = [pool.submit(_blocker_graph(), name="blocker")]
    for i, g in enumerate(graphs, start=1):
        # the deadline is priced from the job's own critical path, which
        # only exists after profiling — set it post-submit (the admission
        # queue saw it as best-effort; slack/preemption read it live)
        t = 1e-4 * i
        job = pool.submit(g, name=f"j{i}", submit_time=t)
        cp = max(job.cp.values(), default=0.0)
        job.deadline = t + cp * deadline_scale
        jobs.append(job)
    return machine, pool, jobs


@settings(**DAG_SETTINGS)
@given(graphs=st.lists(op_graphs(), min_size=2, max_size=3),
       scale=st.floats(0.1, 1.5))
def test_preemption_every_op_completes_exactly_once(graphs, scale):
    """Work conservation: a revoked victim returns to the ready frontier
    exactly once and its op still completes exactly once; deps hold."""
    machine, pool, jobs = _preempting_pool(graphs, scale)
    res = pool.run()
    for job in jobs:
        recs = res.records[job.jid]
        assert len(recs) == job.graph.n_ops
        assert len({r.op.uid for r in recs}) == job.graph.n_ops
        start = {r.op.uid: r.start for r in recs}
        finish = {r.op.uid: r.finish for r in recs}
        for op in job.graph.ops.values():
            for d in op.deps:
                assert finish[d] <= start[op.uid] + 1e-12
        # a preempted node's final (completed) run starts at or after the
        # instant it was revoked
        for p in res.preempted[job.jid]:
            assert start[p.op.uid] >= p.finish - 1e-15


@settings(**DAG_SETTINGS)
@given(graphs=st.lists(op_graphs(), min_size=2, max_size=3),
       scale=st.floats(0.1, 1.5))
def test_preemption_never_oversubscribes_cores(graphs, scale):
    """Across every instant — including preemption instants — physical
    core occupancy (completed runs plus revoked partial runs) stays
    within the machine."""
    machine, pool, jobs = _preempting_pool(graphs, scale)
    res = pool.run()
    spans = [(r.start, r.finish, r.threads)
             for recs in res.records.values() for r in recs if not r.hyper]
    spans += [(p.start, p.finish, p.threads)
              for precs in res.preempted.values() for p in precs
              if not p.hyper]
    for t in sorted({t for s in spans for t in s[:2]}):
        used = sum(th for s0, s1, th in spans if s0 <= t < s1)
        assert used <= machine.spec.cores


@settings(**DAG_SETTINGS)
@given(graphs=st.lists(op_graphs(), min_size=2, max_size=3),
       scale=st.floats(0.1, 1.5))
def test_preemption_service_accounting_sums(graphs, scale):
    """Launch-time charging stays consistent under revocation: service ==
    completed core-seconds + revoked partials at the restart-waste rate."""
    machine, pool, jobs = _preempting_pool(graphs, scale)
    res = pool.run()
    eff = machine.spec.hyper_thread_efficiency
    waste = machine.spec.restart_waste
    for job in jobs:
        granted = sum(r.threads * r.duration * (eff if r.hyper else 1.0)
                      for r in res.records[job.jid])
        wasted = sum(
            p.threads * (p.finish - p.start) * (eff if p.hyper else 1.0)
            * waste for p in res.preempted[job.jid])
        assert job.service == pytest.approx(granted + wasted, rel=1e-9)


@settings(**DAG_SETTINGS)
@given(graph=op_graphs())
def test_preemption_enabled_without_deadlines_matches_corun(graph):
    """The differential property survives the preemption KNOB: enabled
    but with no deadline anywhere, a 1-job pool is still bit-identical to
    CorunScheduler on arbitrary DAGs (nothing can go overdue)."""
    single = corun_timeline(graph, SimMachine(seed=0))
    pooled = pool_timeline(
        graph, SimMachine(seed=0),
        pool_config=PoolConfig(max_active=1,
                               preemption=PreemptionPolicy(enabled=True)))
    assert single.makespan == pooled.makespan
    assert not compare_timelines(timeline_rows(single), timeline_rows(pooled))


# ---------------------------------------------------------------------------
# preemption-economics invariants (multi-victim / eviction / migration)
# ---------------------------------------------------------------------------

# every economics move armed at once: the invariants below must hold with
# victim sets revoked atomically, admitted jobs bounced back to the queue,
# and running ops re-seated at new widths mid-flight
_ECON_POLICY = PreemptionPolicy(enabled=True, max_victims=4,
                                evict_admitted=True, migration=True)


def _economics_pool(graphs, deadline_scale):
    """_preempting_pool with the full economics policy armed (a tighter
    max_active so admission-level eviction has queue pressure to act on)."""
    machine = SimMachine()
    pool = RuntimePool(machine=machine,
                       config=PoolConfig(max_active=2,
                                         preemption=_ECON_POLICY))
    jobs = [pool.submit(_blocker_graph(), name="blocker")]
    for i, g in enumerate(graphs, start=1):
        t = 1e-4 * i
        job = pool.submit(g, name=f"j{i}", submit_time=t)
        cp = max(job.cp.values(), default=0.0)
        job.deadline = t + cp * deadline_scale
        jobs.append(job)
    return machine, pool, jobs


@settings(**DAG_SETTINGS)
@given(graphs=st.lists(op_graphs(), min_size=2, max_size=3),
       scale=st.floats(0.1, 1.5))
def test_economics_every_op_completes_exactly_once(graphs, scale):
    """Work conservation under the full economics policy: victim-set
    revokes, admission evictions, and width migrations all return work to
    a frontier it leaves exactly once — every op still completes exactly
    once and dependencies hold."""
    machine, pool, jobs = _economics_pool(graphs, scale)
    res = pool.run()
    for job in jobs:
        recs = res.records[job.jid]
        assert len(recs) == job.graph.n_ops
        assert len({r.op.uid for r in recs}) == job.graph.n_ops
        start = {r.op.uid: r.start for r in recs}
        finish = {r.op.uid: r.finish for r in recs}
        for op in job.graph.ops.values():
            for d in op.deps:
                assert finish[d] <= start[op.uid] + 1e-12
        for p in res.preempted[job.jid]:
            assert start[p.op.uid] >= p.finish - 1e-15


@settings(**DAG_SETTINGS)
@given(graphs=st.lists(op_graphs(), min_size=2, max_size=3),
       scale=st.floats(0.1, 1.5))
def test_economics_never_oversubscribes_cores(graphs, scale):
    """Core occupancy stays within the machine across every instant —
    including multi-victim revoke instants (several launches cancelled at
    once) and migration instants (revoke + relaunch at the same clock)."""
    machine, pool, jobs = _economics_pool(graphs, scale)
    res = pool.run()
    spans = [(r.start, r.finish, r.threads)
             for recs in res.records.values() for r in recs if not r.hyper]
    spans += [(p.start, p.finish, p.threads)
              for precs in res.preempted.values() for p in precs
              if not p.hyper]
    for t in sorted({t for s in spans for t in s[:2]}):
        used = sum(th for s0, s1, th in spans if s0 <= t < s1)
        assert used <= machine.spec.cores


@settings(**DAG_SETTINGS)
@given(graphs=st.lists(op_graphs(), min_size=2, max_size=3),
       scale=st.floats(0.1, 1.5))
def test_economics_service_accounting_sums(graphs, scale):
    """Charging stays exact under every economics move: service equals
    completed core-seconds plus revoked partials at the restart-waste
    rate.  Admission-level eviction appears in NEITHER term — the free
    move never charges waste."""
    machine, pool, jobs = _economics_pool(graphs, scale)
    res = pool.run()
    eff = machine.spec.hyper_thread_efficiency
    waste = machine.spec.restart_waste
    for job in jobs:
        granted = sum(r.threads * r.duration * (eff if r.hyper else 1.0)
                      for r in res.records[job.jid])
        wasted = sum(
            p.threads * (p.finish - p.start) * (eff if p.hyper else 1.0)
            * waste for p in res.preempted[job.jid])
        assert job.service == pytest.approx(granted + wasted, rel=1e-9)


@settings(**DAG_SETTINGS)
@given(graph=op_graphs())
def test_economics_armed_without_deadlines_matches_corun(graph):
    """Multi-victim and eviction both require an OVERDUE waiter: with no
    deadline anywhere a 1-job pool with those knobs armed must still be
    bit-identical to CorunScheduler on arbitrary DAGs.  Migration is
    deliberately left off — it prices moves without deadlines by design
    (its inertness lock is the off-default, see check_parity)."""
    single = corun_timeline(graph, SimMachine(seed=0))
    pooled = pool_timeline(
        graph, SimMachine(seed=0),
        pool_config=PoolConfig(
            max_active=1,
            preemption=PreemptionPolicy(enabled=True, max_victims=4,
                                        evict_admitted=True)))
    assert single.makespan == pooled.makespan
    assert not compare_timelines(timeline_rows(single), timeline_rows(pooled))


# ---------------------------------------------------------------------------
# topology-aware placement invariants (quadrant core booking)
# ---------------------------------------------------------------------------

@settings(**DAG_SETTINGS)
@given(graphs=st.lists(op_graphs(), min_size=2, max_size=3),
       scale=st.floats(0.1, 1.5))
def test_quadrant_no_core_double_booked_across_preemption(graphs, scale):
    """Under topology="quadrant" every non-hyper launch books concrete
    core ids; at every instant — including preemption instants, where a
    revoked partial run occupies [start, revoke) — no core hosts two
    launches, and a launch books exactly its width in unique cores."""
    machine, pool, jobs = _preempting_pool(graphs, scale,
                                           topology="quadrant")
    res = pool.run()
    spans = [(r.start, r.finish, r) for recs in res.records.values()
             for r in recs if not r.hyper]
    spans += [(p.start, p.finish, p) for precs in res.preempted.values()
              for p in precs if not p.hyper]
    for _, _, r in spans:
        assert len(r.cores) == r.threads
        assert len(set(r.cores)) == len(r.cores)
        assert all(0 <= c < machine.spec.cores for c in r.cores)
    for t in sorted({t for s in spans for t in s[:2]}):
        booked = [c for s0, s1, r in spans if s0 <= t < s1
                  for c in r.cores]
        assert len(booked) == len(set(booked))


@settings(**DAG_SETTINGS)
@given(graphs=st.lists(op_graphs(), min_size=2, max_size=3),
       scale=st.floats(0.1, 1.5))
def test_quadrant_launches_never_exceed_quadrant_capacity(graphs, scale):
    """A launch's per-quadrant core bookings stay within each quadrant's
    physical capacity (and hyper launches book no cores at all)."""
    machine, pool, jobs = _preempting_pool(graphs, scale,
                                           topology="quadrant")
    res = pool.run()
    spec = machine.spec
    cap = {q: len(spec.quadrant_cores(q)) for q in range(spec.quadrants)}
    recs = [r for rs in res.records.values() for r in rs]
    recs += [p for ps in res.preempted.values() for p in ps]
    for r in recs:
        if r.hyper:
            assert r.cores == ()
            continue
        per_q: dict[int, int] = {}
        for c in r.cores:
            q = spec.quadrant_of_core(c)
            per_q[q] = per_q.get(q, 0) + 1
        for q, n in per_q.items():
            assert n <= cap[q]


@settings(**DAG_SETTINGS)
@given(graph=op_graphs())
def test_flat_topology_pool_matches_corun_on_random_dags(graph):
    """topology="flat" spelled out (not defaulted) keeps the differential
    property: a 1-job flat pool is bit-identical to CorunScheduler — the
    topology feature sits behind the same parity lock as Strategies 2-4."""
    from repro.core import RuntimeConfig
    single = corun_timeline(graph, SimMachine(seed=0))
    pooled = pool_timeline(
        graph, SimMachine(seed=0),
        pool_config=PoolConfig(max_active=1, topology="flat"))
    assert single.makespan == pooled.makespan
    assert not compare_timelines(timeline_rows(single), timeline_rows(pooled))
    quad_single = corun_timeline(graph, SimMachine(seed=0),
                                 RuntimeConfig(topology="quadrant"))
    quad_pooled = pool_timeline(graph, SimMachine(seed=0),
                                RuntimeConfig(topology="quadrant"))
    assert quad_single.makespan == quad_pooled.makespan
    assert not compare_timelines(timeline_rows(quad_single),
                                 timeline_rows(quad_pooled))


# ---------------------------------------------------------------------------
# closed-loop plan store invariants (feedback="ewma")
# ---------------------------------------------------------------------------

@settings(**DAG_SETTINGS)
@given(graph=op_graphs())
def test_feedback_zero_error_matches_off_on_random_dags(graph):
    """The blend-math lock on arbitrary DAGs: feedback="ewma" fed a
    zero-error observation stream (every observation exactly matches its
    prediction) is bit-identical to feedback="off" — both through the
    single-graph scheduler and through a 1-job pool."""
    off = corun_timeline(graph, SimMachine(seed=0))
    fb = RuntimeConfig(feedback="ewma")
    for leg in (corun_timeline(graph, SimMachine(seed=0), fb,
                               zero_error=True),
                pool_timeline(graph, SimMachine(seed=0), fb,
                              zero_error=True)):
        assert off.makespan == leg.makespan
        assert not compare_timelines(timeline_rows(off),
                                     timeline_rows(leg))


@settings(**DAG_SETTINGS)
@given(graphs=st.lists(op_graphs(), min_size=2, max_size=3),
       scale=st.floats(0.1, 1.5))
def test_feedback_service_accounting_sums(graphs, scale):
    """Service accounting stays exact under feedback + preemption: what
    a job was charged equals completed core-seconds plus revoked partial
    runs at the restart-waste rate — re-estimated predictions change
    DECISIONS, never the price of granted cores."""
    machine, pool, jobs = _preempting_pool(graphs, scale, feedback="ewma")
    res = pool.run()
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
# observability invariants (tracing must be bit-for-bit inert)
# ---------------------------------------------------------------------------

@settings(**DAG_SETTINGS)
@given(graphs=st.lists(op_graphs(), min_size=2, max_size=3),
       scale=st.floats(0.1, 1.5))
def test_tracing_is_inert_on_random_preempting_mixes(graphs, scale):
    """A live RecordingSink never changes a schedule: the fully-armed pool
    (quadrant placement + ewma feedback + deadline preemption) produces
    the bit-identical timeline traced and untraced, on arbitrary DAG
    mixes — and the traced run must actually record events, so the
    property can't pass vacuously with a disconnected sink."""
    from repro.obs import RecordingSink

    sink = RecordingSink()
    _, pool_a, jobs_a = _traced_preempting_pool(graphs, scale, sink)
    _, pool_b, jobs_b = _traced_preempting_pool(graphs, scale, None)
    res_a, res_b = pool_a.run(), pool_b.run()
    assert sink.events
    assert res_a.makespan == res_b.makespan
    assert res_a.n_preemptions == res_b.n_preemptions
    for ja, jb in zip(jobs_a, jobs_b):
        divs = compare_timelines(
            timeline_rows(res_b.per_job_schedule(jb.jid)),
            timeline_rows(res_a.per_job_schedule(ja.jid)),
            label_a="untraced", label_b="traced")
        assert not divs, divs[:5]


def _traced_preempting_pool(graphs, deadline_scale, sink):
    """_preempting_pool with a trace sink wired into the pool config."""
    machine = SimMachine()
    pool = RuntimePool(machine=machine,
                       config=PoolConfig(
                           max_active=4, topology="quadrant",
                           feedback="ewma", sink=sink,
                           preemption=PreemptionPolicy(enabled=True)))
    jobs = [pool.submit(_blocker_graph(), name="blocker")]
    for i, g in enumerate(graphs, start=1):
        t = 1e-4 * i
        job = pool.submit(g, name=f"j{i}", submit_time=t)
        cp = max(job.cp.values(), default=0.0)
        job.deadline = t + cp * deadline_scale
        jobs.append(job)
    return machine, pool, jobs


@settings(**DAG_SETTINGS)
@given(graphs=st.lists(op_graphs(), min_size=2, max_size=3),
       scale=st.floats(0.1, 1.5))
def test_event_metrics_match_pool_accounting_on_random_mixes(graphs, scale):
    """metrics_from_events over the decision stream alone reproduces the
    pool's service and restart-waste accounting on arbitrary mixes."""
    from repro.obs import RecordingSink, metrics_from_events

    sink = RecordingSink()
    _, pool, jobs = _traced_preempting_pool(graphs, scale, sink)
    res = pool.run()
    ev = metrics_from_events(sink.events)
    assert ev.value("pool.service_core_s") == \
        sum(j.service for j in res.jobs)
    assert ev.value("pool.total_ops") == res.total_ops
    assert ev.value("pool.restart_waste_core_s") == \
        res.metrics["pool.restart_waste_core_s"]


class _CapAssertingQueue(JobQueue):
    """JobQueue that proves the admission-cap invariant at every pop
    (deterministic twin: tests/test_planstore.py::_AssertingQueue)."""

    def pop_admissible(self, active, now=float("inf")):
        job = super().pop_admissible(active, now)
        if (job is not None and self.max_outstanding_demand is not None
                and active):
            outstanding = sum(j.demand for j in active)
            assert outstanding + job.demand \
                <= self.max_outstanding_demand + 1e-9
        return job


@settings(**DAG_SETTINGS)
@given(graphs=st.lists(op_graphs(), min_size=3, max_size=4),
       feedback=st.sampled_from([None, "ewma"]))
def test_feedback_demand_within_admission_cap(graphs, feedback):
    """Re-estimated Job.demand must keep satisfying the admission-cap
    invariant: at every pop, outstanding live demand plus the admitted
    job's fits under the cap (checked inside the asserting queue), and
    every job still runs to completion."""
    pool = RuntimePool(machine=SimMachine(),
                       config=PoolConfig(max_active=3, feedback=feedback))
    pool.queue = _CapAssertingQueue(max_active=3)
    jobs = [pool.submit(g, name=f"j{i}", submit_time=i * 1e-4)
            for i, g in enumerate(graphs)]
    pool.queue.max_outstanding_demand = 1.5 * max(j.demand for j in jobs)
    res = pool.run()
    assert all(j.done for j in jobs)
    assert res.total_ops == sum(g.n_ops for g in graphs)


@settings(**SETTINGS)
@given(threads=st.lists(st.integers(1, 68), min_size=1, max_size=6),
       times=st.lists(st.floats(1e-5, 1.0), min_size=6, max_size=6),
       free=st.integers(0, 68), extra=st.integers(0, 34),
       horizon=st.floats(1e-4, 2.0))
def test_pick_admissible_monotone_in_free_cores(threads, times, free,
                                                extra, horizon):
    """Strategy-3 admission: the pick never exceeds the idle cores or the
    horizon, and admission is monotone — growing the idle-core budget
    never loses admissibility and never picks MORE threads (the admissible
    set only grows, and the rule takes the minimum)."""
    cands = [OpPlan(t, False, y) for t, y in zip(threads, times)]
    pick = pick_admissible(cands, free, horizon)
    if pick is not None:
        assert pick.threads <= free
        assert pick.predicted_time <= horizon
    wider = pick_admissible(cands, free + extra, horizon)
    if pick is not None:
        assert wider is not None
        assert wider.threads <= pick.threads


# ---------------------------------------------------------------------------
# MoE invariants
# ---------------------------------------------------------------------------

@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 100), top_k=st.sampled_from([1, 2]))
def test_moe_dispatch_capacity_respected(seed, top_k):
    from repro.models.layers import moe_block
    key = jax.random.PRNGKey(seed)
    e, d, fdim = 4, 16, 32
    p = {
        "router": jax.random.normal(key, (d, e)) * 0.1,
        "w_gate": jax.random.normal(key, (e, d, fdim)) * 0.1,
        "w_up": jax.random.normal(key, (e, d, fdim)) * 0.1,
        "w_down": jax.random.normal(key, (e, fdim, d)) * 0.1,
    }
    x = jax.random.normal(key, (2, 8, d))
    out, aux = moe_block(p, x, n_experts=e, top_k=top_k,
                         capacity_factor=1.0)
    assert out.shape == x.shape
    assert bool(jnp.isfinite(out).all())
    assert float(aux) >= 0.99   # aux >= 1 at balance (E * sum f*p >= 1)


# ---------------------------------------------------------------------------
# HLO parsing invariants
# ---------------------------------------------------------------------------

@settings(**SETTINGS)
@given(dims=st.lists(st.integers(1, 64), min_size=1, max_size=4))
def test_shape_bytes(dims):
    s = f"f32[{','.join(map(str, dims))}]"
    assert shape_bytes(s) == int(np.prod(dims)) * 4


def test_parse_collectives_ring_formulas():
    hlo = """
  %ag = f32[16,128]{1,0} all-gather(%x), replica_groups={{0,1,2,3}}, dimensions={0}
  %ar = f32[16,128]{1,0} all-reduce(%y), replica_groups={{0,1},{2,3}}, to_apply=%sum
  %rs = f32[4,128]{1,0} reduce-scatter(%z), replica_groups={{0,1,2,3}}, dimensions={0}
  %cp = f32[8,128]{1,0} collective-permute(%w), source_target_pairs={{0,1},{1,0}}
"""
    stats = parse_collectives(hlo, pod_size=2)
    by = stats.by_kind()
    full_ag = 16 * 128 * 4
    assert by["all-gather"][1] == full_ag * 3 / 4
    full_ar = 16 * 128 * 4
    assert by["all-reduce"][1] == 2 * full_ar * 1 / 2
    full_rs = 4 * 128 * 4 * 4
    assert by["reduce-scatter"][1] == full_rs * 3 / 4
    assert by["collective-permute"][1] == 8 * 128 * 4
    # groups {0,1,2,3} cross pod boundary at pod_size=2
    assert stats.dci_link_bytes > 0
    assert stats.ici_link_bytes > 0    # {0,1} stays in pod


def test_parse_collectives_iota_groups():
    hlo = ("  %ar = bf16[256]{0} all-reduce(%x), "
           "replica_groups=[2,2]<=[4], to_apply=%s\n")
    stats = parse_collectives(hlo, pod_size=4)
    assert stats.ops[0].group_size == 2
    assert not stats.ops[0].crosses_pod


# ---------------------------------------------------------------------------
# data determinism
# ---------------------------------------------------------------------------

@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 1000), step=st.integers(0, 50))
def test_data_step_determinism(seed, step):
    from repro.data import DataConfig, SyntheticLM
    cfg = DataConfig(seq_len=16, global_batch=4, vocab=53, seed=seed)
    a = SyntheticLM(cfg).batch_at(step)
    b = SyntheticLM(cfg).batch_at(step)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    assert a["tokens"].min() >= 0 and a["tokens"].max() < 53
