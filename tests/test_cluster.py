"""Cluster pool tests: router properties, cluster-1m parity, rebalance,
priced splits, PlanCache sharing, FAM_CLUSTER observability, and the
cluster-mode daemon.

The three Issue-10 router properties — every job routed exactly once, no
machine over its demand cap at any decision instant, rebalance preserves
exactly-once completion — each have a DETERMINISTIC twin that always
runs; the hypothesis generalizations at the bottom are guarded (the test
image does not ship hypothesis) and exercise the pure ``JobRouter`` over
generated fact tables when the library is available.
"""

from __future__ import annotations

import dataclasses
import os
import random

import pytest

from repro.cluster import (ClusterPool, ClusterResult, JobRouter,
                           MachineFacts, RouterConfig)
from repro.core import SimMachine, StrategyConfig, build_paper_graph
from repro.core.graph import GraphBuilder
from repro.hw import KNL, ClusterSpec
from repro.multitenant import PoolConfig
from repro.multitenant.parity import (cluster_timeline, pool_timeline,
                                      timeline_rows)
from repro.obs import FAM_CLUSTER, RecordingSink, export_cluster_trace
from repro.obs.metrics import metrics_from_events
from repro.obs.perfetto import MACHINE_PID_BASE
from repro.service import JobEntry, JobSpec, PoolDaemon, StoreState

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:          # deterministic twins below still run
    HAVE_HYPOTHESIS = False


def _recorded_uids(result, jid):
    return sorted(rec.op.uid for rec in result.records[jid])


def _two_component_graph(name: str = "twin", chains: int = 2,
                         depth: int = 5):
    """``chains`` disjoint dependency chains in one static graph — the
    smallest shape the cross-machine split can legally partition."""
    b = GraphBuilder(name)
    for _ in range(chains):
        prev = None
        for _ in range(depth):
            prev = b.add("Conv2D", (32, 32, 32, 64), flops=4e9,
                         bytes_moved=1.5e7,
                         deps=([prev] if prev is not None else []))
    return b.build()


# ---------------------------------------------------------------------------
# ClusterSpec
# ---------------------------------------------------------------------------

class TestClusterSpec:
    def test_homogeneous(self):
        c = ClusterSpec.homogeneous(3)
        assert c.n_machines == len(c) == 3
        assert c.total_cores == 3 * KNL.cores
        assert all(m is KNL for m in c.machines)

    def test_heterogeneous(self):
        small = dataclasses.replace(KNL, cores=34)
        c = ClusterSpec(machines=(KNL, small))
        assert c.total_cores == KNL.cores + 34

    def test_empty_rejected(self):
        with pytest.raises(ValueError):
            ClusterSpec(machines=())

    def test_frozen(self):
        with pytest.raises(dataclasses.FrozenInstanceError):
            ClusterSpec().name = "other"


# ---------------------------------------------------------------------------
# JobRouter (pure decision logic)
# ---------------------------------------------------------------------------

def _facts(rows):
    """rows: (load, demand, warm_frac) per machine, 68 cores each."""
    return [MachineFacts(i, 68, load, demand, warm)
            for i, (load, demand, warm) in enumerate(rows)]


class TestJobRouter:
    def test_unknown_policy_rejected(self):
        with pytest.raises(ValueError):
            RouterConfig(policy="lottery")

    def test_empty_facts_rejected(self):
        with pytest.raises(ValueError):
            JobRouter().route([])

    def test_round_robin_cycles(self):
        r = JobRouter(RouterConfig(policy="round_robin"))
        facts = _facts([(0, None, 0)] * 3)
        assert [r.route(facts) for _ in range(6)] == [0, 1, 2, 0, 1, 2]

    def test_demand_picks_smallest_projected_finish(self):
        r = JobRouter()
        # machine 1 idle, machine 0 loaded: 1 wins despite equal demand
        assert r.route(_facts([(100.0, 5.0, 0.0),
                               (0.0, 5.0, 0.0)])) == 1

    def test_warmth_breaks_exact_ties(self):
        r = JobRouter()
        assert r.route(_facts([(10.0, 5.0, 0.0),
                               (10.0, 5.0, 1.0)])) == 1

    def test_index_breaks_full_ties(self):
        r = JobRouter()
        assert r.route(_facts([(10.0, 5.0, 0.5),
                               (10.0, 5.0, 0.5)])) == 0

    def test_projected_finish_optimistic_when_unpriced(self):
        f = MachineFacts(0, 68, load=68.0, demand=None, warm_frac=0.0)
        assert f.projected_finish == 1.0      # load alone, no demand term


# ---------------------------------------------------------------------------
# Routing properties — deterministic twins (always run)
# ---------------------------------------------------------------------------

class TestRoutingProperties:
    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_every_job_routed_exactly_once(self, seed):
        """Assignment covers every submitted jid, each job's ops are
        recorded on EXACTLY the machine the router chose, once each."""
        rng = random.Random(seed)
        n = rng.choice([2, 3])
        models = [rng.choice(["resnet50", "dcgan"]) for _ in range(5)]
        pool = ClusterPool(ClusterSpec.homogeneous(n),
                           config=PoolConfig(max_active=2))
        jobs = [pool.submit(build_paper_graph(m), name=f"{m}.{i}",
                            submit_time=round(rng.uniform(0, 0.005), 6))
                for i, m in enumerate(models)]
        res = pool.run()
        assert sorted(res.assignment) == sorted(j.jid for j in jobs)
        for job in jobs:
            owners = [m for m, r in enumerate(res.machines)
                      if job.jid in r.records]
            assert owners == [res.assignment[job.jid]]
            assert _recorded_uids(res.machines[owners[0]], job.jid) \
                == sorted(job.graph.ops)

    def test_no_machine_over_demand_cap_at_any_instant(self):
        """Per-machine admission honors ``max_outstanding_demand`` at
        every decision instant.  The cap has a deliberate carve-out: a
        SOLO job is always admitted even over the cap (otherwise an
        oversized job could never run), so the invariant is conditional
        on co-running."""
        probe = ClusterPool(ClusterSpec.homogeneous(1))
        big = probe.submit(build_paper_graph("resnet50")).demand
        small = probe.submit(build_paper_graph("dcgan")).demand
        cap = big + 1.5 * small       # big+small co-runs; big+big never
        pool = ClusterPool(
            ClusterSpec.homogeneous(2),
            config=PoolConfig(max_active=4,
                              max_outstanding_demand=cap))
        for i in range(3):
            pool.submit(build_paper_graph("resnet50"), name=f"r{i}")
            pool.submit(build_paper_graph("dcgan"), name=f"d{i}")
        pool.begin()
        saw_corun = False
        while True:
            for p in pool.pools:
                if len(p._active) > 1:
                    saw_corun = True
                    outstanding = sum(j.demand for j in p._active)
                    assert outstanding <= cap + 1e-9
            if not pool.step():
                break
        assert saw_corun, "cap test must actually exercise co-running"
        assert all(cj.done for cj in pool.cluster_jobs)

    def _rebalance_run(self, rebalance: bool):
        pool = ClusterPool(ClusterSpec.homogeneous(2),
                           config=PoolConfig(max_active=1),
                           router=RouterConfig(rebalance=rebalance))
        pool.submit(build_paper_graph("resnet50"), name="hog", machine=0)
        urgent = pool.submit(build_paper_graph("dcgan"), name="urgent",
                             machine=0, submit_time=0.001, deadline=0.04)
        return pool, pool.run(), urgent

    def test_rebalance_preserves_exactly_once_completion(self):
        """The moved job's ops run once, on the target only; the stale
        jid leaves no records anywhere and resolves through the alias."""
        pool, res, urgent = self._rebalance_run(True)
        cj = next(c for c in res.cluster_jobs if c.name == "urgent")
        assert res.n_rebalances == 1 and cj.moves == 1
        assert cj.machine == 1 and cj.history == [(0, urgent.jid)]
        new_jid = cj.jobs[0].jid
        assert new_jid != urgent.jid
        assert pool.current_jid(urgent.jid) == new_jid
        assert urgent.jid not in res.assignment
        for r in res.machines:
            assert urgent.jid not in r.records
        assert _recorded_uids(res.machines[1], new_jid) \
            == sorted(urgent.graph.ops)
        # latency never forgiven: clocked from the ORIGINAL submission
        assert cj.latency == pytest.approx(
            cj.jobs[0].finish_time - 0.001)

    def test_rebalance_disabled_stays_put(self):
        _, res, urgent = self._rebalance_run(False)
        cj = next(c for c in res.cluster_jobs if c.name == "urgent")
        assert res.n_rebalances == 0 and cj.moves == 0
        assert cj.machine == 0 and cj.jobs[0].jid == urgent.jid

    def test_rebalance_beats_staying(self):
        _, moved_res, _ = self._rebalance_run(True)
        _, stay_res, _ = self._rebalance_run(False)
        moved = next(c for c in moved_res.cluster_jobs
                     if c.name == "urgent")
        stayed = next(c for c in stay_res.cluster_jobs
                      if c.name == "urgent")
        assert moved.latency < stayed.latency

    def test_no_deadline_never_rebalances(self):
        pool = ClusterPool(ClusterSpec.homogeneous(2),
                           config=PoolConfig(max_active=1))
        pool.submit(build_paper_graph("resnet50"), machine=0)
        pool.submit(build_paper_graph("dcgan"), machine=0,
                    submit_time=0.001)
        res = pool.run()
        assert res.n_rebalances == 0

    def test_routing_is_deterministic(self):
        def run():
            pool = ClusterPool(ClusterSpec.homogeneous(2),
                               config=PoolConfig(max_active=2))
            for i in range(4):
                m = "resnet50" if i % 2 == 0 else "dcgan"
                pool.submit(build_paper_graph(m), name=f"{m}.{i}")
            return pool.run()

        a, b = run(), run()
        assert a.assignment == b.assignment
        assert a.makespan == b.makespan
        assert a.metrics == b.metrics

    def test_demand_routing_spreads_identical_jobs(self):
        pool = ClusterPool(ClusterSpec.homogeneous(2))
        j0 = pool.submit(build_paper_graph("resnet50"))
        j1 = pool.submit(build_paper_graph("resnet50"))
        assert {pool.assignment[j0.jid], pool.assignment[j1.jid]} == {0, 1}


# ---------------------------------------------------------------------------
# cluster-1m parity: the layering claim
# ---------------------------------------------------------------------------

class TestClusterParity:
    @pytest.mark.parametrize("model", ["resnet50", "dcgan"])
    def test_one_machine_cluster_is_the_pool(self, model):
        a = pool_timeline(build_paper_graph(model), SimMachine(seed=3))
        b = cluster_timeline(build_paper_graph(model), SimMachine(seed=3))
        assert timeline_rows(a) == timeline_rows(b)


# ---------------------------------------------------------------------------
# PlanCache sharing + DemandIndex memoization
# ---------------------------------------------------------------------------

class TestPlanCacheSharing:
    def test_same_fingerprint_pays_probes_once(self):
        """Homogeneous machines share a curve namespace: the second
        machine's submit-time profile is a pure cache hit."""
        pool = ClusterPool(ClusterSpec.homogeneous(2))
        g = build_paper_graph("resnet50")
        pool.submit(build_paper_graph("resnet50"), machine=0)
        spent = pool.plan_cache.stats()["probes_spent"]
        assert pool._warm_frac(1, g) == 1.0
        pool.submit(build_paper_graph("resnet50"), machine=1)
        assert pool.plan_cache.stats()["probes_spent"] == spent

    def test_distinct_fingerprints_pay_separately(self):
        """Machines with different timing identities (here: different
        jitter seeds) must NOT share curves."""
        pool = ClusterPool(ClusterSpec.homogeneous(2),
                           machines=[SimMachine(seed=0),
                                     SimMachine(seed=7)])
        pool.submit(build_paper_graph("dcgan"), machine=0)
        spent = pool.plan_cache.stats()["probes_spent"]
        assert pool._warm_frac(1, build_paper_graph("dcgan")) == 0.0
        pool.submit(build_paper_graph("dcgan"), machine=1)
        assert pool.plan_cache.stats()["probes_spent"] > spent

    def test_demand_index_memoizes_repeat_shapes(self):
        pool = ClusterPool(ClusterSpec.homogeneous(2))
        pool.submit(build_paper_graph("dcgan"))
        misses = pool.demand_index.misses
        pool.submit(build_paper_graph("dcgan"))
        assert pool.demand_index.hits >= 1
        assert pool.demand_index.misses == misses


# ---------------------------------------------------------------------------
# Priced cross-machine splits
# ---------------------------------------------------------------------------

class TestSplit:
    def test_components(self):
        g = _two_component_graph(chains=3, depth=2)
        comps = ClusterPool._components(g)
        assert [len(c) for c in comps] == [2, 2, 2]
        assert sorted(u for c in comps for u in c) == sorted(g.ops)

    def _split_pool(self, transfer_cost_s: float):
        return ClusterPool(
            ClusterSpec(machines=(KNL, KNL),
                        transfer_cost_s=transfer_cost_s),
            router=RouterConfig(split=True))

    def test_cheap_transfer_splits_across_two_machines(self):
        pool = self._split_pool(1e-4)
        pool.submit(_two_component_graph(), name="twin")
        cj = pool.cluster_jobs[-1]
        assert cj.split and len(cj.jobs) == 2
        parts = {pool.assignment[j.jid] for j in cj.jobs}
        assert parts == {0, 1}
        res = pool.run()
        assert res.n_splits == 1
        uids = sorted(u for j in cj.jobs
                      for u in _recorded_uids(
                          res.machines[res.assignment[j.jid]], j.jid))
        assert uids == sorted(_two_component_graph().ops)

    def test_expensive_transfer_refuses_split(self):
        pool = self._split_pool(1e9)
        pool.submit(_two_component_graph(), name="twin")
        res = pool.run()
        assert res.n_splits == 0
        assert not pool.cluster_jobs[-1].split

    def test_split_off_by_default(self):
        pool = ClusterPool(ClusterSpec(machines=(KNL, KNL),
                                       transfer_cost_s=1e-4))
        pool.submit(_two_component_graph(), name="twin")
        assert pool.n_splits == 0

    def test_single_component_never_splits(self):
        pool = self._split_pool(1e-4)
        pool.submit(build_paper_graph("dcgan"))
        assert pool.n_splits == 0

    def test_cancel_takes_all_parts(self):
        """Split parts stand and fall together: cancelling by EITHER
        part's jid removes both halves before any op runs."""
        pool = self._split_pool(1e-4)
        job = pool.submit(_two_component_graph(), name="twin")
        cj = pool.cluster_jobs[-1]
        assert pool.cancel(job.jid) is True
        res = pool.run()
        for part in cj.jobs:
            m = res.assignment[part.jid]
            assert not res.machines[m].records.get(part.jid)


# ---------------------------------------------------------------------------
# FAM_CLUSTER observability (positive coverage — the single-machine
# trace artifact legitimately excludes this family)
# ---------------------------------------------------------------------------

class TestClusterObservability:
    def _traced_run(self, tmp_path):
        sink = RecordingSink()
        pool = ClusterPool(
            ClusterSpec.homogeneous(2),
            config=PoolConfig(max_active=2,
                              strategy=StrategyConfig(sink=sink)))
        for i, m in enumerate(["resnet50", "dcgan", "resnet50", "dcgan"]):
            pool.submit(build_paper_graph(m), name=f"{m}.{i}")
        res = pool.run()
        return sink, res

    def test_route_events_and_metrics(self, tmp_path):
        sink, _ = self._traced_run(tmp_path)
        routes = [e for e in sink.events
                  if e.family == FAM_CLUSTER and e.kind == "route"]
        assert len(routes) == 4
        assert all(e.data["policy"] == "demand"
                   and not e.data["forced"]
                   and e.data["demand"] is not None
                   and len(e.data["loads"]) == 2 for e in routes)
        snap = metrics_from_events(sink.events).snapshot()
        assert snap.get("cluster.route") == 4
        assert sum(snap.get(f"cluster.machine.{m}.routed", 0)
                   for m in range(2)) == 4

    def test_rebalance_event(self):
        sink = RecordingSink()
        pool = ClusterPool(
            ClusterSpec.homogeneous(2),
            config=PoolConfig(max_active=1,
                              strategy=StrategyConfig(sink=sink)))
        pool.submit(build_paper_graph("resnet50"), name="hog", machine=0)
        pool.submit(build_paper_graph("dcgan"), name="urgent", machine=0,
                    submit_time=0.001, deadline=0.04)
        pool.run()
        moves = [e for e in sink.events
                 if e.family == FAM_CLUSTER and e.kind == "rebalance"]
        assert len(moves) == 1
        assert moves[0].data["from"] == 0 and moves[0].data["to"] == 1
        assert moves[0].data["slack"] <= 0.0

    def test_split_event(self):
        sink = RecordingSink()
        pool = ClusterPool(
            ClusterSpec(machines=(KNL, KNL), transfer_cost_s=1e-4),
            config=PoolConfig(strategy=StrategyConfig(sink=sink)),
            router=RouterConfig(split=True))
        pool.submit(_two_component_graph(), name="twin")
        ev = [e for e in sink.events
              if e.family == FAM_CLUSTER and e.kind == "split"]
        assert len(ev) == 1
        assert ev[0].data["machines"] == [0, 1]
        assert ev[0].data["gain"] > ev[0].data["cost"]

    def test_perfetto_export_per_machine_lanes(self, tmp_path):
        sink, res = self._traced_run(tmp_path)
        path = tmp_path / "cluster_trace.json"
        trace = export_cluster_trace(res, str(path), sink.events)
        assert path.exists()
        pids = {e.get("pid") for e in trace["traceEvents"]}
        assert {MACHINE_PID_BASE, MACHINE_PID_BASE + 1} <= pids
        flows = [e for e in trace["traceEvents"]
                 if e.get("cat") == "cluster"
                 and e.get("ph") in ("s", "f")]
        assert flows, "route->launch flow arrows must be emitted"


# ---------------------------------------------------------------------------
# Cluster-mode daemon: placement is state, recovery restores it
# ---------------------------------------------------------------------------

class TestClusterDaemon:
    def test_cluster_xor_machine(self, tmp_path):
        with pytest.raises(ValueError):
            PoolDaemon(tmp_path, cluster=ClusterSpec.homogeneous(2),
                       machine=SimMachine())

    def test_placement_survives_restart_and_drains(self, tmp_path):
        spec = ClusterSpec.homogeneous(2)
        cfg = PoolConfig(max_active=2)
        d1 = PoolDaemon(tmp_path, cluster=spec, config=cfg)
        for i, w in enumerate(["resnet50", "dcgan", "dcgan", "resnet50"]):
            d1.submit(JobSpec(workload=w, name=f"j{i}"))
        st1 = d1.status()
        placement1 = {j["id"]: j["machine"] for j in st1["jobs"]}
        assert st1["machines"] == 2
        assert set(placement1.values()) <= {0, 1}
        d1.checkpoint()
        d1.close()

        d2 = PoolDaemon(tmp_path, cluster=spec, config=cfg)
        st2 = d2.status()
        assert {j["id"]: j["machine"] for j in st2["jobs"]} == placement1
        res = d2.drain()
        assert isinstance(res, ClusterResult)
        assert all(cj.done for cj in d2.pool.cluster_jobs)
        assert len(st2["clocks"]) == 2
        d2.close()

    def test_legacy_store_without_cluster_fields_loads(self):
        entry = JobEntry(spec=JobSpec(workload="dcgan"), order=0)
        d = entry.to_dict()
        d.pop("machine")
        assert JobEntry.from_dict(d).machine is None

        state = StoreState(entries=[entry])
        sd = state.to_dict()
        sd.pop("clocks")
        assert StoreState.from_dict(sd).clocks is None


# ---------------------------------------------------------------------------
# XLA host-device fan-out (executor side of the cluster)
# ---------------------------------------------------------------------------

class TestHostDevices:
    def test_existing_device_count_flag_wins(self, monkeypatch):
        from repro.core.runtime import _request_host_devices
        monkeypatch.setenv(
            "XLA_FLAGS", "--xla_force_host_platform_device_count=4")
        _request_host_devices(2)
        assert os.environ["XLA_FLAGS"].count(
            "xla_force_host_platform_device_count") == 1

    def test_flag_appended_once(self, monkeypatch):
        from repro.core.runtime import _request_host_devices
        monkeypatch.setenv("XLA_FLAGS", "--some_other_flag")
        _request_host_devices(3)
        flags = os.environ["XLA_FLAGS"]
        assert "--some_other_flag" in flags
        assert "--xla_force_host_platform_device_count=3" in flags

    @pytest.mark.slow
    def test_device_for_round_robins(self):
        jax = pytest.importorskip("jax")
        from repro.core.runtime import RealGraphExecutor
        ex = RealGraphExecutor(n_devices=2)
        d0 = ex.device_for(0)
        assert d0 == jax.devices()[0]        # the default backend's
        n = len(jax.devices())
        assert ex.device_for(n) == d0        # wraps modulo the grant
        if n >= 2:
            assert ex.device_for(1) != d0


# ---------------------------------------------------------------------------
# hypothesis generalizations (skipped when hypothesis is absent; the
# deterministic twins above always run)
# ---------------------------------------------------------------------------

if HAVE_HYPOTHESIS:
    _fact_rows = st.lists(
        st.tuples(st.floats(0, 1e4), st.floats(1e-3, 1e3),
                  st.floats(0, 1)),
        min_size=1, max_size=8)

    class TestRouterHypothesis:
        @settings(deadline=None, max_examples=100)
        @given(rows=_fact_rows)
        def test_demand_route_minimizes_projected_finish(self, rows):
            facts = _facts(rows)
            chosen = JobRouter().route(facts)
            picked = next(f for f in facts if f.index == chosen)
            best = min((f.projected_finish, -f.warm_frac, f.index)
                       for f in facts)
            assert (picked.projected_finish, -picked.warm_frac,
                    picked.index) == best

        @settings(deadline=None, max_examples=50)
        @given(rows=_fact_rows, k=st.integers(1, 32))
        def test_round_robin_routes_each_arrival_exactly_once(
                self, rows, k):
            r = JobRouter(RouterConfig(policy="round_robin"))
            facts = _facts(rows)
            n = len(facts)
            choices = [r.route(facts) for _ in range(k)]
            assert all(0 <= c < n for c in choices)
            # arrivals spread one at a time, never skipping a machine
            for m in range(n):
                assert choices.count(m) in (k // n, k // n + 1)
