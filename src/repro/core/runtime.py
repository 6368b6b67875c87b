"""The runtime driver — the paper's Fig. 2 workflow.

``ConcurrencyRuntime`` glues the pieces together exactly in the paper's
order: the first N training steps run ops serially while the hill-climbing
profiler measures them (profiling steps); the resulting curves freeze a
``ConcurrencyPlan`` (Strategies 1-2); every subsequent step executes under
the co-run scheduler (Strategies 3-4).  The same step graph is reused
across steps (the paper's stable-step observation, §II-A), so profiling
cost amortizes over thousands of steps.

Two executors:

* the **simulated executor** (``SimMachine``-timed) validates the decision
  logic deterministically — this is what the paper-table benchmarks use;
* ``RealGraphExecutor`` runs op payloads (real jitted JAX callables) on a
  worker pool with dependency tracking — used by the examples and
  integration tests to show the runtime drives real computation.
"""

from __future__ import annotations

import dataclasses
import os
import time
from concurrent.futures import FIRST_COMPLETED, Future, ThreadPoolExecutor, wait

from repro.core.concurrency import ConcurrencyController, ConcurrencyPlan
from repro.core.graph import Op, OpGraph
from repro.core.interference import InterferenceRecorder
from repro.core.perfmodel import (CurveCache, HillClimbProfiler, ProfileStore,
                                  paper_case_lists)
from repro.core.planstore import (OBS_FINISH, OpObservation, PlanStore,
                                  make_plan_store)
from repro.core.scheduler import CorunScheduler, ScheduleResult, uniform_schedule
from repro.core.simmachine import Placement, SimMachine
from repro.core.strategy import (CONFIG_SCHEMA_VERSION, StrategyConfig,
                                 _check_config_dict,
                                 fold_deprecated_strategy_kwargs)
from repro.obs.trace import TraceSink


@dataclasses.dataclass(init=False)
class RuntimeConfig:
    """Single-job runtime knobs.  Strategy-owned knobs (S3/S4 switches,
    candidate counts, topology, feedback, sink, ...) live ONCE on the
    composed ``strategy`` field; only the knobs the runtime itself
    consumes (profiling interval, S2 clamp, interference threshold) are
    declared here.  The old flat constructor kwargs
    (``RuntimeConfig(feedback="ewma")``) keep working with a
    DeprecationWarning — they fold onto ``strategy``."""

    interval: int = 4               # hill-climb probe interval x
    max_deviation: int = 2          # Strategy 2 clamp (paper's empirical 2)
    strategy2: bool = True
    interference_threshold: float = 1.35
    strategy: StrategyConfig = dataclasses.field(
        default_factory=StrategyConfig)

    def __init__(self, interval: int = 4, max_deviation: int = 2,
                 strategy2: bool = True,
                 interference_threshold: float = 1.35,
                 strategy: StrategyConfig | None = None, **deprecated):
        self.interval = interval
        self.max_deviation = max_deviation
        self.strategy2 = strategy2
        self.interference_threshold = interference_threshold
        self.strategy = fold_deprecated_strategy_kwargs(
            type(self).__name__,
            strategy if strategy is not None else StrategyConfig(),
            deprecated)

    # read-only views of the strategy-owned knobs, so the sprawling
    # existing read sites (schedulers, benchmarks, tests) keep working
    @property
    def enable_s3(self) -> bool: return self.strategy.enable_s3

    @property
    def enable_s4(self) -> bool: return self.strategy.enable_s4

    @property
    def candidates(self) -> int: return self.strategy.candidates

    @property
    def max_ht_corunners(self) -> int: return self.strategy.max_ht_corunners

    @property
    def min_fallback_cores(self) -> int:
        return self.strategy.min_fallback_cores

    @property
    def fallback_slack(self) -> float: return self.strategy.fallback_slack

    @property
    def topology(self) -> str: return self.strategy.topology

    @property
    def feedback(self) -> str: return self.strategy.feedback

    @property
    def sink(self) -> TraceSink: return self.strategy.sink

    def strategy_config(self) -> StrategyConfig:
        """The shared-core view of these knobs (see repro.core.strategy).
        The multi-tenant PoolConfig composes the same StrategyConfig, so
        Strategy-3/4 rule parameters cannot drift between schedulers."""
        return self.strategy

    def to_dict(self) -> dict:
        """Versioned JSON form (the daemon's persisted store and the CLI
        share this serialization; the strategy nests its own document)."""
        return {"schema": CONFIG_SCHEMA_VERSION,
                "interval": self.interval,
                "max_deviation": self.max_deviation,
                "strategy2": self.strategy2,
                "interference_threshold": self.interference_threshold,
                "strategy": self.strategy.to_dict()}

    @classmethod
    def from_dict(cls, d) -> "RuntimeConfig":
        d = dict(d)
        strat = d.pop("strategy", None)
        kw = _check_config_dict(
            cls.__name__, d,
            {"interval", "max_deviation", "strategy2",
             "interference_threshold"})
        if strat is not None:
            kw["strategy"] = StrategyConfig.from_dict(strat)
        return cls(**kw)


@dataclasses.dataclass
class TrainingSummary:
    profiling_steps: int
    profiling_time: float           # serial time spent probing
    step_time: float                # steady-state scheduled step time
    baseline_step_time: float       # TF-recommendation uniform schedule
    total_steps: int

    @property
    def speedup(self) -> float:
        return self.baseline_step_time / self.step_time

    @property
    def total_time(self) -> float:
        return self.profiling_time + self.step_time * max(
            0, self.total_steps - self.profiling_steps)

    @property
    def profiling_overhead(self) -> float:
        return self.profiling_time / max(self.total_time, 1e-12)


class ConcurrencyRuntime:
    def __init__(self, machine: SimMachine | None = None,
                 config: RuntimeConfig | None = None,
                 plan_cache: "CurveCache | None" = None):
        self.machine = machine or SimMachine()
        self.config = config or RuntimeConfig()
        # optional cross-job curve cache (multi-tenant pool): profiling
        # probes one job paid for are reused by every later job
        self.plan_cache = plan_cache
        self.store: ProfileStore | None = None
        self.plan: ConcurrencyPlan | None = None
        self.controller: ConcurrencyController | None = None
        # the closed-loop plan store (built at profile time): every
        # prediction the scheduler consumes and every completion it
        # reports flows through it; persists across execute_step calls so
        # feedback="ewma" corrections carry from one step to the next
        self.planstore: PlanStore | None = None
        self.recorder = InterferenceRecorder(
            threshold=self.config.interference_threshold)

    # ---- phase 1: profiling steps -------------------------------------
    def _measure(self, op: Op, threads: int, variant: bool) -> float:
        return self.machine.op_time(
            op, Placement(threads, cache_sharing=variant))

    def profile(self, graph: OpGraph) -> ProfileStore:
        if self.plan_cache is not None:
            # caches that can pin themselves must refuse reuse across a
            # different timing function OR probe protocol: a curve's
            # measured samples carry the probe spacing, which Strategy-3
            # candidates and the S2 clamp's case_step assume
            bind = getattr(self.plan_cache, "bind_machine", None)
            if bind is not None:
                bind((self.machine.fingerprint, self.config.interval))
        profiler = HillClimbProfiler(
            measure=self._measure,
            case_lists=paper_case_lists(self.machine.spec.cores,
                                        self.machine.spec.tiles),
            interval=self.config.interval)
        # dynamic graphs are profiled/planned through their static
        # profile_view — one clone of every op a region could ever
        # materialize, so the frozen plan covers loop bodies and branches
        # before the first iteration exists (a static graph is its own
        # view: bit-identical to profiling the graph directly)
        view = graph.profile_view()
        self.store = profiler.profile_graph(view, cache=self.plan_cache)
        self.controller = ConcurrencyController(
            self.store, max_deviation=self.config.max_deviation,
            default_threads=self.machine.spec.cores,
            interval=self.config.interval)
        self.plan = self.controller.build_plan(view)
        self.planstore = make_plan_store(self.config.feedback,
                                         self.controller)
        return self.store

    def profiling_cost(self) -> tuple[int, float]:
        """(#profiling steps, serial seconds spent probing).

        The paper bounds N <= C/x * 2; each probing step runs every op once
        serially at that step's concurrency."""
        assert self.store is not None
        probes_per_curve = [c.probes for c in self.store.curves.values()]
        n_steps = max(probes_per_curve) if probes_per_curve else 0
        # curves served by a warm plan cache carry probes=0 — their sample
        # times were paid by another job, not this run
        probe_time = sum(y for c in self.store.curves.values() if c.probes
                         for pts in c.samples.values() for _, y in pts)
        return n_steps, probe_time

    # ---- phase 2: scheduled steps --------------------------------------
    def scheduler(self) -> CorunScheduler:
        assert self.plan is not None and self.controller is not None
        cfg = self.config
        return CorunScheduler(
            self.machine, self.controller, self.plan,
            recorder=self.recorder,
            enable_s3=cfg.enable_s3,
            enable_s4=cfg.enable_s4,
            strategy2=cfg.strategy2,
            max_ht_corunners=cfg.max_ht_corunners,
            candidates=cfg.candidates,
            min_fallback_cores=cfg.min_fallback_cores,
            fallback_slack=cfg.fallback_slack,
            topology=cfg.topology,
            feedback=cfg.feedback,
            sink=cfg.sink,
            planstore=self.planstore)

    def execute_step(self, graph: OpGraph) -> ScheduleResult:
        if self.plan is None:
            self.profile(graph)
        return self.scheduler().run(graph)

    # ---- end-to-end ------------------------------------------------------
    def train(self, graph: OpGraph, total_steps: int = 1000,
              baseline_intra: int | None = None) -> TrainingSummary:
        self.profile(graph)
        n_steps, probe_time = self.profiling_cost()
        result = self.execute_step(graph)
        baseline = uniform_schedule(
            graph, self.machine,
            intra=baseline_intra or self.machine.spec.cores, inter=1)
        return TrainingSummary(
            profiling_steps=n_steps,
            profiling_time=probe_time,
            step_time=result.makespan,
            baseline_step_time=baseline.makespan,
            total_steps=total_steps)


# ---------------------------------------------------------------------------
# Real-payload executor
# ---------------------------------------------------------------------------

def report_payload_observation(store: PlanStore, plan: ConcurrencyPlan | None,
                               op, dt: float) -> None:
    """Report one real payload completion through ``PlanStore.observe``.

    The wall time is attributed to the op's frozen-plan width (falling
    back to solo when the plan has no entry), so real timings feed the
    same closed loop the simulated schedulers use.  Shared by the batch
    ``RealGraphExecutor.run`` path and the service daemon's persistent
    executor."""
    if plan is not None and op.size_key in plan.per_instance:
        p = plan.per_instance[op.size_key]
        threads, variant = p.threads, p.variant
    else:
        threads, variant = 1, True
    try:
        predicted = store.predict(op, threads, variant)
    except KeyError:
        # op never profiled under this store — the observation record
        # still needs a predicted value (it is informative only:
        # AdaptivePlanStore re-derives the base prediction itself and
        # skips ops without a curve)
        predicted = dt
    store.observe(OpObservation(
        op=op, threads=threads, variant=variant, hyper=False,
        predicted=predicted, observed=dt, kind=OBS_FINISH))


def _host_is_backend() -> bool:
    """Will jax run on the host CPU?  Read from ``JAX_PLATFORMS`` without
    starting jax, because the host device count is fixed when it starts:
    only a platform list led by ``cpu`` says so."""
    return os.environ.get("JAX_PLATFORMS", "").split(",")[0] == "cpu"


def _request_host_devices(n: int) -> None:
    """Ask XLA for ``n`` host-platform devices.  Appends the flag to
    ``XLA_FLAGS`` unless a device count is already pinned there (the
    launch tools set 512 at import; respect any explicit choice).  A
    no-op on an already-initialized jax — the count is locked at first
    init, and ``device_for`` round-robins over whatever jax granted."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" in flags:
        return
    flag = f"--xla_force_host_platform_device_count={n}"
    os.environ["XLA_FLAGS"] = f"{flags} {flag}".strip()


class RealGraphExecutor:
    """Dependency-ordered execution of op payloads on a worker pool.

    ``op.payload`` is ``fn(dep_results: dict[uid, value]) -> value``.  The
    worker count plays the role of inter-op parallelism; per-op results are
    returned with wall-clock timings so the runtime's decisions can be
    validated against real JAX computations.

    Real timings can feed the same closed loop as the simulated
    schedulers: pass ``store``/``plan`` to ``run`` and every payload
    completion is reported through ``PlanStore.observe`` as an
    ``OBS_FINISH`` event at the op's frozen-plan width — the first step
    toward a pool-backed real executor whose observed wall times drive
    online re-estimation.

    ``persistent=True`` switches to the service-daemon mode: the worker
    pool outlives any one graph and callers drive it op-by-op with
    ``submit_op`` (the pool's launch decisions pick the order) instead of
    handing over a whole graph.  ``submit_op`` futures wait for their
    dependency futures INSIDE the worker, which keeps ``Future.cancel``
    meaningful: a revoked op that has not reached a worker yet is
    cancelled before any payload runs.  Deadlock-free because payloads
    are only submitted in dependency order (the pool launches an op only
    after its deps completed), so every queued task waits only on
    strictly earlier submissions.

    ``n_devices`` maps the cluster daemon's simulated machines onto
    DISTINCT devices of jax's default backend: ``device_for(machine)``
    returns the device a machine's payloads should land on (a chip on an
    accelerator host).  When that backend is the host CPU, it requests
    that many host-platform XLA devices
    (``--xla_force_host_platform_device_count``, which only takes effect
    if set before jax's first initialization — jax locks the device
    count then)."""

    def __init__(self, max_workers: int = 2, *, persistent: bool = False,
                 n_devices: int | None = None):
        self.max_workers = max_workers
        self.n_devices = n_devices
        self._devices: tuple | None = None     # resolved lazily
        if n_devices is not None and n_devices > 1 and _host_is_backend():
            _request_host_devices(n_devices)
        self._pool: ThreadPoolExecutor | None = (
            ThreadPoolExecutor(max_workers=max_workers)
            if persistent else None)

    # ---- persistent (service-daemon) mode ------------------------------
    def device_for(self, machine: int | None):
        """The JAX device simulated machine ``machine`` maps to
        (round-robin when jax granted fewer devices than machines; None
        when unmapped or ``n_devices`` was never set)."""
        if machine is None or self.n_devices is None:
            return None
        if self._devices is None:
            import jax
            self._devices = tuple(jax.devices())
        return self._devices[machine % len(self._devices)]

    def submit_op(self, op, deps: dict[int, object],
                  device=None) -> Future:
        """Submit one op's payload to the persistent worker set.

        ``deps`` maps dep uid -> either the dep's ``Future`` (resolved
        inside the worker) or an already-materialized value (ops without
        payloads produce ``None`` directly).  ``device`` (from
        ``device_for``) pins the payload's jax computations to one host
        device.  Returns a future of ``(result, wall_seconds)``."""
        assert self._pool is not None, "submit_op needs persistent=True"

        def call() -> tuple[object, float]:
            # dep futures resolve to (value, wall_s); payloads see values
            vals = {u: (f.result()[0] if isinstance(f, Future) else f)
                    for u, f in deps.items()}
            ts = time.perf_counter()
            if device is not None:
                import jax
                with jax.default_device(device):
                    out = op.payload(vals) if op.payload else None
            else:
                out = op.payload(vals) if op.payload else None
            return out, time.perf_counter() - ts

        return self._pool.submit(call)

    def close(self) -> None:
        """Shut down the persistent worker set (queued work cancelled,
        running payloads finish).  No-op in batch mode."""
        if self._pool is not None:
            self._pool.shutdown(wait=False, cancel_futures=True)
            self._pool = None

    def run(self, graph: OpGraph, *, store: PlanStore | None = None,
            plan: ConcurrencyPlan | None = None
            ) -> tuple[dict[int, object], dict[int, float], float]:
        results: dict[int, object] = {}
        timings: dict[int, float] = {}
        pending = {u: len(op.deps) for u, op in graph.ops.items()}
        ready = [u for u, n in pending.items() if n == 0]
        t0 = time.perf_counter()

        def observe(uid: int, dt: float) -> None:
            if store is None:
                return
            report_payload_observation(store, plan, graph.ops[uid], dt)

        with ThreadPoolExecutor(max_workers=self.max_workers) as pool:
            futures: dict[Future, int] = {}

            def submit(uid: int) -> None:
                op = graph.ops[uid]
                deps = {d: results[d] for d in op.deps}

                def call(op=op, deps=deps):
                    ts = time.perf_counter()
                    out = op.payload(deps) if op.payload else None
                    return out, time.perf_counter() - ts

                futures[pool.submit(call)] = uid

            for u in ready:
                submit(u)
            while futures:
                done, _ = wait(list(futures), return_when=FIRST_COMPLETED)
                for fut in done:
                    uid = futures.pop(fut)
                    out, dt = fut.result()
                    results[uid] = out
                    timings[uid] = dt
                    observe(uid, dt)
                    for c in graph.consumers(uid):
                        pending[c] -= 1
                        if pending[c] == 0:
                            submit(c)
        return results, timings, time.perf_counter() - t0
