"""Fault tolerance: heartbeat, straggler detection, checkpoint-retry loop.

Scope note (DESIGN.md §6): in-process mechanisms are fully implemented
and tested — what belongs to the cluster manager (re-scheduling a dead
host, swapping hardware) is exposed as policy decisions
(``StragglerMonitor.decide``) the manager consumes.
"""

from __future__ import annotations

import dataclasses
import json
import os
import time
from typing import Callable

import jax
from jax.errors import JaxRuntimeError

from repro.obs import compile_counter

# host spans each iteration of ``run_with_recovery`` writes into a
# profiler trace, on the clock of the device planes
STEP_SPAN = "repro.step"
DATA_SPAN = "repro.data"


class Heartbeat:
    """Periodic liveness file: {step, time}.  A watchdog (or another
    host) treats staleness > timeout as failure."""

    def __init__(self, path: str, interval_s: float = 10.0):
        self.path = path
        self.interval_s = interval_s
        self._last = 0.0

    def beat(self, step: int, force: bool = False) -> None:
        now = time.time()
        if not force and now - self._last < self.interval_s:
            return
        tmp = self.path + ".tmp"
        with open(tmp, "w") as f:
            json.dump({"step": step, "time": now}, f)
        os.rename(tmp, self.path)
        self._last = now

    @staticmethod
    def is_stale(path: str, timeout_s: float) -> bool:
        if not os.path.exists(path):
            return True
        with open(path) as f:
            return time.time() - json.load(f)["time"] > timeout_s


@dataclasses.dataclass
class StragglerMonitor:
    """Per-host step-time EMA + z-score flagging.

    observe() ingests per-host step times (from an allgather in real
    deployments); decide() emits the mitigation policy:
      - "exclude": host consistently beyond z_threshold -> re-mesh without it
      - "watch":   transient slowness
    """

    z_threshold: float = 3.0
    ema_alpha: float = 0.2
    min_observations: int = 5
    consecutive_to_exclude: int = 3
    min_relative_excess: float = 0.2   # must also be >20% over median

    def __post_init__(self) -> None:
        self._ema: dict[str, float] = {}
        self._count: dict[str, int] = {}
        self._flags: dict[str, int] = {}

    def observe(self, host_times: dict[str, float]) -> dict[str, str]:
        for h, t in host_times.items():
            prev = self._ema.get(h, t)
            self._ema[h] = (1 - self.ema_alpha) * prev + self.ema_alpha * t
            self._count[h] = self._count.get(h, 0) + 1

        out: dict[str, str] = {}
        # flag on THIS round's raw times (an EMA would keep flagging a
        # host for many rounds after one transient spike); robust
        # median/MAD stats so a single straggler cannot inflate its own
        # detection threshold, plus a relative floor so sub-20% jitter
        # never flags even when MAD ~ 0
        vals = sorted(host_times.values())
        if len(vals) < 2:
            return out
        mid = len(vals) // 2
        median = (vals[mid] if len(vals) % 2
                  else 0.5 * (vals[mid - 1] + vals[mid]))
        devs = sorted(abs(v - median) for v in vals)
        mad = (devs[mid] if len(devs) % 2
               else 0.5 * (devs[mid - 1] + devs[mid]))
        scale = max(1.4826 * mad, 1e-9)
        for h, v in host_times.items():
            if self._count[h] < self.min_observations:
                continue
            z = (v - median) / scale
            if v < median * (1.0 + self.min_relative_excess):
                z = 0.0
            if z > self.z_threshold:
                self._flags[h] = self._flags.get(h, 0) + 1
                out[h] = ("exclude"
                          if self._flags[h] >= self.consecutive_to_exclude
                          else "watch")
            else:
                self._flags[h] = 0
        return out

    def healthy_hosts(self, hosts: list[str]) -> list[str]:
        return [h for h in hosts
                if self._flags.get(h, 0) < self.consecutive_to_exclude]


# status codes of a runtime error that the same program on the same state
# raises again on replay: an allocation or compile that does not fit the
# device, or a program the backend refuses
_DETERMINISTIC_STATUS = ("RESOURCE_EXHAUSTED", "INVALID_ARGUMENT",
                         "UNIMPLEMENTED", "FAILED_PRECONDITION")


def _replay_can_fix(exc: BaseException) -> bool:
    """Is ``exc`` a fault that restoring a checkpoint and replaying may get
    past?  Runtime and I/O errors may be transient; a runtime error with a
    deterministic status, and every other exception (raised while tracing
    or lowering: shapes, dtypes, a kernel's tiling, a bug), are not."""
    if isinstance(exc, JaxRuntimeError):
        return not str(exc).startswith(_DETERMINISTIC_STATUS)
    return isinstance(exc, (RuntimeError, OSError))


@dataclasses.dataclass
class RecoveryStats:
    failures: int = 0
    restores: int = 0
    steps_replayed: int = 0
    compiles: int = 0          # traces to a jaxpr inside the loop


def run_with_recovery(step_fn: Callable, state, *, n_steps: int,
                      save_every: int, manager, data_prefetch=None,
                      max_failures: int = 5,
                      on_metrics: Callable | None = None
                      ) -> tuple[object, RecoveryStats]:
    """Drive (state, batch) -> (state, metrics) with checkpoint/restore.

    An exception from step_fn that a replay may get past
    (``_replay_can_fix``) triggers restore-from-latest and replay; any other
    is re-raised at once.  ``data_prefetch`` must expose
    .next()/.state()/.cursor and a ``source.batch_at(step)`` for
    deterministic replay.  Each iteration is a ``repro.step`` span in a
    profiler trace, and its ``batch_at`` a ``repro.data`` span inside it;
    the compilations the loop causes are counted into the stats."""
    stats = RecoveryStats()
    compiles = compile_counter()
    traces0, _ = compiles.read()
    step = 0
    while step < n_steps:
        try:
            with jax.profiler.StepTraceAnnotation(STEP_SPAN, step_num=step):
                if data_prefetch is not None:
                    with jax.profiler.TraceAnnotation(DATA_SPAN):
                        batch = data_prefetch.source.batch_at(step)
                else:
                    batch = None
                state, metrics = step_fn(state, batch, step)
                if on_metrics is not None:
                    on_metrics(step, metrics)
                step += 1
                if save_every and step % save_every == 0:
                    manager.save(step, state,
                                 extra={"data_cursor": step})
        except Exception as exc:
            stats.failures += 1
            if stats.failures > max_failures or not _replay_can_fix(exc):
                raise
            restored = manager.restore()
            if restored is None:
                # no checkpoint yet: restart from scratch
                stats.steps_replayed += step
                step = 0
                continue
            state, extra, ck_step = restored
            stats.restores += 1
            stats.steps_replayed += max(0, step - ck_step)
            step = ck_step
    manager.wait()
    stats.compiles = compiles.read()[0] - traces0
    return state, stats
