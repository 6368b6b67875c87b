"""What the train step and its loop put into a profiler trace, and the
compile counter.

* The compiled train step of the smoke OLMo config carries the five
  scopes (``embed``, ``attention``, ``mlp``, ``head``, ``optimizer``) in
  its instructions' ``op_name`` metadata, through remat, the layer scan
  and autodiff, while the scan's own slicing stays unscoped.
* ``run_with_recovery`` writes one ``repro.step`` span per iteration and a
  ``repro.data`` span inside it around ``batch_at``.
* The compile counter counts a trace for a new shape, none for a repeat
  call, and nothing once stopped.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax._src import monitoring   # jax.monitoring's listener lists
from jax.profiler import ProfileData

from repro.configs import get_config
from repro.obs import CompileCounter, compile_counter
from repro.train import TrainConfig, abstract_state, make_train_step
from repro.train.fault import DATA_SPAN, STEP_SPAN, run_with_recovery

SCOPES = ("embed", "attention", "mlp", "head", "optimizer")


@pytest.fixture(scope="module")
def step_hlo():
    """Compiled with the persistent cache off: an entry written by the
    same program without scopes would be loaded with its op names."""
    from jax.experimental.compilation_cache import compilation_cache
    cfg = get_config("olmo-1b", smoke=True)
    tcfg = TrainConfig()
    tok = jax.ShapeDtypeStruct((2, 32), jnp.int32)
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return jax.jit(make_train_step(cfg, tcfg)).lower(
            abstract_state(cfg, tcfg),
            {"tokens": tok, "targets": tok}).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()


def _op_names(text: str, opcode: str = r"[a-z][a-z0-9\-]*") -> list[str]:
    pat = re.compile(r"= [^=]*?\s" + opcode + r'\(.*op_name="([^"]*)"')
    return [m.group(1) for m in pat.finditer(text)]


@pytest.mark.parametrize("scope", SCOPES)
def test_compiled_step_carries_scope(step_hlo, scope):
    part = re.compile(r"(?:^|/)(?:\w+\()*" + scope + r"\)*(?:/|$)")
    assert any(part.search(n) for n in _op_names(step_hlo)), scope


def test_backward_ops_carry_their_forward_scope(step_hlo):
    names = _op_names(step_hlo, "dot")
    back = [n for n in names if "transpose(" in n]
    assert any("/attention/" in n for n in back)
    assert any("/mlp/" in n for n in back)


def test_layer_slicing_stays_unscoped(step_hlo):
    sliced = _op_names(step_hlo, "dynamic-slice")
    assert sliced and any(not any(s in n for s in SCOPES) for n in sliced)


class _Source:
    def batch_at(self, step):
        return {"x": step}


class _Prefetch:
    source = _Source()


class _Manager:
    def restore(self):
        return None

    def wait(self):
        pass


def _host_spans(trace_dir) -> list[tuple[str, int, int]]:
    (path,) = trace_dir.glob("**/*.xplane.pb")
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                           for e in line.events
                           if e.name in (STEP_SPAN, DATA_SPAN))
    return out


def test_loop_writes_step_and_data_spans(tmp_path):
    f = jax.jit(lambda s, x: s + x)

    def step_fn(state, batch, step):
        state = f(state, batch["x"])
        return state, {"loss": state}

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        state, stats = run_with_recovery(
            step_fn, jnp.zeros(()), n_steps=3, save_every=0,
            manager=_Manager(), data_prefetch=_Prefetch())
    finally:
        jax.profiler.stop_trace()
    assert float(state) == 0 + 1 + 2 and stats.failures == 0
    spans = _host_spans(tmp_path)
    steps = sorted((s, e) for n, s, e in spans if n == STEP_SPAN)
    data = sorted((s, e) for n, s, e in spans if n == DATA_SPAN)
    assert len(steps) == len(data) == 3
    for (s0, e0), (s1, e1) in zip(steps, data):
        assert s0 <= s1 <= e1 <= e0


def test_loop_counts_its_compiles():
    inputs = [jnp.zeros(n) for n in (1, 2, 2)]
    f = jax.jit(lambda s: s + 1)

    def step_fn(state, batch, step):
        # a new shape at steps 0 and 1, a repeat at step 2
        return f(inputs[step]), {}

    _, stats = run_with_recovery(step_fn, None, n_steps=3, save_every=0,
                                 manager=_Manager())
    assert stats.compiles == 2


def test_counter_counts_new_shapes_and_not_repeats():
    counter = CompileCounter().start()
    try:
        f = jax.jit(lambda x: x * 2 + 1)
        a, b = jnp.ones(5), jnp.ones(7)
        t0, s0 = counter.read()
        f(a).block_until_ready()
        t1, s1 = counter.read()
        f(a).block_until_ready()
        t2, s2 = counter.read()
        f(b).block_until_ready()
        t3, _ = counter.read()
    finally:
        counter.stop()
    assert (t1 - t0, t2 - t1, t3 - t2) == (1, 0, 1)
    assert s1 > s0 and s2 == s1


def test_nested_traces_count_once():
    x = jnp.ones(3)
    counter = CompileCounter().start()
    try:
        inner = jax.jit(lambda x: jnp.sin(x))
        outer = jax.jit(lambda x: inner(x) + inner(2 * x))
        outer(x).block_until_ready()
        traces, _ = counter.read()
    finally:
        counter.stop()
    assert traces == 1


def test_stopped_counter_leaves_no_listener():
    before = monitoring.get_event_time_span_listeners()
    counter = CompileCounter().start()
    counter.start()                      # a second start adds nothing
    assert len(monitoring.get_event_time_span_listeners()) == \
        len(before) + 1
    counter.stop()
    counter.stop()
    assert monitoring.get_event_time_span_listeners() == before
    jax.jit(lambda x: x - 3)(jnp.ones(11)).block_until_ready()
    assert counter.read() == (0, 0.0)


def test_process_counter_is_one_listener():
    assert compile_counter() is compile_counter()
    listeners = monitoring.get_event_time_span_listeners()
    assert sum(getattr(cb, "__self__", None) is compile_counter()
               for cb in listeners) == 1
