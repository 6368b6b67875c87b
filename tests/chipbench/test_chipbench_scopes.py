"""Device time per named scope and device idle time under the program's
data fetch, on HLO text and intervals made by hand, and on a small trace
recorded on one v5e (at the end).

The module below holds, in the shape a TPU compile gives:

* ``fusion.1``: a weight-gradient ``convolution`` of the ``mlp`` scope
  fused into the layer loop's ``dynamic-update-slice``, which is the root
  and carries no scope: charged to ``mlp``;
* ``fusion.2``: no dot, root in ``attention`` under ``jvp``: ``attention``;
* ``fusion.3``: a nested fusion holding a ``dot`` of ``head``: ``head``;
* ``fusion.4``: the ``optimizer``'s elementwise update: ``optimizer``;
* ``copy.5`` and ``while.6``: the loop's own plumbing: unscoped;
* ``gather.7``: the ``embed`` lookup, charged by its own ``op_name``.
"""

from pathlib import Path

import pytest

from chipbench import scope_reduce as sr
from chipbench import trace_reduce

HLO = """\
HloModule jit_train_step, is_scheduled=true

%fused_computation.1 (param_0: f32[8,64,64], param_1: bf16[4,64], param_2: bf16[4,64], param_3: s32[]) -> f32[8,64,64] {
  %param_0 = f32[8,64,64]{2,1,0} parameter(0)
  %param_1 = bf16[4,64]{1,0} parameter(1)
  %param_2 = bf16[4,64]{1,0} parameter(2)
  %convolution.9 = f32[64,64]{1,0} convolution(%param_1, %param_2), dim_labels=bf_io->bf, metadata={op_name="jit(train_step)/while/body/transpose(jvp())/while/body/checkpoint/mlp/bsd,df->bsf/dot_general" stack_frame_id=3}
  %bitcast.1 = f32[1,64,64]{2,1,0} bitcast(%convolution.9)
  %param_3 = s32[] parameter(3)
  %constant.1 = s32[] constant(0)
  ROOT %dynamic-update-slice.1 = f32[8,64,64]{2,1,0} dynamic-update-slice(%param_0, %bitcast.1, %param_3, %constant.1, %constant.1), metadata={op_name="jit(train_step)/while/body/transpose(jvp())/while/body/dynamic_update_slice" stack_frame_id=4}
}

%fused_computation.2 (param_0.1: f32[4,64]) -> f32[4,64] {
  %param_0.1 = f32[4,64]{1,0} parameter(0)
  ROOT %exponential.1 = f32[4,64]{1,0} exponential(%param_0.1), metadata={op_name="jit(train_step)/while/body/jvp(attention)/exp"}
}

%fused_computation.3.inner (param_0.4: bf16[4,64], param_1.4: bf16[64,128]) -> f32[4,128] {
  %param_0.4 = bf16[4,64]{1,0} parameter(0)
  %param_1.4 = bf16[64,128]{1,0} parameter(1)
  ROOT %dot.3 = f32[4,128]{1,0} dot(%param_0.4, %param_1.4), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(train_step)/jvp()/head/bsd,dv->bsv/dot_general"}
}

%fused_computation.3 (param_0.3: bf16[4,64], param_1.3: bf16[64,128]) -> f32[4] {
  %param_0.3 = bf16[4,64]{1,0} parameter(0)
  %param_1.3 = bf16[64,128]{1,0} parameter(1)
  %fusion.30 = f32[4,128]{1,0} fusion(%param_0.3, %param_1.3), kind=kOutput, calls=%fused_computation.3.inner
  %constant.3 = f32[] constant(0)
  ROOT %reduce.3 = f32[4]{0} reduce(%fusion.30, %constant.3), dimensions={1}, to_apply=%add, metadata={op_name="jit(train_step)/jvp()/reduce_max"}
}

%fused_computation.4 (param_0.5: f32[64], param_1.5: f32[64]) -> f32[64] {
  %param_0.5 = f32[64]{0} parameter(0)
  %param_1.5 = f32[64]{0} parameter(1)
  ROOT %subtract.4 = f32[64]{0} subtract(%param_0.5, %param_1.5), metadata={op_name="jit(train_step)/optimizer/sub"}
}

ENTRY %main.10 (p0: f32[64], p1: f32[64]) -> f32[64] {
  %p0 = f32[64]{0} parameter(0)
  %p1 = f32[64]{0} parameter(1)
  %gather.7 = bf16[4,64]{1,0} gather(%p0, %p1), offset_dims={1}, metadata={op_name="jit(train_step)/jvp()/embed/get"}
  %copy.5 = f32[64]{0} copy(%p0), metadata={op_name="jit(train_step)/while/body/dynamic_slice"}
  %while.6 = (s32[], f32[64]{0}) while(%tuple), condition=%cond, body=%body, metadata={op_name="jit(train_step)/while"}
  %fusion.1 = f32[8,64,64]{2,1,0:T(8,128)} fusion(%a, %b, %c, %d), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(train_step)/while/body/transpose(jvp())/while/body/dynamic_update_slice" stack_frame_id=4}
  %fusion.2 = f32[4,64]{1,0} fusion(%e), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(train_step)/while/body/jvp(attention)/exp"}
  %fusion.3 = (f32[4]{0}, f32[4,128]{1,0:T(8,128)S(1)}) fusion(%f, %g), kind=kOutput, calls=%fused_computation.3
  ROOT %fusion.4 = f32[64]{0} fusion(%p0, %p1), kind=kLoop, calls=%fused_computation.4, metadata={op_name="jit(train_step)/optimizer/sub"}
}
"""


def test_fusion_is_charged_to_its_dot_else_its_root():
    names = sr.hlo_op_names(HLO)
    got = {n: sr.scope_of(names[n]) for n in
           ("fusion.1", "fusion.2", "fusion.3", "fusion.4", "copy.5",
            "while.6", "gather.7")}
    assert got == {"fusion.1": "mlp", "fusion.2": "attention",
                   "fusion.3": "head", "fusion.4": "optimizer",
                   "copy.5": sr.UNSCOPED, "while.6": sr.UNSCOPED,
                   "gather.7": "embed"}


@pytest.mark.parametrize("op_name, scope", [
    ("jit(f)/transpose(jvp(attention))/dot_general", "attention"),
    ("jit(f)/optimizer/while/body/mlp/add", "mlp"),
    ("jit(f)/attention_like/add", sr.UNSCOPED),
    ("jit(f)/bsd,dh->bsh/dot_general", sr.UNSCOPED),
    ("", sr.UNSCOPED),
])
def test_innermost_scope_wins(op_name, scope):
    assert sr.scope_of(op_name) == scope


# two steps on one device (seconds): each a ``repro.step`` holding a
# ``repro.data`` and then the benchmark's ``train``; the window is
# 1.0 .. 9.0
OPS = [("%fusion.1 = f32[8,64,64] fusion(...)", 1.5, 2.5),
       ("%while.6 = (s32[]) while(...)", 2.5, 4.0),
       ("%fusion.2 = f32[4,64] fusion(...)", 2.75, 3.25),   # in the loop
       ("%gather.7 = bf16[4,64] gather(...)", 3.5, 4.0),    # in the loop
       ("%fusion.3 = (f32[4]) fusion(...)", 6.0, 7.0),
       ("%fusion.4 = f32[64] fusion(...)", 7.0, 8.5),
       ("%copy.99 = f32[64] copy(...)", 8.5, 8.75)]          # not in HLO
SPANS = [("repro.step", 0.0, 4.5), ("repro.data", 0.2, 0.9),
         ("train", 1.0, 4.4),
         ("repro.step", 4.5, 9.5), ("repro.data", 4.6, 5.5),
         ("train", 5.6, 9.0)]
TRACE = trace_reduce.Trace(device_ops={"/device:TPU:0": OPS},
                           host_spans=SPANS)


@pytest.fixture
def reduced():
    return sr.reduce(TRACE, HLO)


def test_scopes_partition_the_self_time(reduced):
    assert reduced["scope_s"] == {
        "embed": 0.5, "attention": 0.5, "mlp": 1.0, "head": 1.0,
        "optimizer": 1.5, sr.UNSCOPED: (1.5 - 0.5 - 0.5) + 0.25}
    assert reduced["self_s"] == pytest.approx(5.25)
    assert sum(reduced["scope_s"].values()) == pytest.approx(
        reduced["self_s"])


def test_idle_under_the_data_fetch(reduced):
    # idle 1.0..1.5, 4.0..6.0 and 8.75..9.0; 4.6..5.5 of it is fetching,
    # in the one program step that starts inside the window
    assert reduced["data_idle_s"] == pytest.approx(0.9)
    assert reduced["program_steps"] == 1
    assert trace_reduce.reduce(TRACE)["busy_s"] == pytest.approx(
        reduced["self_s"])


def test_readings_per_step(reduced):
    assert sr.per_step_ms(reduced) == pytest.approx(
        {"attention": 250.0, "mlp": 500.0, "vocab": 750.0,
         "optimizer": 750.0, "unscoped": 375.0, "data_idle": 900.0})


UNNAMED = (HLO.replace("mlp", "x").replace("attention", "x")
           .replace("head", "x").replace("optimizer", "x")
           .replace("embed", "x"))
BARE = [(n, s, e) for n, s, e in SPANS if not n.startswith("repro.")]


@pytest.mark.parametrize("spans, hlo, want", [
    (BARE, UNNAMED, {}),
    (BARE, HLO, {"attention", "mlp", "vocab", "optimizer", "unscoped"}),
    (SPANS, "", {"data_idle"}),
])
def test_a_program_without_scopes_or_spans_reads_nothing(spans, hlo, want):
    r = sr.reduce(trace_reduce.Trace(device_ops={"/device:TPU:0": OPS},
                                     host_spans=spans), hlo)
    assert (r["scope_s"] is None) == ("mlp" not in want)
    assert (r["data_idle_s"] is None) == ("data_idle" not in want)
    assert set(sr.per_step_ms(r)) == set(want)


@pytest.mark.parametrize("reduced_", [
    None, {"steps": 0, "scope_s": None, "data_idle_s": None,
           "program_steps": 0}])
def test_nothing_to_read(reduced_):
    assert sr.per_step_ms(reduced_) == {}


def test_no_device_op_or_no_step_span_reduces_to_none():
    assert sr.reduce(trace_reduce.Trace({}, SPANS), HLO) is None
    assert sr.reduce(trace_reduce.Trace({"/device:TPU:0": OPS}, BARE[:0]),
                     HLO) is None


# ``data/scoped_v5e.xplane.pb`` and ``data/scoped_v5e.hlo.txt``, recorded
# on one v5e by ``data/record_scoped_v5e.py``: three steps of a two-layer
# scan whose body holds an ``attention`` fusion (``fusion.28``) and an
# ``mlp`` fusion (``fusion.29``), each step a ``repro.step`` span holding a
# ``repro.data`` span and then ``train``.  Read off the events by hand
# (nanoseconds):
#
# * window: first ``train`` start 52135237 to last ``train`` end 67224616;
# * the device's clock reads about 1.4 ms early against the host's (the
#   host's ``tpu::System::Execute=>Done`` of step 2 is at 60656673, its
#   last op ends at 59063076), so step 1's ops (51576650..51618262) fall
#   before the window and the ops of steps 2 and 3 inside their
#   ``repro.data`` spans;
# * ``fusion.28``: 5614 + 5614 (step 2) + 5615 + 5616 (step 3) = 22459;
#   ``fusion.29``: 5927 + 5926 + 5928 + 5925 = 23706; all ops' self time,
#   the busy time, 83082; so unscoped 83082 - 22459 - 23706 = 36917;
# * ``repro.data`` in the window: 53580986..59651776 and
#   60714496..66161696, 11517990 together, less the 83082 busy inside
#   them: 11434908 idle; two ``repro.step`` spans start in the window
#   (53577726, 60712376).
SCOPED = Path(__file__).parent / "data" / "scoped_v5e"


@pytest.fixture(scope="module")
def recorded():
    return sr.load(f"{SCOPED}.xplane.pb")


@pytest.fixture(scope="module")
def scoped(recorded):
    return sr.reduce(recorded, Path(f"{SCOPED}.hlo.txt").read_text())


def test_recorded_scopes(recorded, scoped):
    whole = trace_reduce.reduce(recorded)
    assert scoped["steps"] == whole["steps"] == 3
    assert whole["window_s"] == pytest.approx(15089379e-9, abs=2e-9)
    assert whole["busy_s"] == pytest.approx(83082e-9, abs=2e-9)
    assert scoped["self_s"] == pytest.approx(83082e-9, abs=2e-9)
    want = {"attention": 22459, "mlp": 23706, sr.UNSCOPED: 36917,
            "embed": 0, "head": 0, "optimizer": 0}
    assert scoped["scope_s"] == pytest.approx(
        {k: v * 1e-9 for k, v in want.items()}, abs=2e-9)


def test_recorded_data_idle(scoped):
    assert scoped["data_idle_s"] == pytest.approx(11434908e-9, abs=2e-9)
    assert scoped["program_steps"] == 2
    assert sr.per_step_ms(scoped)["data_idle"] == pytest.approx(
        11434908e-6 / 2)
