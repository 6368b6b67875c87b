"""The collective count of a compiled step (``repro.obs.collectives``).

* On HLO text written the way the TPU compiler writes it: an op in a loop
  body counts once per trip, an async op split over start, continuation
  and done fusions counts once, an all-reduce-scatter fusion counts as
  the reduce-scatter it is, and bytes follow the ring model.
* The launcher's step on the (2, 2) mesh with ``default_plan``, on four
  forced CPU devices: all-gathers and all-reduces or reduce-scatters,
  with bytes; on one device, nothing.
"""

import json
import os
import subprocess
import sys
import tempfile
import textwrap

from repro.obs import collectives, count_collectives, record_collectives

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# a while over 16 trips (bound in its condition, no known_trip_count), in
# whose body one all-gather (channel 7) is split over an async start, a
# continuation and a done fusion; one all-reduce-scatter fusion; and in
# the entry an all-reduce-start/-done pair and a reducer (to_apply)
TPU_LIKE = """\
HloModule jit_train_step, is_scheduled=true

%add (x: f32[], y: f32[]) -> f32[] {
  %x = f32[] parameter(0)
  %y = f32[] parameter(1)
  ROOT %sum = f32[] add(%x, %y)
}

%fused_start (p: bf16[32,64]) -> (bf16[32,64], bf16[64,64]) {
  %p = bf16[32,64]{1,0} parameter(0)
  %all-gather.1 = bf16[64,64]{1,0} all-gather(%p), channel_id=7, replica_groups=[2,2]<=[4], dimensions={0}
  ROOT %cc = (bf16[32,64]{1,0}, bf16[64,64]{1,0}) custom-call(%all-gather.1), custom_call_target="AsyncCollectiveStart"
}

%async_collective_fusion.1 (p: bf16[32,64], q: f32[8,64]) -> (f32[8,64], bf16[64,64]) {
  %p = bf16[32,64]{1,0} parameter(0)
  %all-gather.2 = bf16[64,64]{1,0} all-gather(%p), channel_id=7, replica_groups=[2,2]<=[4], dimensions={0}
  ROOT %t = (f32[8,64]{1,0}, bf16[64,64]{1,0}) tuple(%q, %all-gather.2)
}

%fused_done (p: bf16[32,64]) -> bf16[64,64] {
  %p = bf16[32,64]{1,0} parameter(0)
  %all-gather.3 = bf16[64,64]{1,0} all-gather(%p), channel_id=7, replica_groups=[2,2]<=[4], dimensions={0}
  ROOT %cc = bf16[64,64]{1,0} custom-call(%all-gather.3), custom_call_target="AsyncCollectiveDone"
}

%all-reduce-scatter.1 (a: f32[128,64], b: f32[64,64]) -> (f32[64,64], f32[32,64]) {
  %a = f32[128,64]{1,0} parameter(0)
  %b = f32[64,64]{1,0} parameter(1)
  %all-reduce.9 = (f32[128,64]{1,0}, f32[64,64]{1,0}) all-reduce(%a, %b), channel_id=9, replica_groups=[2,2]<=[4], to_apply=%add
  ROOT %t = (f32[64,64]{1,0}, f32[32,64]{1,0}) tuple(%a, %b)
}

%body (w: (s32[], bf16[32,64])) -> (s32[], bf16[32,64]) {
  %w = (s32[], bf16[32,64]{1,0}) parameter(0)
  %p = bf16[32,64]{1,0} get-tuple-element(%w), index=1
  %fusion.1 = (bf16[32,64]{1,0}, bf16[64,64]{1,0}) fusion(%p), kind=kCustom, calls=%fused_start
  %fusion.2 = (f32[8,64]{1,0}, bf16[64,64]{1,0}) fusion(%p, %q), kind=kCustom, calls=%async_collective_fusion.1
  %fusion.3 = bf16[64,64]{1,0} fusion(%p), kind=kCustom, calls=%fused_done
  %fusion.4 = (f32[64,64]{1,0}, f32[32,64]{1,0}) fusion(%a, %b), kind=kCustom, calls=%all-reduce-scatter.1
  ROOT %r = (s32[], bf16[32,64]{1,0}) tuple(%i, %p)
}

%cond (w: (s32[], bf16[32,64])) -> pred[] {
  %n = s32[]{:T(128)} constant(16)
  %w = (s32[], bf16[32,64]{1,0}) parameter(0)
  %i = s32[] get-tuple-element(%w), index=0
  ROOT %lt = pred[] compare(%i, %n), direction=LT
}

ENTRY %main.1_spmd (x: bf16[32,64], g: f32[256]) -> f32[256] {
  %x = bf16[32,64]{1,0} parameter(0)
  %g = f32[256]{0} parameter(1)
  %while.1 = (s32[], bf16[32,64]{1,0}) while(%t0), condition=%cond, body=%body
  %all-reduce-start.1 = f32[256]{0} all-reduce-start(%g), channel_id=3, replica_groups=[2,2]<=[4], to_apply=%add
  %all-reduce-done.1 = f32[256]{0} all-reduce-done(%all-reduce-start.1)
  ROOT %reduce.1 = f32[] reduce(%g, %z), dimensions={0}, to_apply=%add
}
"""


def test_counts_each_op_once_per_trip():
    got = count_collectives(TPU_LIKE)
    assert list(got) == ["all-gather", "reduce-scatter", "all-reduce"]
    # groups of 2: an all-gather sends half its result, a reduce-scatter
    # half of the full tensor, an all-reduce all of it (2 (g-1)/g)
    assert got["all-gather"] == {"ops": 16, "bytes": 16 * 64 * 64 * 2 / 2}
    assert got["reduce-scatter"] == {
        "ops": 16, "bytes": 16 * (128 * 64 + 64 * 64) * 4 / 2}
    assert got["all-reduce"] == {"ops": 1, "bytes": 256 * 4}


def test_known_trip_count_wins_over_the_condition():
    text = TPU_LIKE.replace(
        "condition=%cond, body=%body",
        'condition=%cond, body=%body, '
        'backend_config={"known_trip_count":{"n":"3"}}')
    assert count_collectives(text)["all-gather"]["ops"] == 3


def test_registry_holds_the_last_step_recorded():
    record_collectives(TPU_LIKE)
    snap = collectives().snapshot()
    assert snap["all-gather.ops"] == 16
    assert snap["all-reduce.bytes"] == 1024
    record_collectives("")
    assert collectives().snapshot() == {}


def test_launcher_counts_collectives_of_the_mesh_step():
    """The launcher on four forced devices builds the (2, 2) mesh and
    ``default_plan``; its summary carries the compiled step's count."""
    code = """
        import json, sys, tempfile
        from repro.launch.train import parser, train
        with tempfile.TemporaryDirectory() as d:
            res = train(parser().parse_args([
                "--smoke", "--steps", "2", "--batch", "8", "--seq", "32",
                "--save-every", "0", "--ckpt-dir", d]))
        print("RESULT", json.dumps(res["collectives"]))
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=180)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [ln for ln in out.stdout.splitlines() if ln.startswith("RESULT")]
    got = json.loads(line[-1][len("RESULT "):])
    assert got["all-gather.ops"] > 0 and got["all-gather.bytes"] > 0
    assert (got.get("all-reduce.bytes", 0) > 0
            or got.get("reduce-scatter.bytes", 0) > 0)


def test_launcher_counts_nothing_on_one_device():
    from repro.launch.train import parser, train
    with tempfile.TemporaryDirectory() as d:
        res = train(parser().parse_args([
            "--smoke", "--layers", "1", "--steps", "1", "--batch", "2",
            "--seq", "16", "--save-every", "0", "--ckpt-dir", d]))
    assert res["collectives"] == {}
