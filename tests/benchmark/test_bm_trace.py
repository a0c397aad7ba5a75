"""The reduction from a profiler trace to device numbers: on hand-made
events (exact arithmetic), on a small slice recorded on the chip and
kept as a fixture, and the loader on a trace recorded here on the CPU
(which has no device plane, so it must come back empty)."""

import json
import os
import re

import pytest

from bm_paths import FIXTURES

from benchmark.harness import trace


def test_busy_is_the_union_of_op_intervals_and_gaps_are_named():
    ops = [("%fusion.1 = f32[32,8]{1,0:T(8,128)} fusion(bf16[3] %p)", 0.0, 1.0),
           ("%fusion.2 = f32[32,8]{1,0} fusion(f32[4] %paged_attention_"
            "decode_layered.3)", 0.5, 1.0),                  # overlaps
           ("%paged_attention_decode_layered.3 = bf16[32,8,4,128]{3,2,1,0} "
            "custom-call(s32[1] %x)", 1.5, 0.25),
           ("%while.7 = (s32[], bf16[4]) while(%t)", 3.0, 1.0),
           ("%copy.4 = bf16[3,768]{1,0} copy(bf16[3,768] %y)", 3.0, 1.0)]
    mods = [("jit_decode_window(123)", 0.0, 1.75),
            ("jit_prefill_step(9)", 3.0, 1.0)]
    r = trace.reduce({"/device:TPU:0": {"ops": ops, "modules": mods}}, 5.0)
    assert r["busy_s"] == pytest.approx(1.5 + 0.25 + 1.0)
    assert r["window_s"] == 5.0 and r["chips"] == 1
    assert r["kernel_s"] == pytest.approx(0.25)
    assert r["modules"]["decode_window"] == {
        "count": 1, "mean_s": 1.75, "total_s": 1.75}
    # 1.75 -> 3.0 is one idle gap between the two programs
    assert r["idle_gaps"][0][0] == "decode_window -> prefill_step"
    assert r["idle_gaps"][0][1] == pytest.approx(1.25)
    ops_by = dict(map(tuple, r["device_ops"]))
    assert ops_by["fusion f32[32,8]"] == pytest.approx(2.0)
    assert ops_by["paged_attention_decode_layered bf16[32,8,4,128]"] == 0.25
    assert not any(k.startswith("while") for k in ops_by)   # a container
    assert trace.module_stats(r, trace.WINDOW_MODULE)["count"] == 1
    assert trace.module_stats(r, re.compile("nothing")) is None


def test_window_is_never_shorter_than_the_span_of_the_ops():
    ops = [("a", 10.0, 1.0), ("b", 14.0, 1.0)]
    r = trace.reduce({"/device:TPU:0": {"ops": ops, "modules": []}}, 2.0)
    assert r["window_s"] == pytest.approx(5.0)
    assert r["busy_s"] <= r["window_s"]


def test_two_chips_are_averaged_and_no_ops_is_nothing():
    one = {"ops": [("a", 0.0, 1.0)], "modules": []}
    two = {"ops": [("a", 0.0, 3.0)], "modules": []}
    r = trace.reduce({"/device:TPU:0": one, "/device:TPU:1": two}, 4.0)
    assert r["busy_s"] == pytest.approx(2.0) and r["chips"] == 2
    assert trace.reduce({}, 4.0) is None
    assert trace.reduce({"/device:TPU:0": {"ops": [], "modules": []}},
                        4.0) is None


FIXTURE = os.path.join(FIXTURES, "v5e_chat_steady_slice.json")


@pytest.mark.skipif(not os.path.exists(FIXTURE),
                    reason="no recorded slice in this checkout")
def test_recorded_chip_slice_reduces_to_sane_numbers():
    """0.3 s of mixtral-8x7b.chat-steady recorded on a v5e (PR 23): the
    names the reduction looks for are the names the chip writes."""
    with open(FIXTURE) as f:
        planes = {p: {k: [tuple(e) for e in v] for k, v in lines.items()}
                  for p, lines in json.load(f).items()}
    assert all(trace.DEVICE_PLANE.match(p) for p in planes)
    r = trace.reduce(planes, 0.3)
    assert 0 < r["busy_s"] <= r["window_s"]
    assert 0 < r["kernel_s"] < r["busy_s"]
    assert trace.module_stats(r, trace.WINDOW_MODULE)["count"] >= 1
    assert len(r["device_ops"]) <= 10 and len(r["idle_gaps"]) <= 10
    assert all(s > 0 for _, s in r["device_ops"])


def test_loader_reads_a_trace_recorded_here_and_finds_no_device(tmp_path):
    import jax
    import jax.numpy as jnp

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    jnp.dot(jnp.ones((64, 64)), jnp.ones((64, 64))).block_until_ready()
    jax.profiler.stop_trace()
    path = trace.find_xplane(str(tmp_path))
    assert path and path.endswith(".xplane.pb")
    assert trace.load(path) == {}              # the CPU is not a device
    view = trace.summarize(path)
    assert "/host:CPU" in view
    assert trace.find_xplane(str(tmp_path / "nowhere")) is None
