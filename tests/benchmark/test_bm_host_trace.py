"""benchmark/harness/host_trace.py: the scope of a device op read from an
xplane file's wire format, device seconds per scope, idle gaps named by
the step thread's dyn.* phase — on a hand-made trace file (every number
counted by hand) — and the frontend of harness/serve.py showing a
request's whole path under one trace id."""

import json
import os
import subprocess
import sys

import pytest

from bm_paths import ROOT
from test_bm_e2e import root  # noqa: F401 — the tiny cell's fixture

from benchmark.harness import counters, host_trace


# ------------------------------------------------- a trace file, by hand

def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _int(num: int, value: int) -> bytes:
    return _varint(num << 3) + _varint(value)


def _msg(num: int, payload) -> bytes:
    if isinstance(payload, str):
        payload = payload.encode()
    return _varint(num << 3 | 2) + _varint(len(payload)) + payload


def _stat_meta(key: int, name: str) -> bytes:
    return _msg(5, _int(1, key) + _msg(2, _int(1, key) + _msg(2, name)))


def _event_meta(key: int, name: str, *stats: bytes) -> bytes:
    meta = _int(1, key) + _msg(2, name) + b"".join(_msg(5, s) for s in stats)
    return _msg(4, _int(1, key) + _msg(2, meta))


def _line(name: str, events) -> bytes:
    """events: (metadata id, start_us, duration_us)."""
    body = _int(1, 1) + _msg(2, name)
    for meta, start, dur in events:
        body += _msg(4, _int(1, meta) + _int(2, start * 10 ** 6)
                     + _int(3, dur * 10 ** 6))
    return _msg(3, body)


MOE = "jit(decode_window)/while/body/closed_call/moe/moe.experts/dot_general:"
SAMPLE = "jit(decode_window)/jit(sample_tokens)/sample/top_k:"
OPS = {1: "%fusion.1 = f32[32,8,14336]{2,0,1} fusion(bf16[3] %p), kind=kOutput",
       2: "%sort.2 = (f32[32,32000]{1,0}, s32[32,32000]{1,0}) sort(%a, %b)",
       3: "%copy.3 = bf16[3,768,8,64,128]{4,3,2,1,0} copy(bf16[3,768] %pool)",
       4: "%while.4 = (s32[], bf16[4]) while(%t), body=%b"}


@pytest.fixture(scope="module")
def xplane(tmp_path_factory):
    """One chip: ops at 0-400 us (moe), 400-500 (sample), idle 500-800,
    copy 800-1000, all inside a while 0-1000; then idle 1000-1600 and a
    moe op 1600-1700. The step thread: one dyn.step 100-1200 with
    dispatch_window 100-300 and readback_window 450-1100 (a nested
    process phase 700-780 inside it); the next dyn.step starts at 1500."""
    device = (
        _msg(2, "/device:TPU:0")
        + _stat_meta(1, "tf_op") + _stat_meta(2, "flops")
        + _stat_meta(9, SAMPLE)                 # a ref_value target
        + _event_meta(1, OPS[1], _int(1, 2) + _int(3, 77),
                      _int(1, 1) + _msg(5, MOE))
        + _event_meta(2, OPS[2], _int(1, 1) + _int(7, 9))
        + _event_meta(3, OPS[3], _int(1, 2) + _int(3, 5))
        + _event_meta(4, OPS[4])
        + _line("XLA Ops", [(4, 0, 1000), (1, 0, 400), (2, 400, 100),
                            (3, 800, 200), (1, 1600, 100)])
        + _line("XLA Modules", []))
    names = {1: "dyn.step", 2: "dyn.dispatch_window",
             3: "dyn.readback_window", 4: "dyn.process_window",
             5: "PjitFunction(decode_window)"}
    host = (_msg(2, "/host:CPU")
            + b"".join(_event_meta(k, v) for k, v in names.items())
            + _line("python3", [(1, 100, 1100), (2, 100, 200), (5, 150, 20),
                                (3, 450, 650), (4, 700, 80),
                                (1, 1500, 300)])
            + _line("pjrt-tpu-tasks/1", [(5, 0, 10)]))
    path = tmp_path_factory.mktemp("trace") / "hand.xplane.pb"
    path.write_bytes(_msg(1, device) + _msg(1, host))
    return str(path)


def test_the_scope_is_read_from_the_events_metadata(xplane):
    scopes = host_trace.op_scopes(xplane)
    assert scopes == {OPS[1]: MOE, OPS[2]: SAMPLE}      # str and ref values
    assert host_trace.scopes_of(MOE) == ["moe", "moe.experts"]
    assert host_trace.scopes_of(SAMPLE) == ["sample"]
    assert host_trace.scopes_of("jit(decode_window)/while:") == []


def test_load_joins_scopes_and_finds_the_step_threads_phases(xplane):
    loaded = host_trace.load(xplane)
    ops = loaded["ops"]["/device:TPU:0"]
    assert len(ops) == 5
    by_name = {o[0]: o for o in ops}
    assert by_name[OPS[2]][1:] == (pytest.approx(400e-6),
                                   pytest.approx(100e-6), SAMPLE)
    assert by_name[OPS[3]][3] == ""
    assert [p[0] for p in loaded["phases"]] == [
        "dyn.step", "dyn.dispatch_window", "dyn.readback_window",
        "dyn.process_window", "dyn.step"]


def test_device_seconds_per_scope_and_the_unscoped_remainder(xplane):
    got = host_trace.scope_seconds(host_trace.load(xplane))
    us = 1e-6
    assert got["busy_s"] == pytest.approx(1100 * us)    # the while spans 0-1000
    assert got["op_s"] == pytest.approx(800 * us)       # containers left out
    assert got["scopes"]["moe"] == pytest.approx(500 * us)
    assert got["scopes"]["moe.experts"] == pytest.approx(500 * us)
    assert got["scopes"]["sample"] == pytest.approx(100 * us)
    assert got["scopes"]["unscoped"] == pytest.approx(200 * us)
    assert host_trace.scope_seconds({"ops": {}, "phases": []}) is None


def test_scope_share_reads_the_runs_own_newest_trace(xplane, tmp_path):
    d = tmp_path / ".bench_trace" / "cell" / "plugins" / "profile" / "t1"
    d.mkdir(parents=True)
    os.link(xplane, d / "host.xplane.pb")
    reader = str(tmp_path / "benchmark" / "metrics" / "moe_busy_share.py")
    stats = {"stats1": {counters.PHASES_KEY: {"idle": 1.0}}}
    raw = {"trace": {"busy_s": 1100e-6}, **stats}
    assert host_trace.find_xplane(str(tmp_path)) == str(d / "host.xplane.pb")
    assert host_trace.scope_share(raw, "moe", reader) == \
        pytest.approx(100.0 * 500 / 1100)
    assert host_trace.scope_share(raw, "sample", reader) == \
        pytest.approx(100.0 * 100 / 1100)
    assert host_trace.scope_share(raw, "mlp", reader) == 0.0
    # not this run's file (another busy time), not traced, no file
    assert host_trace.scope_share({"trace": {"busy_s": 2.0}, **stats},
                                  "moe", reader) is None
    assert host_trace.scope_share({"trace": None, **stats}, "moe",
                                  reader) is None
    # the parent's program: its stats() has no phases, and the scoped
    # names in its trace came out of a shared compile cache
    for parent in ({}, {"stats1": {"queue_wait_seconds_total": 1.0}}):
        assert host_trace.scope_share({"trace": raw["trace"], **parent},
                                      "moe", reader) is None
    assert host_trace.scope_share(
        raw, "moe", str(tmp_path / "x" / "benchmark" / "metrics" / "m.py")
    ) is None


def _place(xplane, root):
    """The hand-made trace as the newest traced run under ``root``."""
    d = os.path.join(root, ".bench_trace", "hand", "plugins", "profile", "t1")
    os.makedirs(d)
    os.link(xplane, os.path.join(d, "host.xplane.pb"))


def test_op_seconds_sums_the_ops_a_pattern_names(xplane, tmp_path):
    """The second helper a reader of its own file can call: no scopes
    and no stats() needed, the same guards on whose trace it is."""
    _place(xplane, str(tmp_path))
    reader = str(tmp_path / "benchmark" / "metrics" / "some_kernel_s.py")
    raw = {"trace": {"busy_s": 1100e-6}}
    us = 1e-6
    assert host_trace.op_seconds(raw, "fusion", reader) == \
        pytest.approx(500 * us)
    assert host_trace.op_seconds(raw, "sort|copy", reader) == \
        pytest.approx(300 * us)
    assert host_trace.op_seconds(raw, "usion", reader) == 0.0   # from its start
    # a container's time is its body's: everything but the while
    assert host_trace.op_seconds(raw, ".", reader) == pytest.approx(800 * us)
    assert host_trace.op_seconds(raw, "paged_attention", reader) == 0.0
    # not this run's file, not traced, no file under the reader's root
    assert host_trace.op_seconds({"trace": {"busy_s": 2.0}}, "fusion",
                                 reader) is None
    assert host_trace.op_seconds({"trace": None}, "fusion", reader) is None
    assert host_trace.op_seconds(
        raw, "fusion", str(tmp_path / "x" / "benchmark" / "metrics" / "m.py")
    ) is None


def test_an_added_reader_counts_a_latent_pool_from_the_raw_material(
        xplane, root):  # noqa: F811
    """test_bm_e2e's ``cache_read_gb_s``, a file added to the copy: the
    bytes of a cached token from ``raw["model"]["kv_pools"]``, checked
    against ``raw["model"]["config"]``, over ``op_seconds``."""
    from test_bm_e2e import TINY_MLA_CONFIG

    from benchmark.harness import cells

    read = cells.load_reader("cache_read_gb_s", root)
    model = {"num_layers": 3, "page_size": 16, "kv_itemsize": 2,
             "config": dict(TINY_MLA_CONFIG),
             "kv_pools": [{"shape": [3, 64, 1, 16, 32], "itemsize": 2},
                          {"shape": [3, 64, 1, 16, 8], "itemsize": 2}]}
    rows = [{"prompt_len": 10, "chunk_n": [1, 2]},      # 11 + 12
            {"prompt_len": 20, "chunk_n": [1]}]         # prefill only
    raw = {"trace": None, "model": model, "rows": rows}
    assert read(raw) is None                            # not traced
    _place(xplane, root)
    raw["trace"] = {"busy_s": 1100e-6}
    assert read(raw) == pytest.approx(240 * 23 / 500e-6 / 1e9)
    gqa = dict(model, config={}, kv_pools=[
        {"shape": [3, 64, 2, 16, 16], "itemsize": 2}] * 2)
    assert read(dict(raw, model=gqa)) == pytest.approx(
        384 * 23 / 500e-6 / 1e9)
    with pytest.raises(ValueError, match="no latent pool"):
        read(dict(raw, model=dict(model, kv_pools=gqa["kv_pools"])))


def test_nested_phases_are_cut_into_disjoint_pieces():
    us = 1e-6
    phases = [("dyn.step", 100 * us, 1100 * us),
              ("dyn.dispatch_window", 100 * us, 200 * us),
              ("dyn.readback_window", 450 * us, 650 * us),
              ("dyn.process_window", 700 * us, 80 * us)]
    pieces = [(n, round(a / us), round(b / us))
              for n, a, b in host_trace.exclusive_phases(phases)]
    assert pieces == [("dyn.dispatch_window", 100, 300),
                      ("dyn.other", 300, 450),
                      ("dyn.readback_window", 450, 700),
                      ("dyn.process_window", 700, 780),
                      ("dyn.readback_window", 780, 1100),
                      ("dyn.other", 1100, 1200)]


def test_idle_gaps_are_named_by_the_phase_that_covered_them(xplane):
    """Without the while (a container spans its body's gaps) the gap
    500-800 lies under readback_window (220 us of it) against 80 us of
    process_window; 1000-1600 is 100 us readback, 100 other, 300 between
    two steps, 100 of the next step."""
    loaded = host_trace.load(xplane)
    ops = [o for o in loaded["ops"]["/device:TPU:0"]
           if not o[0].startswith("%while")]
    gaps = host_trace.gap_phases({"ops": {"d": ops},
                                  "phases": loaded["phases"]})
    assert [(k, round(s * 1e6), n) for k, s, n in gaps] == [
        ("outside dyn.step", 600, 1), ("dyn.readback_window", 300, 1)]
    view = host_trace.summarize(xplane)
    assert view["steps"] == 2 and view["idle_gaps_by_phase"][0][0] == \
        "outside dyn.step"
    assert view["unscoped_top"] == [["copy bf16[3,768,8,64,128]",
                                     pytest.approx(200e-6)]]
    assert view["step_thread_s"]["dyn.readback_window"] == \
        pytest.approx(570e-6)


# ------------------------------------- served by harness/serve.py's frontend

SERVE_ONE = r"""
import asyncio, json, sys
sys.path.insert(0, %(repo)r)
from benchmark.harness import cells, serve

async def main():
    import aiohttp
    cell = cells.load_cell("tiny-moe.tiny-open", %(root)r)
    args, built = await asyncio.to_thread(serve.build, cell, 7,
                                          serve.free_port())
    rid = "bench-trace-1"
    try:
        async with serve.serving(args, built) as base:
            async with aiohttp.ClientSession() as http:
                body = {"model": built[1].name, "stream": True,
                        "max_tokens": 6, "temperature": 0,
                        "messages": [{"role": "user", "content": "hello"}]}
                async with http.post(base + "/v1/chat/completions",
                                     json=body,
                                     headers={"X-Request-Id": rid}) as r:
                    assert r.status == 200, await r.text()
                    async for line in r.content:
                        if line.decode().strip() == "data: [DONE]":
                            break
                for _ in range(100):
                    async with http.get(base + "/v1/traces/" + rid) as r:
                        out = await r.json() if r.status == 200 else {}
                    if (out.get("cost") or {}).get("finish_reason"):
                        break
                    await asyncio.sleep(0.05)
    finally:
        await built[0].stop()
    print("TRACE " + json.dumps(out))

asyncio.run(main())
"""


def test_a_served_request_shows_its_whole_path_under_one_trace_id(root):  # noqa: F811
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=os.path.join(root, ".jax_cache"))
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-c", SERVE_ONE % {"repo": ROOT, "root": root}],
        env=env, cwd=root, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = next(ln for ln in proc.stdout.splitlines()
                if ln.startswith("TRACE "))
    body = json.loads(line[len("TRACE "):])
    want = {"http.request", "preprocess", "engine.queue",
            "engine.prefill_wait", "engine.prefill", "engine.decode",
            "http.first_chunk"}
    assert want <= set(body["stages"])
    assert {s["trace_id"] for s in body["spans"]} == {body["trace_id"]}
    assert all(body["stages"][k] >= 0 for k in want)
