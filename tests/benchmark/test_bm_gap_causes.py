"""benchmark/harness/gap_causes.py and host_counters.py, and the
per-layer metrics of PR 35 that read them: on a hand-made trace file
with a device plane and three host threads (every number counted by
hand, in microseconds), and on hand-made ``stats()`` pairs. A program
without the loop's brackets and the new counters (the parent) reads None
everywhere and fails nowhere."""

import json
import os

import pytest

from bm_paths import ROOT
from test_bm_host_trace import _event_meta, _line, _msg

from benchmark.harness import cells, counters, gap_causes, host_counters

US = 1e-6
OPS = {1: "%fusion.1 = f32[32,8]{1,0} fusion(bf16[3] %p), kind=kOutput",
       2: "%copy.2 = bf16[3,768]{1,0} copy(bf16[3,768] %pool)"}
MODULES = {3: "jit_prefill_step(123)", 4: "jit_decode_window(456)"}
STEP = {1: "dyn.step", 2: "dyn.dispatch_window", 3: "dyn.readback_window",
        4: "dyn.dispatch_prefill", 5: "dyn.readback_prefill",
        6: "dyn.gc"}
LOOP = {11: "dyn.loop.deliver", 12: "dyn.loop.encode_write",
        13: "dyn.loop.engine_loop", 14: "dyn.loop.intake"}
DETOK = {21: "dyn.detok"}
# every engine has these counters and threads: one entry each, without
# a ``workloads`` list (until PR 45 cells 6 and 7 listed copies)
EVERY_CELL = ("loop_thread_busy_share", "emit_to_wire_ms_mean",
              "step_offcpu_share", "gc_pause_share",
              "step_gap_stream_share", "idle_host_work_share",
              "idle_readback_share", "idle_no_work_share")
# the TTFT side: cell 1, and cell 3 as ``.tpot`` (PERF.md section 3)
TTFT_SIDE = ("intake_ms_mean", "prefill_device_wait_ms_mean",
             "prefill_readback_lag_ms_mean")
NEW = EVERY_CELL + TTFT_SIDE + tuple(n + ".tpot" for n in TTFT_SIDE)
BUSY_S, WINDOW_S = 970 * US, 2500 * US


def _device() -> bytes:
    """Ops 0-400, 1000-1200, 1230-1300, 2000-2300: busy 970, one gap of
    30 (launch spacing) and two of 600 and 700. Programs: a prefill_step
    0-150 (enqueued before the slice began), a window 1000-1300, a
    prefill_step 2000-2300."""
    return (_msg(2, "/device:TPU:0")
            + b"".join(_event_meta(k, v) for k, v in
                       {**OPS, **MODULES}.items())
            + _line("XLA Ops", [(1, 0, 400), (1, 1000, 200), (2, 1230, 70),
                                (1, 2000, 300)])
            + _line("XLA Modules", [(3, 0, 150), (4, 1000, 300),
                                    (3, 2000, 300)]))


def _host(streams: bool = True) -> bytes:
    """The step thread: dyn.step 100-900 (dispatch_window 100-300,
    readback_window 500-800), 1100-1900 (an empty dispatch_prefill
    1100-1110, a real one 1400-1700, readback_prefill 1750-1850 with a
    collection 1760-1780 inside it) and 2100-2400 (readback_prefill
    2150-2350). The loop thread: deliver 910-950, encode_write 960-990,
    engine_loop 1000-1050, deliver 1950-1980, intake 2200-2250. One
    detokeniser worker: 940-970."""
    host = (_msg(2, "/host:CPU")
            + b"".join(_event_meta(k, v) for k, v in
                       {**STEP, **LOOP, **DETOK}.items())
            + _line("python3", [(1, 100, 800), (2, 100, 200), (3, 500, 300),
                                (1, 1100, 800), (4, 1100, 10),
                                (4, 1400, 300), (5, 1750, 100),
                                (6, 1760, 20),
                                (1, 2100, 300), (5, 2150, 200)]))
    if streams:
        host += _line("python3", [(11, 910, 40), (12, 960, 30),
                                  (13, 1000, 50), (11, 1950, 30),
                                  (14, 2200, 50)])
        host += _line("python3", [(21, 940, 30)])
    return host + _line("pjrt-tpu-tasks/1", [])


def _write(path, streams: bool = True) -> str:
    path.write_bytes(_msg(1, _device()) + _msg(1, _host(streams)))
    return str(path)


@pytest.fixture(scope="module")
def xplane(tmp_path_factory):
    return _write(tmp_path_factory.mktemp("gaps") / "hand.xplane.pb")


@pytest.fixture(scope="module")
def parent_xplane(tmp_path_factory):
    """The same slice from a program without the loop's brackets."""
    return _write(tmp_path_factory.mktemp("gaps") / "parent.xplane.pb",
                  streams=False)


def _place(xplane: str, root) -> str:
    """The hand-made trace as the newest traced run under ``root``;
    returns a reader's path under that root."""
    d = root / ".bench_trace" / "hand" / "plugins" / "profile" / "t1"
    d.mkdir(parents=True)
    os.link(xplane, d / "host.xplane.pb")
    return str(root / "benchmark" / "metrics" / "some_share.py")


def test_threads_are_told_by_their_events(xplane):
    loaded = gap_causes.load(xplane)
    assert len(loaded["threads"]) == 3          # the empty line is left out
    step = gap_causes.step_thread(loaded)
    assert sum(e[0] == "dyn.step" for e in step) == 3
    assert not any(e[0].startswith("dyn.loop.") for e in step)
    streams = gap_causes.stream_spans(loaded)
    assert [(round(a / US), round(b / US)) for a, b in streams.spans] == [
        (910, 990), (1950, 1980)]               # engine_loop, intake: no
    assert len(loaded["modules"]["/device:TPU:0"]) == 3


def test_an_idle_gap_that_straddles_phases_is_split_by_overlap(xplane):
    """Gap 400-1000: other 400-500 and 800-900 (work 200), readback
    500-800 (300), outside dyn.step 900-1000 (100, of which 910-990 a
    stream ran). Gap 1300-2000: other + the real dispatch 1300-1750 and
    1850-1900 (work 500), readback_prefill 1750-1850 (80 of readback:
    the collection 1760-1780 nested in it is the host's own work),
    outside 1900-2000 (100, streams 30). The gap of 30 is launch
    spacing; 200 of the 2,500 traced lie past the last op."""
    got = gap_causes.idle_split(gap_causes.load(xplane), WINDOW_S)
    us = {k: round(v / US, 3) for k, v in got.items()}
    assert us == {"host_work": 720, "readback": 380, "no_work": 200,
                  "no_work_streams": 110, "small_gaps": 30, "busy": 970,
                  "span_s": 2500, "edges": 200}
    # a shorter window than the ops' own span: the span is the ops'
    assert gap_causes.idle_split(gap_causes.load(xplane), 0.0)[
        "span_s"] == pytest.approx(2300 * US)


def test_the_three_idle_shares_add_up_to_the_idle_share(xplane, tmp_path):
    reader = _place(xplane, tmp_path)
    raw = {"trace": {"busy_s": BUSY_S, "window_s": WINDOW_S}}
    shares = {c: gap_causes.idle_share(raw, c, reader)
              for c in ("host_work", "readback", "no_work")}
    assert shares == {"host_work": pytest.approx(28.8),
                      "readback": pytest.approx(15.2),
                      "no_work": pytest.approx(8.0)}
    idle = 100.0 * (1.0 - BUSY_S / WINDOW_S)    # device_idle_share's own
    rest = gap_causes.idle_split(gap_causes.load(xplane), WINDOW_S)
    remainder = 100.0 * (rest["small_gaps"] + rest["edges"]) / WINDOW_S
    assert sum(shares.values()) + remainder == pytest.approx(idle)
    # not this run's file, not traced
    assert gap_causes.idle_share({"trace": {"busy_s": 2.0, "window_s": 5.0}},
                                 "no_work", reader) is None
    assert gap_causes.idle_share({"trace": None}, "no_work", reader) is None


def test_step_gaps_by_whether_a_stream_ran(xplane):
    """Two gaps of 200 (900-1100, 1900-2100); a stream bracket ran
    910-990 and 1950-1980."""
    got = gap_causes.step_gap_split(gap_causes.load(xplane))
    assert got["gaps"] == 2
    assert got["gap_s"] == pytest.approx(400 * US)
    assert got["stream_s"] == pytest.approx(110 * US)


def test_a_prefill_cut_by_the_slices_edge_is_dropped_from_the_match(xplane):
    """The program at 0-150 was enqueued before the slice: no dispatch
    precedes it, so it has no device wait; its readback (1750-1850) is in
    the slice and is matched. The program at 2000-2300 waited 300 behind
    the real dispatch (1400-1700; the empty one at 1100 does not count)
    and was fetched 50 after it ended."""
    got = gap_causes.prefill_lives(gap_causes.load(xplane))
    assert got["executions"] == 2 and got["readbacks"] == 2
    assert got["dispatches"] == 1 and got["empty_dispatches"] == 1
    assert got["device_wait_s"] == [pytest.approx(300 * US)]
    assert got["readback_lag_s"] == [pytest.approx(1700 * US),
                                     pytest.approx(50 * US)]


def test_a_readback_before_any_program_ended_is_dropped():
    us = US
    step = [("dyn.step", 0.0, 400 * us),
            ("dyn.readback_prefill", 10 * us, 30 * us),     # its program: cut
            ("dyn.dispatch_prefill", 50 * us, 150 * us),
            ("dyn.readback_prefill", 320 * us, 60 * us)]
    loaded = {"ops": {}, "threads": [step], "modules": {
        "d": [("jit_prefill_step(1)", 250 * us, 100 * us)]}}
    got = gap_causes.prefill_lives(loaded)
    assert got["device_wait_s"] == [pytest.approx(50 * us)]
    assert got["readback_lag_s"] == [pytest.approx(30 * us)]
    assert gap_causes.prefill_lives(dict(loaded, modules={})) is None
    assert gap_causes.prefill_lives(dict(loaded, threads=[])) is None


def test_the_trace_readers_on_the_run_and_on_the_parent(
        xplane, parent_xplane, tmp_path):
    raw = {"trace": {"busy_s": BUSY_S, "window_s": WINDOW_S}}
    (tmp_path / "change").mkdir()
    (tmp_path / "parent").mkdir()
    _place(xplane, tmp_path / "change")
    _place(parent_xplane, tmp_path / "parent")
    want = {"step_gap_stream_share": 27.5, "idle_host_work_share": 28.8,
            "idle_readback_share": 15.2, "idle_no_work_share": 8.0,
            "prefill_device_wait_ms_mean": 0.3,
            "prefill_readback_lag_ms_mean": 0.875}
    for name, value in want.items():
        src = cells.reader_path(name)
        for side in ("change", "parent"):
            # the reader finds the run's trace from its own path
            mdir = tmp_path / side / "benchmark" / "metrics"
            mdir.mkdir(parents=True, exist_ok=True)
            copy = mdir / os.path.basename(src)
            copy.write_text(open(src).read())
            read = cells._module(str(copy), f"gc_{side}_{name}").read
            if side == "change":
                assert read(raw) == pytest.approx(value), name
                assert read({"trace": None}) is None
            else:
                assert read(raw) is None, name
    view = gap_causes.summarize(xplane, WINDOW_S)
    assert view["idle_share_by_cause"]["readback"] == pytest.approx(15.2)
    assert view["prefills"]["device_wait_matched"] == 1
    json.dumps(view)
    assert gap_causes.summarize(parent_xplane)["step_gaps"] is None


# ------------------------------------------------------------- counters

PHASES0 = dict.fromkeys(("admit", "dispatch_window", "process_window",
                         "readback_window", "readback_prefill", "idle",
                         "between_steps", "other"), 0.0)
PHASES1 = {"admit": 1.0, "dispatch_window": 2.0, "process_window": 5.0,
           "readback_window": 30.0, "readback_prefill": 2.0, "idle": 4.0,
           "between_steps": 5.0, "other": 1.0}       # 50 s of wall time
CPU1 = {"admit": 0.9, "dispatch_window": 1.5, "process_window": 3.5,
        "readback_window": 0.5, "readback_prefill": 0.1, "idle": 0.0,
        "between_steps": 0.2, "other": 0.4}


def _raw():
    s0 = {counters.PHASES_KEY: dict(PHASES0),
          host_counters.CPU_KEY: dict(PHASES0),
          "thread_cpu_seconds_total": {"step": 1.0, "loop": 2.0,
                                       "detok": 0.5},
          "thread_runq_wait_seconds_total": {"step": 0.1, "loop": 0.2,
                                             "detok": 0.0},
          "gc_pause_seconds_total": 0.5,
          "emit_to_wire_seconds_total": 1.0, "emit_to_wire_total": 100,
          "intake_seconds_total": 0.0, "intake_total": 0}
    s1 = {counters.PHASES_KEY: dict(PHASES1),
          host_counters.CPU_KEY: dict(CPU1),
          "thread_cpu_seconds_total": {"step": 9.0, "loop": 27.0,
                                       "detok": 3.5},
          "thread_runq_wait_seconds_total": {"step": 0.15, "loop": 0.25,
                                             "detok": 0.0},
          "gc_pause_seconds_total": 0.75,
          "emit_to_wire_seconds_total": 4.0, "emit_to_wire_total": 1100,
          "intake_seconds_total": 0.5, "intake_total": 250}
    return {"stats0": s0, "stats1": s1, "trace": None}


def test_the_counter_readers_by_hand():
    raw = _raw()
    read = {n: cells.load_reader(n) for n in NEW}
    assert host_counters.wall_s(raw) == pytest.approx(50.0)
    assert read["loop_thread_busy_share"](raw) == pytest.approx(50.0)
    assert read["emit_to_wire_ms_mean"](raw) == pytest.approx(3.0)
    assert read["intake_ms_mean"](raw) == pytest.approx(2.0)
    assert read["intake_ms_mean.tpot"](raw) == pytest.approx(2.0)
    # work phases: admit + dispatch_window + process_window + other = 9 s
    # of wall, 6.3 s of CPU
    assert read["step_offcpu_share"](raw) == pytest.approx(30.0)
    # no metric of the benchmark: its machines' kernel keeps no
    # run-queue time (the helper serves a platform that does)
    assert host_counters.share_of_wall(raw, host_counters.thread_seconds(
        raw, "thread_runq_wait_seconds_total", ("step", "loop"))
    ) == pytest.approx(0.2)
    assert read["gc_pause_share"](raw) == pytest.approx(0.5)


@pytest.mark.parametrize("name", NEW)
def test_a_new_reader_returns_none_on_the_parents_raw(name):
    """The parent's stats() has the phases and none of the new keys;
    its run may be traced or not."""
    old = {counters.PHASES_KEY: dict(PHASES1), "first_tokens_total": 3,
           "queue_wait_seconds_total": 1.0}
    read = cells.load_reader(name)
    for trace in (None, {"busy_s": 1.0, "window_s": 5.0}):
        raw = {"stats0": dict(old), "stats1": dict(old), "trace": trace,
               "rows": [], "window_s": 50.0}
        assert read(raw) is None
    assert read({"stats0": {}, "stats1": {}, "trace": None}) is None


def test_a_platform_without_schedstat_or_gc_callbacks_reads_none():
    """Such a stats() leaves the keys out (or a thread out of the
    dictionary): absent, never zero."""
    raw = _raw()
    for s in (raw["stats0"], raw["stats1"]):
        del s["gc_pause_seconds_total"]
        del s["thread_runq_wait_seconds_total"]
        del s["thread_cpu_seconds_total"]["loop"]
    assert cells.load_reader("gc_pause_share")(raw) is None
    assert host_counters.thread_seconds(
        raw, "thread_runq_wait_seconds_total", ("step", "loop")) is None
    assert cells.load_reader("loop_thread_busy_share")(raw) is None
    assert cells.load_reader("step_offcpu_share")(raw) is not None


def _listed_in_pr_35():
    """(entry, cell) as PRs 35 and 33 listed them: the eight in cells
    1-6, the TTFT side in cell 1 and, as a variant, in cell 3."""
    accepted = [w["name"] for w in cells.load_benchmark()["workloads"]][:6]
    return ([(n, c) for n in EVERY_CELL for c in accepted]
            + [(n, accepted[0]) for n in TTFT_SIDE]
            + [(n + ".tpot", accepted[2]) for n in TTFT_SIDE])


@pytest.mark.parametrize("name, cell", _listed_in_pr_35(),
                         ids=lambda v: v)
def test_a_new_entry_has_a_reader_and_a_list_of_accepted_cells(name, cell):
    bench = cells.load_benchmark()
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    assert entry in cells.metrics_in(bench, cell, "per_layer")
    assert ("workloads" in entry) == (name not in EVERY_CELL)
    assert os.path.isfile(cells.reader_path(name))
    assert entry["source"] == ("device_trace" if "gap_causes" in open(
        cells.reader_path(name)).read() else "program_counter")
    with open(os.path.join(ROOT, "PERF.md")) as f:
        assert f"`{name.split('.')[0]}`" in f.read()
