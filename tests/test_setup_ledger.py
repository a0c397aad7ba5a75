"""The set-up ledger (runtime/profiling.py SetupLedger): process start to
readiness as spans that nest across threads, the jit pipeline's stages
from ``jax.monitoring``'s events by span and by program, the warm grid
by form, and the one duration listener it shares with the compile fence.
"""

import json
import os
import subprocess
import sys
import threading
import time

import jax
import jax.monitoring
import jax.numpy as jnp
import pytest
from jax._src import monitoring

from dynamo_tpu.engine import jit_fence
from dynamo_tpu.engine.jax_engine import EngineConfig, JaxEngine
from dynamo_tpu.engine.jit_fence import CompileFence, PostWarmupCompileError
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.runtime import profiling
from dynamo_tpu.runtime.profiling import JIT_STAGES, OUTSIDE, SetupLedger

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE, LOWER, COMPILE = profiling.JIT_STAGE_EVENTS
NEW_KEYS = ("setup_span_seconds_total", "setup_span_calls_total",
            "setup_spans", "setup_span_jit_seconds",
            "jit_stage_seconds_total", "jit_stage_calls_total",
            "compile_cache_hits_total", "compile_cache_misses_total",
            "jit_program_seconds", "warmup_programs")


class Clock:
    """A clock a test moves by hand."""

    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


@pytest.fixture
def ledger(monkeypatch):
    """A fresh ledger in the process's place, on a hand-moved clock."""
    clock = Clock()
    led = SetupLedger(clock)
    monkeypatch.setattr(profiling, "_setup", led)
    return led


def _stage(event, seconds, fun_name):
    """One stage as JAX reports it: the scalar when it begins, the
    duration when it ends."""
    profiling._on_jit_begin(event, 0.0, fun_name=fun_name)
    profiling._on_jit_duration(event, seconds, fun_name=fun_name)


def test_spans_nest_across_threads_and_a_parent_holds_its_children(ledger):
    clock = ledger.clock

    def child():
        with ledger.span("load_params") as s:
            clock.t += 2.0
        assert s.depth == 1

    with ledger.span("engine_setup") as outer:
        clock.t += 0.5
        t = threading.Thread(target=child)
        t.start()
        t.join(10)
        assert not t.is_alive()
        clock.t += 0.25
    with ledger.span("engine_init"):
        clock.t += 1.0
    st = ledger.stats()
    assert outer.seconds == pytest.approx(2.75)
    assert st["setup_span_seconds_total"] == pytest.approx(
        {"engine_setup": 2.75, "load_params": 2.0, "engine_init": 1.0})
    assert st["setup_span_calls_total"] == {
        "engine_setup": 1, "load_params": 1, "engine_init": 1}
    # in order of opening, with the depth each was opened at
    assert [(s[0], s[3]) for s in st["setup_spans"]] == [
        ("engine_setup", 0), ("load_params", 1), ("engine_init", 0)]
    assert st["setup_spans"][1][1:3] == pytest.approx([100.5, 102.5])


def test_no_update_is_lost_between_threads(ledger):
    """More threads than cores bracket and report stages at once: every
    span and every stage is counted, and the totals stay the spans'."""
    threads, rounds = 16, 200
    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)

    def work(k):
        for _ in range(rounds):
            with ledger.span(f"worker.{k % 4}"):
                _stage(LOWER, 0.5, f"jit(program_{k})")

    try:
        ts = [threading.Thread(target=work, args=(k,))
              for k in range(threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(60)
        assert not any(t.is_alive() for t in ts)
    finally:
        sys.setswitchinterval(was)
    st = ledger.stats()
    assert sum(st["setup_span_calls_total"].values()) == threads * rounds
    assert st["jit_stage_calls_total"]["lower"] == threads * rounds
    assert st["jit_stage_seconds_total"]["lower"] == 0.5 * threads * rounds
    assert sum(r["lower"] for r in st["jit_program_seconds"].values()) == \
        0.5 * threads * rounds
    assert not ledger._open


def test_the_ordered_list_is_capped_and_the_sums_are_not(ledger):
    for _ in range(SetupLedger.MAX_SPANS + 40):
        with ledger.span("warmup.window"):
            ledger.clock.t += 0.01
    st = ledger.stats()
    assert len(st["setup_spans"]) == SetupLedger.MAX_SPANS
    assert st["setup_span_calls_total"]["warmup.window"] == \
        SetupLedger.MAX_SPANS + 40


def test_a_stage_lands_in_the_innermost_span_in_outside_and_by_program(
        ledger):
    _stage(TRACE, 0.3, "agree")                 # no span open
    with ledger.span("warmup"):
        _stage(TRACE, 0.3, "sample_tokens")
        with ledger.span("warmup.prefill"):
            _stage(TRACE, 1.0, "prefill_step")
            _stage(LOWER, 2.0, "jit(prefill_step)")
            _stage(COMPILE, 4.0, "jit(prefill_step)")
    st = ledger.stats()
    assert st["setup_span_jit_seconds"] == {
        OUTSIDE: {"trace": 0.3},
        "warmup": {"trace": 0.3},
        "warmup.prefill": {"trace": 1.0, "lower": 2.0,
                           "backend_compile": 4.0}}
    assert st["jit_stage_seconds_total"] == pytest.approx(
        {"trace": 1.6, "lower": 2.0, "cache_read": 0.0,
         "backend_compile": 4.0})
    assert st["jit_stage_calls_total"] == {
        "trace": 3, "lower": 1, "cache_read": 0, "backend_compile": 1}
    row = st["jit_program_seconds"]["prefill_step"]
    assert row == {"trace": 1.0, "lower": 2.0, "cache_read": 0.0,
                   "backend_compile": 4.0, "calls": 1}
    # the four totals are the spans' and outside's, summed
    for stage in JIT_STAGES:
        assert st["jit_stage_seconds_total"][stage] == pytest.approx(sum(
            s.get(stage, 0.0) for s in st["setup_span_jit_seconds"].values()))
    assert "warmup 0.0 (trace 1.3 lower 2.0 cache 0.0 compile 4.0)" in \
        ledger.ready_line()


def test_a_stage_inside_another_is_not_added_twice(ledger):
    """A jitted jnp function traced inside a program's trace reports a
    duration that the program's own already holds."""
    profiling._on_jit_begin(TRACE, 0.0, fun_name="prog")
    _stage(TRACE, 0.4, "_where")
    _stage(TRACE, 0.1, "_reduce_sum")
    profiling._on_jit_duration(TRACE, 1.0, fun_name="prog")
    st = ledger.stats()
    assert st["jit_stage_seconds_total"]["trace"] == 1.0
    assert list(st["jit_program_seconds"]) == ["prog"]


def test_a_cache_hit_is_a_read_and_no_compile(ledger):
    """pxla wraps compile_or_get_cached whole: on a hit the backend event
    holds the retrieval that the cache just reported."""
    seen = []
    profiling._compile_subscribers.append(lambda s, hit: seen.append(hit))
    try:
        profiling._on_jit_event(profiling.CACHE_HIT_EVENT)
        profiling._on_jit_duration(profiling.CACHE_READ_EVENT, 0.5)
        _stage(COMPILE, 0.52, "jit(decode_window)")
        profiling._on_jit_event(profiling.CACHE_MISS_EVENT)
        _stage(COMPILE, 3.0, "jit(decode_window)")
    finally:
        profiling._compile_subscribers.pop()
    st = ledger.stats()
    assert seen == [True, False]
    assert st["compile_cache_hits_total"] == 1
    assert st["compile_cache_misses_total"] == 1
    assert st["jit_stage_seconds_total"]["cache_read"] == 0.5
    assert st["jit_stage_seconds_total"]["backend_compile"] == \
        pytest.approx(3.02)
    assert st["jit_stage_calls_total"]["backend_compile"] == 1
    assert st["jit_program_seconds"]["decode_window"]["calls"] == 2


def test_eager_programs_fold_into_one_row_and_the_rest_into_other(ledger):
    _stage(LOWER, 0.1, "jit(convert_element_type)")
    _stage(LOWER, 0.2, "jit(broadcast_in_dim)")
    _stage(LOWER, 0.3, "jit(subtract)")
    _stage(LOWER, 0.05, "jit(_where)")
    for k in range(SetupLedger.MAX_PROGRAMS + 3):
        _stage(LOWER, 1.0 + k, f"jit(program_{k})")
    table = ledger.stats()["jit_program_seconds"]
    assert table["eager"]["lower"] == pytest.approx(0.65)
    assert len(table) == SetupLedger.MAX_PROGRAMS + 1
    # eager stays whatever it cost; the four cheapest of the rest fell out
    assert table["other"]["lower"] == pytest.approx(1.0 + 2.0 + 3.0 + 4.0)
    # no jitted entry point of the program is named like one of JAX's own
    for name in ("prefill_step", "decode_window", "sample_tokens",
                 "_merge_carry", "_gather_pages", "_inject_pages",
                 "logprob_aux", "verify_greedy_draft", "exact_top_k"):
        assert not profiling._is_eager(name), name


def test_stats_hands_out_one_table_until_something_changes(ledger):
    with ledger.span("engine_init"):
        pass
    first = ledger.stats()
    assert ledger.stats() is first
    assert ledger.stats()["setup_spans"] is first["setup_spans"]
    _stage(TRACE, 0.1, "late")
    assert ledger.stats() is not first


def test_prom_lines_and_host_stats_carry_the_ledger(ledger):
    assert profiling._setup_prom_lines() == []      # a bare frontend
    with ledger.span("engine_init"):
        ledger.clock.t += 1.5
        _stage(LOWER, 0.25, "jit(zeros)")
    lines = profiling.render_prom_lines()
    assert 'dyn_engine_setup_span_seconds{span="engine_init"} 1.500000' \
        in lines
    assert 'dyn_engine_jit_stage_seconds_total{stage="lower"} 0.250000' \
        in lines
    assert "dyn_engine_compile_cache_hits_total 0" in lines
    assert "dyn_engine_compile_cache_misses_total 0" in lines
    host = profiling.host_stats()
    assert all(k in host for k in NEW_KEYS)


def test_one_duration_listener_a_process_shared_with_the_fence():
    """Two engines' worth of installs and two armed fences register the
    ledger's listener once, and nothing else of this program's."""
    profiling.install_jit_listeners()
    profiling.install_jit_listeners()
    a, b = CompileFence("l1", mode=""), CompileFence("l2", mode="")
    a.arm()
    b.arm()
    try:
        ours = [cb for cb in monitoring.get_event_duration_listeners()
                if getattr(cb, "__module__", "").startswith("dynamo_tpu")]
        assert ours == [profiling._on_jit_duration]
        assert profiling._compile_subscribers.count(jit_fence._dispatch) == 1
    finally:
        a.disarm()
        b.disarm()


def test_the_fence_trips_through_the_shared_listener_and_says_which(caplog):
    fence = CompileFence("shared", mode="raise")
    fence.arm()
    try:
        with pytest.raises(PostWarmupCompileError, match="XLA compile"):
            jax.jit(lambda x: x * 3 + 52)(jnp.zeros((5,)))
        assert fence.post_warmup_compiles == 1
        # a program read from the persistent cache stalls serving too
        with pytest.raises(PostWarmupCompileError,
                           match="read from the compile cache"):
            profiling._on_jit_duration(profiling.CACHE_READ_EVENT, 0.2)
            _stage(COMPILE, 0.21, "jit(unwarmed)")
        assert fence.post_warmup_compiles == 2
    finally:
        fence.disarm()


def test_warmup_of_the_tiny_engine_fills_every_key(ledger, monkeypatch):
    monkeypatch.setattr(ledger, "clock", time.monotonic)
    cfg = ModelConfig.tiny()
    ecfg = EngineConfig(page_size=8, num_pages=64, max_batch=4,
                        prefill_chunk=32, batch_buckets=(2, 4),
                        prefill_buckets=(16, 32), page_buckets=(8,),
                        max_prefill_batch=2, decode_steps=2,
                        warmup_logprobs=False)
    eng = JaxEngine(cfg, ecfg, seed=0)
    try:
        n = eng.warmup()
        st = eng.stats()
    finally:
        eng.fence.disarm()
    assert all(k in st for k in NEW_KEYS)
    rows = st["warmup_programs"]
    assert len(rows) == n
    assert {r["program"] for r in rows} == {
        "prefill_fn", "decode_multi_fn", "_merge_carry"}
    assert all(r["form"].startswith(r["program"] + "(") for r in rows)
    spans = st["setup_span_seconds_total"]
    assert abs(spans["warmup"] - st["warmup_seconds"]) < 0.01
    assert {"engine_init", "warmup", "warmup.prefill", "warmup.window",
            "warmup.sample", "warmup.cost_timing"} <= set(spans)
    grid = ecfg.warmed_grid()
    prefills = (len(grid["prefill_lens"]) * len(grid["prefill_batches"])
                * len(grid["page_buckets"]))
    assert st["setup_span_calls_total"]["warmup.prefill"] == prefills
    # a parent holds its children, and the stages charged inside warmup
    # fit in it
    inside = [k for k in spans if k.startswith("warmup.")]
    assert sum(spans[k] for k in inside) <= spans["warmup"]
    jit = st["setup_span_jit_seconds"]
    assert sum(sum(jit.get(k, {}).values())
               for k in inside + ["warmup"]) <= st["warmup_seconds"]
    # each row's stages fit in its seconds, and its program was built
    for r in rows:
        assert sum(r[s] for s in JIT_STAGES) <= r["seconds"]
    assert st["jit_program_seconds"]["prefill_step"]["calls"] == prefills


_CACHED_CHILD = """
import json, sys
sys.path.insert(0, {root!r})
from dynamo_tpu.runtime.compile_cache import enable_compile_cache
from dynamo_tpu.runtime.profiling import setup_ledger
enable_compile_cache()
import jax, jax.numpy as jnp
jax.jit(lambda x: (x @ x.T).sum() + 52)(jnp.ones((8, 8))).block_until_ready()
st = setup_ledger().stats()
print(json.dumps({{k: st[k] for k in (
    "jit_stage_seconds_total", "compile_cache_hits_total",
    "compile_cache_misses_total", "setup_span_seconds_total")}}))
"""


def test_a_second_process_reads_what_the_first_compiled(tmp_path):
    """With the persistent cache in a directory of its own: the first
    process misses and compiles, the second hits, reads, and compiles
    nothing."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    runs = []
    for _ in range(2):
        out = subprocess.run(
            [sys.executable, "-c", _CACHED_CHILD.format(root=ROOT)],
            env=env, capture_output=True, text=True, timeout=240)
        assert out.returncode == 0, out.stderr[-2000:]
        runs.append(json.loads(out.stdout.strip().splitlines()[-1]))
    cold, warm = runs
    assert cold["compile_cache_hits_total"] == 0
    assert cold["compile_cache_misses_total"] > 0
    assert cold["jit_stage_seconds_total"]["backend_compile"] > 0
    assert cold["jit_stage_seconds_total"]["cache_read"] == 0
    assert warm["compile_cache_hits_total"] > 0
    assert warm["compile_cache_misses_total"] == 0
    assert warm["jit_stage_seconds_total"]["cache_read"] > 0
    assert warm["jit_stage_seconds_total"]["backend_compile"] < \
        0.25 * cold["jit_stage_seconds_total"]["backend_compile"]
    assert warm["jit_stage_seconds_total"]["trace"] > 0     # never cached
    assert "jax_import" in warm["setup_span_seconds_total"]
