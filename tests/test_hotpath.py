"""Decode hot-path tests: the window against the single-step reference
for every pinned request kind, the pipelined arm's prefill policy, the
sampler-parameter upload's reuse, one chunk per row-window and the
per-token path beside it, admission on the step thread, zero post-warmup
compiles under default AND exotic warmed_grid configs, async-detok
ordering/cancellation, and the CPU hotpath bench smoke."""

import asyncio
import inspect
import threading

import numpy as np
import pytest

from dynamo_tpu.engine.jax_engine import EngineConfig, JaxEngine, Sequence
from dynamo_tpu.llm.backend import Backend
from dynamo_tpu.llm.protocols.common import (EngineOutput,
                                             PreprocessedRequest,
                                             SamplingOptions,
                                             StopConditions)
from dynamo_tpu.llm.tokenizer import ByteTokenizer
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.runtime import Context


def _ecfg(**kw):
    base = dict(page_size=4, num_pages=64, max_batch=4, prefill_chunk=32,
                prefill_buckets=(32,), batch_buckets=(4,),
                page_buckets=(16,))
    base.update(kw)
    return EngineConfig(**base)


def _req(tokens, mt=10, eos=(), stop_ids=(), **sampling):
    return PreprocessedRequest(
        token_ids=list(tokens), sampling=SamplingOptions(**sampling),
        stop=StopConditions(max_tokens=mt, ignore_eos=not eos,
                            stop_token_ids=list(stop_ids) or None),
        eos_token_ids=list(eos))


async def _chunks(engine, req, ctx=None):
    """Every EngineOutput of one request: (token_ids, finish_reason)."""
    outs = []
    async for out in engine.generate(req, ctx or Context()):
        outs.append((list(out.token_ids), out.finish_reason))
        if out.finish_reason:
            break
    return outs


async def _collect(engine, req):
    outs = await _chunks(engine, req)
    return [t for ids, _ in outs for t in ids], outs[-1][1]


def _mixed_requests():
    """greedy, penalties, logit_bias, and a seeded sampled row — the
    pinned token-identity surface (unseeded sampling is exempt by
    design: the sampler-param cache freezes its build-time reseeds)."""
    rng = np.random.RandomState(11)
    p = [rng.randint(1, 400, n).tolist() for n in (8, 15, 22, 30, 2)]
    return {
        # a prompt of page_size - 2: the first window's commit lies on
        # both sides of a page boundary
        "page_straddle": _req(p[4]),
        "greedy": _req(p[0]),
        "penalties": _req(p[1], repetition_penalty=1.3,
                          frequency_penalty=0.4),
        "logit_bias": _req(p[2], logit_bias={7: -100.0, 19: 4.0}),
        "seeded": _req(p[3], temperature=0.8, top_k=16, seed=123),
    }


@pytest.mark.parametrize("kind", ["greedy", "penalties", "logit_bias",
                                  "seeded", "page_straddle"])
def test_window_matches_single_step_per_kind(run_async, kind):
    """Each pinned request kind gives the same tokens through the
    pipelined K-step window as through single steps."""
    cfg = ModelConfig.tiny()

    async def gen(k):
        eng = JaxEngine(cfg, _ecfg(decode_steps=k), seed=0)
        out = await _collect(eng, _mixed_requests()[kind])
        await eng.stop()
        return out

    single, window = run_async(gen(1)), run_async(gen(4))
    assert single == window
    assert len(window[0]) == 10 and window[1] == "length"


def test_stop_string_identity_through_backend(run_async):
    """e2e stop-string arm: Backend + real engine. A stop string cut from
    the free-running text must truncate identically (text and finish
    reason) whether the tokens arrive one by one (decode_steps 1) or as
    row-windows (decode_steps 4)."""
    cfg = ModelConfig.tiny()
    tok = ByteTokenizer()

    async def gen(k, stop):
        eng = JaxEngine(cfg, _ecfg(decode_steps=k), seed=0)
        be = Backend(eng, tok)
        req = PreprocessedRequest(
            token_ids=list(range(60, 80)), sampling=SamplingOptions(),
            stop=StopConditions(max_tokens=16, ignore_eos=True,
                                stop=stop),
            eos_token_ids=[])
        text, fin = "", None
        async for out in be.generate(req, Context()):
            text += out.text or ""
            if out.finish_reason:
                fin = out.finish_reason
                break
        await eng.stop()
        return text, fin

    free, fin = run_async(gen(4, None))
    assert fin == "length" and len(free) > 4
    needle = free[2:5]
    a = run_async(gen(1, [needle]))
    b = run_async(gen(4, [needle]))
    assert a == b
    assert b[1] == "stop" and needle not in b[0]


# ------------------------------------------- the pipelined arm, by hand
# The engine is never started: the test thread calls _step() itself, so
# each iteration's dispatches can be read off before the next one runs
# (_emit puts straight into the queue while no loop thread is captured).


def _submit(eng, req):
    seq = Sequence(req=req, context=Context(), out=asyncio.Queue(),
                   tokens=list(req.token_ids),
                   num_prompt=len(req.token_ids))
    eng.waiting.append(seq)
    return seq


def _spy_dispatches(eng):
    """Per _step: which of the two dispatches were called and shipped.
    ``stepped.order`` has a string an iteration of what it shipped, in
    the order it reached the device's queue: P a prefill, W a window;
    ``stepped.queue(start)`` joins them: the order on the device's queue
    from iteration ``start`` on, whatever iteration shipped what. Every
    prefill dispatch checks that at most one prefill before it is still
    un-read: never three."""
    log, order, prefills = [], [], []
    for kind, mark in (("_dispatch_prefill", "P"),
                       ("_dispatch_decode_window", "W")):
        def spy(*a, _fn=getattr(eng, kind), _kind=kind, _mark=mark, **kw):
            if _mark == "P":
                assert stepped.unread() <= 1, "a third prefill un-read"
            out = _fn(*a, **kw)
            log[-1][_kind] = log[-1].get(_kind, False) or out is not None
            if out is not None:
                order[-1] += _mark
                if _mark == "P":
                    prefills.append(out)
            return out
        setattr(eng, kind, spy)
    step = eng._step

    def stepped():
        log.append({"running": len(eng.running)})
        order.append("")
        step()
        eng._reap()
        assert stepped.unread() == len(eng._pending_prefills) <= 2
        return log[-1]

    stepped.order = order
    stepped.queue = lambda start=0: "".join(order[start:])
    stepped.unread = lambda: sum(not pf.processed for pf in prefills)
    return stepped, log


def _step_until(stepped, cond, limit=64):
    for _ in range(limit):
        if cond():
            return
        stepped()
    raise AssertionError("condition not reached")


def _decoding_row(mt=40, **kw):
    """An engine with one row mid-decode (a window in flight), its spy,
    and the row."""
    eng = JaxEngine(ModelConfig.tiny(), _ecfg(decode_steps=4, **kw), seed=0)
    stepped, _ = _spy_dispatches(eng)
    first = _submit(eng, _req(range(1, 9), mt=mt))
    _step_until(stepped, lambda: first.generated >= 5)
    assert eng.prefill_window_topups_total == 0, "nothing decoded beside it"
    assert eng.prefill_runahead_total == 0
    return eng, stepped, first


def _in_one_of(eng, seq):
    """Where the scheduler holds a live sequence: exactly one place."""
    return [name for name in ("waiting", "prefilling", "running")
            if seq in getattr(eng, name)]


@pytest.mark.parametrize("case", ["prompt_waiting", "nothing_waiting"])
def test_a_prefill_iteration_ships_a_window_only_behind_the_last_prefill(
        case):
    """Prefill priority (prefill_token_budget None), with a row decoding.
    While a prefill is still due after the one just shipped, the
    iteration ships that prefill too and no window between them; when
    nothing is left to prefill it ships a window behind the last prefill,
    and the next iteration reads that prefill back before it ships
    anything else. On the device's queue: P P W W with a second prompt
    waiting, P W W without."""
    waiting = case == "prompt_waiting"
    # a prefill dispatch of one row: of two prompts admitted together
    # the second is still due after the first ships
    eng, stepped, first = _decoding_row(
        max_prefill_batch=1 if waiting else 8)
    second = _submit(eng, _req(range(20, 40), mt=4))
    third = _submit(eng, _req(range(50, 70), mt=4)) if waiting else None
    _step_until(stepped, lambda: second in eng.prefilling)
    if waiting:
        assert third in eng.prefilling
    start = len(stepped.order)
    it = stepped()
    assert it == {"running": 1, "_dispatch_prefill": True,
                  "_dispatch_decode_window": True}
    assert stepped.order[-1] == ("PPW" if waiting else "PW")
    assert not eng.prefilling
    assert eng.prefill_window_topups_total == 1
    assert eng.stats()["prefill_window_topups_total"] == 1
    assert eng.stats()["prefill_runahead_total"] == int(waiting)
    last = third if waiting else second
    assert last not in eng.running, "the last prefill is not read back yet"
    assert stepped.unread() == 1
    # the prefill BEFORE the last was read back first: its row decodes
    # in the window behind the run, as it would an iteration later
    assert eng._pending.batch == ([first, second] if waiting else [first])
    # the iteration after: one window, and the last prefill's row is in
    # it, so the prefill was read back before that window shipped
    stepped()
    assert stepped.order[start:] == ["PPW" if waiting else "PW", "W"]
    assert stepped.queue(start) == ("PPWW" if waiting else "PWW")
    assert [s for s in eng._pending.batch if s is not first] == (
        [second, third] if waiting else [second])
    _step_until(stepped, lambda: all(
        s.finished for s in (first, second, third) if s is not None))
    assert eng.mixed_dispatches == 0
    assert first.generated == 40 and second.generated == 4
    assert eng.prefill_dispatches_total == (3 if waiting else 2)
    assert eng.prefill_window_topups_total == 1
    # no prefill ever followed a prefill where nothing waited
    assert eng.prefill_runahead_total == int(waiting)
    assert eng.prefill_runahead_total < eng.prefill_dispatches_total


def test_the_order_of_programs_is_the_one_without_the_top_up():
    """Rule 3 of _step_window: the window behind a prefill reaches the
    queue an iteration sooner and nothing else moves. A prompt that lands
    in the iteration after the pair is served before any second window:
    P W P' W, never P W W P'."""
    eng, stepped, first = _decoding_row(mt=48)
    second = _submit(eng, _req(range(20, 40), mt=8))
    _step_until(stepped, lambda: second in eng.prefilling)
    start = len(stepped.order)
    stepped()                                   # P W
    third = _submit(eng, _req(range(50, 70), mt=8))
    stepped()                                   # P' W
    assert stepped.order[start:] == ["PW", "PW"]
    assert stepped.queue(start) == "PWPW"
    assert second in eng.running and third not in eng.running
    assert eng._pending.batch == [first, second]
    stepped()                                   # third's row joins
    assert stepped.order[-1] == "W"
    assert eng._pending.batch == [first, second, third]
    assert eng.prefill_window_topups_total == 2
    assert eng.prefill_runahead_total == 0, "no prefill followed a prefill"
    _step_until(stepped, lambda: first.finished and second.finished
                and third.finished)
    assert (first.generated, second.generated, third.generated) == (48, 8, 8)


@pytest.mark.parametrize("chunks,want", [
    (2, ["PPW"]), (3, ["PP", "PW"]), (5, ["PP", "P", "P", "PW"])])
def test_no_window_between_the_chunks_of_one_prompt(chunks, want):
    """The same arrival with a prompt of several chunks beside a decoding
    row: P1 .. Pn W on the queue. The first iteration of the run ships
    two and leaves both un-read; every later one reads the older back,
    ships one and reads the other at its end, and the window comes behind
    the last (never three prefills un-read: _spy_dispatches)."""
    eng, stepped, first = _decoding_row(num_pages=128, page_buckets=(64,))
    n = 32 * (chunks - 1) + 8
    second = _submit(eng, _req(range(20, 20 + n), mt=4))
    _step_until(stepped, lambda: second in eng.prefilling)
    start = len(stepped.order)
    for i, shipped in enumerate(want[:-1]):
        stepped()
        assert stepped.order[-1] == shipped
        assert stepped.unread() == (2 if i == 0 else 1)
    _step_until(stepped, lambda: second in eng.running)
    assert stepped.order[start:start + len(want)] == want
    assert stepped.queue(start).startswith("P" * chunks + "W")
    assert eng.prefill_window_topups_total == 1
    # the one dispatch that shared an iteration with the one before it
    assert eng.prefill_runahead_total == 1
    _step_until(stepped, lambda: first.finished and second.finished)
    assert first.generated == 40 and second.generated == 4
    assert eng.prefill_dispatches_total == 1 + chunks


def test_a_row_the_bucket_choice_held_back_rides_the_same_iteration():
    """Two prompts admitted together under a table that ships one row a
    program (choose_prefill_bucket holds the second back): P P W in ONE
    iteration, and the first prompt's row decodes in that W."""
    eng, stepped, first = _decoding_row(batch_buckets=(1, 4))
    eng._prefill_costs = {(1, 32): (1.0, 1.0), (4, 32): (4.4, 4.5)}
    second = _submit(eng, _req(range(20, 40), mt=4))
    third = _submit(eng, _req(range(50, 70), mt=4))
    _step_until(stepped, lambda: third in eng.prefilling)
    assert second in eng.prefilling
    stepped()
    assert stepped.order[-1] == "PPW"
    assert eng.prefill_rows_held_back_total == 1
    assert eng.prefill_runahead_total == 1
    assert eng._pending.batch == [first, second]
    assert second.generated == 1 and third.generated == 0
    _step_until(stepped, lambda: first.finished and second.finished
                and third.finished)
    assert (first.generated, second.generated, third.generated) == (40, 4, 4)


def test_a_prompt_admitted_behind_a_finishing_prefill_rides_the_same_iteration():
    """A prefill whose row finishes its prompt (and draws its first
    token) with the next prompt still WAITING: the admission behind the
    first dispatch brings it in and its prefill follows at once
    (sample_tokens then a prefill: no host iteration between them)."""
    eng, stepped, first = _decoding_row()
    second = _submit(eng, _req(range(20, 40), mt=4))
    _step_until(stepped, lambda: second in eng.prefilling)
    third = _submit(eng, _req(range(50, 70), mt=4))
    assert third in eng.waiting
    stepped()
    assert stepped.order[-1] == "PPW"
    assert eng.prefill_runahead_total == 1
    assert eng._pending.batch == [first, second]
    assert third not in eng.running and stepped.unread() == 1
    stepped()
    assert stepped.order[-1] == "W"
    assert eng._pending.batch == [first, second, third]
    _step_until(stepped, lambda: first.finished and second.finished
                and third.finished)
    assert (first.generated, second.generated, third.generated) == (40, 4, 4)


@pytest.mark.parametrize("how", ["flush", "abort", "drain", "preempt"])
def test_two_prefills_out_are_both_settled(how, run_async):
    """Prompts that ship one row a program beside a decoding row. With
    four, the first iteration ships two prefills and leaves both un-read,
    each with a row that finished its prompt: _flush_pipeline reads both
    back, _abort_all fails both rows with everyone else, drain() waits
    for both. With two, the same iteration ships the window behind them,
    and a pool that runs out under that window (the flush inside
    _grow_or_preempt, the second prefill and the window in flight still
    out) loses nobody: every live row is in exactly one of waiting /
    prefilling / running."""
    eng, stepped, first = _decoding_row(max_prefill_batch=1, max_batch=8,
                                        batch_buckets=(8,))
    n = 2 if how == "preempt" else 4
    rest = [_submit(eng, _req(range(20 * i, 20 * i + 20), mt=4))
            for i in range(1, n + 1)]
    everyone = [first] + rest
    _step_until(stepped, lambda: rest[-1] in eng.prefilling)
    if how == "preempt":
        grow, fails = eng.pm.grow, [2]

        def short(pages, target):
            # the first row the window grows finds the pool empty, and
            # again after the flush: the newest arrival is preempted
            if fails[0] and stepped.order[-1] == "PP":
                fails[0] -= 1
                return False
            return grow(pages, target)

        eng.pm.grow = short
        stepped()
        assert stepped.order[-1] == "PPW" and fails == [0]
        assert not eng._pending_prefills, "the flush read the second too"
        # both prompts were prefilled; the newest went back to waiting
        assert [_in_one_of(eng, s) for s in everyone] == [
            ["running"], ["running"], ["waiting"]]
        assert eng._pending.batch == [first, rest[0]]
    else:
        stepped()
        assert stepped.order[-1] == "PP" and eng.prefilling == rest[2:]
        assert [[s for _, s in pf.finishing]
                for pf in eng._pending_prefills] == [rest[:1], rest[1:2]]
        assert all(not _in_one_of(eng, s) for s in rest[:2]), "parked"
    if how == "flush":
        eng._flush_pipeline()
        assert not eng._pending_prefills and not eng._inflight
        assert eng.running == [first] + rest[:2]
    elif how == "abort":
        eng._abort_all()
        assert not eng._pending_prefills and not eng._inflight
        assert [s.finished for s in everyone] == ["error"] * 5
        assert all(s.finish_emitted for s in everyone)
        assert not (eng.running or eng.prefilling or eng.waiting)
        assert eng.pm.active == 0
        return
    elif how == "drain":
        async def main():
            eng.start()
            drained = await eng.drain(timeout_s=60.0)
            await eng.stop()
            return drained
        assert run_async(main()) is True
    if how != "drain":
        _step_until(stepped, lambda: all(s.finished for s in everyone))
    assert [s.generated for s in everyone] == [40] + [4] * n
    assert all(s.finish_emitted for s in everyone)
    assert not eng._pending_prefills and eng.pm.active == 0


@pytest.mark.parametrize("chunks", [1, 2, 3])
@pytest.mark.parametrize("kind", ["greedy", "seeded", "penalties"])
def test_window_behind_a_prefill_matches_single_step(kind, chunks,
                                                     monkeypatch):
    """A prompt of one, two or three chunks arrives while a row of the
    pinned kind is mid-decode. Behind a single prefill the window takes
    the decoding row from the in-flight window's carry (_merge_carry),
    or, for a row with sampling penalties, lands that window first;
    behind a run of prefills (the second enqueued in the iteration that
    shipped the first, a third in the next) the in-flight window is read
    back before the window is built. Both rows get the tokens that
    single steps give them."""
    from dynamo_tpu.engine import jax_engine
    merge = jax_engine._merge_carry
    orders = []         # every engine's order, the running one last
    merged = []         # the iterations, counted from 1, that merged

    def counted(*a):
        merged.append(len(orders[-1]))
        return merge(*a)

    monkeypatch.setattr(jax_engine, "_merge_carry", counted)

    def gen(k):
        eng = JaxEngine(ModelConfig.tiny(), _ecfg(
            decode_steps=k, num_pages=128, page_buckets=(32,)), seed=0)
        stepped, _ = _spy_dispatches(eng)
        orders.append(stepped.order)
        req = _mixed_requests()[kind]
        req.stop.max_tokens = 24
        first = _submit(eng, req)
        _step_until(stepped, lambda: first.generated >= 5)
        second = _submit(eng, _req(range(20, 32 * chunks + 8), mt=6))
        _step_until(stepped, lambda: first.finished and second.finished)
        return [s.tokens[s.num_prompt:] for s in (first, second)], eng

    single, _ = gen(1)
    assert not merged
    window, eng = gen(4)
    assert window == single
    assert [len(t) for t in window] == [24, 6]
    assert eng.prefill_window_topups_total == 1
    assert eng.prefill_runahead_total == min(chunks, 2) - 1
    # order[0] is the first prompt's own prefill, with nothing to decode
    pair = orders[-1].index({1: "PW", 2: "PPW", 3: "PW"}[chunks]) + 1
    assert chunks < 3 or orders[-1][pair - 2] == "PP"
    assert (pair in merged) == (kind != "penalties" and chunks == 1)


def test_a_sweep_that_ships_nothing_still_ships_a_window():
    """Prefill priority again, but the only prefill candidate was
    cancelled before its sweep: the iteration fills the device with a
    decode window instead of idling."""
    eng = JaxEngine(ModelConfig.tiny(), _ecfg(decode_steps=4), seed=0)
    stepped, log = _spy_dispatches(eng)
    first = _submit(eng, _req(range(1, 9), mt=24))
    _step_until(stepped, lambda: first.generated >= 5)
    second = _submit(eng, _req(range(20, 40), mt=4))
    _step_until(stepped, lambda: second in eng.prefilling)
    second.context.stop_generating()
    it = stepped()
    assert it == {"running": 1, "_dispatch_prefill": False,
                  "_dispatch_decode_window": True}
    assert second.finished == "cancelled"
    _step_until(stepped, lambda: first.finished)
    assert first.generated == 24


def test_sampler_upload_reused_until_the_batch_changes():
    """Two windows over the same rows and page counts share one upload
    (the cache entry is the same object); a row finishing rebuilds it
    for the rows that remain."""
    eng = JaxEngine(ModelConfig.tiny(), EngineConfig(
        page_size=32, num_pages=16, max_batch=4, prefill_chunk=32,
        prefill_buckets=(32,), batch_buckets=(4,), page_buckets=(4,),
        decode_steps=2), seed=0)
    stepped, log = _spy_dispatches(eng)
    short = _submit(eng, _req(range(1, 9), mt=5))
    long_ = _submit(eng, _req(range(30, 36), mt=14))
    seen = []          # (rows of the window, the cache entry after it)
    while not (short.finished and long_.finished):
        if stepped().get("_dispatch_decode_window"):
            seen.append((list(eng._samp_cache[0][2]), eng._samp_cache))
    pairs = list(zip(seen, seen[1:]))
    same = [(a, b) for a, b in pairs if a[0] == b[0]]
    changed = [(a, b) for a, b in pairs if a[0] != b[0]]
    assert same and all(a[1] is b[1] for a, b in same)
    assert changed and all(a[1] is not b[1] for a, b in changed)
    assert [s is long_ for s in seen[-1][0]] == [True]


# --------------------------------- one chunk a row-window, or token by token


def test_a_row_window_reaches_the_client_as_one_output(run_async):
    """A row whose stop ids fit the device table: the prefill's token,
    then one EngineOutput per K-step window, cut by the device's emitted
    count at the budget."""
    async def main():
        eng = JaxEngine(ModelConfig.tiny(), _ecfg(decode_steps=4), seed=0)
        outs = await _chunks(eng, _req(range(40, 60), mt=10))
        await eng.stop()
        return outs

    outs = run_async(main())
    assert [len(ids) for ids, _ in outs if ids] == [1, 4, 4, 1]
    assert outs[-1][1] == "length"


def test_a_stop_list_wider_than_the_device_table_stops_on_the_host(
        run_async):
    """More stop ids than max_eos_ids: the device table holds only the
    first two, so the row takes the per-token path (one EngineOutput a
    token) and the host's check stops it on the third id, mid-window,
    with nothing of the window's tail emitted."""
    cfg = ModelConfig.tiny()

    async def gen(stop_ids):
        eng = JaxEngine(cfg, _ecfg(decode_steps=4, max_eos_ids=2), seed=0)
        outs = await _chunks(eng, _req(range(40, 60), mt=12,
                                       stop_ids=stop_ids))
        await eng.stop()
        return outs

    free = [t for ids, _ in run_async(gen(())) for t in ids]
    assert len(free) == 12
    hit = free[5]                       # lands mid-window for K=4
    unused = [v for v in range(1, 400) if v not in free][:2]
    outs = run_async(gen(unused + [hit]))
    assert [t for ids, _ in outs for t in ids] == free[:free.index(hit) + 1]
    assert all(len(ids) <= 1 for ids, _ in outs)
    assert outs[-1][1] == "eos"


# ------------------------------------------------------------- admission


def test_admission_runs_on_the_step_thread_in_its_own_phase(run_async):
    """_admit is called from the step thread only (never from _loop's
    thread) and its time is the ledger's `admit` phase."""
    eng = JaxEngine(ModelConfig.tiny(), _ecfg(decode_steps=4), seed=0)
    admit, threads = eng._admit, set()

    def spy():
        threads.add(threading.current_thread().name)
        admit()

    eng._admit = spy

    async def main():
        out = await asyncio.gather(_collect(eng, _req(range(1, 9), mt=6)),
                                   _collect(eng, _req(range(9, 30), mt=6)))
        stats = eng.stats()
        await eng.stop()
        return out, stats

    out, stats = run_async(main())
    assert all(len(t) == 6 for t, _ in out)
    assert threads and all(t.startswith("jax-step") for t in threads)
    assert stats["step_phase_seconds_total"]["admit"] > 0.0
    assert "_admit" not in inspect.getsource(JaxEngine._loop)


def _run_fence_grid(run_async, name, ecfg):
    """post_warmup_compiles must stay 0 while serving a mixed workload on
    the given warmed grid — the warmed_grid() enumeration must cover the
    coalesced window's emitted-counts output too."""
    cfg = ModelConfig.tiny()
    eng = JaxEngine(cfg, ecfg, seed=0)
    eng.warmup()
    assert eng.fence.armed

    async def main():
        # no penalty rows here: the penalized window variant is
        # deliberately NOT warmed (a first penalty request pays one
        # compile per bucket, by documented contract)
        reqs = [_req(list(range(1, 20)), mt=9),
                _req(list(range(30, 64)), mt=7),
                _req([9, 9, 9, 9, 9, 9], mt=6,
                     temperature=0.9, seed=3)]
        outs = await asyncio.gather(*(_collect(eng, r) for r in reqs))
        await eng.stop()
        return outs

    outs = run_async(main())
    assert all(len(t) >= 6 for t, _ in outs)
    assert eng.stats()["post_warmup_compiles_total"] == 0, (
        f"{name} grid compiled mid-serving")
    eng.fence.disarm()


def test_fence_zero_default_grid(run_async):
    _run_fence_grid(run_async, "default", _ecfg(decode_steps=4))


@pytest.mark.slow
def test_fence_zero_exotic_grid(run_async):
    """Exotic grid: prefill_chunk above the largest prefill bucket,
    max_batch off the bucket list, odd window length."""
    _run_fence_grid(run_async, "exotic", EngineConfig(
        page_size=4, num_pages=64, max_batch=3, prefill_chunk=48,
        prefill_buckets=(16, 32), batch_buckets=(1, 2),
        page_buckets=(8, 16), max_prefill_batch=2, decode_steps=5))


class _ChunkEngine:
    """Fake engine: yields pre-cut token chunks with tiny await points, so
    Backend chunk handling interleaves across concurrent streams."""

    def __init__(self, chunks):
        self.chunks = chunks

    async def generate(self, request, context):
        for c in self.chunks:
            await asyncio.sleep(0)
            yield EngineOutput(token_ids=list(c))
        yield EngineOutput(token_ids=[], finish_reason="length")


def test_async_detok_ordering_under_concurrency(run_async):
    """Detokenisation runs on the detok executor: per-request chunk
    texts must come back in chunk order and concatenate to exactly the inline decode of
    the same ids, across many concurrent streams."""
    tok = ByteTokenizer()
    texts = [f"stream-{i}: héllo wörld →🌍 {'x' * i}" for i in range(6)]

    async def one(text):
        ids = tok.encode(text, add_special_tokens=False)
        chunks = [ids[j:j + 3] for j in range(0, len(ids), 3)]
        be = Backend(_ChunkEngine(chunks), tok)
        req = PreprocessedRequest(
            token_ids=[1], sampling=SamplingOptions(),
            stop=StopConditions(max_tokens=len(ids) + 1, ignore_eos=True),
            eos_token_ids=[])
        parts = []
        async for out in be.generate(req, Context()):
            if out.text:
                parts.append(out.text)
            if out.finish_reason:
                break
        return parts

    async def main():
        return await asyncio.gather(*(one(t) for t in texts))

    all_parts = run_async(main())
    for text, parts in zip(texts, all_parts):
        assert "".join(parts) == text
        assert "�" not in "".join(parts)


def test_async_detok_cancellation_isolated(run_async):
    """Cancelling one stream mid-decode must not corrupt or stall a
    concurrent stream sharing the detok executor."""
    tok = ByteTokenizer()
    text = "the quick brown fox jumps over the lazy dog " * 4

    async def victim(started=None):
        ids = tok.encode(text, add_special_tokens=False)
        be = Backend(_ChunkEngine([ids[j:j + 2]
                                   for j in range(0, len(ids), 2)]), tok)
        req = PreprocessedRequest(
            token_ids=[1], sampling=SamplingOptions(),
            stop=StopConditions(max_tokens=len(ids) + 1, ignore_eos=True),
            eos_token_ids=[])
        got = ""
        async for out in be.generate(req, Context()):
            got += out.text or ""
            if started is not None:
                started.set()
            await asyncio.sleep(0)  # cancellation window
            if out.finish_reason:
                break
        return got

    async def main():
        # cancel on t1's first chunk, not after a fixed sleep: on a fast
        # machine all 88 chunks were through before 10 ms had passed
        started = asyncio.Event()
        t1 = asyncio.ensure_future(victim(started))
        t2 = asyncio.ensure_future(victim())
        await started.wait()
        t1.cancel()
        survivor = await t2
        with pytest.raises(asyncio.CancelledError):
            await t1
        return survivor

    assert run_async(main()) == text


def test_hotpath_scenario_cpu_smoke():
    """CI smoke: the CPU hotpath scenario must produce ONE record with
    post_warmup_compiles == 0 and itl_raw_chunk_p99_ms present."""
    import sys

    import bench

    argv = sys.argv
    sys.argv = ["bench.py", "--cpu", "--model", "tiny",
                "--scenario", "hotpath", "--requests", "4",
                "--concurrency", "2", "--isl", "48", "--osl", "24",
                "--decode-steps", "4"]
    try:
        args = bench.parse_args()
    finally:
        sys.argv = argv
    record = bench._run_scenario(args)
    detail = record["detail"]
    assert record["unit"] == "ms"
    assert isinstance(record["value"], (int, float))
    assert detail["post_warmup_compiles"] == 0
    assert "itl_raw_chunk_p99_ms" in detail
    assert "loop_lag_p99_ms" in detail


# ------------------------- dynahot DL022 fix regressions (ISSUE 18)


def test_sequence_stop_set_cached_once():
    """The per-token stop check reads ONE cached frozenset (built on
    first access) instead of rebuilding `x or []` defaults per token —
    later mutation of the request's lists must not change it (proves
    the cache is actually hit, not rebuilt)."""
    req = _req([1, 2, 3], mt=10, eos=(7,))
    req.stop.stop_token_ids = [9]
    seq = Sequence(req=req, context=Context(), out=asyncio.Queue(),
                   tokens=[1, 2, 3], num_prompt=3)
    first = seq.stop_set
    assert first == frozenset({7, 9})
    assert seq.dev_stop_count == 2
    req.stop.stop_token_ids.append(11)   # post-hoc mutation: ignored
    assert seq.stop_set is first
    assert seq.dev_stop_count == 2


def test_sequence_stop_set_respects_ignore_eos():
    req = _req([1], mt=10, eos=(7,))
    req.stop.ignore_eos = True
    req.stop.stop_token_ids = [9]
    seq = Sequence(req=req, context=Context(), out=asyncio.Queue(),
                   tokens=[1], num_prompt=1)
    assert seq.stop_set == frozenset({9})
    assert seq.dev_stop_count == 1


def test_emit_routes_by_thread_id_without_exception_probe():
    """_emit's on/off-loop routing is one thread-id compare: on the
    captured thread it puts directly; off it goes through
    call_soon_threadsafe; with no captured tid (engine not started) it
    puts directly — and no asyncio loop probe is involved at all."""
    import threading
    import types

    calls = []
    fake_loop = types.SimpleNamespace(
        call_soon_threadsafe=lambda fn, *a: calls.append(a))
    q = asyncio.Queue()
    seq = Sequence(req=_req([1]), context=Context(), out=q,
                   tokens=[1], num_prompt=1)
    eng = types.SimpleNamespace(
        latency=types.SimpleNamespace(observe=lambda *a, **k: None),
        _aio_loop=fake_loop, _aio_loop_tid=threading.get_ident())
    out = EngineOutput(token_ids=[5], prompt_tokens=1)
    JaxEngine._emit(eng, seq, out)          # on-thread: direct put
    assert q.qsize() == 1 and not calls
    eng._aio_loop_tid = threading.get_ident() + 1
    JaxEngine._emit(eng, seq, out)          # off-thread: via the loop
    assert q.qsize() == 1 and len(calls) == 1
    eng._aio_loop_tid = None
    JaxEngine._emit(eng, seq, out)          # pre-start: direct put
    assert q.qsize() == 2 and len(calls) == 1


def test_router_decision_overlap_consistent():
    """KvScheduler.schedule reads the chosen worker's capped overlap
    once: the decision record, the optimistic accounting, and the
    hit-rate event must all carry the SAME value."""
    from dynamo_tpu.llm.kv_router.indexer import OverlapScores
    from dynamo_tpu.llm.kv_router.protocols import ForwardPassMetrics
    from dynamo_tpu.llm.kv_router.scheduler import KvScheduler

    events = []
    sched = KvScheduler(block_size=16, on_hit_rate_event=events.append)
    sched.update_metrics({1: ForwardPassMetrics(
        request_active_slots=0, request_total_slots=8,
        kv_active_blocks=0, kv_total_blocks=100)})
    chosen = sched.schedule(64, OverlapScores({1: 2}), request_id="r1")
    assert chosen == 1
    dec = sched.decisions[-1]
    expect = min(2, (64 + 15) // 16)
    assert dec["overlap_blocks"] == expect
    assert events[-1].overlap_blocks == expect
    assert sched.workers[1].extra_blocks == (64 + 15) // 16 - expect
