"""Runtime compile fence (engine/jit_fence.py) + bucket-grid coverage.

The engine's zero-compile serving invariant has two enforcement layers:
dynajit (static, tests/test_lint.py) and the runtime fence tested here —
armed by ``warmup()``, it counts every post-warmup XLA compile via JAX's
monitoring hook. The e2e test drives a mixed prefill/decode/spec
workload through a warmed CPU engine and pins the counter at ZERO: this
is the regression gate for the ROADMAP item-3 hot-path refactor (any
change that lets an unbucketed shape or a mismatched call form reach a
jitted entry fails here, not on a chip). It guards, among others, the
two warmup bugs the fence found when first armed: explicit-vs-defaulted
``penalties=None`` / ``logprobs_topn=0`` kwargs keying different jit
cache entries than the warmed forms.
"""

import asyncio
import logging

import jax
import jax.numpy as jnp
import pytest

from dynamo_tpu.engine.jax_engine import EngineConfig, JaxEngine
from dynamo_tpu.engine.jit_fence import (COMPILE_EVENT, CompileFence,
                                         PostWarmupCompileError)
from dynamo_tpu.llm.protocols.common import (PreprocessedRequest,
                                             SamplingOptions,
                                             StopConditions)
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.runtime import Context

# ------------------------------------------------------------- fence unit


def _fresh_jit_compile(salt: int):
    """Force a real backend compile (a never-seen-before jaxpr/shape)."""
    f = jax.jit(lambda x: x * 2 + salt)
    f(jnp.zeros((salt % 7 + 1,)))


def test_fence_counts_only_when_armed():
    fence = CompileFence("t1", mode="")
    _fresh_jit_compile(101)          # before arm: not counted
    assert fence.post_warmup_compiles == 0
    fence.arm()
    _fresh_jit_compile(102)
    assert fence.post_warmup_compiles >= 1
    n = fence.post_warmup_compiles
    fence.disarm()
    _fresh_jit_compile(103)
    assert fence.post_warmup_compiles == n


def test_fence_warn_mode_logs(caplog):
    fence = CompileFence("t2", mode="warn")
    fence.arm()
    with caplog.at_level(logging.WARNING, "dynamo_tpu.engine.fence"):
        _fresh_jit_compile(104)
    fence.disarm()
    assert any("XLA compile after warmup" in r.message
               for r in caplog.records)


def test_fence_messages_name_last_dispatched_form(caplog):
    """A tripped fence names the offending call form — jit name plus
    per-operand dtype[shape] and static kwarg values — from the note the
    engine's dispatch wrapper stamps via note_dispatch (raw refs on the
    hot path, rendered only here on the trip path)."""
    fence = CompileFence("t2b", mode="warn")
    assert fence.last_dispatch_form() == "<no dispatch recorded>"
    fence.note_dispatch("decode_multi_fn",
                        (jnp.zeros((2, 8), jnp.bfloat16), 3),
                        {"k_steps": 2, "logprobs_topn": 20})
    form = fence.last_dispatch_form()
    assert form.startswith("decode_multi_fn(")
    assert "bfloat16[2,8]" in form
    assert "logprobs_topn=20" in form
    fence.arm()
    with caplog.at_level(logging.WARNING, "dynamo_tpu.engine.fence"):
        _fresh_jit_compile(107)
    fence.disarm()
    assert any("last dispatched form" in r.getMessage()
               and "decode_multi_fn(" in r.getMessage()
               for r in caplog.records)


def test_fence_raise_mode_names_form():
    fence = CompileFence("t3b", mode="raise")
    fence.note_dispatch("prefill_fn",
                        (jnp.zeros((4,), jnp.int32),), None)
    fence.arm()
    try:
        with pytest.raises(PostWarmupCompileError,
                           match=r"prefill_fn\(int32\[4\]\)"):
            _fresh_jit_compile(108)
    finally:
        fence.disarm()


def test_fence_raise_mode():
    fence = CompileFence("t3", mode="raise")
    fence.arm()
    try:
        with pytest.raises(PostWarmupCompileError):
            _fresh_jit_compile(105)
    finally:
        fence.disarm()


def test_fence_mode_reads_env(monkeypatch):
    fence = CompileFence("t4")
    assert fence.mode == ""
    monkeypatch.setenv("DYN_JIT_FENCE", "warn")
    assert fence.mode == "warn"


def test_fence_records_timeline_event():
    from dynamo_tpu.runtime.tracing import StepTimeline

    tl = StepTimeline(16)
    fence = CompileFence("t5", timeline=tl, mode="")
    fence.arm()
    _fresh_jit_compile(106)
    fence.disarm()
    kinds = [e["kind"] for e in tl.snapshot()]
    assert "compile" in kinds


# --------------------------------------------------- bucket-grid coverage


@pytest.mark.parametrize("ecfg", [
    EngineConfig(),                                        # the default
    EngineConfig(page_size=8, num_pages=64, max_batch=8,   # max_batch not
                 prefill_chunk=32, batch_buckets=(1, 2, 4),  # in buckets
                 prefill_buckets=(16,), page_buckets=(8,)),
    EngineConfig(page_size=8, num_pages=128, max_batch=6,  # chunk beyond
                 prefill_chunk=64, batch_buckets=(1, 2),   # last bucket,
                 prefill_buckets=(8,), page_buckets=(4, 16)),  # via 2x
])
def test_bucket_grid_covers_every_reachable_shape(ecfg):
    """Every shape the bucket helpers can produce for an admissible
    request must be in warmed_grid() — _pick doubles past its last
    bucket, so the declared tuples alone under-cover exotic configs
    (serving would compile mid-flight; the old warmup did exactly
    that for these configs)."""
    grid = ecfg.warmed_grid()
    cap_pages = min(ecfg.page_buckets[-1], max(ecfg.num_pages - 1, 1))
    for n in range(1, ecfg.prefill_chunk + 1):
        assert ecfg.bucket_len(n) in grid["prefill_lens"]
    for n in range(1, ecfg.max_batch + 1):
        assert ecfg.bucket_batch(n) in grid["decode_batches"]
        assert ecfg.prefill_bucket_batch(n) in grid["prefill_batches"]
    for n in range(1, cap_pages + 1):
        assert ecfg.bucket_pages(n) in grid["page_buckets"]


def test_default_grid_matches_declared_buckets():
    """On the DEFAULT config the exact image equals the declared tuples,
    so the warmed-grid rework changed no default warmup program set."""
    ecfg = EngineConfig()
    grid = ecfg.warmed_grid()
    assert grid["prefill_lens"] == sorted(ecfg.prefill_buckets)
    assert grid["decode_batches"] == sorted(ecfg.batch_buckets)
    assert grid["page_buckets"] == sorted(ecfg.page_buckets)


# ------------------------------------------------------------- fence e2e


def _req(tokens, mt=6, **sampling):
    return PreprocessedRequest(
        token_ids=list(tokens), sampling=SamplingOptions(**sampling),
        stop=StopConditions(max_tokens=mt, ignore_eos=True),
        eos_token_ids=[])


def test_fence_zero_compiles_mixed_workload(caplog):
    """The tier-1 zero-compile gate: warm a tiny CPU engine (spec decode
    on, fused pipelined windows), then drive a mixed prefill/decode/spec
    workload — spec-friendly greedy prompts, a sampled row (window
    fallback arm), prompt lengths crossing both prefill buckets,
    concurrent admission — and assert NOT ONE XLA compile happened
    after warmup. Then an intentionally unbucketed jit call trips the
    fence in warn mode."""
    cfg = ModelConfig.tiny()
    ecfg = EngineConfig(page_size=8, num_pages=64, max_batch=4,
                        prefill_chunk=32, batch_buckets=(1, 2, 4),
                        prefill_buckets=(16, 32), page_buckets=(8,),
                        max_prefill_batch=2, decode_steps=2,
                        spec_decode=True, spec_tokens=2)
    eng = JaxEngine(cfg, ecfg, seed=0)
    eng.warmup()
    assert eng.fence.armed

    async def one(r):
        toks = []
        async for out in eng.generate(r, Context()):
            toks.extend(out.token_ids)
            if out.finish_reason is not None:
                assert out.finish_reason != "error"
        return toks

    async def main():
        reqs = [_req([5, 6, 7, 5, 6, 7, 5, 6] * 2),     # spec-friendly
                _req(list(range(1, 20))),               # 19 tok prompt
                _req([9, 9, 9, 9, 9, 9, 9, 9] * 3),     # spec-friendly
                _req(list(range(30, 41)),
                     temperature=0.9, seed=7),          # sampled fallback
                _req(list(range(50, 55)), mt=4)]        # short row
        out = await asyncio.gather(*(one(r) for r in reqs))
        served = eng.fence.post_warmup_compiles
        # an intentionally unbucketed call trips warn mode — while the
        # engine is live: stop() disarms the fence (a stopped engine's
        # fence must not count the next engine's warm-up)
        eng.fence._mode_override = "warn"
        with caplog.at_level(logging.WARNING, "dynamo_tpu.engine.fence"):
            jax.jit(lambda x: x - 3)(jnp.zeros((11,)))
        await eng.stop()
        return out, served

    results, served = asyncio.run(main())
    assert all(len(r) >= 4 for r in results)
    assert served == 0, (
        "the zero-compile serving invariant broke: a jitted engine entry "
        "compiled mid-serving (run with jax_log_compiles to locate it)")
    # the engine's dispatch wrapper stamped real step-fn call forms, so
    # any trip above would have named the offending form
    assert eng.fence.last_dispatch_form().split("(")[0] in {
        "prefill_fn", "decode_fn", "decode_multi_fn", "verify_fn",
        "long_prefill_fn"}
    assert eng.fence.post_warmup_compiles >= 1
    assert eng.stats()["post_warmup_compiles_total"] >= 1
    assert not eng.fence.armed, "stop() disarms the fence"
    before = eng.fence.post_warmup_compiles
    jax.jit(lambda x: x - 5)(jnp.zeros((13,)))
    assert eng.fence.post_warmup_compiles == before
    assert any("XLA compile after warmup" in r.message
               for r in caplog.records)


def test_warmup_covers_host_tier_programs():
    """With the host tier enabled, warmup compiles the pow2 offload
    gather / restore scatter programs, so the first eviction under load
    never compiles (the dynajit warmup-coverage rule pins the entries;
    this pins the shapes)."""
    cfg = ModelConfig.tiny()
    ecfg = EngineConfig(page_size=8, num_pages=16, max_batch=2,
                        prefill_chunk=16, batch_buckets=(1, 2),
                        prefill_buckets=(16,), page_buckets=(4,),
                        decode_steps=1, host_pages=8)
    eng = JaxEngine(cfg, ecfg, seed=0)
    eng.warmup(decode=False)
    # replay the tier drain's gather/scatter at several distinct batch
    # sizes: each pads to a pow2 the warmup loop already compiled, so
    # the fence stays quiet
    for size in (1, 2, 3, 5):
        idx = jnp.zeros(
            _next_pow2(size), jnp.int32)
        from dynamo_tpu.engine.jax_engine import (_gather_pages,
                                                  _inject_pages)

        g = _gather_pages(eng.kv_k, idx)
        eng.kv_k = _inject_pages(
            eng.kv_k, jnp.full((_next_pow2(size),), ecfg.num_pages,
                               jnp.int32), g)
    assert eng.fence.post_warmup_compiles == 0
    eng.fence.disarm()


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p
