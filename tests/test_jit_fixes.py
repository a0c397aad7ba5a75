"""Regression tests for the true positives dynajit (DL015-DL017) found
in the engine — each was FIXED, not baselined (tools/dynalint gate), and
each fix is pinned here:

- the host-tier dtype probe resolved the pool dtype through a device
  round-trip (``np.asarray(jnp.zeros((), dtype))``) — DL017;
- ``extract_pages`` / ``inject_pages`` / ``extract_pages_chunked``
  gathered/scattered with request-length page index arrays — one XLA
  compile per distinct page count, mid-serving, on the disagg path —
  DL015. Now pow2-padded (extract trims host-side; inject pads the
  rows and drops the out-of-range scatter targets), so the compiled
  program set is O(log n) and warmable.
"""

import asyncio

import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.jax_engine import EngineConfig, JaxEngine
from dynamo_tpu.engine.jit_fence import CompileFence
from dynamo_tpu.models.config import ModelConfig


def mk_engine(**eng_kw):
    cfg = ModelConfig.tiny()
    defaults = dict(page_size=8, num_pages=32, max_batch=4,
                    prefill_chunk=32, decode_steps=1)
    defaults.update(eng_kw)
    return JaxEngine(cfg, EngineConfig(**defaults), seed=0)


# --------------------------------------------------- host-tier dtype probe


def test_host_pool_dtype_without_device_roundtrip():
    """On the lossless tier (``host_tier_int8=False``) the host pools
    must match the device pool dtype (incl. bf16), resolved WITHOUT a
    device round-trip (jax_engine DL017 fix). With the dynaheat
    int8-default tier the host pools are int8 by design."""
    eng = mk_engine(host_pages=8, num_pages=16, host_tier_int8=False)
    assert eng.host_k is not None
    assert eng.host_k.dtype == np.dtype(eng.kv_k.dtype)
    assert eng.host_v.dtype == np.dtype(eng.kv_v.dtype)
    eng_bf16 = JaxEngine(ModelConfig.tiny(),
                         EngineConfig(page_size=8, num_pages=16,
                                      host_pages=8, host_tier_int8=False),
                         seed=0, dtype=jnp.bfloat16)
    assert eng_bf16.host_k.dtype == np.dtype(jnp.bfloat16)
    # int8 tier default-on: host pools hold quantized pages regardless
    # of the device dtype (halved host-link bytes; identity pinned in
    # tests/test_kv_offload.py)
    eng_i8 = mk_engine(host_pages=8, num_pages=16)
    assert eng_i8.ecfg.host_tier_int8 is True
    assert eng_i8.host_k.dtype == np.dtype(np.int8)


# ------------------------------------------------ pow2-padded extract/inject


def _rand_pages(eng, n, seed=0):
    rng = np.random.RandomState(seed)
    k = rng.randn(*(eng.kv_k.shape[0], n, *eng.kv_k.shape[2:])) \
        .astype(np.float32)
    v = rng.randn(*(eng.kv_v.shape[0], n, *eng.kv_v.shape[2:])) \
        .astype(np.float32)
    return k, v


def test_extract_inject_roundtrip_identity(run_async):
    """Padded inject → padded extract round-trips content exactly, and
    neither touches pages outside the given ids."""
    eng = mk_engine()

    async def main():
        pages = [3, 7, 11, 2, 9]                     # 5 → pads to 8
        k, v = _rand_pages(eng, len(pages), seed=1)
        before = np.asarray(eng.kv_k)
        await eng.inject_pages(pages, k, v)
        got_k, got_v = await eng.extract_pages(pages)
        np.testing.assert_array_equal(got_k, k)
        np.testing.assert_array_equal(got_v, v)
        # untouched pages keep their content (the pad scatter dropped)
        after = np.asarray(eng.kv_k)
        others = [p for p in range(eng.ecfg.num_pages)
                  if p not in pages]
        np.testing.assert_array_equal(after[:, others], before[:, others])
        await eng.stop()

    run_async(main())


def test_extract_inject_compile_count_is_pow2_bounded(run_async):
    """Distinct page counts within one pow2 bucket share ONE compiled
    gather/scatter program (the DL015 fix): after the first 5-page
    extract+inject compiles the size-8 programs, 6- and 7-page calls
    compile NOTHING new."""
    eng = mk_engine()
    fence = CompileFence("extract-regression", mode="")

    async def main():
        k, v = _rand_pages(eng, 5, seed=2)
        await eng.inject_pages([1, 2, 3, 4, 5], k, v)
        await eng.extract_pages([1, 2, 3, 4, 5])     # compiles size-8
        fence.arm()
        for ids in ([6, 7, 8, 9, 10, 11], [1, 3, 5, 7, 9, 11, 13]):
            ki, vi = _rand_pages(eng, len(ids), seed=len(ids))
            await eng.inject_pages(ids, ki, vi)
            got_k, got_v = await eng.extract_pages(ids)
            np.testing.assert_array_equal(got_k, ki)
            np.testing.assert_array_equal(got_v, vi)
        assert fence.post_warmup_compiles == 0, (
            "a same-bucket page count recompiled the gather/scatter")
        fence.disarm()
        await eng.stop()

    run_async(main())


def test_extract_chunked_pads_final_slice(run_async):
    """The chunked extract's remainder slice is padded to chunk_pages:
    content identity holds and the remainder compiles no fresh gather
    once the full-chunk program exists."""
    eng = mk_engine()
    fence = CompileFence("chunked-regression", mode="")

    async def main():
        pages = [2, 4, 6, 8, 10, 12]                 # 6 pages, chunks of 4
        k, v = _rand_pages(eng, len(pages), seed=3)
        await eng.inject_pages(pages, k, v)
        parts = []
        first = True
        async for off, kc, vc, _dt in eng.extract_pages_chunked(pages, 4):
            if first:
                # the size-4 gather program now exists; the padded
                # 2-page remainder must reuse it
                fence.arm()
                first = False
            parts.append((off, kc, vc))
        assert fence.post_warmup_compiles == 0, (
            "the remainder slice compiled its own gather")
        fence.disarm()
        got_k = np.concatenate([kc for _, kc, _ in parts], axis=1)
        got_v = np.concatenate([vc for _, _, vc in parts], axis=1)
        assert [off for off, _, _ in parts] == [0, 4]
        np.testing.assert_array_equal(got_k, k)
        np.testing.assert_array_equal(got_v, v)
        await eng.stop()

    run_async(main())


def test_extract_single_page_and_full_pool(run_async):
    """Pow2 padding edge cases: 1 page (no pad) and a count already at a
    pow2 boundary (no pad) stay exact."""
    eng = mk_engine()

    async def main():
        for ids in ([5], [1, 2, 3, 4]):
            k, v = _rand_pages(eng, len(ids), seed=len(ids) + 10)
            await eng.inject_pages(ids, k, v)
            got_k, got_v = await eng.extract_pages(ids)
            np.testing.assert_array_equal(got_k, k)
            np.testing.assert_array_equal(got_v, v)
        await eng.stop()

    run_async(main())
