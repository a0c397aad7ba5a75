"""Tensor-parallel serving: the Pallas decode kernel survives TP via
shard_map (VERDICT r2 weak #5 — r2 silently dropped the kernel whenever
mesh.size > 1), and the multi-host bootstrap is launchable end-to-end.

Reference parity: vLLM multi-node TP rode a Ray head/follower bootstrap
(lib/llm/src/engines/vllm/ray.rs); here every process runs the same
`dynamo-run` command with --coordinator/--num-processes/--process-id and
jax.distributed forms the global mesh (SURVEY §5 comm backend).
"""

import socket
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.models import llama
from dynamo_tpu.parallel.mesh import (MeshSpec, shard_batch, shard_kv_cache,
                                      shard_params)


def _window_args(cfg, params, kv_k, kv_v, B, P, E=4):
    table = np.zeros((B, P), np.int32)
    # distinct pages per row (page 0 reserved)
    for b in range(B):
        table[b] = np.arange(1 + b * P, 1 + (b + 1) * P)
    start = np.full(B, 9, np.int32)  # some pool context
    return dict(
        tokens=jnp.asarray(np.arange(1, B + 1, dtype=np.int32)),
        positions=jnp.asarray(start),
        done=jnp.zeros(B, bool),
        steps=jnp.zeros(B, jnp.int32),
        remaining=jnp.full(B, 100, jnp.int32),
        kv_k=kv_k, kv_v=kv_v,
        page_table=jnp.asarray(table),
        temperature=jnp.zeros(B),
        top_k=jnp.zeros(B, jnp.int32),
        top_p=jnp.ones(B),
        seeds=jnp.zeros(B, jnp.uint32),
        eos_table=jnp.full((B, E), -1, jnp.int32),
    )


def test_sharded_window_kernel_matches_unsharded():
    """Fused decode window with the kernel shard_map'd over (data, model)
    axes == the unsharded XLA window, token-for-token (greedy)."""
    cfg = ModelConfig.tiny(num_heads=4, num_kv_heads=2, head_dim=64,
                           hidden_size=64, vocab_size=256)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    spec = llama.KVCacheSpec(num_pages=64, page_size=4)
    B, P, K = 4, 4, 3

    # seed the pool with real prefill content so attention has context
    def prefill(kv_k, kv_v):
        pre, _ = llama.make_step_fns(cfg, allow_pallas=False)
        T = 12
        toks = jnp.asarray(np.tile(np.arange(2, T + 2, dtype=np.int32)[None],
                                   (B, 1)))
        pos = jnp.tile(jnp.arange(T, dtype=jnp.int32)[None], (B, 1))
        table = np.zeros((B, P), np.int32)
        for b in range(B):
            table[b] = np.arange(1 + b * P, 1 + (b + 1) * P)
        slots = np.zeros((B, T), np.int32)
        for b in range(B):
            posn = np.arange(T)
            slots[b] = table[b][posn // 4] * 4 + posn % 4
        lg, kv_k, kv_v = pre(params, toks, pos, kv_k, kv_v,
                             jnp.asarray(table), jnp.asarray(slots),
                             jnp.full(B, T - 1, jnp.int32))
        return kv_k, kv_v

    # unsharded XLA reference
    kv_k, kv_v = llama.init_kv_cache(cfg, spec)
    kv_k, kv_v = prefill(kv_k, kv_v)
    ref_fn = llama.make_decode_window_fn(cfg, allow_pallas=False)
    a = _window_args(cfg, params, kv_k, kv_v, B, P)
    ref_toks, _, ref_carry, _, _ = ref_fn(
        params, a["tokens"], a["positions"], a["done"], a["steps"],
        a["remaining"], a["kv_k"], a["kv_v"], a["page_table"],
        a["temperature"], a["top_k"], a["top_p"], a["seeds"],
        a["eos_table"], k_steps=K)

    # sharded kernel path (interpret mode) on a data=2 x model=2 mesh
    mesh = MeshSpec(data=2, model=2).build()
    kv_k2, kv_v2 = llama.init_kv_cache(cfg, spec)
    kv_k2, kv_v2 = prefill(kv_k2, kv_v2)
    kv_k2, kv_v2 = shard_kv_cache(kv_k2, kv_v2, cfg, mesh)
    sp = shard_params(params, cfg, mesh)
    tp_fn = llama.make_decode_window_fn(cfg, allow_pallas=True, mesh=mesh,
                                        pallas_interpret=True)
    a = _window_args(cfg, sp, kv_k2, kv_v2, B, P)
    sb = shard_batch(mesh, tokens=a["tokens"], positions=a["positions"],
                     page_table=a["page_table"])
    got_toks, _, got_carry, _, _ = tp_fn(
        sp, sb["tokens"], sb["positions"], a["done"], a["steps"],
        a["remaining"], kv_k2, kv_v2, sb["page_table"],
        a["temperature"], a["top_k"], a["top_p"], a["seeds"],
        a["eos_table"], k_steps=K)

    np.testing.assert_array_equal(np.asarray(got_toks), np.asarray(ref_toks))
    np.testing.assert_array_equal(np.asarray(got_carry[1]),
                                  np.asarray(ref_carry[1]))  # positions


MULTIHOST_WORKER = textwrap.dedent("""
    import os, sys
    import jax
    jax.config.update("jax_platforms", "cpu")
    from dynamo_tpu.parallel.mesh import initialize_multihost
    coord, pid = sys.argv[1], int(sys.argv[2])
    initialize_multihost(coord, 2, pid)
    assert jax.process_count() == 2, jax.process_count()
    assert len(jax.devices()) == 2, jax.devices()
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    import numpy as np
    mesh = Mesh(np.asarray(jax.devices()).reshape(2), ("model",))
    x = jax.make_array_from_callback(
        (2,), NamedSharding(mesh, P("model")),
        lambda idx: np.ones((1,), np.float32))
    y = jax.jit(lambda a: jnp.sum(a), out_shardings=NamedSharding(mesh, P()))(x)
    assert float(y) == 2.0, float(y)
    print("MULTIHOST_OK", pid, flush=True)
""")


def test_multihost_two_process_smoke(tmp_path):
    """Two real processes join via initialize_multihost (the Ray-bootstrap
    replacement) and run a jitted collective over the global 2-device CPU
    mesh. Environment assembly rides the shared forced-device-count
    harness (tests/device_harness.py): devices=1 strips XLA_FLAGS so each
    process contributes exactly one CPU device."""
    from device_harness import forced_device_env

    script = tmp_path / "worker.py"
    script.write_text(MULTIHOST_WORKER)
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    coord = f"127.0.0.1:{port}"
    env = forced_device_env(devices=1)
    procs = [subprocess.Popen([sys.executable, str(script), coord, str(i)],
                              env=env, stdout=subprocess.PIPE,
                              stderr=subprocess.STDOUT, text=True)
             for i in range(2)]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=100)
            outs.append(out)
    finally:
        for p in procs:
            p.kill()
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"proc {i} failed:\n{out}"
        assert f"MULTIHOST_OK {i}" in out


def test_long_prompt_takes_ring_path(run_async):
    """Serving wire-up of the sequence-parallel prefill (VERDICT r2 item
    5): a prompt above long_prefill_threshold is prefetched through
    make_long_prefill_fn on the seq-axis mesh — and the continuation is
    token-identical to the ordinary chunked-prefill engine."""
    import asyncio

    from dynamo_tpu.engine.jax_engine import EngineConfig, JaxEngine
    from dynamo_tpu.llm.protocols.common import (PreprocessedRequest,
                                                 SamplingOptions,
                                                 StopConditions)
    from dynamo_tpu.runtime.engine import Context

    cfg = ModelConfig.tiny(num_heads=4, num_kv_heads=2, head_dim=8,
                           hidden_size=32, vocab_size=256)
    params = llama.init_params(cfg, jax.random.PRNGKey(1))
    prompt = [(i * 13) % 200 + 1 for i in range(40)]

    async def gen(engine):
        req = PreprocessedRequest(
            token_ids=list(prompt), sampling=SamplingOptions(),
            stop=StopConditions(max_tokens=6, ignore_eos=True),
            eos_token_ids=[])
        toks = []
        async for out in engine.generate(req, Context()):
            toks.extend(out.token_ids)
            if out.finish_reason:
                break
        await engine.stop()
        return toks

    base_ecfg = dict(page_size=4, num_pages=64, max_batch=4,
                     prefill_chunk=32, prefill_buckets=(32,),
                     batch_buckets=(4,), page_buckets=(16,))
    want = run_async(gen(JaxEngine(cfg, EngineConfig(**base_ecfg),
                                   params=params)))

    mesh = MeshSpec(seq=4).build()
    engine = JaxEngine(cfg, EngineConfig(long_prefill_threshold=16,
                                         **base_ecfg),
                       params=params, mesh=mesh)
    got = run_async(gen(engine))
    assert engine.long_prefills_total == 1, "ring path not taken"
    assert engine.stats()["long_prefills_total"] == 1
    assert got == want
    # short prompts still take the chunked path
    engine2 = JaxEngine(cfg, EngineConfig(long_prefill_threshold=16,
                                          **base_ecfg),
                        params=params, mesh=mesh)

    async def gen_short(engine):
        req = PreprocessedRequest(
            token_ids=list(prompt[:10]), sampling=SamplingOptions(),
            stop=StopConditions(max_tokens=4, ignore_eos=True),
            eos_token_ids=[])
        toks = []
        async for out in engine.generate(req, Context()):
            toks.extend(out.token_ids)
            if out.finish_reason:
                break
        await engine.stop()
        return toks

    run_async(gen_short(engine2))
    assert engine2.long_prefills_total == 0


def _prefill_inputs(B, P, T, ps):
    """Shared prefill batch: distinct pages per row (page 0 reserved)."""
    toks = np.tile(np.arange(2, T + 2, dtype=np.int32)[None], (B, 1))
    pos = np.tile(np.arange(T, dtype=np.int32)[None], (B, 1))
    table = np.zeros((B, P), np.int32)
    slots = np.zeros((B, T), np.int32)
    for b in range(B):
        table[b] = np.arange(1 + b * P, 1 + (b + 1) * P)
        posn = np.arange(T)
        slots[b] = table[b][posn // ps] * ps + posn % ps
    return (jnp.asarray(toks), jnp.asarray(pos), jnp.asarray(table),
            jnp.asarray(slots), jnp.full(B, T - 1, jnp.int32))


def test_sharded_prefill_kernel_matches_unsharded(monkeypatch):
    """The prefill kernel under TP: prefill_step on a data=2 x model=2
    mesh routes through paged_attention_prefill_sharded (interpret mode)
    and its logits + KV pool writes match the unsharded XLA gather
    path."""
    monkeypatch.setenv("DYN_PALLAS_INTERPRET", "1")
    cfg = ModelConfig.tiny(num_heads=4, num_kv_heads=2, head_dim=64,
                           hidden_size=64, vocab_size=256)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    spec = llama.KVCacheSpec(num_pages=64, page_size=4)
    B, P, T = 4, 4, 12
    toks, pos, table, slots, last = _prefill_inputs(B, P, T, 4)

    kv_k, kv_v = llama.init_kv_cache(cfg, spec)
    pre_ref, _ = llama.make_step_fns(cfg, allow_pallas=False)
    lg_ref, kv_k_ref, kv_v_ref = pre_ref(params, toks, pos, kv_k, kv_v,
                                         table, slots, last)

    mesh = MeshSpec(data=2, model=2).build()
    sp = shard_params(params, cfg, mesh)
    kv_k2, kv_v2 = shard_kv_cache(*llama.init_kv_cache(cfg, spec), cfg, mesh)
    pre_tp, _ = llama.make_step_fns(cfg, mesh=mesh)
    sb = shard_batch(mesh, tokens=toks, positions=pos, page_table=table,
                     flat_slots=slots, last_idx=last)
    lg_tp, kv_k_tp, kv_v_tp = pre_tp(sp, sb["tokens"], sb["positions"],
                                     kv_k2, kv_v2, sb["page_table"],
                                     sb["flat_slots"], sb["last_idx"])

    np.testing.assert_allclose(np.asarray(lg_tp), np.asarray(lg_ref),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(kv_k_tp), np.asarray(kv_k_ref),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(kv_v_tp), np.asarray(kv_v_ref),
                               rtol=2e-5, atol=2e-5)


def test_sharded_k1_decode_kernel_matches_unsharded(monkeypatch):
    """K=1 decode kernel under TP (VERDICT r3 task 5): decode_step on a
    data=2 x model=2 mesh routes through paged_attention_decode_sharded
    (interpret mode) and matches the unsharded XLA path."""
    monkeypatch.setenv("DYN_PALLAS_INTERPRET", "1")
    cfg = ModelConfig.tiny(num_heads=4, num_kv_heads=2, head_dim=64,
                           hidden_size=64, vocab_size=256)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    spec = llama.KVCacheSpec(num_pages=64, page_size=4)
    B, P, T = 4, 4, 12
    toks, pos, table, slots, last = _prefill_inputs(B, P, T, 4)

    def seed(kv):
        pre, _ = llama.make_step_fns(cfg, allow_pallas=False)
        _, k, v = pre(params, toks, pos, *kv, table, slots, last)
        return k, v

    d_toks = jnp.asarray(np.arange(5, 5 + B, dtype=np.int32))
    d_pos = jnp.full(B, T, jnp.int32)
    d_slots = jnp.asarray(np.asarray(table)[:, T // 4] * 4 + T % 4,
                          jnp.int32)

    kv_ref = seed(llama.init_kv_cache(cfg, spec))
    _, dec_ref = llama.make_step_fns(cfg, allow_pallas=False)
    lg_ref, _, _ = dec_ref(params, d_toks, d_pos, *kv_ref, table, d_slots)

    mesh = MeshSpec(data=2, model=2).build()
    sp = shard_params(params, cfg, mesh)
    kv_tp = shard_kv_cache(*seed(llama.init_kv_cache(cfg, spec)), cfg, mesh)
    _, dec_tp = llama.make_step_fns(cfg, mesh=mesh)
    sb = shard_batch(mesh, tokens=d_toks, positions=d_pos, page_table=table,
                     flat_slots=d_slots)
    lg_tp, _, _ = dec_tp(sp, sb["tokens"], sb["positions"], *kv_tp,
                         sb["page_table"], sb["flat_slots"])

    np.testing.assert_allclose(np.asarray(lg_tp), np.asarray(lg_ref),
                               rtol=2e-5, atol=2e-5)


def test_sharded_window_kernel_gemma2_matches_xla(monkeypatch):
    """The sharded pool+window kernel path with Gemma-2 semantics (score
    softcap + sliding window with its per-row lower bound crossing shard_map
    as a new operand) is token-identical to the unsharded XLA window."""
    monkeypatch.setenv("DYN_PALLAS_INTERPRET", "1")
    cfg = ModelConfig.tiny(num_heads=4, num_kv_heads=2, head_dim=64,
                           hidden_size=64, vocab_size=256,
                           model_type="gemma2", sandwich_norms=True,
                           attn_logit_softcap=20.0, sliding_window=6,
                           query_pre_attn_scalar=64)
    params = llama.init_params(cfg, jax.random.PRNGKey(2))
    spec = llama.KVCacheSpec(num_pages=64, page_size=4)
    B, P, K = 4, 4, 3

    def prefill(kv_k, kv_v):
        pre, _ = llama.make_step_fns(cfg, allow_pallas=False)
        T = 12
        toks = jnp.asarray(np.tile(np.arange(2, T + 2, dtype=np.int32)[None],
                                   (B, 1)))
        pos = jnp.tile(jnp.arange(T, dtype=jnp.int32)[None], (B, 1))
        table = np.zeros((B, P), np.int32)
        for b in range(B):
            table[b] = np.arange(1 + b * P, 1 + (b + 1) * P)
        slots = np.zeros((B, T), np.int32)
        for b in range(B):
            posn = np.arange(T)
            slots[b] = table[b][posn // 4] * 4 + posn % 4
        _, kv_k, kv_v = pre(params, toks, pos, kv_k, kv_v,
                            jnp.asarray(table), jnp.asarray(slots),
                            jnp.full(B, T - 1, jnp.int32))
        return kv_k, kv_v

    monkeypatch.setenv("DYN_DISABLE_PALLAS", "1")  # XLA reference window
    kv_k, kv_v = prefill(*llama.init_kv_cache(cfg, spec))
    ref_fn = llama.make_decode_window_fn(cfg, allow_pallas=False)
    a = _window_args(cfg, params, kv_k, kv_v, B, P)
    ref_toks, _, ref_carry, _, _ = ref_fn(
        params, a["tokens"], a["positions"], a["done"], a["steps"],
        a["remaining"], a["kv_k"], a["kv_v"], a["page_table"],
        a["temperature"], a["top_k"], a["top_p"], a["seeds"],
        a["eos_table"], k_steps=K)
    monkeypatch.delenv("DYN_DISABLE_PALLAS")

    mesh = MeshSpec(data=2, model=2).build()
    kv_k2, kv_v2 = prefill(*llama.init_kv_cache(cfg, spec))
    kv_k2, kv_v2 = shard_kv_cache(kv_k2, kv_v2, cfg, mesh)
    sp = shard_params(params, cfg, mesh)
    tp_fn = llama.make_decode_window_fn(cfg, allow_pallas=True, mesh=mesh,
                                        pallas_interpret=True)
    a = _window_args(cfg, sp, kv_k2, kv_v2, B, P)
    sb = shard_batch(mesh, tokens=a["tokens"], positions=a["positions"],
                     page_table=a["page_table"])
    got_toks, _, got_carry, _, _ = tp_fn(
        sp, sb["tokens"], sb["positions"], a["done"], a["steps"],
        a["remaining"], kv_k2, kv_v2, sb["page_table"],
        a["temperature"], a["top_k"], a["top_p"], a["seeds"],
        a["eos_table"], k_steps=K)

    np.testing.assert_array_equal(np.asarray(got_toks), np.asarray(ref_toks))
    np.testing.assert_array_equal(np.asarray(got_carry[1]),
                                  np.asarray(ref_carry[1]))
