"""Compile the main path's kernels and step programs for a DESCRIBED
v5e, at the widths the chip serves. Nothing runs: the TPU compiler is
installed here and refuses what the attached chip would refuse (a block
shape the tiling cannot hold, too much VMEM, a program past HBM), which
interpret-mode tests cannot see — a prefill kernel passed every
interpret test while no width of it compiled (PR 21).

The topology is described inside a module-scoped fixture (never at
import, never autouse): only the xdist worker that is handed this file
loads libtpu, and every worker collects the same tests. The persistent
compile cache is off around these compiles — an entry written for a
described device cannot be read back without one.
"""

import os
import re
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import (NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from dynamo_tpu.models import llama
from dynamo_tpu.ops import paged_attention as pa

# the two GQA shapes the repo's presets serve (run.py --model 1b | 8b)
WIDTHS = {"1b": dict(H=32, KV=8, hd=64), "8b": dict(H=32, KV=8, hd=128)}
PS, NUM_PAGES, L = 64, 512, 16     # EngineConfig defaults, 1B depth
B_DEC, P_DEC = 32, 64              # decode batch bucket x page bucket
B_PRE, P_PRE = 8, 64               # max_prefill_batch x page bucket


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def tpu_kernel_path(monkeypatch):
    """The model code picks the kernel by asking jax.default_backend(),
    which sees the CPU here; steer it from the test (not a new option)."""
    monkeypatch.delenv("DYN_DISABLE_PALLAS", raising=False)
    monkeypatch.delenv("DYN_PALLAS_INTERPRET", raising=False)
    monkeypatch.setattr(llama, "_use_pallas", lambda: True)


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _on(sharding, tree):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


# ------------------------------------------------------------ decode kernel


@pytest.mark.parametrize("softcap", [None, 50.0], ids=["nocap", "softcap"])
@pytest.mark.parametrize("lower", [False, True], ids=["full", "window"])
@pytest.mark.parametrize("stats", [False, True], ids=["out", "stats"])
@pytest.mark.parametrize("entry", ["plain", "layered"])
@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_decode_kernel_compiles(one_chip, width, entry, stats, lower,
                                softcap):
    w = WIDTHS[width]
    s = partial(_sds, one_chip)
    q = s((B_DEC, w["H"], w["hd"]), jnp.bfloat16)
    pool = (NUM_PAGES, w["KV"], PS, w["hd"])
    table = s((B_DEC, P_DEC), jnp.int32)
    lengths = s((B_DEC,), jnp.int32)
    lo = lengths if lower else None
    if entry == "plain":
        k = s(pool, jnp.bfloat16)
        lowered = pa.paged_attention_decode.lower(
            q, k, k, table, lengths, return_stats=stats, softcap=softcap,
            lower=lo)
    else:
        k = s((L,) + pool, jnp.bfloat16)
        lowered = pa.paged_attention_decode_layered.lower(
            q, k, k, s((), jnp.int32), table, lengths, return_stats=stats,
            softcap=softcap, lower=lo)
    assert _has_kernel(lowered.compile())


@pytest.mark.parametrize("B,P,KV,group,ps", [
    (32, 64, 8, 4, 64),      # mixtral-8x7b.chat-steady / .shared-prefix
    (64, 32, 4, 8, 64),      # qwen3-30b-a3b.decode-heavy
    (128, 32, 1, 20, 64),    # jamba2-3b.reason-decode
    (64, 16, 4, 8, 128),     # pages of 128 (cell 5's page size)
    (1, 64, 8, 4, 64),       # one row: the K = 1 decode step
    (8, 1, 8, 4, 64),        # a one-page table
], ids=["mixtral", "qwen3", "jamba", "ps128", "b1", "p1"])
def test_decode_kernel_compiles_at_cell_shapes(one_chip, B, P, KV, group,
                                               ps):
    """The benchmark's decode shapes at the pages a chunk the rule by
    shape gives them: a chunk's buffers and its float32 upcasts have to
    fit the chip's scoped VMEM at every one."""
    s = partial(_sds, one_chip)
    k = s((3, 768, KV, ps, 128), jnp.bfloat16)
    lengths = s((B,), jnp.int32)
    lowered = pa.paged_attention_decode_layered.lower(
        s((B, KV * group, 128), jnp.bfloat16), k, k, s((), jnp.int32),
        s((B, P), jnp.int32), lengths, return_stats=True, lower=lengths)
    assert _has_kernel(lowered.compile())


# ----------------------------------------------------------- prefill kernel


@pytest.mark.parametrize("softcap,window", [(None, False), (50.0, True)],
                         ids=["plain", "softcap-window"])
@pytest.mark.parametrize("B,T,P,KV,group", [
    (8, 256, 32, 4, 8),      # qwen3-30b-a3b.decode-heavy
    (4, 512, 64, 8, 4),      # mixtral-8x7b.chat-steady / .shared-prefix
    (8, 512, 32, 1, 20),     # jamba2-3b.reason-decode
    (4, 512, 64, 4, 8),      # lfm2-24b-a2b.agent-loop: heads packed 2 a row
], ids=["qwen3", "mixtral", "jamba", "lfm2-packed"])
def test_prefill_kernel_compiles(one_chip, B, T, P, KV, group, softcap,
                                 window):
    """The paged prefill kernel at the benchmark's prefill shapes (the
    cells' largest batch and length buckets, heads of 128 lanes), at the
    block and chunk its rule by shape gives them: a block's buffers have
    to fit the VMEM limit the call asks for."""
    s = partial(_sds, one_chip)
    q = s((B, T, KV * group, 128), jnp.bfloat16)
    k = s((NUM_PAGES, KV, PS, 128), jnp.bfloat16)
    lowered = pa.paged_attention_prefill.lower(
        q, k, k, s((B, P), jnp.int32), s((B, T), jnp.int32),
        softcap=softcap, eff_win=s((B,), jnp.int32) if window else None)
    assert _has_kernel(lowered.compile())


def test_sharded_kernels_compile_on_four_chips(topo):
    """The tensor-parallel wrappers (shard_map over the head axis) on the
    described 2x2: what `chip_smoke.py --chips 4` runs under model=4."""
    from dynamo_tpu.parallel.mesh import MeshSpec

    w = WIDTHS["1b"]
    mesh = MeshSpec(model=4).build(list(topo.devices))

    def s(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    q = s((B_DEC, w["H"], w["hd"]), jnp.bfloat16, P(None, "model", None))
    pools = s((L, NUM_PAGES, w["KV"], PS, w["hd"]), jnp.bfloat16,
              P(None, None, "model", None, None))
    table = s((B_DEC, P_DEC), jnp.int32, P())
    lengths = s((B_DEC,), jnp.int32, P())
    dec = jax.jit(partial(pa.paged_attention_decode_sharded, mesh=mesh)
                  ).lower(q, pools, pools, s((), jnp.int32, P()), table,
                          lengths).compile()
    assert _has_kernel(dec)
    T, hd = 128, WIDTHS["8b"]["hd"]   # the prefill kernel: 128 lanes only
    qp = s((B_PRE, T, w["H"], hd), jnp.bfloat16,
           P(None, None, "model", None))
    pool = s((NUM_PAGES, w["KV"], PS, hd), jnp.bfloat16,
             P(None, "model", None, None))
    pre = jax.jit(partial(pa.paged_attention_prefill_sharded, mesh=mesh)
                  ).lower(qp, pool, pool, s((B_PRE, P_PRE), jnp.int32, P()),
                          s((B_PRE, T), jnp.int32, P())).compile()
    assert _has_kernel(pre)


# ------------------------------------------- whole step programs, 1B preset


def _preset_1b():
    import types

    from dynamo_tpu.run import build_model_config

    return build_model_config(types.SimpleNamespace(model="1b",
                                                    model_path=None))


def _engine_shapes(cfg, sharding, num_pages=NUM_PAGES):
    """params + KV pools exactly as JaxEngine.__init__ builds them, as
    shapes: nothing can be put on a described device."""
    params = jax.eval_shape(
        lambda: llama.init_params(cfg, jax.random.PRNGKey(0)))
    kv_k, kv_v = jax.eval_shape(
        lambda: llama.init_kv_cache(cfg, llama.KVCacheSpec(num_pages, PS)))
    return (_on(sharding, params), _on(sharding, kv_k),
            _on(sharding, kv_v))


def _lower_window(fn, sharding, params, kv_k, kv_v, B, P, logprobs_topn=0):
    """The fused window lowered as the engine calls it (decode_steps 4)."""
    s = partial(_sds, sharding)
    i32, f32 = s((B,), jnp.int32), s((B,), jnp.float32)
    return fn.lower(
        params, i32, i32, s((B,), jnp.bool_), i32, i32, kv_k, kv_v,
        s((B, P), jnp.int32), f32, i32, f32, s((B,), jnp.uint32),
        s((B, 8), jnp.int32), None, k_steps=4, logprobs_topn=logprobs_topn)


@pytest.mark.parametrize("logprobs_topn", [0, 20], ids=["plain", "logprobs"])
def test_decode_window_program_compiles(one_chip, tpu_kernel_path,
                                        logprobs_topn):
    """One warmed bucket of the fused decode window as JaxEngine builds
    it (decode_steps=4, B=32, P=64): kernel inside, fits the chip."""
    cfg = _preset_1b()
    params, kv_k, kv_v = _engine_shapes(cfg, one_chip)
    fn = llama.make_decode_window_fn(cfg, True, 64)
    compiled = _lower_window(fn, one_chip, params, kv_k, kv_v, B_DEC, P_DEC,
                             logprobs_topn).compile()
    assert _has_kernel(compiled)
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < 16 * 1024 ** 3)


@pytest.mark.parametrize("flash", [False, True], ids=["xla", "flash"])
def test_prefill_program_compiles(one_chip, tpu_kernel_path, flash):
    """One warmed bucket of chunked prefill (PB=8, T=512, P=64) as a TPU
    backend builds it: at the 1B preset's heads of 64 on the XLA gather
    path (the chip's compiler refuses the kernel's page copies there),
    at heads of 128 with the paged prefill kernel inside."""
    import dataclasses

    cfg = _preset_1b()
    if flash:
        cfg = dataclasses.replace(cfg, num_heads=16, num_kv_heads=4,
                                  head_dim=128)
    params, kv_k, kv_v = _engine_shapes(cfg, one_chip)
    prefill, _ = llama.make_step_fns(cfg)
    s = partial(_sds, one_chip)
    PB, T = B_PRE, 512
    compiled = prefill.lower(
        params, s((PB, T), jnp.int32), s((PB, T), jnp.int32), kv_k, kv_v,
        s((PB, P_PRE), jnp.int32), s((PB, T), jnp.int32),
        s((PB,), jnp.int32), s((PB, T // PS), jnp.int32)).compile()
    assert _has_kernel(compiled) == flash
    _head_behind_one_conditional(compiled.as_text())
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < 16 * 1024 ** 3)


def test_decode_window_program_compiles_model4(topo, tpu_kernel_path):
    """The model=4 engine's window (KV=8 divides): the kernel under
    shard_map plus the collectives GSPMD inserts around it."""
    from dynamo_tpu.parallel.mesh import (MeshSpec, kv_cache_pspec,
                                          param_pspecs)

    cfg = _preset_1b()
    mesh = MeshSpec(model=4).build(list(topo.devices))
    rep = NamedSharding(mesh, P())
    params, kv_k, kv_v = _engine_shapes(cfg, rep)
    specs = param_pspecs(cfg)
    params = {k: jax.ShapeDtypeStruct(
        v.shape, v.dtype, sharding=NamedSharding(
            mesh, specs.get(k, P(*([None] * len(v.shape))))))
        for k, v in params.items()}
    kvs = NamedSharding(mesh, kv_cache_pspec(cfg))
    kv_k, kv_v = _on(kvs, kv_k), _on(kvs, kv_v)
    fn = llama.make_decode_window_fn(cfg, True, 64, mesh=mesh)
    compiled = _lower_window(fn, rep, params, kv_k, kv_v, B_DEC,
                             P_DEC).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "all-reduce" in text or "all-gather" in text


# ------------------- the window's commit at the benchmark's pool shapes


def _pool_sized_copies(text: str, pool_elems: int):
    """Names of the optimized program's `copy` ops (plain or async) whose
    result holds at least a pool's elements."""
    import re

    out = []
    shape = r"\w+\[([\d,]*)\]"
    for m in re.finditer(
            rf"^\s*(?:ROOT )?%?([\w.\-]+) = (?:\({shape}[^)]*\)|{shape}\S*) "
            r"(?:copy|copy-start)\(", text, re.M):
        n = 1
        for d in filter(None, (m.group(2) or m.group(3)).split(",")):
            n *= int(d)
        if n >= pool_elems:
            out.append(m.group(1))
    return out


def _tails_stay_layer_major(text: str, tails, B: int, program: str):
    """The conv tails of a jamba.Blocks family in an optimized program: a
    decode program (B its rows) carries them layer-major, [M, B, W], and
    a token's step advances a layer's block through ops/conv_step.py
    where it lies; a prefill chunk has no such step. (The compiler's own
    gather and scatter of the rows, once a program, still pass through
    rows-major values: PERF.md section 7.)"""
    M, _, W = tails.shape
    assert ("conv_tail_step" in text) == (program != "prefill")
    if program != "prefill":
        assert "bf16[%d,%d,%d]" % (M, B, W) in text


def _computations(text: str):
    """The optimized program's computations (the entry, a loop's body, a
    fusion's body, ...) by name, each as its text."""
    return {m.group(1): body for body in
            re.split(r"\n(?=(?:ENTRY )?%[\w.\-]+ \()", text)
            if (m := re.match(r"(?:ENTRY )?%([\w.\-]+) \(", body))}


def _computations_with(text: str, shape: str, op: str):
    """The optimized program's computations (a fusion's body is one)
    that hold an `op` whose result is of `shape`, each as its text."""
    found = re.compile(r" = %s\S* %s\(" % (re.escape(shape), op))
    return [body for body in _computations(text).values()
            if found.search(body)]


def _cell_config(name: str, experts: int):
    """A benchmark configuration at every published width, the expert
    count cut so that a whole program's compile stays short."""
    import dataclasses

    from dynamo_tpu.models.config import ModelConfig

    cfg = ModelConfig.from_local_path(os.path.join(
        ROOT, "benchmark", "configs", name))
    return dataclasses.replace(
        cfg, num_experts=experts,
        num_experts_per_tok=min(cfg.num_experts_per_tok, experts))


def test_pool_sized_copy_is_recognised():
    """The guard below reads the compiler's text: it must see the copies
    the parent of PR 30 made (lines from its compiled window)."""
    text = """
  %copy.562 = bf16[6,1280,4,64,128]{4,3,1,0,2:T(8,128)(2,1)} copy(%kv_k.1)
  %copy.564 = bf16[491520,4,128]{2,1,0:T(4,128)(2,1)} copy(%bitcast.42), metadata={}
  %copy.614 = s32[64,2,1]{1,0,2:T(8,128)S(1)} copy(%bitcast_or_fusion.4)
  %bitcast.55 = bf16[6,1280,4,64,128]{4,3,2,1,0:T(8,128)(2,1)} bitcast(%fusion.43)
  %copy-start.3 = (bf16[7680,4,64,128]{3,2,1,0}, bf16[7680,4,64,128]{3,2,1,0}, u32[]) copy-start(%x)
"""
    assert _pool_sized_copies(text, 6 * 1280 * 4 * 64 * 128) == [
        "copy.562", "copy.564", "copy-start.3"]


@pytest.mark.parametrize("name,experts,num_pages,B,P", [
    ("qwen3-30b-a3b", 8, 1280, 64, 32), ("mixtral-8x7b", 2, 768, 32, 64)])
def test_window_commit_makes_no_pool_sized_copy(one_chip, tpu_kernel_path,
                                                name, experts, num_pages,
                                                B, P):
    """The fused decode window at the pool shapes of the benchmark's
    cells 2 and 1 ([6, 1280, 4, 64, 128] B 64, [3, 768, 8, 64, 128] B 32;
    every width as published, the expert count cut so the compile stays
    short): commit_window writes whole pages along the pool's major
    axis, so the optimized program holds NO copy of a pool's size, the
    pools alias their inputs, and the temporaries stay under one pool.
    A row scatter in kv_carry brings back eight relayout copies of the
    pool a window (four at KV 8) and one pool of temporaries: 14% of
    cell 2's device time (ledger, PR 29)."""
    cfg = _cell_config(name, experts)
    params, kv_k, kv_v = _engine_shapes(cfg, one_chip, num_pages)
    assert kv_k.shape == (cfg.num_layers, num_pages, cfg.num_kv_heads, PS,
                          128)
    fn = llama.make_decode_window_fn(cfg, True, 64)
    compiled = _lower_window(fn, one_chip, params, kv_k, kv_v, B,
                             P).compile()
    assert _has_kernel(compiled)
    assert _pool_sized_copies(compiled.as_text(), kv_k.size) == []
    mem = compiled.memory_analysis()
    pool_bytes = kv_k.size * kv_k.dtype.itemsize
    assert mem.alias_size_in_bytes >= 2 * pool_bytes
    assert mem.temp_size_in_bytes < pool_bytes


@pytest.mark.parametrize("name,experts,cell,pool,parent_temp", [
    ("mixtral-8x7b", 2, "mixtral-8x7b.chat-steady", (3, 768, 8, PS, 128),
     1_746_892_288),
    ("qwen3-30b-a3b", 8, "qwen3-30b-a3b.decode-heavy", (6, 1280, 4, PS, 128),
     1_494_419_968)])
def test_prefill_carries_its_pools_and_copies_none(one_chip, tpu_kernel_path,
                                                   name, experts, cell, pool,
                                                   parent_temp):
    """The largest prefill program of the benchmark's cells 1 / 3 (PB 4 x
    T 512 on [3, 768, 8, 64, 128]) and of cell 2 (PB 8 x T 256 on [6,
    1280, 4, 64, 128]), from the cells' engine data, every width as
    published, the expert count cut so the compile stays short: the
    pools ride llama.forward's scan as its carry, seen as [L * pages,
    ...], and a layer scatters its chunk's pages into the donated
    buffer. So no copy of a pool's size exists, both pools alias their
    inputs, and the temporaries hold no pool. As scanned xs / ys (until
    PR 49) the program held two plain copies of a pool, a per-layer
    slice and update of each pool's size, and three pools among its
    temporaries: 1,746,892,288 bytes where this form reads 370,185,216
    (cells 1 / 3, two experts) and 1,494,419,968 where it reads 609,792
    (cell 2, eight experts) (scratch compiles, PR 49); with every
    expert 1.34 -> 0.22 GB and 2.17 -> 0.55 GB, and 0 where the parent
    took 1.67 GB more for each GB of pool."""
    import json

    with open(os.path.join(ROOT, "benchmark", "workloads",
                           cell + ".json")) as f:
        e = json.load(f)["engine"]
    cfg = _cell_config(name, experts)
    params, kv_k, kv_v = _engine_shapes(cfg, one_chip, e["num_pages"])
    assert kv_k.shape == pool
    s = partial(_sds, one_chip)
    PB, T = e["max_prefill_batch"], e["prefill_buckets"][-1]
    compiled = llama.make_step_fns(cfg)[0].lower(
        params, s((PB, T), jnp.int32), s((PB, T), jnp.int32), kv_k, kv_v,
        s((PB, e["page_buckets"][-1]), jnp.int32), s((PB, T), jnp.int32),
        s((PB,), jnp.int32), s((PB, T // PS), jnp.int32)).compile()
    assert _has_kernel(compiled)
    assert _pool_sized_copies(compiled.as_text(), kv_k.size) == []
    _head_behind_one_conditional(compiled.as_text())
    mem = compiled.memory_analysis()
    pool_bytes = kv_k.size * kv_k.dtype.itemsize
    assert mem.alias_size_in_bytes >= 2 * pool_bytes
    assert mem.temp_size_in_bytes < parent_temp - pool_bytes


# -------------------- MLA + routed experts at Moonlight-16B-A3B's widths


# moonshotai/Moonlight-16B-A3B config.json (the model-configs catalog
# row), every width as published; depth cut to the dense layer plus one
# expert layer. ROADMAP B1's first cell starts from here.
MOONLIGHT = {
    "model_type": "deepseek_v3", "vocab_size": 163840, "hidden_size": 2048,
    "intermediate_size": 11264, "moe_intermediate_size": 1408,
    "num_hidden_layers": 2, "first_k_dense_replace": 1,
    "num_attention_heads": 16, "num_key_value_heads": 16,
    "kv_lora_rank": 512, "q_lora_rank": None, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "v_head_dim": 128, "n_routed_experts": 64,
    "n_shared_experts": 2, "num_experts_per_tok": 6, "n_group": 1,
    "topk_group": 1, "norm_topk_prob": True, "scoring_func": "sigmoid",
    "topk_method": "noaux_tc", "routed_scaling_factor": 2.446,
    "rope_theta": 50000, "rms_norm_eps": 1e-05, "hidden_act": "silu",
    "tie_word_embeddings": False, "max_position_embeddings": 8192,
}


@pytest.mark.parametrize("step", ["prefill", "decode"])
def test_mla_moe_step_compiles_at_moonlight_widths(one_chip, step):
    from dynamo_tpu.models import mla
    from dynamo_tpu.models.config import ModelConfig

    cfg = ModelConfig.from_hf_config(dict(MOONLIGHT))
    assert cfg.is_mla and cfg.num_experts == 64 and cfg.num_layers == 2
    params = _on(one_chip, jax.eval_shape(
        lambda: mla.init_params(cfg, jax.random.PRNGKey(0))))
    kv_k, kv_v = (_on(one_chip, x) for x in jax.eval_shape(
        lambda: mla.init_kv_cache(cfg, llama.KVCacheSpec(NUM_PAGES, PS))))
    prefill, decode = mla.make_step_fns(cfg)
    s = partial(_sds, one_chip)
    if step == "prefill":
        PB, T = B_PRE, 512
        lowered = prefill.lower(
            params, s((PB, T), jnp.int32), s((PB, T), jnp.int32), kv_k,
            kv_v, s((PB, P_PRE), jnp.int32), s((PB, T), jnp.int32),
            s((PB,), jnp.int32))
    else:
        B = B_DEC
        lowered = decode.lower(
            params, s((B,), jnp.int32), s((B,), jnp.int32), kv_k, kv_v,
            s((B, P_DEC), jnp.int32), s((B,), jnp.int32))
    mem = lowered.compile().memory_analysis()
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < 16 * 1024 ** 3)


# -------- the sorted expert dispatch as one kernel (ops/moe_grouped.py)


@pytest.mark.parametrize("name,E,first,k,D,I,N", [
    ("mixtral", 8, None, 2, 4096, 14336, 512),      # I in tiles
    ("qwen3", 128, None, 8, 2048, 768, 2048),
    ("lfm2", 64, None, 4, 2048, 1536, 1024),
    ("granite", 36, 18, 10, 4096, 768, 2048),       # a share of 72
], ids=lambda v: v if isinstance(v, str) else "")
def test_moe_grouped_kernel_compiles_at_cell_shapes(one_chip,
                                                    tpu_kernel_path, name,
                                                    E, first, k, D, I, N):
    """One layer's sorted dispatch at the cells' widths, stacks in place
    in bfloat16: the kernel is there, takes the VMEM its tile was sized
    for, and nothing around it outgrows the rows' own buffers (block
    j's rows in, its slot out) and a chunk of either on its way."""
    block = llama.moe_block(N, k, (2, E, D, I))
    per = llama._MOE_CHUNK_ROWS // block
    n_max = -(-(-(-N * k // block) + E) // per) * per
    s = partial(_sds, one_chip)
    compiled = jax.jit(lambda x, w, idx, wg, wu, wd, live, layer:
                       llama.moe_experts_blocked(
                           x, w, idx, wg, wu, wd, block, live=live,
                           layer=layer, first=first)).lower(
        s((N, D), jnp.float32), s((N, k), jnp.float32), s((N, k), jnp.int32),
        s((2, E, D, I), jnp.bfloat16), s((2, E, D, I), jnp.bfloat16),
        s((2, E, I, D), jnp.bfloat16), s((N,), jnp.bool_),
        s((), jnp.int32)).compile()
    assert _has_kernel(compiled)
    assert compiled.memory_analysis().temp_size_in_bytes \
        < 1.25 * n_max * block * D * (2 + 4) + (128 << 20)


# ------------- latent attention at Kanana-2-30B-A3B's widths (cell 5)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _kanana_cell():
    """(configuration directory, the cell's engine data): the pool, the
    page size and the buckets are read from the benchmark's own files,
    so the compiles below are of the shapes the cell runs."""
    import json

    with open(os.path.join(ROOT, "benchmark", "workloads",
                           "kanana-2-30b-a3b.doc-qa.json")) as f:
        engine = json.load(f)["engine"]
    return (os.path.join(ROOT, "benchmark", "configs", "kanana-2-30b-a3b"),
            engine)


def _kanana(one_chip, experts=8):
    """The configuration as the cell runs it (every width as published),
    the expert count cut so the compiles stay short; params and the two
    pools as shapes on the described chip."""
    import dataclasses

    from dynamo_tpu.models import mla
    from dynamo_tpu.models.config import ModelConfig

    path, e = _kanana_cell()
    cfg = dataclasses.replace(ModelConfig.from_local_path(path),
                              num_experts=experts)
    params = _on(one_chip, jax.eval_shape(
        lambda: mla.init_params(cfg, jax.random.PRNGKey(0))))
    kv_k, kv_v = (_on(one_chip, x) for x in jax.eval_shape(
        lambda: mla.init_kv_cache(cfg, llama.KVCacheSpec(
            e["num_pages"], e["page_size"]))))
    assert kv_k.shape == (6, e["num_pages"], 1, e["page_size"], 512)
    # the rope key in whole lanes
    assert kv_v.shape == (6, e["num_pages"], 1, e["page_size"], 128)
    return cfg, params, kv_k, kv_v, e


@pytest.mark.parametrize("B", [64, 8, 1])
@pytest.mark.parametrize("page_size,P", [(128, 72), (64, 144)])
def test_latent_decode_kernel_compiles(one_chip, page_size, P, B):
    """32 heads against one latent head of 512 + a rope key padded to
    128, a grid step a row and 1,024 cached tokens a chunk of its loop,
    at the cell's ``batch_buckets`` and at both page sizes a 9,216-token
    bucket can have: the kernel itself (its page copies are slices of
    the pools in HBM, which the chip's compiler takes only at whole
    lanes), and no relayout of either pool in front of it (a rope pool
    64 columns wide would be copied whole: the runtime stores it with
    PAGES as its minor axis)."""
    s = partial(_sds, one_chip)
    pages = 64 * P
    compiled = jax.jit(partial(pa.latent_attention_decode_layered,
                               scale=192 ** -0.5)).lower(
        s((B, 32, 512), jnp.bfloat16), s((B, 32, 128), jnp.bfloat16),
        s((6, pages, 1, page_size, 512), jnp.bfloat16),
        s((6, pages, 1, page_size, 128), jnp.bfloat16), s((), jnp.int32),
        s((B, P), jnp.int32), s((B,), jnp.int32)).compile()
    assert _has_kernel(compiled)
    assert _pool_sized_copies(compiled.as_text(),
                              6 * pages * page_size * 128) == []
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 20


@pytest.mark.parametrize("program", ["window", "prefill", "prefill-rows"])
def test_latent_programs_make_no_pool_sized_copy(one_chip, tpu_kernel_path,
                                                 program):
    """models/mla.py at the pool shapes of kanana-2-30b-a3b.doc-qa
    ([6, 1536, 1, 128, 512] latents, [6, 1536, 1, 128, 128] rope keys):
    the pools are read-only inside the fused window (B 64, P 72) and
    inside a prefill chunk (PB 8 x T 512), and one commit per pool writes
    whole pages (``prefill-rows``: token rows, the engine's path for an
    unaligned chunk) along its major axis. So the optimized program
    holds no copy of a pool's size, both pools alias their inputs, and
    the temporaries stay under the latent pool. The segment slice and
    concatenate that forward() made of the pools until PR 31 were two
    pool-sized copies in every program."""
    from dynamo_tpu.models import mla

    cfg, params, kv_k, kv_v, e = _kanana(one_chip)
    s = partial(_sds, one_chip)
    P = e["page_buckets"][-1]
    if program == "window":
        fn = mla.make_decode_window_fn(cfg, True, 64)
        compiled = _lower_window(fn, one_chip, params, kv_k, kv_v,
                                 e["max_batch"], P).compile()
    else:
        prefill, _ = mla.make_step_fns(cfg)
        PB, T = e["max_prefill_batch"], e["prefill_chunk"]
        pslots = s((PB, T // e["page_size"]), jnp.int32) \
            if program == "prefill" else None
        compiled = prefill.lower(
            params, s((PB, T), jnp.int32), s((PB, T), jnp.int32), kv_k,
            kv_v, s((PB, P), jnp.int32), s((PB, T), jnp.int32),
            s((PB,), jnp.int32), pslots).compile()
    assert _has_kernel(compiled)      # decode's, and a multi-row chunk's
    _head_behind_one_conditional(compiled.as_text(), program)
    assert _pool_sized_copies(compiled.as_text(), kv_v.size) == []
    mem = compiled.memory_analysis()
    pools = sum(x.size * x.dtype.itemsize for x in (kv_k, kv_v))
    assert mem.alias_size_in_bytes >= pools
    assert mem.temp_size_in_bytes < kv_k.size * kv_k.dtype.itemsize


# ---- gated short convolutions + packed GQA at LFM2-24B-A2B's widths (cell 6)


def _lfm2(one_chip, experts=8):
    """The configuration as the cell runs it (every width as published),
    the expert count cut so the compiles stay short; params, the two KV
    pools and the state pools (by slot, by page) as shapes on the
    described chip, at the cell's engine data."""
    import dataclasses
    import json

    from dynamo_tpu.models import lfm2
    from dynamo_tpu.models.config import ModelConfig

    with open(os.path.join(ROOT, "benchmark", "workloads",
                           "lfm2-24b-a2b.agent-loop.json")) as f:
        e = json.load(f)["engine"]
    cfg = dataclasses.replace(ModelConfig.from_local_path(os.path.join(
        ROOT, "benchmark", "configs", "lfm2-24b-a2b")), num_experts=experts)
    spec = llama.KVCacheSpec(e["num_pages"], e["page_size"])
    params = _on(one_chip, jax.eval_shape(
        lambda: lfm2.init_params(cfg, jax.random.PRNGKey(0))))
    kv_k, kv_v = (_on(one_chip, x) for x in jax.eval_shape(
        lambda: lfm2.init_kv_cache(cfg, spec)))
    state = _on(one_chip, jax.eval_shape(lambda: (
        *lfm2.init_state(cfg, e["max_batch"] + 1),
        lfm2.init_state_snapshots(cfg, spec))))
    # two KV heads of 64 side by side in 128 lanes; 48 KB of state a row
    # and a page (6 conv layers x 2 x 2,048 bf16)
    assert kv_k.shape == kv_v.shape == (2, e["num_pages"], 4,
                                        e["page_size"], 128)
    assert [x.shape for x in state] == [(e["max_batch"] + 1, 24576),
                                        (e["num_pages"], 24576)]
    return lfm2, cfg, params, kv_k, kv_v, state, e


def _lfm2_prefill(one_chip, shapes, PB, T):
    """models/lfm2.py's prefill chunk of PB rows x T tokens (whole pages)
    at ``_lfm2``'s shapes, compiled."""
    lfm2, cfg, params, kv_k, kv_v, state, e = shapes
    s = partial(_sds, one_chip)
    return lfm2.make_step_fns(cfg)[0].lower(
        params, s((PB, T), jnp.int32), s((PB, T), jnp.int32), kv_k, kv_v,
        s((PB, e["page_buckets"][-1]), jnp.int32), s((PB, T), jnp.int32),
        s((PB,), jnp.int32), s((PB, T // e["page_size"]), jnp.int32),
        state, s((PB,), jnp.int32), s((PB,), jnp.int32)).compile()


@pytest.mark.parametrize("program", ["window", "prefill"])
def test_lfm2_programs_alias_their_pools_and_copy_none(one_chip,
                                                       tpu_kernel_path,
                                                       program):
    """models/lfm2.py at the pool shapes of lfm2-24b-a2b.agent-loop
    ([2, 4096, 4, 64, 128] K and V, [65, 24576] state by slot, [4096,
    24576] snapshots by page): the fused window (B 64, P 64, the cell's
    8 steps) and a prefill chunk (the cell's largest, PB 4 x T 512, whole
    pages) take the snapshot pool
    among their operands, write every pool along its major axis in
    place, and hold no copy of a pool's size; all four pools alias their
    inputs. (The token-row commit of an unaligned chunk and of the K=1
    decode step, llama._scatter_pages, relayouts a pool here as it does
    in llama.py: no cell runs it.) The published layout of the heads ([2, 4096, 8, 64, 64]) is
    relayouted around every program: six pool-sized copies and 2 GiB of
    temporaries a window (scratch compile, PR 33), and the decode
    kernel's fast form cannot slice 64 lanes (PR 32): hence the packing,
    which the window's kernel here confirms (tpu_custom_call, KV' 4)."""
    shapes = lfm2, cfg, params, kv_k, kv_v, state, e = _lfm2(one_chip)
    if program == "window":
        s = partial(_sds, one_chip)
        B, P = e["max_batch"], e["page_buckets"][-1]
        i32, f32 = s((B,), jnp.int32), s((B,), jnp.float32)
        compiled = lfm2.make_decode_window_fn(cfg, True, 64).lower(
            params, i32, i32, s((B,), jnp.bool_), i32, i32, kv_k, kv_v,
            s((B, P), jnp.int32), f32, i32, f32, s((B,), jnp.uint32),
            s((B, 8), jnp.int32), None, state, i32,
            k_steps=e["decode_steps"], logprobs_topn=0).compile()
        assert _has_kernel(compiled)
    else:
        compiled = _lfm2_prefill(one_chip, shapes, e["max_prefill_batch"],
                                 e["prefill_chunk"])
        # the chunk's attention is the paged prefill kernel on the packed
        # pool: no float32 scores over the whole table (2.06 GiB of
        # temporaries on the XLA arm, 0.12 now: scratch compile, PR 34)
        assert _has_kernel(compiled)
        assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 29
    smallest = min(kv_k.size, state[1].size)
    assert _pool_sized_copies(compiled.as_text(), smallest) == []
    _head_behind_one_conditional(compiled.as_text(), program)
    mem = compiled.memory_analysis()
    pools = sum(x.size * x.dtype.itemsize
                for x in (kv_k, kv_v, *state))
    assert mem.alias_size_in_bytes >= pools
    if program == "window":
        assert mem.temp_size_in_bytes < kv_k.size * kv_k.dtype.itemsize / 4
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < 15.75 * 1024 ** 3)


# ------ the block window and block-causal prefill at SDAR-30B-A3B's widths


@pytest.mark.parametrize("program", ["window", "window-logprobs",
                                     "prefill"])
def test_sdar_programs_alias_their_pools_and_copy_none(one_chip,
                                                       tpu_kernel_path,
                                                       program):
    """The programs of sdar-30b-a3b-chat.decode-heavy at the cell's pool
    ([6, 1280, 4, 64, 128]) and engine data, every width as published:
    the block window (B 64, P 32, the cell's decode_steps: whole blocks
    of 4, each a [B, 2L] forward that carries the block before it, with
    the decode kernel at group 8 x 8 = 64 and the 512 rows' experts
    through ops/moe_grouped.py, then a while loop of [B, L] denoising
    forwards, the kernel at group 32 and the 256 rows' experts through
    ops/moe_grouped.py too, since the shape rule stands at the chip's
    ridge (PR 66; dense before); a token operand of two blocks a row) and a block-causal prefill chunk (PB 8
    x T 256, the prefill kernel with the block edge; the expert count
    cut to 8 so that compile stays short: it takes the dense form). The
    pools alias their inputs, and the window holds no copy of a pool's
    size:
    its pools are read-only inside the loops and written once, by
    commit_window, along their major axis. Neither does the prefill,
    block-causal or causal (the same program with block_length 1, cell
    2's, is compiled beside it): the pools ride llama.forward's scan as
    its carry, a chunk's pages are scattered into the donated buffers,
    and the block mask adds no temporary."""
    import dataclasses
    import json

    from dynamo_tpu.models.config import ModelConfig

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "workloads",
                           "sdar-30b-a3b-chat.decode-heavy.json")) as f:
        e = json.load(f)["engine"]
    cfg = ModelConfig.from_local_path(os.path.join(
        root, "benchmark", "configs", "sdar-30b-a3b-chat"))
    assert cfg.block_length == 4 and e["decode_steps"] % 4 == 0
    if program == "prefill":
        cfg = dataclasses.replace(cfg, num_experts=8)
    params, kv_k, kv_v = _engine_shapes(cfg, one_chip, e["num_pages"])
    assert kv_k.shape == (6, 1280, 4, PS, 128)
    s = partial(_sds, one_chip)
    P = e["page_buckets"][-1]
    if program == "prefill":
        PB, T = e["max_prefill_batch"], e["prefill_chunk"]

        def lower(c):
            return llama.make_step_fns(c)[0].lower(
                params, s((PB, T), jnp.int32), s((PB, T), jnp.int32), kv_k,
                kv_v, s((PB, P), jnp.int32), s((PB, T), jnp.int32),
                s((PB,), jnp.int32), s((PB, T // PS), jnp.int32)).compile()

        compiled = lower(cfg)
        causal = lower(dataclasses.replace(cfg, block_length=1))
        assert _has_kernel(compiled)
        assert _pool_sized_copies(compiled.as_text(), kv_k.size) == []
        assert _pool_sized_copies(causal.as_text(), kv_k.size) == []
        mem, was = compiled.memory_analysis(), causal.memory_analysis()
        assert mem.alias_size_in_bytes == was.alias_size_in_bytes
        assert mem.temp_size_in_bytes < was.temp_size_in_bytes + 2 ** 20
        return
    else:
        B = e["max_batch"]
        i32, f32 = s((B,), jnp.int32), s((B,), jnp.float32)
        compiled = llama.make_decode_window_fn(cfg, True, 64).lower(
            params, s((B, 2 * cfg.block_length), jnp.int32), i32,
            s((B,), jnp.bool_), i32, i32, kv_k, kv_v, s((B, P), jnp.int32),
            f32, i32, f32, s((B,), jnp.uint32), s((B, 8), jnp.int32), None,
            k_steps=e["decode_steps"],
            logprobs_topn=20 if program == "window-logprobs" else 0
        ).compile()
    assert _has_kernel(compiled)
    text = compiled.as_text()
    assert _pool_sized_copies(text, kv_k.size) == []
    # a block's two forwards and the sorted dispatch of each, once each:
    # the window's blocks are ONE loop
    assert text.count("tpu_custom_call") == 4
    # either forward's sorted dispatch reads w[layer, expert] where the
    # stacks lie: nothing that runs only MOVES a layer's
    # experts (sliced out of the scan's xs for the kernel, each of the
    # three would be copied a layer a forward: 1.2 GB)
    down = params["w_down"]
    assert _weight_sized_relayouts(text, down.size // down.shape[0],
                                   pools=(kv_k.size,)) == []
    mem = compiled.memory_analysis()
    pool_bytes = kv_k.size * kv_k.dtype.itemsize
    assert mem.alias_size_in_bytes >= 2 * pool_bytes
    # the agreement check's variant holds [B x 4, V] log-probabilities
    # and their top 20 besides. The other (595.8 MiB; 595.5 before PR 66
    # sent the one-block forward's 256 rows through the sorted dispatch
    # as well, whose buffers lie where the two-block forward's did; 585.0
    # on the parent of PR 62: the two-block forward's rows in and out of
    # the sorted dispatch, [4,096 + padding, 2,048], and a buffer of one
    # block more; scratch compiles, PR 62, PR 66): 145 MB + THREE arrays of the
    # logits' size (156 MB each) in the arm of the draw that a sampled
    # row switches on, where the program without a branch held two: the
    # head's output (the logits over the temperature) is the operand of
    # sample_tokens' conditional, and an operand lives as long as its
    # conditional, so exact_top_k's two relayouts stand beside it and not
    # in its place (456 -> 612 MB; wherever the divide stands)
    logits_bytes = B * cfg.block_length * cfg.vocab_size * 4
    assert mem.temp_size_in_bytes < (
        2 * pool_bytes if program == "window-logprobs"
        else pool_bytes + logits_bytes)
    # what the arm of a sampled row costs beside that: the two relayouts
    # a draw, at the two places a block draws (after its first forward,
    # which is peeled out of the denoising loop, and in that loop's body:
    # 2 x 2 = 4, the parent's count, which unrolled two blocks of one
    # place each; none of them runs for a greedy batch and no two are
    # alive at once, as the bound above says), and the temperature's
    # divide in the head's own output fusion, not a pass over the logits
    # of its own
    if program == "window":
        import re
        rows, V = B * cfg.block_length, cfg.vocab_size
        chunks = "%d,%d,128" % (rows, -(-V // 128))
        assert len(re.findall(r" = f32\[(?:%d,%d|%s)\]\S* copy\(" % (
            rows, V, chunks), text)) == 4
        wide = _computations_with(text, "f32[%d,%d]" % (rows, V), "divide")
        assert wide and all(" convolution(" in body for body in wide), (
            [body.splitlines()[0] for body in wide])
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < 15.75 * 1024 ** 3)


# ---- the selective-scan step on the state pool at Jamba2-3B's widths (cell 4)


def _jamba(one_chip):
    """The configuration as jamba2-3b.reason-decode runs it (full depth,
    every width as published); params and the three pools as shapes on
    the described chip, at the cell's engine data."""
    import json

    from dynamo_tpu.models import jamba
    from dynamo_tpu.models.config import ModelConfig

    with open(os.path.join(ROOT, "benchmark", "workloads",
                           "jamba2-3b.reason-decode.json")) as f:
        e = json.load(f)["engine"]
    cfg = ModelConfig.from_local_path(os.path.join(
        ROOT, "benchmark", "configs", "jamba2-3b"))
    params = _on(one_chip, jax.eval_shape(
        lambda: jamba.init_params(cfg, jax.random.PRNGKey(0))))
    kv_k, kv_v = (_on(one_chip, x) for x in jax.eval_shape(
        lambda: jamba.init_kv_cache(cfg, llama.KVCacheSpec(e["num_pages"],
                                                           64))))
    state = _on(one_chip, jax.eval_shape(
        lambda: jamba.init_state(cfg, e["max_batch"] + 1)))
    assert state[0].shape == (129, 26, 16, 5120)
    return jamba, cfg, params, kv_k, kv_v, state, e


@pytest.mark.parametrize("B", [128, 8, 1, 24])
def test_scan_kernel_compiles(one_chip, B):
    """ops/selective_scan.py at cell 4's pool and its three batch buckets
    (and a batch whose last group of rows is short): the chip's compiler
    takes the row copies, the ring in VMEM and the blocks of rows."""
    from dynamo_tpu.ops.selective_scan import selective_scan_step

    s = partial(_sds, one_chip)
    S, M, N, di = 129, 26, 16, 5120
    f32 = jnp.float32
    assert _has_kernel(selective_scan_step.lower(
        s((S, M, N, di), f32), s((B,), jnp.int32), s((), jnp.int32),
        s((B, di), f32), s((B, di), f32), s((B, N), f32), s((B, N), f32),
        s((N, di), f32), s((B,), jnp.bool_)).compile())


@pytest.mark.parametrize("M,B,C", [
    (26, 128, 5120), (9, 64, 8448), (6, 128, 12288), (3, 128, 24576),
    (3, 8, 24576), (3, 1, 24576)],
    ids=["jamba2-3b", "granite-4.0-h-small", "kimi-linear-48b-a3b",
         "solar-open2-250b", "eight-rows", "one-row"])
def test_conv_tail_kernel_compiles(one_chip, M, B, C):
    """ops/conv_step.py at the carried tails of the four cells that run
    it (cells 4, 8, 10, 11: [M, B, 3 * C] bf16 at their window batch) and
    at a prefill batch's rows (a chunk of one token): the chip's compiler
    takes the blocks of whole rows (16 to 64 rows, 2.4 MB at most), the
    taps at lane offsets of C and the VMEM asked for."""
    from dynamo_tpu.ops.conv_step import conv_tail_step

    s = partial(_sds, one_chip)
    f32 = jnp.float32
    assert _has_kernel(conv_tail_step.lower(
        s((M, B, 3 * C), jnp.bfloat16), s((), jnp.int32), s((B, C), f32),
        s((B,), jnp.bool_), s((4, C), f32), s((C,), f32)).compile())


@pytest.mark.parametrize("program", ["window", "decode_step", "prefill"])
def test_jamba_programs_write_no_array_of_the_state_pools_size(
        one_chip, tpu_kernel_path, program):
    """models/jamba.py at the shapes of jamba2-3b.reason-decode (state
    pool [129, 26, 16, 5120] float32 = 1.10 GB): the fused window (B 128,
    4 steps) and decode_step advance the scan state IN the pool through
    the kernel: no value of the optimized program has the gathered rows'
    shape [128, 26, 16, 5120] (the parent gathered them, copied them into
    the layer loops' carry and scattered them back: 1.96 GiB of
    temporaries a window, 0.31 now; scratch compile, PR 36), and no copy
    of a pool's size exists. A prefill chunk (PB 8 x T 512) gathers its
    eight rows and stores them row by row in place: the pool aliases its
    input and is never copied."""
    jamba, cfg, params, kv_k, kv_v, state, e = _jamba(one_chip)
    s = partial(_sds, one_chip)
    P, B = e["page_buckets"][-1], e["max_batch"]
    i32, f32 = s((B,), jnp.int32), s((B,), jnp.float32)
    if program == "window":
        compiled = jamba.make_decode_window_fn(cfg, True, 64).lower(
            params, i32, i32, s((B,), jnp.bool_), i32, i32, kv_k, kv_v,
            s((B, P), jnp.int32), f32, i32, f32, s((B,), jnp.uint32),
            s((B, 8), jnp.int32), None, state, i32, k_steps=4,
            logprobs_topn=0).compile()
    elif program == "decode_step":
        compiled = jamba.make_step_fns(cfg)[1].lower(
            params, i32, i32, kv_k, kv_v, s((B, P), jnp.int32), i32, state,
            i32).compile()
    else:
        PB, T = e["max_prefill_batch"], e["prefill_chunk"]
        compiled = jamba.make_step_fns(cfg)[0].lower(
            params, s((PB, T), jnp.int32), s((PB, T), jnp.int32), kv_k,
            kv_v, s((PB, P), jnp.int32), s((PB, T), jnp.int32),
            s((PB,), jnp.int32), s((PB, T // 64), jnp.int32), state,
            s((PB,), jnp.int32)).compile()
    text = compiled.as_text()
    _head_behind_one_conditional(text, program)
    _tails_stay_layer_major(text, state[1], B, program)
    assert _has_kernel(compiled)
    assert _pool_sized_copies(text, state[0].size) == []
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= sum(
        x.size * x.dtype.itemsize for x in (kv_k, kv_v, *state))
    if program != "prefill":
        assert "f32[%d,26,16,5120]" % B not in text
        assert mem.temp_size_in_bytes < 2 ** 29
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < 15.75 * 1024 ** 3)


def _granite(one_chip):
    """The configuration as granite-4.0-h-small.rag-decode runs it (10 of
    40 layers, 36 of 72 experts held, every width as published); params
    and the three pools as shapes on the described chip, at the cell's
    engine data."""
    import json

    from dynamo_tpu.models import granite
    from dynamo_tpu.models.config import ModelConfig

    with open(os.path.join(ROOT, "benchmark", "workloads",
                           "granite-4.0-h-small.rag-decode.json")) as f:
        e = json.load(f)["engine"]
    cfg = ModelConfig.from_local_path(os.path.join(
        ROOT, "benchmark", "configs", "granite-4.0-h-small"))
    params = _on(one_chip, jax.eval_shape(
        lambda: granite.init_params(cfg, jax.random.PRNGKey(0))))
    kv_k, kv_v = (_on(one_chip, x) for x in jax.eval_shape(
        lambda: granite.init_kv_cache(cfg, llama.KVCacheSpec(e["num_pages"],
                                                             64))))
    state = _on(one_chip, jax.eval_shape(
        lambda: granite.init_state(cfg, e["max_batch"] + 1)))
    assert state[0].shape == (65, 9, 128, 8192)     # 4 MiB a layer a row
    return granite, cfg, params, kv_k, kv_v, state, e


@pytest.mark.parametrize("B", [64, 4, 1])
def test_ssd_step_kernel_compiles(one_chip, B):
    """ops/selective_scan.py ssd_step at cell 8's pool and its three
    batch buckets: the chip's compiler takes the 4 MiB row copies, the
    12 MiB ring in VMEM and the lane chunks of a row."""
    from dynamo_tpu.ops.selective_scan import ssd_step

    s = partial(_sds, one_chip)
    S, M, N, C = 65, 9, 128, 8192
    f32 = jnp.float32
    assert _has_kernel(ssd_step.lower(
        s((S, M, N, C), f32), s((B,), jnp.int32), s((), jnp.int32),
        s((B, C), f32), s((B, C), f32), s((B, N), f32), s((B, N), f32),
        s((B,), jnp.bool_)).compile())


@pytest.mark.parametrize("B", [128, 8, 1])
def test_ssd_step_kernel_by_groups_compiles(one_chip, B):
    """ssd_step with B and C by group at the pool and the three batch
    buckets of nemotron-3-super-120b-a12b.agent-reason (8 groups of 16
    heads: 1,024 channels a group, two lane chunks): the chip's compiler
    takes B and C as [N, G] a row and the static lane slice that picks a
    group's column."""
    from dynamo_tpu.ops.selective_scan import ssd_step

    s = partial(_sds, one_chip)
    S, M, N, C, G = 129, 5, 128, 8192, 8
    f32 = jnp.float32
    assert _has_kernel(ssd_step.lower(
        s((S, M, N, C), f32), s((B,), jnp.int32), s((), jnp.int32),
        s((B, C), f32), s((B, C), f32), s((B, G, N), f32),
        s((B, G, N), f32), s((B,), jnp.bool_)).compile())


@pytest.mark.parametrize("program", ["window", "decode_step", "prefill"])
def test_granite_programs_write_no_array_of_the_state_pools_size(
        one_chip, tpu_kernel_path, program):
    """models/granite.py at the shapes of granite-4.0-h-small.rag-decode
    (matrix-state pool [65, 9, 128, 8192] float32 = 2.28 GiB): the fused
    window (B 64, 4 steps) and decode_step advance the state IN the pool
    through the kernel: no value of the optimized program has the
    gathered rows' shape [64, 9, 128, 8192] (2.25 GiB: gathered and
    scattered back it would be 4.5 GiB a window) and no copy of a pool's
    size exists. A prefill chunk (PB 4 x T 512) gathers its four rows and
    stores them row by row in place: the pool aliases its input and is
    never copied. Every program fits beside the 13.1 GiB resident."""
    granite, cfg, params, kv_k, kv_v, state, e = _granite(one_chip)
    s = partial(_sds, one_chip)
    P, B = e["page_buckets"][-1], e["max_batch"]
    i32, f32 = s((B,), jnp.int32), s((B,), jnp.float32)
    if program == "window":
        compiled = granite.make_decode_window_fn(cfg, True, 64).lower(
            params, i32, i32, s((B,), jnp.bool_), i32, i32, kv_k, kv_v,
            s((B, P), jnp.int32), f32, i32, f32, s((B,), jnp.uint32),
            s((B, 8), jnp.int32), None, state, i32, k_steps=4,
            logprobs_topn=0).compile()
    elif program == "decode_step":
        compiled = granite.make_step_fns(cfg)[1].lower(
            params, i32, i32, kv_k, kv_v, s((B, P), jnp.int32), i32, state,
            i32).compile()
    else:
        PB, T = e["max_prefill_batch"], e["prefill_chunk"]
        compiled = granite.make_step_fns(cfg)[0].lower(
            params, s((PB, T), jnp.int32), s((PB, T), jnp.int32), kv_k,
            kv_v, s((PB, P), jnp.int32), s((PB, T), jnp.int32),
            s((PB,), jnp.int32), s((PB, T // 64), jnp.int32), state,
            s((PB,), jnp.int32)).compile()
    text = compiled.as_text()
    _head_behind_one_conditional(text, program)
    _tails_stay_layer_major(text, state[1], B, program)
    assert _has_kernel(compiled)
    assert _pool_sized_copies(text, state[0].size) == []
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= sum(
        x.size * x.dtype.itemsize for x in (kv_k, kv_v, *state))
    if program != "prefill":
        assert "f32[%d,9,128,8192]" % B not in text
        assert mem.temp_size_in_bytes < 2 ** 29
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < 15.75 * 1024 ** 3)


@pytest.fixture(scope="module")
def state_program(one_chip):
    """program(cell, name) -> (the cell's shapes, the lowered program,
    the compiled one) of a family that carries (state, state_slots)
    behind its K/V operands (``cell``: ``_kimi`` or ``_solar``): the
    fused window at the cell's largest batch and its ``decode_steps``,
    ``decode_step`` at the same batch, a prefill chunk at
    ``max_prefill_batch``. Compiled once a run of this file, under the
    caller's ``tpu_kernel_path``: the pool rules and the weight rules
    read the same program."""
    made = {}

    def program(cell, name):
        if (cell, name) in made:
            return made[cell, name]
        shapes = model, cfg, params, kv_k, kv_v, state, e = cell(one_chip)
        s = partial(_sds, one_chip)
        P, B = e["page_buckets"][-1], e["max_batch"]
        i32, f32 = s((B,), jnp.int32), s((B,), jnp.float32)
        if name == "window":
            lowered = model.make_decode_window_fn(cfg, True, 64).lower(
                params, i32, i32, s((B,), jnp.bool_), i32, i32, kv_k, kv_v,
                s((B, P), jnp.int32), f32, i32, f32, s((B,), jnp.uint32),
                s((B, 8), jnp.int32), None, state, i32,
                k_steps=e.get("decode_steps", 4), logprobs_topn=0)
        elif name == "decode_step":
            lowered = model.make_step_fns(cfg)[1].lower(
                params, i32, i32, kv_k, kv_v, s((B, P), jnp.int32), i32,
                state, i32)
        else:
            PB, T = e["max_prefill_batch"], e["prefill_chunk"]
            lowered = model.make_step_fns(cfg)[0].lower(
                params, s((PB, T), jnp.int32), s((PB, T), jnp.int32), kv_k,
                kv_v, s((PB, P), jnp.int32), s((PB, T), jnp.int32),
                s((PB,), jnp.int32), s((PB, T // e["page_size"]), jnp.int32),
                state, s((PB,), jnp.int32))
        made[cell, name] = shapes, lowered, lowered.compile()
        return made[cell, name]

    return program


def _kimi(one_chip):
    """The configuration as kimi-linear-48b-a3b.doc-reason runs it (8 of
    27 layers, 64 of 256 experts held, every width as published); params
    and the four pools as shapes on the described chip, at the cell's
    engine data."""
    import json

    from dynamo_tpu.models import kimi_linear
    from dynamo_tpu.models.config import ModelConfig

    with open(os.path.join(ROOT, "benchmark", "workloads",
                           "kimi-linear-48b-a3b.doc-reason.json")) as f:
        e = json.load(f)["engine"]
    cfg = ModelConfig.from_local_path(os.path.join(
        ROOT, "benchmark", "configs", "kimi-linear-48b-a3b"))
    params = _on(one_chip, jax.eval_shape(
        lambda: kimi_linear.init_params(cfg, jax.random.PRNGKey(0))))
    kv_k, kv_v = (_on(one_chip, x) for x in jax.eval_shape(
        lambda: kimi_linear.init_kv_cache(
            cfg, llama.KVCacheSpec(e["num_pages"], e["page_size"]))))
    state = _on(one_chip, jax.eval_shape(
        lambda: kimi_linear.init_state(cfg, e["max_batch"] + 1)))
    assert state[0].shape == (129, 6, 128, 4096)    # 2 MiB a layer a row
    return kimi_linear, cfg, params, kv_k, kv_v, state, e


@pytest.mark.parametrize("B", [128, 8, 1])
def test_kda_step_kernel_compiles(one_chip, B):
    """ops/kda.py kda_step at the new cell's pool and its three batch
    buckets: the chip's compiler takes the 2 MiB row copies, the 6 MiB
    ring in VMEM, the [128, 32] blocks of q, k and the decay and the
    unrolled loop over a row's 32 heads."""
    from dynamo_tpu.ops.kda import kda_step

    s = partial(_sds, one_chip)
    S, M, N, H, dv = 129, 6, 128, 32, 128
    f32 = jnp.float32
    assert _has_kernel(kda_step.lower(
        s((S, M, N, H * dv), f32), s((B,), jnp.int32), s((), jnp.int32),
        s((B, H, N), f32), s((B, H, N), f32), s((B, H, dv), f32),
        s((B, H, N), f32), s((B, H), f32), s((B,), jnp.bool_)).compile())


@pytest.mark.parametrize("B", [8, 1])
def test_kda_chunk_kernel_compiles(one_chip, B):
    """ops/kda.py kda_chunk at the cell's prefill programs (PB 8 and PB 1
    x T 512, 32 heads of 128 x 128): the chip's compiler takes the
    float32 products at Precision.HIGHEST (Mosaic's fp32 contraction),
    the [128, 128] tables of a grid step, the lane rotations that place
    the off-diagonal pieces and the transposes; the kernel is in the
    program under its name. On the chip tools/kda_chunk_timing.py runs
    it against _kda_chunk on the same operands: state and outputs agree
    to 2e-5 of the largest value (it reads 3e-6: two HIGHEST forms; a
    single-pass bfloat16 product anywhere in the scan reads 1e-2)."""
    from dynamo_tpu.ops import kda

    s = partial(_sds, one_chip)
    T, H, d = 512, 32, 128
    f32 = jnp.float32
    compiled = kda.kda_chunk.lower(
        s((B, d, H * d), f32), s((B, T, H, d), f32), s((B, T, H, d), f32),
        s((B, T, H, d), f32), s((B, T, H, d), f32),
        s((B, T, H), f32)).compile()
    assert _has_kernel(compiled) and kda.CHUNK_NAME in compiled.as_text()


@pytest.mark.parametrize("program", ["window", "decode_step", "prefill"])
def test_kimi_linear_programs_write_no_array_of_a_pools_size(
        tpu_kernel_path, state_program, program):
    """models/kimi_linear.py at the shapes of
    kimi-linear-48b-a3b.doc-reason (matrix-state pool [129, 6, 128, 4096]
    float32 = 1.51 GiB; latent pools of the 2 attending layers, 0.875 +
    0.22 GiB): the fused window (B 128, 4 steps) and decode_step advance
    the state IN the pool through the kernel: no value of the optimized
    program has the gathered rows' shape [128, 6, 128, 4096] (1.5 GiB:
    gathered and scattered back it would be 3 GiB a window) and no copy
    of a pool's size exists, the state's or the latents'. A prefill
    chunk (PB 8 x T 512) gathers its eight rows and stores them row by
    row in place, and commits its latents once a pool: the pools alias
    their inputs and are never copied. Every program fits beside the
    10.7 GiB resident."""
    (_, cfg, params, kv_k, kv_v, state, e), _, compiled = state_program(
        _kimi, program)
    B = e["max_batch"]
    text = compiled.as_text()
    _head_behind_one_conditional(text, program)
    _tails_stay_layer_major(text, state[1], B, program)
    assert _has_kernel(compiled)
    # a chunk's scan is the chunk kernel, a token's the step kernel
    assert ("kda_chunk" in text) == (program == "prefill")
    assert ("kda_step" in text) == (program != "prefill")
    # no copy of the state pool's size or more, of a pool or of weights
    # (the experts' stacks: test_dense_experts_relay_no_expert_stack)
    assert _pool_sized_copies(text, state[0].size) == []
    for pool in (state[0], kv_k, kv_v):
        dims = ",".join(map(str, pool.shape))
        assert not re.search(
            r" = \w+\[%s\]\S* (?:copy|copy-start)\(" % dims, text), dims
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= sum(
        x.size * x.dtype.itemsize for x in (kv_k, kv_v, *state))
    if program != "prefill":
        assert "f32[%d,6,128,4096]" % B not in text
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < 15.75 * 1024 ** 3)


def _solar(one_chip):
    """The configuration as solar-open2-250b.long-reason runs it (4 of 48
    layers, 40 of 320 experts held, an eighth of the vocabulary, every
    width as published); params and the four pools as shapes on the
    described chip, at the cell's engine data."""
    import json

    from dynamo_tpu.models import solar_open2
    from dynamo_tpu.models.config import ModelConfig

    with open(os.path.join(ROOT, "benchmark", "workloads",
                           "solar-open2-250b.long-reason.json")) as f:
        e = json.load(f)["engine"]
    cfg = ModelConfig.from_local_path(os.path.join(
        ROOT, "benchmark", "configs", "solar-open2-250b"))
    params = _on(one_chip, jax.eval_shape(
        lambda: solar_open2.init_params(cfg, jax.random.PRNGKey(0))))
    kv_k, kv_v = (_on(one_chip, x) for x in jax.eval_shape(
        lambda: solar_open2.init_kv_cache(
            cfg, llama.KVCacheSpec(e["num_pages"], e["page_size"]))))
    state = _on(one_chip, jax.eval_shape(
        lambda: solar_open2.init_state(cfg, e["max_batch"] + 1)))
    assert state[0].shape == (129, 3, 128, 8192)    # 4 MiB a layer a row
    assert kv_k.shape == (1, e["num_pages"], 8, 128, 128)
    return solar_open2, cfg, params, kv_k, kv_v, state, e


@pytest.mark.parametrize("B", [128, 1])
def test_kda_step_kernel_compiles_at_64_heads(one_chip, B):
    """ops/kda.py kda_step at Solar Open 2's pool: the chip's compiler
    takes the 4 MiB row copies, the 12 MiB ring in VMEM (28 MiB asked
    for), the [128, 64] blocks of q, k and the decay and the unrolled
    loop over a row's 64 heads."""
    from dynamo_tpu.ops.kda import kda_step

    s = partial(_sds, one_chip)
    S, M, N, H, dv = 129, 3, 128, 64, 128
    f32 = jnp.float32
    assert _has_kernel(kda_step.lower(
        s((S, M, N, H * dv), f32), s((B,), jnp.int32), s((), jnp.int32),
        s((B, H, N), f32), s((B, H, N), f32), s((B, H, dv), f32),
        s((B, H, N), f32), s((B, H), f32), s((B,), jnp.bool_)).compile())


@pytest.mark.parametrize("program", ["window", "decode_step", "prefill"])
def test_solar_open2_programs_write_no_array_of_a_pools_size(
        tpu_kernel_path, state_program, program):
    """models/solar_open2.py at the shapes of solar-open2-250b.long-reason
    (matrix-state pool [129, 3, 128, 8192] float32 = 1.51 GiB; K and V
    pools of the one attending layer, 1.5 GiB each): the fused window (B
    128, 4 steps) and decode_step advance the state IN the pool through
    the kernel and read K/V through the GQA decode kernel: no value of
    the optimized program has the gathered rows' shape [128, 3, 128,
    8192] and no copy of a pool's size exists, the state's or (in the
    window, the program a cell serves decode with) K/V's. A
    prefill chunk (PB 8 x T 512) runs the chunk kernel and the GQA
    prefill kernel in one program, gathers its eight rows and stores
    them row by row in place: the pools alias their inputs and are never
    copied. The gate is in every program under its scope. Every program
    fits beside the 10.7 GiB resident."""
    (_, cfg, params, kv_k, kv_v, state, e), lowered, compiled = (
        state_program(_solar, program))
    B = e["max_batch"]
    assert "attn.gate" in lowered.as_text(debug_info=True)
    text = compiled.as_text()
    _head_behind_one_conditional(text, program)
    _tails_stay_layer_major(text, state[1], B, program)
    assert _has_kernel(compiled)
    # a chunk's scan is the chunk kernel, a token's the step kernel; the
    # attention beside it is the GQA kernel of the same kind
    assert ("kda_chunk" in text) == (program == "prefill")
    assert ("kda_step" in text) == (program != "prefill")
    assert ("paged_attention_prefill" if program == "prefill"
            else "paged_attention_decode") in text
    # no copy of the state pool's size or more, of a pool or of weights.
    # decode_step (K = 1 without the window: no cell's served path, the
    # program the window is tested against) writes its token's K/V by
    # jamba.GQA's flat scatter, which relays the K/V pools out, as for
    # Jamba and Granite: only the state pool is held to the rule there
    served = program != "decode_step"
    assert not served or _pool_sized_copies(text, state[0].size) == []
    for pool in (state[0], kv_k, kv_v) if served else (state[0],):
        dims = ",".join(map(str, pool.shape))
        assert not re.search(
            r" = \w+\[%s\]\S* (?:copy|copy-start)\(" % dims, text), dims
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= sum(
        x.size * x.dtype.itemsize for x in (kv_k, kv_v, *state))
    if program != "prefill":
        assert "f32[%d,3,128,8192]" % B not in text
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < 15.75 * 1024 ** 3)


def test_kernel_cache_key_does_not_hold_the_checkout_path(one_chip):
    """The Pallas kernel's serialized module rides inside the
    tpu_custom_call's opaque config, source locations included: without
    the canonicalization that enable_compile_cache() sets, every
    kernel-bearing program's cache key depends on where the checkout
    lies (a run from an unpacked `git archive` recompiled everything)."""
    import base64
    import re

    from dynamo_tpu.runtime import compile_cache

    s = partial(_sds, one_chip)
    w = WIDTHS["1b"]

    def kernel_body(B) -> bytes:
        # a batch size of its own per call: traces and kernel lowerings
        # are cached per shape within a process
        k = s((NUM_PAGES, w["KV"], PS, w["hd"]), jnp.bfloat16)
        text = pa.paged_attention_decode.lower(
            s((B, w["H"], w["hd"]), jnp.bfloat16), k, k,
            s((B, P_DEC), jnp.int32), s((B,), jnp.int32)).compiler_ir() \
            .operation.get_asm(enable_debug_info=False)
        body = re.search(r'body\\22: \\22([A-Za-z0-9+/=]+)\\22', text)
        return base64.b64decode(body.group(1))

    checkout = compile_cache._CHECKOUT.encode()
    knob = "jax_hlo_source_file_canonicalization_regex"
    was = getattr(jax.config, knob)
    try:
        jax.config.update(knob, None)  # an earlier test may have set it
        assert checkout in kernel_body(24), "the path rides in by default"
        jax.config.update(knob, re.escape(compile_cache._CHECKOUT + os.sep))
        body = kernel_body(40)
        assert checkout not in body and b"paged_attention.py" in body
    finally:
        jax.config.update(knob, was)


# ---- two kinds of layer, a pool each, at SmallThinker's widths (cell 9)


def _compile_by_kind(one_chip, model, cfg, params, kv, wkv, slots, e, program,
                     PB=None):
    """One of the three serving programs of a configuration with a pool a
    kind of layer (``model`` = the module whose step functions the cell's
    engine takes), compiled at the cell's engine data ``e``: the fused
    window and decode_step at ``max_batch`` rows, a prefill chunk at
    ``PB`` rows (the cell's largest prefill batch unless given)."""
    s = partial(_sds, one_chip)
    kv_k, kv_v = kv
    P, B = e["page_buckets"][-1], e["max_batch"]
    i32, f32 = s((B,), jnp.int32), s((B,), jnp.float32)
    if program == "window":
        return model.make_decode_window_fn(cfg, True, 64).lower(
            params, i32, i32, s((B,), jnp.bool_), i32, i32, kv_k, kv_v,
            s((B, P), jnp.int32), f32, i32, f32, s((B,), jnp.uint32),
            s((B, 8), jnp.int32), None, wkv,
            (s((B, slots), jnp.int32), i32),
            k_steps=e.get("decode_steps", 4), logprobs_topn=0).compile()
    if program == "decode_step":
        return model.make_step_fns(cfg)[1].lower(
            params, i32, i32, kv_k, kv_v, s((B, P), jnp.int32), i32, wkv,
            (s((B, slots), jnp.int32), i32, s((B, 1), jnp.int32))).compile()
    PB, T = PB or e["max_prefill_batch"], e["prefill_chunk"]
    return model.make_step_fns(cfg)[0].lower(
        params, s((PB, T), jnp.int32), s((PB, T), jnp.int32), kv_k,
        kv_v, s((PB, P), jnp.int32), s((PB, T), jnp.int32),
        s((PB,), jnp.int32), s((PB, T // 64), jnp.int32), wkv,
        (s((PB, slots), jnp.int32), s((PB,), jnp.int32),
         s((PB, T // 64), jnp.int32))).compile()


def _smallthinker(one_chip):
    """The configuration as smallthinker-21b-a3b.mixed-length runs it (8
    of 52 layers, every width as published); params, both kinds' pools
    and the rows' tables into the window layers' pool as shapes on the
    described chip, at the cell's engine data."""
    import json

    from dynamo_tpu.models.config import ModelConfig

    with open(os.path.join(ROOT, "benchmark", "workloads",
                           "smallthinker-21b-a3b.mixed-length.json")) as f:
        e = json.load(f)["engine"]
    cfg = ModelConfig.from_local_path(os.path.join(
        ROOT, "benchmark", "configs", "smallthinker-21b-a3b"))
    params = _on(one_chip, jax.eval_shape(
        lambda: llama.init_params(cfg, jax.random.PRNGKey(0))))
    kv = tuple(_on(one_chip, x) for x in jax.eval_shape(
        lambda: llama.init_kv_cache(cfg, llama.KVCacheSpec(e["num_pages"],
                                                           64))))
    wkv = tuple(_on(one_chip, x) for x in jax.eval_shape(
        lambda: llama.init_window_kv_cache(
            cfg, llama.KVCacheSpec(e["window_pages"], 64))))
    assert kv[0].shape == (2, 5632, 4, 64, 128)
    assert wkv[0].shape == (6, 3520, 4, 64, 128)
    slots = llama.window_table_slots(cfg, 64, e["prefill_chunk"])
    assert slots == 73      # the window, not the context of 217 pages
    return cfg, params, kv, wkv, slots, e


@pytest.fixture(scope="module")
def by_kind_program(one_chip):
    """program(cell, name, PB=None) -> (model, cfg, params, kv, wkv,
    slots, e, the compiled program) of cell 9 (``smallthinker``) or 12
    (``command_a``) through ``_compile_by_kind``, compiled once a run of
    this file under the caller's ``tpu_kernel_path``: the pool rules and
    the weight rule read the same window and the same ``decode_step``."""
    made = {}

    def program(cell, name, PB=None):
        key = cell, name, PB if name == "prefill" else None
        if key not in made:
            shapes = (_command_a(one_chip) if cell == "command_a"
                      else (llama, *_smallthinker(one_chip)))
            made[key] = *shapes, _compile_by_kind(one_chip, *shapes, name,
                                                  PB=PB)
        return made[key]

    return program


@pytest.mark.parametrize("program", ["window", "decode_step", "prefill"])
def test_smallthinker_programs_write_no_array_of_either_pools_size(
        by_kind_program, tpu_kernel_path, program):
    """models/llama.py by kind at the shapes of smallthinker-21b-a3b.
    mixed-length (full layers' pool [2, 5632, ...] = 0.69 GiB each of K
    and V, window layers' [6, 3520, ...] = 1.29 GiB each): the fused
    window (B 48, 4 steps) reads both pools where they lie and commits
    each kind's K/V by whole pages in place; decode_step writes its one
    token a row the same way; a prefill chunk (PB 8 x T 512) carries the
    pools through its scan over the periods and scatters whole pages.
    No copy of either pool's size exists, all four pools alias their
    inputs, and the temporaries stay far under a pool (as scanned xs /
    ys, llama.forward's form until PR 49, the pools were 6.27 GiB of
    temporaries: scratch compile, PR 46)."""
    _, cfg, params, (kv_k, kv_v), wkv, slots, e, compiled = by_kind_program(
        "smallthinker", program)
    text = compiled.as_text()
    _head_behind_one_conditional(text, program)
    assert _has_kernel(compiled)
    assert _pool_sized_copies(text, kv_k.size) == []    # the smaller pool
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= sum(
        x.size * x.dtype.itemsize for x in (kv_k, kv_v, *wkv))
    assert mem.temp_size_in_bytes < (2 ** 29 if program != "prefill"
                                     else 1.25 * 2 ** 30)
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < 15.75 * 1024 ** 3)


# ---- a parallel block, 128 / 8 heads, two pools at 530 pages (cell 12)


def _command_a(one_chip):
    """The configuration as command-a-plus-05-2026.rag-long runs it (4 of
    32 layers, 16 of 128 experts, every width as published); params,
    both kinds' pools and the rows' tables into the window layers' pool
    as shapes on the described chip, at the cell's engine data."""
    import json

    from dynamo_tpu.models import cohere2_moe
    from dynamo_tpu.models.config import ModelConfig

    with open(os.path.join(ROOT, "benchmark", "workloads",
                           "command-a-plus-05-2026.rag-long.json")) as f:
        e = json.load(f)["engine"]
    cfg = ModelConfig.from_local_path(os.path.join(
        ROOT, "benchmark", "configs", "command-a-plus-05-2026"))
    params = _on(one_chip, jax.eval_shape(
        lambda: cohere2_moe.init_params(cfg, jax.random.PRNGKey(0))))
    kv = tuple(_on(one_chip, x) for x in jax.eval_shape(
        lambda: cohere2_moe.init_kv_cache(
            cfg, llama.KVCacheSpec(e["num_pages"], 64))))
    wkv = tuple(_on(one_chip, x) for x in jax.eval_shape(
        lambda: cohere2_moe.init_window_kv_cache(
            cfg, llama.KVCacheSpec(e["window_pages"], 64))))
    assert kv[0].shape == (1, e["num_pages"], 8, 64, 128)
    assert wkv[0].shape == (3, e["window_pages"], 8, 64, 128)
    slots = llama.window_table_slots(cfg, 64, e["prefill_chunk"])
    assert slots == 73      # the window, not the context of 530 pages
    assert e["page_buckets"][-1] * 64 >= 33920
    return cohere2_moe, cfg, params, kv, wkv, slots, e


@pytest.mark.parametrize("program", ["window", "decode_step", "prefill"])
def test_command_a_programs_write_no_array_of_either_pools_size(
        by_kind_program, tpu_kernel_path, program):
    """models/llama.py by kind in its parallel form at the shapes of
    command-a-plus-05-2026.rag-long (the full layer's pool [1, 7232, ...]
    and the window layers' [3, 2337, ...], 0.88 GiB and 0.86 GiB each of
    K and V; a table of 531 pages a row = 33,984 tokens): the fused
    window (B 32) reads both pools where they lie and commits each
    kind's K/V by whole pages in place and returns its pair counts;
    decode_step writes its one token a row the same way; a prefill chunk
    (PB 8 x T 512, 128 query heads) carries the pools through its scan
    and scatters whole pages. No copy of either pool's size exists, all
    four pools alias their inputs, and arguments + temporaries fit the
    chip."""
    _, cfg, params, (kv_k, kv_v), wkv, slots, e, compiled = by_kind_program(
        "command_a", program)
    text = compiled.as_text()
    _head_behind_one_conditional(text, program)
    assert _has_kernel(compiled)
    assert _pool_sized_copies(text, wkv[0].size) == []  # the smaller pool
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= sum(
        x.size * x.dtype.itemsize for x in (kv_k, kv_v, *wkv))
    assert mem.temp_size_in_bytes < (0.75 * 2 ** 30 if program != "prefill"
                                     else 1.5 * 2 ** 30)
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < 15.75 * 1024 ** 3)


@pytest.mark.parametrize("kernel", ["decode", "prefill"])
def test_attention_kernels_lower_at_sixteen_query_heads_a_kv_head(one_chip,
                                                                 kernel):
    """128 query heads over 8 KV heads of 128 (a q block of [8, 16, 128]
    a row): the decode kernel at the cell's 32 rows over a 531-page
    table with a window's lower bound, the prefill kernel at PB 8 x T
    512 over the same table, at the sizes their rules by shape give."""
    s = partial(_sds, one_chip)
    B, P, KV, G = 32, 531, 8, 16
    if kernel == "decode":
        k = s((3, 2337, KV, 64, 128), jnp.bfloat16)
        lengths = s((B,), jnp.int32)
        lowered = pa.paged_attention_decode_layered.lower(
            s((B, KV * G, 128), jnp.bfloat16), k, k, s((), jnp.int32),
            s((B, P), jnp.int32), lengths, return_stats=True, lower=lengths)
    else:
        k = s((7232, KV, 64, 128), jnp.bfloat16)
        lowered = pa.paged_attention_prefill.lower(
            s((8, 512, KV * G, 128), jnp.bfloat16), k, k,
            s((8, P), jnp.int32), s((8, 512), jnp.int32),
            eff_win=s((8,), jnp.int32))
    assert _has_kernel(lowered.compile())


# ------- the q / k / v products leave the weights where they lie (PR 57)


_RELAYS = {"parameter", "constant", "slice", "dynamic-slice", "bitcast",
           "copy", "transpose", "reshape"}


def _weight_sized_relayouts(text: str, least: int, pools=()):
    """(name, shape, op) of the optimized program's instructions that RUN
    (in the entry computation, a loop's body or a branch: not a fusion's
    own producers) and only MOVE at least ``least`` elements: a `copy`
    that changes the layout (one that changes the memory space alone is
    the compiler's prefetch), a `reshape`, a `transpose`, or a fusion
    whose body only slices, bitcasts, copies or transposes. Results of a
    pool's element count (``pools``) are the page commits' and not
    these."""
    comps = _computations(text)
    runs, todo = set(), [next(n for n, body in comps.items()
                              if body.startswith("ENTRY "))]
    while todo:
        name = todo.pop()
        if name in runs:
            continue
        runs.add(name)
        for line in comps[name].splitlines():
            if re.search(r" (?:while|call|conditional)\(", line):
                todo += re.findall(
                    r"(?:body|condition|to_apply|true_computation|"
                    r"false_computation)=%([\w.\-]+)", line)
                for group in re.findall(r"branch_computations=\{([^}]*)\}",
                                        line):
                    todo += re.findall(r"%([\w.\-]+)", group)
    shape = r"\w+\[[\d,]*\](?:\{[^}]*\})?"
    inst = re.compile(rf"\s*(?:ROOT )?%([\w.\-]+) = \(?({shape})"
                      rf"(?:, ({shape}))?[^=]*? ([\w\-]+)\((?:%([\w.\-]+))?")
    lines = {name: [(m, line) for line in comps[name].splitlines()
                    if (m := inst.match(line))] for name in comps}
    shapes = {m.group(1): m.group(2) for found in lines.values()
              for m, _ in found}

    def laid(s):        # a shape without its memory space
        return re.sub(r"S\(\d+\)", "", s)

    out = []
    for name in runs:
        for m, line in lines[name]:
            res, first, second, op, operand = m.groups()
            if op == "copy-start":      # (the operand's, the result's, ..)
                src, first = first, second
            else:
                src = shapes.get(operand, "")
            n = 1
            for d in filter(None, re.search(r"\[([\d,]*)\]",
                                            first).group(1).split(",")):
                n *= int(d)
            if n < least or n in pools:
                continue
            if op in ("copy", "copy-start"):
                moved = laid(src) != laid(first)
            elif op == "fusion":
                body = comps[re.search(r"calls=%([\w.\-]+)", line).group(1)]
                moved = set(re.findall(r" = \S+ ([\w\-]+)\(", body)) <= _RELAYS
            else:
                moved = op in ("reshape", "transpose")
            if moved:
                out.append((res, first, op))
    return out


def test_a_weight_sized_relayout_is_recognised():
    text = """HloModule m, is_scheduled=true

%fused_computation.1 (p: bf16[4,64,32]) -> bf16[64,32] {
  %p = bf16[4,64,32]{2,1,0:T(8,128)(2,1)} parameter(0)
  %slice.1 = bf16[1,64,32]{1,2,0:T(8,128)(2,1)} slice(%p), slice={[1:2], [0:64], [0:32]}
  ROOT %bitcast.1 = bf16[64,32]{0,1:T(8,128)(2,1)} bitcast(%slice.1)
}

%fused_computation.2 (p.1: bf16[4,64,32], x: bf16[8,64]) -> bf16[8,32] {
  %p.1 = bf16[4,64,32]{2,1,0:T(8,128)(2,1)} parameter(0)
  %x = bf16[8,64]{1,0:T(8,128)(2,1)} parameter(1)
  %fusion.9 = bf16[64,32]{1,0:T(8,128)(2,1)} fusion(%p.1), kind=kLoop, calls=%fused_computation.1
  ROOT %convolution.1 = bf16[8,32]{1,0:T(8,128)(2,1)} convolution(%x, %fusion.9), dim_labels=bf_io->bf
}

%body.1 (t: (bf16[4,64,32], bf16[8,64])) -> (bf16[4,64,32], bf16[8,64]) {
  %t = (bf16[4,64,32]{2,1,0:T(8,128)(2,1)}, bf16[8,64]{1,0:T(8,128)(2,1)}) parameter(0)
  %w = bf16[4,64,32]{2,1,0:T(8,128)(2,1)} get-tuple-element(%t), index=0
  %reshape.7 = bf16[16,2,64]{2,1,0:T(2,128)(2,1)} reshape(%copy.3)
  ROOT %tuple.1 = (bf16[4,64,32]{2,1,0:T(8,128)(2,1)}, bf16[8,64]{1,0:T(8,128)(2,1)}) tuple(%w, %x.1)
}

%cond.1 (t.1: (bf16[4,64,32], bf16[8,64])) -> pred[] {
  %t.1 = (bf16[4,64,32]{2,1,0:T(8,128)(2,1)}, bf16[8,64]{1,0:T(8,128)(2,1)}) parameter(0)
  ROOT %constant.1 = pred[] constant(false)
}

ENTRY %main.1 (w.1: bf16[4,64,32], x.1: bf16[8,64]) -> bf16[8,32] {
  %w.1 = bf16[4,64,32]{2,1,0:T(8,128)(2,1)} parameter(0)
  %x.1 = bf16[8,64]{1,0:T(8,128)(2,1)} parameter(1)
  %fusion.2 = bf16[64,32]{0,1:T(8,128)(2,1)} fusion(%w.1), kind=kLoop, calls=%fused_computation.1
  %copy.3 = bf16[64,32]{1,0:T(8,128)(2,1)} copy(%fusion.2)
  %copy-start.4 = (bf16[64,32]{1,0:T(8,128)(2,1)}, bf16[64,32]{1,0:T(8,128)(2,1)S(1)}, u32[]{:S(2)}) copy-start(%copy.3)
  %copy-done.4 = bf16[64,32]{1,0:T(8,128)(2,1)S(1)} copy-done(%copy-start.4)
  %copy.5 = bf16[64,32]{1,0:T(8,128)(2,1)S(1)} copy(%copy.3)
  %fusion.6 = bf16[8,32]{1,0:T(8,128)(2,1)} fusion(%w.1, %x.1), kind=kOutput, calls=%fused_computation.2
  %while.1 = (bf16[4,64,32]{2,1,0:T(8,128)(2,1)}, bf16[8,64]{1,0:T(8,128)(2,1)}) while(%tuple.0), condition=%cond.1, body=%body.1
  ROOT %copy.8 = bf16[8,32]{0,1:T(8,128)(2,1)} copy(%fusion.6)
}
"""
    found = _weight_sized_relayouts(text, 64 * 32)
    # the slice seen transposed, its transposition, the reshape in the
    # loop; not the prefetches (copy-start.4, copy.5: the memory space
    # alone), not the slice inside the product's own fusion (fusion.9),
    # not the product (fusion.6), not the small copy (copy.8)
    assert sorted(n for n, _, _ in found) == ["copy.3", "fusion.2",
                                              "reshape.7"]
    assert _weight_sized_relayouts(text, 64 * 32, pools=(64 * 32,)) == []


@pytest.mark.parametrize("program", ["window", "decode_step", "prefill"])
@pytest.mark.parametrize("cell", ["command_a", "smallthinker"])
def test_by_kind_programs_relay_no_weight(by_kind_program, tpu_kernel_path,
                                          cell, program):
    """models/llama.py by kind, cells 12 and 9: the q / k / v products
    hand their results over behind a fence (``llama._qkv``), so the
    layout the heads' consumers want is paid on q. Without it the
    compiler asks the product for q head-major, turns it into a
    convolution over the head axis, relays the layer's WEIGHT for it
    (cell 12: `slice_bitcast_fusion` + `copy bf16[16384,4096]` and, where
    a layer rotates interleaved pairs, `reshape bf16[128,64,2,4096]`;
    cell 9: `copy bf16[8,2560,3584]` and two of `bf16[8,2560,512]`) and,
    the stacks being program parameters, does so once an execution at
    the program's entry: 4.0 ms of cell 12's 70.5 ms window, 4.8 ms of
    its 30.2 ms prefill chunk (ledger, PR 56). A prefill chunk is
    compiled at ONE row, the smallest program of the warm grid: its
    activations are far under a layer's wq, so whatever moves that many
    elements and is no pool is a weight."""
    _, cfg, params, kv, wkv, _, _, compiled = by_kind_program(cell, program,
                                                              PB=1)
    assert _has_kernel(compiled)
    _head_behind_one_conditional(compiled.as_text(), program)
    assert _weight_sized_relayouts(
        compiled.as_text(), params["wq"].size // cfg.num_layers,
        pools=(kv[0].size, wkv[0].size)) == []
    if cell == "command_a" and program == "window":
        # 617.6 MiB with the three relaid wq among them, 19.8 behind the
        # fence (scratch compile, PR 57)
        assert compiled.memory_analysis().temp_size_in_bytes < 128 * 2 ** 20


# ----- the dense-over-experts down product reads w_down where it lies (PR 59)


@pytest.mark.parametrize("cell,program", [
    ("kimi", "window"), ("kimi", "decode_step"), ("solar", "window"),
    ("solar", "decode_step"), ("lfm2", "prefill")])
def test_dense_experts_relay_no_expert_stack(one_chip, tpu_kernel_path,
                                             state_program, cell, program):
    """llama.moe_experts' dense arm at the row counts where the compiler
    used to relay the experts' stack: the B 128 window and decode_step of
    cells 10 and 11 (128 x 1 rows) and cell 6's PB 1 x T 256 prefill
    chunk (1 x 256 rows, all 64 experts). The down product contracts (e,
    i) at once on [E, I, D] as stored, so no instruction that runs only
    MOVES a layer's w_down_e in elements or more and is no pool. With the
    product that kept e (`btei,eid->bted`) the compiler wanted I minor on
    w_down from 128 rows up and, the stack being sliced a layer inside
    the loop over layers, relaid ALL layers at the program's entry, once
    an execution: `copy bf16[7,64,1024,2304]{2,3,1,0}` in both of cell
    10's programs (6.5 ms of an 85 ms window, 1.97 GiB of temporaries)
    and `copy bf16[4,40,1280,4096]{2,3,1,0}` in cell 11's window (5.0 ms
    of 142): those three cases fail on the parent of PR 59. Cell 11's
    decode_step and cell 6's chunk hold there too. Cell 6's is a limit
    of this way of compiling: lowered from shapes, here or on the chip
    itself, the parent's program shows no copy, while the program the
    engine compiles from its arrays cost 22.4 ms on the chip for the
    14.2 it costs now (warmup()'s timed table, PERF.md, PR 59; PR 33
    saw `copy bf16[6,64,1536,2048]` in its trace). Since PR 66 the
    chunk's 256 rows stand past the shape rule's edge (the chip's ridge)
    and take the sorted dispatch, which reads w[layer, expert] in place:
    the case keeps the shape under the rule, whichever form that picks.
    A layer's w_gate_e / w_up_e have the same
    element count, so the rule holds them as well (the flat `[N, E*I] @
    [E*I, D]` form relays those two instead)."""
    if cell == "lfm2":
        shapes = _, cfg, params, kv_k, kv_v, state, e = _lfm2(one_chip,
                                                              experts=64)
        assert 256 in e["prefill_buckets"]
        compiled = _lfm2_prefill(one_chip, shapes, 1, 256)
    else:
        (_, cfg, params, kv_k, kv_v, state, e), _, compiled = state_program(
            {"kimi": _kimi, "solar": _solar}[cell], program)
    down = params["w_down_e"]
    assert down.shape[1:] == (cfg.num_experts, cfg.moe_intermediate_size,
                              cfg.hidden_size)
    assert _weight_sized_relayouts(
        compiled.as_text(), down.size // down.shape[0],
        pools=tuple(x.size for x in (kv_k, kv_v, *state))) == []
    if (cell, program) == ("kimi", "window"):
        # 2,386.9 MiB with the relaid stack among them, 334.0 without
        # (scratch compile, PR 59)
        assert compiled.memory_analysis().temp_size_in_bytes < 512 * 2 ** 20


# ----- a prefill program holds its head behind ONE conditional (PR 64)


def _head_reads(text: str, leaf: str = "lm_head"):
    """(the conditionals among the optimized program's instructions, the
    instructions of its entry computation that read ``params[leaf]`` and
    are neither a conditional nor the tuple one takes as a branch's
    operand): what llama.prefill_logits asks of a prefill program is
    (one, none)."""
    comps = _computations(text)
    conds = [line for body in comps.values() for line in body.splitlines()
             if re.search(r" conditional\(", line)]
    entry = next(b for b in comps.values() if b.startswith("ENTRY "))
    head = re.search(r"%([\w.\-]+) = [^\n]* parameter\(\d+\)[^\n]*"
                     r"op_name=\"params\[\\'" + leaf + r"\\'\]\"", entry)
    if head is None:        # the embedding's transpose is the head
        return conds, []
    handed = {m for line in conds for m in re.findall(r"%([\w.\-]+)", line)}
    reads = re.compile(r"[(,] ?%%%s[,)]" % re.escape(head.group(1)))
    return conds, [
        m.group(1) for line in entry.splitlines()
        if reads.search(line)
        and (m := re.match(r"\s*(?:ROOT )?%([\w.\-]+) = ", line))
        and not (re.search(r" conditional\(", line)
                 or (re.search(r" tuple\(", line) and m.group(1) in handed))]


def _head_behind_one_conditional(text: str, program: str = "prefill"):
    """A prefill program's only conditional is llama.prefill_logits', and
    nothing outside it reads the head (the other programs: not asked)."""
    if program.startswith("prefill"):
        conds, outside = _head_reads(text)
        assert len(conds) == 1 and outside == []


def test_a_read_of_the_head_outside_the_conditional_is_recognised():
    text = """HloModule m, is_scheduled=true

%zeros (e: ()) -> (f32[2,32]) {
  %e = () parameter(0)
  %c = f32[]{:T(128)} constant(0)
  %b = f32[2,32]{1,0:T(2,128)} broadcast(%c), dimensions={}
  ROOT %t = (f32[2,32]{1,0:T(2,128)}) tuple(%b)
}

%project (a: (bf16[64,32], bf16[2,64])) -> (f32[2,32]) {
  %a = (bf16[64,32]{1,0:T(8,128)(2,1)}, bf16[2,64]{1,0:T(8,128)(2,1)}) parameter(0)
  %w = bf16[64,32]{1,0:T(8,128)(2,1)} get-tuple-element(%a), index=0
  %x = bf16[2,64]{1,0:T(8,128)(2,1)} get-tuple-element(%a), index=1
  %d = f32[2,32]{1,0:T(2,128)} convolution(%x, %w), dim_labels=bf_io->bf
  ROOT %t.1 = (f32[2,32]{1,0:T(2,128)}) tuple(%d)
}

ENTRY %main.1 (head.1: bf16[64,32], x.1: bf16[2,64], p.1: pred[]) -> f32[2,32] {
  %head.1 = bf16[64,32]{1,0:T(8,128)(2,1)} parameter(0), metadata={op_name="params[\\'lm_head\\']"}
  %x.1 = bf16[2,64]{1,0:T(8,128)(2,1)} parameter(1), metadata={op_name="x"}
  %p.1 = pred[]{:T(128)} parameter(2), metadata={op_name="p"}
  %tuple.2 = (bf16[64,32]{1,0:T(8,128)(2,1)}, bf16[2,64]{1,0:T(8,128)(2,1)}) tuple(%head.1, %x.1)
  %tuple.3 = () tuple()
  %cond.1 = (f32[2,32]{1,0:T(2,128)}) conditional(%p.1, %tuple.2, %tuple.3), true_computation=%project, false_computation=%zeros
  ROOT %g = f32[2,32]{1,0:T(2,128)} get-tuple-element(%cond.1), index=0
}
"""
    conds, outside = _head_reads(text)
    assert len(conds) == 1 and outside == []
    # the head copied on its way in, and read by an op of the entry
    copied = text.replace(
        "  %tuple.2 = ", "  %copy.9 = bf16[64,32]{0,1:T(8,128)(2,1)} "
        "copy(%head.1)\n  %tuple.2 = ")
    assert _head_reads(copied)[1] == ["copy.9"]
    plain = text.replace("tuple(%head.1, %x.1)", "tuple(%x.1, %x.1)").replace(
        "  %tuple.3 = ", "  %dot.4 = f32[2,32]{1,0:T(2,128)} "
        "convolution(%x.1, %head.1), dim_labels=bf_io->bf\n  %tuple.3 = ")
    assert _head_reads(plain)[1] == ["dot.4"]
    # a tuple that no conditional takes is a read
    loose = text.replace("conditional(%p.1, %tuple.2, %tuple.3)",
                         "conditional(%p.1, %tuple.3, %tuple.3)")
    assert _head_reads(loose)[1] == ["tuple.2"]


@pytest.fixture(scope="module")
def phi4flash_cell(one_chip):
    from tools.cell_programs import Cell

    return Cell("phi-4-mini-flash-reasoning.long-think", one_chip)


def test_phi4flash_prefill_keeps_head_and_cross_half_in_one_conditional(
        phi4flash_cell, tpu_kernel_path, PB=1):
    """Cell 14's PB 1 x T 512 prefill bucket (every width and the engine
    data of the cell; PB 8 reads the same by the scratch compile of PR
    64): ONE conditional holds the gather at the last position, the
    seven cross layers and the head, so nothing of the entry computation
    reads ``lm_head`` or a cross layer's memory-unit weights; the
    conditional takes the full layer's K/V pool (1.41 GiB a side) and
    the weights as operands and copies none: no copy of a
    pool's size exists, every pool aliases its input, and the only
    weight-sized ops that just move data are the parent's own four on
    one layer's ``wq`` (2560 x 2560; scratch compile, PR 64). The other
    arm is a broadcast of zeros."""
    c = phi4flash_cell
    (name, lowered), = [(n, low) for n, low in c.programs(kinds=("prefill",))
                        if n.startswith(f"prefill PB={PB} ")]
    compiled = lowered.compile()
    text = compiled.as_text()
    assert _has_kernel(compiled)
    _head_behind_one_conditional(text)
    assert "params[\\'w_gmu_in\\']" in text
    assert _head_reads(text, "w_gmu_in")[1] == []
    zeros = [b for b in _computations(text).values()
             if re.match(r"%region[\w.\-]* \(arg_empty_tuple", b)]
    assert len(zeros) == 1 and " broadcast(" in zeros[0] \
        and "fusion(" not in zeros[0]
    pools = [x.size for x in c.pools()]
    # (the conv tails' pool is smaller than a PB 8 chunk's activations)
    assert _pool_sized_copies(text, min(c.kv_k.size, c.wkv[0].size,
                                        c.state[0].size)) == []
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= sum(
        x.size * x.dtype.itemsize for x in c.pools())
    D = c.cfg.hidden_size   # a chunk's activations are far under a wq
    moved = _weight_sized_relayouts(text, D * D, pools=tuple(pools))
    assert sorted(shape.split("{")[0] for _, shape, _ in moved) == [
        f"bf16[1,{D},{D}]"] * 2 + [f"bf16[{D},{D}]"] * 2


def test_phi4flash_decode_step_holds_no_conditional(phi4flash_cell,
                                                    tpu_kernel_path):
    """``forward`` without a head is the decode step's, as it was: the
    cross half follows the self half with no branch between (the lowered
    program's digest is the parent's: tools/cell_programs.py --digest)."""
    (_, lowered), = phi4flash_cell.programs(kinds=("decode_step",))
    text = lowered.as_text()
    assert "stablehlo.case" not in text and "stablehlo.if" not in text
