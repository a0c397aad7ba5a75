"""benchmark/rehearse.py's memory count for a cell whose model generates
by diffusion over blocks, without a chip:

    python3 tools/diffusion_block_memory.py [--workload CELL] [--steps 8,16]

rehearse.py lowers the decode window with a ``[B]`` token operand; such
a model's window takes ``[B, 2L]`` (two blocks a row: the pending one
beside the open one, llama._make_block_window_fn), so rehearse.py cannot
size its cell (PERF.md section 7) and this does, the same way: every
program of the cell's warm grid (each prefill bucket, each window bucket
with top-20 logprobs on the smallest), the benchmark's weight maker and
one layer of the configuration's plain reference are compiled for a
described v5e at the cell's engine data, nothing runs, and the
compiler's own ``memory_analysis`` gives arguments, temporaries and
aliased bytes. GB = 2**30 bytes. The last line is what
``about.json``'s ``memory`` holds: resident = parameters + K/V pools,
peak = resident + the largest temporaries of a serving program.
``--steps`` compiles the window at other ``decode_steps`` beside the
cell's (how W was weighed before the chip chose it).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from functools import partial

os.environ["TPU_LOG_DIR"] = "disabled"
os.environ["JAX_PLATFORMS"] = "cpu"

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from benchmark.harness import cells, weights  # noqa: E402
from dynamo_tpu.engine.jax_engine import EngineConfig  # noqa: E402
from dynamo_tpu.models import llama  # noqa: E402
from dynamo_tpu.models.config import ModelConfig  # noqa: E402

LIMIT_GB = 15.75


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="sdar-30b-a3b-chat.decode-heavy")
    ap.add_argument("--root", default=ROOT)
    ap.add_argument("--steps", help="decode_steps to compile the window "
                    "at, comma-separated (default: the cell's)")
    a = ap.parse_args()

    jax.config.update("jax_enable_compilation_cache", False)
    llama._use_pallas = lambda: True     # the chip's arms, as on the chip
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    def on(tree):
        return jax.tree.map(lambda x: s(x.shape, x.dtype), tree)

    def nbytes(tree):
        return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))

    cell = cells.load_cell(a.workload, a.root)
    cfg = ModelConfig.from_local_path(cell["model_path"])
    if cfg.block_length <= 1:
        raise SystemExit(f"{a.workload}: block_length {cfg.block_length}; "
                         "benchmark/rehearse.py sizes this cell")
    ecfg = dataclasses.replace(EngineConfig(),
                               **cells.engine_overrides(cell))
    steps = ([int(x) for x in a.steps.split(",")] if a.steps
             else [ecfg.decode_steps])
    grid = ecfg.warmed_grid()
    params = on(jax.eval_shape(
        lambda: llama.init_params(cfg, jax.random.PRNGKey(0))))
    kv_k, kv_v = (on(x) for x in jax.eval_shape(
        lambda: llama.init_kv_cache(
            cfg, llama.KVCacheSpec(ecfg.num_pages, ecfg.page_size))))
    out = {"grid": grid, "params_gb": nbytes(params) / 2 ** 30,
           "kv_pool_gb": nbytes((kv_k, kv_v)) / 2 ** 30, "programs": []}

    def record(name, lowered):
        t0 = time.monotonic()
        mem = lowered.compile().memory_analysis()
        row = {"program": name,
               "arguments_gb": round(mem.argument_size_in_bytes / 2 ** 30, 3),
               "temporaries_gb": round(mem.temp_size_in_bytes / 2 ** 30, 3),
               "alias_gb": round(mem.alias_size_in_bytes / 2 ** 30, 3),
               "compile_s": round(time.monotonic() - t0, 1)}
        out["programs"].append(row)
        print(json.dumps(row), flush=True)

    prefill, _ = llama.make_step_fns(cfg)
    window = llama.make_decode_window_fn(cfg, True, ecfg.max_top_k)
    ps, L = ecfg.page_size, cfg.block_length
    i32 = jnp.int32
    for P in grid["page_buckets"]:
        for T in grid["prefill_lens"]:
            for PB in grid["prefill_batches"]:
                pslots = s((PB, T // ps), i32) if T % ps == 0 else None
                record(f"prefill PB={PB} T={T} P={P}", prefill.lower(
                    params, s((PB, T), i32), s((PB, T), i32), kv_k, kv_v,
                    s((PB, P), i32), s((PB, T), i32), s((PB,), i32),
                    pslots))
        for B in grid["decode_batches"]:
            row_i, row_f = s((B,), i32), s((B,), jnp.float32)
            for K in steps:
                first = B == grid["decode_batches"][0] and K == steps[0]
                for topn in (0, 20) if first else (0,):
                    record(f"window B={B} P={P} steps={K} topn={topn}",
                           window.lower(
                               params, s((B, 2 * L), i32), row_i,
                               s((B,), jnp.bool_), row_i, row_i, kv_k, kv_v,
                               s((B, P), i32), row_f, row_i, row_f,
                               s((B,), jnp.uint32),
                               s((B, ecfg.max_eos_ids), i32), None,
                               k_steps=K, logprobs_topn=topn))
    serving = len(out["programs"])
    record("weights.make_params", jax.jit(
        lambda k: weights.build_tree(llama, cfg, k, cell["weight_scales"])
    ).lower(s((2,), jnp.uint32)))
    layer = getattr(cells.load_reference(cell), "layer", None)
    if layer is not None:
        record("reference layer T=112", jax.jit(partial(layer, cfg)).lower(
            params, s((112, cfg.hidden_size), jnp.float32), s((), i32)))
    worst = max(r["temporaries_gb"] for r in out["programs"][:serving])
    out["resident_gb"] = out["params_gb"] + out["kv_pool_gb"]
    out["peak_gb"] = out["resident_gb"] + worst
    out["limit_gb"] = LIMIT_GB
    out["fits"] = out["peak_gb"] < LIMIT_GB
    print(json.dumps({k: v for k, v in out.items() if k != "programs"}))


if __name__ == "__main__":
    main()
