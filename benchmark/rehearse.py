#!/usr/bin/env python3
"""Compile a cell's warm grid for a DESCRIBED v5e, from shapes. Nothing
runs and no chip is needed: the TPU's compiler is installed in the
sandbox and refuses what the attached chip would refuse (a program past
HBM, a kernel the tiling cannot hold).

    JAX_PLATFORMS=cpu python3 benchmark/rehearse.py --workload <cell>

--root <dir> reads BENCHMARK.json and the cell's files from a copy (the
code stays this checkout's): a configuration is sized there before it
is in the repo's BENCHMARK.json.

Prints, per program, arguments + temporaries as the compiler counts
them, and the sum a serving process holds (parameters + KV pool + the
largest program's temporaries) against the 15.75 GB the compiler allows.
The output for each cell is kept in its configuration's about.json. A
compile that passes is not a chip run and gives no time.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

HBM_LIMIT = 15.75 * 2 ** 30     # what the v5e compiler allows a program


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--root", default=ROOT,
                    help="where BENCHMARK.json and benchmark/ are read from")
    ap.add_argument("--topn", default="0",
                    help="logprobs_topn variants of the window, e.g. 0,20")
    a = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmark.harness import cells, weights
    from dynamo_tpu.engine.jax_engine import EngineConfig
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.models.registry import get_model_module

    jax.config.update("jax_enable_compilation_cache", False)
    # the model code asks jax.default_backend(), which sees the CPU here
    llama._use_pallas = lambda: True
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    def on(tree):
        return jax.tree.map(lambda x: s(x.shape, x.dtype), tree)

    cell = cells.load_cell(a.workload, a.root)
    cfg = ModelConfig.from_local_path(cell["model_path"])
    model = get_model_module(cfg)
    ecfg = dataclasses.replace(EngineConfig(),
                               **cells.engine_overrides(cell))
    grid = ecfg.warmed_grid()
    params = on(jax.eval_shape(
        lambda: model.init_params(cfg, jax.random.PRNGKey(0))))
    kv_k, kv_v = (on(x) for x in jax.eval_shape(
        lambda: model.init_kv_cache(cfg, llama.KVCacheSpec(
            ecfg.num_pages, ecfg.page_size))))
    nbytes = lambda t: sum(x.size * x.dtype.itemsize  # noqa: E731
                           for x in jax.tree.leaves(t))
    p_bytes, kv_bytes = nbytes(params), nbytes((kv_k, kv_v))
    out = {"cell": a.workload, "grid": grid, "GB": 2 ** 30,
           "params_gb": p_bytes / 2 ** 30, "kv_pool_gb": kv_bytes / 2 ** 30,
           "programs": []}

    def record(name, lowered):
        t0 = time.monotonic()
        mem = lowered.compile().memory_analysis()
        row = {"program": name,
               "arguments_gb": mem.argument_size_in_bytes / 2 ** 30,
               "temporaries_gb": mem.temp_size_in_bytes / 2 ** 30,
               "compile_s": round(time.monotonic() - t0, 1)}
        out["programs"].append(row)
        print(json.dumps(row), flush=True)

    prefill, _ = model.make_step_fns(cfg)
    # the decode program as JaxEngine.__init__ makes it: every module of
    # models/registry.py supplies the window (models/window.py make_window)
    window = model.make_decode_window_fn(cfg, True, ecfg.max_top_k)
    ps = ecfg.page_size
    for P in grid["page_buckets"]:
        for T in grid["prefill_lens"]:
            for PB in grid["prefill_batches"]:
                pslots = (s((PB, T // ps), jnp.int32) if T % ps == 0
                          else None)
                record(f"prefill PB={PB} T={T} P={P}", prefill.lower(
                    params, s((PB, T), jnp.int32), s((PB, T), jnp.int32),
                    kv_k, kv_v, s((PB, P), jnp.int32),
                    s((PB, T), jnp.int32), s((PB,), jnp.int32), pslots))
        for B in grid["decode_batches"]:
            i32, f32 = s((B,), jnp.int32), s((B,), jnp.float32)
            for topn in [int(x) for x in a.topn.split(",")]:
                record(f"window B={B} P={P} topn={topn}", window.lower(
                    params, i32, i32, s((B,), jnp.bool_), i32, i32, kv_k,
                    kv_v, s((B, P), jnp.int32), f32, i32, f32,
                    s((B,), jnp.uint32), s((B, ecfg.max_eos_ids), jnp.int32),
                    None, k_steps=ecfg.decode_steps, logprobs_topn=topn))
    # the benchmark's own programs: the weights and, where the
    # configuration's reference exposes it, one reference layer
    key = s((2,), jnp.uint32)
    record("weights.make_params", jax.jit(lambda k: weights.build_tree(
        model, cfg, k, cell["weight_scales"])).lower(key))
    from functools import partial

    layer = getattr(cells.load_reference(cell), "layer", None)
    if layer is not None:
        T = 104
        record("reference layer T=104", jax.jit(partial(layer, cfg)).lower(
            params, s((T, cfg.hidden_size), jnp.float32), s((), jnp.int32)))
    worst = max(r["temporaries_gb"] for r in out["programs"]
                if not r["program"].startswith("weights"))
    out["resident_gb"] = out["params_gb"] + out["kv_pool_gb"]
    out["peak_gb"] = out["resident_gb"] + worst
    out["limit_gb"] = HBM_LIMIT / 2 ** 30
    out["fits"] = out["peak_gb"] < out["limit_gb"]
    print(json.dumps({k: v for k, v in out.items() if k != "programs"}))
    return 0 if out["fits"] else 1


if __name__ == "__main__":
    sys.exit(main())
