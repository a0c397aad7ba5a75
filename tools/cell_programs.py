#!/usr/bin/env python3
"""A cell's serving programs for a DESCRIBED v5e, from shapes, whatever
operands the model module's programs take: benchmark/rehearse.py's
memory count for the cells it cannot lower (a model that keeps
recurrent state: ``(state, state_slots)`` trailing operands, and
``state_src`` where the module snapshots; a window whose token operand
is two blocks a row; a model with a K/V pool a kind of layer: the window
layers' pools and the rows' tables into them in the same two places; a
model with both: pairs in those two places, JaxEngine._state_args),
and a digest of each program as lowered.

    python3 tools/cell_programs.py --workload CELL            # memory
    python3 tools/cell_programs.py --workload CELL --digest   # no compile

Memory (the default): every program of the cell's warm grid (each
prefill bucket, each window bucket, ``decode_step`` at the largest
batch), the benchmark's weight maker and one layer of the
configuration's plain reference are compiled, nothing runs, and the
compiler's own ``memory_analysis`` gives arguments, temporaries and
aliased bytes. GB = 2**30 bytes. The last line is what ``about.json``'s
``memory`` holds: resident = parameters + K/V pools (both kinds') + state pools, peak
= resident + the largest temporaries of a serving program.

``--digest``: sha256[:16] of each serving program's lowered StableHLO
(with the scope names, without source lines; ``--ops-only``: without
the scope names too), one line a program and one for the table: what a PR that must leave a cell's programs as they
were compares between its parent's checkout and its own (``--code
<checkout>`` imports ``dynamo_tpu`` and ``benchmark`` from there;
``--root`` says where BENCHMARK.json and the cell's files are read).
A compile that passes is not a chip run and gives no time.
"""

from __future__ import annotations

import argparse
import dataclasses
import hashlib
import json
import os
import sys
import time
from functools import partial

os.environ["TPU_LOG_DIR"] = "disabled"
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LIMIT_GB = 15.75


class Cell:
    """A cell's configuration, engine data and operands, the operands as
    shapes on ``chip`` (a described device's sharding), and its serving
    programs lowered from them. Imports ``dynamo_tpu`` and ``benchmark``
    from ``sys.path`` when it is built (``main``'s ``--code``)."""

    def __init__(self, workload: str, chip, root: str = ROOT):
        from benchmark.harness import cells
        from dynamo_tpu.engine.jax_engine import EngineConfig
        from dynamo_tpu.models import llama
        from dynamo_tpu.models.config import ModelConfig
        from dynamo_tpu.models.registry import family_of

        self.chip = chip
        self.cell = cells.load_cell(workload, root)
        self.cfg = cfg = ModelConfig.from_local_path(self.cell["model_path"])
        self.fam = fam = family_of(cfg)  # the record JaxEngine.__init__ reads
        self.model = model = fam.module
        self.ecfg = ecfg = dataclasses.replace(
            EngineConfig(), **cells.engine_overrides(self.cell))
        self.grid = ecfg.warmed_grid()
        spec = llama.KVCacheSpec(ecfg.num_pages, ecfg.page_size)
        on = self.on
        self.params = on(jax.eval_shape(
            lambda: model.init_params(cfg, jax.random.PRNGKey(0))))
        self.kv_k, self.kv_v = (on(x) for x in jax.eval_shape(
            lambda: model.init_kv_cache(cfg, spec)))
        self.state, self.snapshots = None, False
        if fam.init_state is not None:
            state = jax.eval_shape(
                lambda: fam.init_state(cfg, ecfg.max_batch + 1))
            if fam.init_state_snapshots is not None:
                self.snapshots = True
                state = (*state, jax.eval_shape(
                    lambda: fam.init_state_snapshots(cfg, spec)))
            self.state = on(state)
        # the window layers' pools and the rows' tables into them, for a
        # model with a pool a kind of layer (as JaxEngine.__init__ and
        # _window_tables build them)
        self.wkv, self.w_slots = None, 0
        if fam.pool_by_kind:
            self.w_slots = model.window_table_slots(
                cfg, ps := ecfg.page_size,
                max(ecfg.prefill_chunk, 2 * ecfg.decode_steps + 1))
            self.wkv = on(jax.eval_shape(lambda: model.init_window_kv_cache(
                cfg, llama.KVCacheSpec(
                    ecfg.window_pages or ecfg.max_batch * self.w_slots + 1,
                    ps))))

    def s(self, shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=self.chip)

    def on(self, tree):
        return jax.tree.map(lambda x: self.s(x.shape, x.dtype), tree)

    def pools(self):
        """Every pool the programs carry: K/V, the window layers', state."""
        return jax.tree.leaves((self.kv_k, self.kv_v, self.wkv, self.state))

    def state_args(self, rows, prefill=False, T=None):
        s, ecfg = self.s, self.ecfg
        if self.wkv is not None:
            tables = (s((rows, self.w_slots)), s((rows,)))
            if T is not None:
                paged = prefill and T % ecfg.page_size == 0
                tables += (s((rows, T // ecfg.page_size) if paged
                             else (rows, T)),)
            if self.state is not None:   # both, as pairs, window first
                return ((self.wkv, self.state), (tables, s((rows,))))
            return (self.wkv, tables)
        if self.state is None:
            return ()
        return (self.state, s((rows,)),
                *([s((rows,))] if prefill and self.snapshots else []))

    def programs(self, topns=(0,), kinds=("prefill", "window",
                                          "decode_step")):
        """(name, lowered) of the cell's warm grid, lowered one at a time:
        each prefill bucket, each window bucket (a program a ``topns``),
        ``decode_step`` at the largest batch."""
        s, ecfg, grid = self.s, self.ecfg, self.grid
        params, kv_k, kv_v = self.params, self.kv_k, self.kv_v
        prefill, decode_step = self.model.make_step_fns(self.cfg)
        window = self.model.make_decode_window_fn(self.cfg, True,
                                                  ecfg.max_top_k)
        ps = ecfg.page_size
        L = self.cfg.block_length if self.fam.by_blocks else 1
        for P in grid["page_buckets"]:
            for T in grid["prefill_lens"] if "prefill" in kinds else ():
                for PB in grid["prefill_batches"]:
                    pslots = s((PB, T // ps)) if T % ps == 0 else None
                    yield f"prefill PB={PB} T={T} P={P}", prefill.lower(
                        params, s((PB, T)), s((PB, T)), kv_k, kv_v,
                        s((PB, P)), s((PB, T)), s((PB,)), pslots,
                        *self.state_args(PB, prefill=True, T=T))
            for B in grid["decode_batches"] if "window" in kinds else ():
                row_i, row_f = s((B,)), s((B,), jnp.float32)
                # a block window's token operand: the pending block
                # beside the open one
                tokens = row_i if L == 1 else s((B, 2 * L))
                for topn in topns:
                    yield f"window B={B} P={P} topn={topn}", window.lower(
                        params, tokens, row_i, s((B,), jnp.bool_), row_i,
                        row_i, kv_k, kv_v, s((B, P)), row_f, row_i, row_f,
                        s((B,), jnp.uint32), s((B, ecfg.max_eos_ids)), None,
                        *self.state_args(B), k_steps=ecfg.decode_steps,
                        logprobs_topn=topn)
            if L == 1 and "decode_step" in kinds:
                B = grid["decode_batches"][-1]
                yield f"decode_step B={B} P={P}", decode_step.lower(
                    params, s((B,)), s((B,)), kv_k, kv_v, s((B, P)),
                    s((B,)), *self.state_args(B, T=1))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--root", default=ROOT,
                    help="where BENCHMARK.json and benchmark/ are read from")
    ap.add_argument("--code", default=ROOT,
                    help="the checkout whose dynamo_tpu/ is lowered")
    ap.add_argument("--digest", action="store_true",
                    help="digests of the lowered programs; no compile")
    ap.add_argument("--ops-only", action="store_true",
                    help="with --digest: of the operations alone, without "
                    "the scope names (what a PR that adds a scope to a "
                    "shared path compares)")
    a = ap.parse_args()
    sys.path.insert(0, os.path.abspath(a.code))

    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmark.harness import cells, weights
    from dynamo_tpu.models import llama

    jax.config.update("jax_enable_compilation_cache", False)
    # a location is the op's name-scope path alone, as in a serving
    # process (runtime/compile_cache.py): no file, no line
    jax.config.update("jax_traceback_in_locations_limit", 0)
    # the chip's arms, as on the chip: the model code asks
    # jax.default_backend(), which sees the CPU here (every family's
    # choice of kernel reads llama._use_pallas: llama.kernel_mode)
    llama._use_pallas = lambda: True
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    c = Cell(a.workload, SingleDeviceSharding(topo.devices[0]), a.root)
    cell, cfg, model, params, s = c.cell, c.cfg, c.model, c.params, c.s

    def nbytes(tree):
        return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))

    out = {"cell": a.workload, "grid": c.grid,
           "params_gb": nbytes(params) / 2 ** 30,
           "kv_pool_gb": nbytes((c.kv_k, c.kv_v)) / 2 ** 30,
           "state_pool_gb": nbytes(c.state) / 2 ** 30,
           "window_kv_pool_gb": nbytes(c.wkv) / 2 ** 30, "programs": []}
    digests = []

    def record(name, lowered):
        if a.digest:
            text = lowered.as_text(debug_info=not a.ops_only)
            row = {"program": name,
                   "sha256_16": hashlib.sha256(text.encode()).hexdigest()[:16]}
            digests.append(row["sha256_16"])
        else:
            t0 = time.monotonic()
            mem = lowered.compile().memory_analysis()
            row = {"program": name,
                   "arguments_gb": round(
                       mem.argument_size_in_bytes / 2 ** 30, 3),
                   "temporaries_gb": round(
                       mem.temp_size_in_bytes / 2 ** 30, 3),
                   "alias_gb": round(mem.alias_size_in_bytes / 2 ** 30, 3),
                   "compile_s": round(time.monotonic() - t0, 1)}
            out["programs"].append(row)
        print(json.dumps(row), flush=True)

    for name, lowered in c.programs((0, 20) if a.digest else (0,)):
        record(name, lowered)
    if a.digest:
        table = hashlib.sha256("".join(digests).encode()).hexdigest()[:16]
        print(json.dumps({"cell": a.workload, "programs": len(digests),
                          "sha256_16_of_table": table}))
        return 0
    serving = len(out["programs"])
    record("weights.make_params", jax.jit(
        lambda k: weights.build_tree(model, cfg, k, cell["weight_scales"])
    ).lower(s((2,), jnp.uint32)))
    layer = getattr(cells.load_reference(cell), "layer", None)
    if layer is not None:
        record("reference layer T=104", jax.jit(partial(layer, cfg)).lower(
            params, s((104, cfg.hidden_size), jnp.float32), s(())))
    worst = max(r["temporaries_gb"] for r in out["programs"][:serving])
    out["resident_gb"] = (out["params_gb"] + out["kv_pool_gb"]
                          + out["state_pool_gb"] + out["window_kv_pool_gb"])
    out["peak_gb"] = out["resident_gb"] + worst
    out["limit_gb"] = LIMIT_GB
    out["fits"] = out["peak_gb"] < LIMIT_GB
    print(json.dumps({k: v for k, v in out.items() if k != "programs"}))
    return 0 if out["fits"] else 1


if __name__ == "__main__":
    sys.exit(main())
