"""The two tables of models/registry.py: ``FAMILIES`` (which family
claims a ``model_type``, which a ``ModelConfig`` is of, what its module
declares) and ``REFUSALS`` (which serving feature is refused to which
capability). No program is compiled here: a refused engine raises
before it builds anything, and what is served is asked of the table and
built once, at tiny size.
"""

import glob
import json
import os
import re

import jax
import numpy as np
import pytest

from dynamo_tpu.engine.jax_engine import EngineConfig, JaxEngine
from dynamo_tpu.llm.disagg.decode import DisaggDecodeEngine
from dynamo_tpu.llm.disagg.prefill_worker import PrefillWorker
from dynamo_tpu.llm.disagg.transfer import KvTransferServer
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.models.registry import (CAPABILITIES, FAMILIES, REFUSALS,
                                        family_of, get_model_module,
                                        reader_of)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BY_NAME = {f.name: f for f in FAMILIES}

# (family, module) the parent of PR 58 picked for each configuration of
# the benchmark, by its two chains
CELLS = {
    "command-a-plus-05-2026": ("cohere2_moe", "cohere2_moe"),
    "granite-4.0-h-small": ("granite", "granite"),
    "jamba2-3b": ("jamba", "jamba"),
    "kanana-2-30b-a3b": ("mla", "mla"),
    "kimi-linear-48b-a3b": ("kimi_linear", "kimi_linear"),
    "lfm2-24b-a2b": ("lfm2", "lfm2"),
    # since PR 65 (a family of its own, asked before mla's)
    "longcat-flash-omni": ("longcat_flash", "longcat_flash"),
    "mixtral-8x7b": ("llama", "llama"),
    # since PR 60 (a family of its own, asked before granite's)
    "nemotron-3-super-120b-a12b": ("nemotron_h", "nemotron_h"),
    # since PR 63 (a family of its own, asked before jamba's)
    "phi-4-mini-flash-reasoning": ("phi4flash", "phi4flash"),
    "qwen3-30b-a3b": ("llama", "llama"),
    "sdar-30b-a3b-chat": ("llama_by_blocks", "llama"),
    "smallthinker-21b-a3b": ("llama_by_kind", "llama"),
    "solar-open2-250b": ("solar_open2", "solar_open2"),
}

# the model_type of every dict the other tests hand from_hf_config (by
# hand or through a transformers config's to_dict()) and that no
# configuration of the benchmark has
_HF = dict(vocab_size=512, hidden_size=64, intermediate_size=128,
           num_hidden_layers=4, num_attention_heads=4,
           num_key_value_heads=2, head_dim=16, moe_intermediate_size=32,
           norm_topk_prob=True, n_routed_experts=8, kv_lora_rank=16)
TYPES = {
    None: ("llama", "llama"), "llama": ("llama", "llama"),
    "mistral": ("llama", "llama"), "mixtral": ("llama", "llama"),
    "qwen2": ("llama", "llama"), "qwen3": ("llama", "llama"),
    "qwen3_moe": ("llama", "llama"), "gemma": ("llama", "llama"),
    "gemma2": ("llama", "llama"), "deepseek_v2": ("mla", "mla"),
    "deepseek_v3": ("mla", "mla"),
}

# a ModelConfig of each family, by hand: what the family's ``has`` asks
TINY = {
    "kimi_linear": dict(kda_n_heads=2, kv_lora_rank=16),
    "solar_open2": dict(kda_n_heads=2),
    "longcat_flash": dict(kv_lora_rank=16, q_lora_rank=24,
                          moe_router="longcat_flash", num_experts=4,
                          router_experts=12, zero_experts=4),
    "mla": dict(kv_lora_rank=16),
    "nemotron_h": dict(mamba_n_heads=2, mamba_d_state=4,
                       moe_latent_size=8),
    "granite": dict(mamba_n_heads=2, mamba_d_state=4),
    "lfm2": dict(layer_types=("conv", "full_attention")),
    # 8 layers by the family's rule: 2 x (Mamba, window), (Mamba, full),
    # (memory unit, cross)
    "phi4flash": dict(mamba_d_state=4, mamba_dt_rank=4, num_layers=8,
                      kv_pool_by_kind=True, sliding_window=8,
                      layer_window=(None, 8, None, 8) + (None,) * 4),
    "jamba": dict(mamba_d_state=4, mamba_dt_rank=4, attn_layer_period=2,
                  attn_layer_offset=1),
    "cohere2_moe": dict(parallel_block=True, kv_pool_by_kind=True),
    "llama_by_kind": dict(kv_pool_by_kind=True),
    "llama_by_blocks": dict(block_length=4),
    "llama": {},
}

ENGINE_FEATURES = {
    "host_pages": dict(host_pages=8),
    "spec_decode": dict(spec_decode=True),
    "long_prefill_threshold": dict(long_prefill_threshold=64),
    "mesh": {},
}
DISAGG = {
    "disagg_prefill": lambda e: PrefillWorker(None, e),
    "disagg_decode": lambda e: DisaggDecodeEngine(e, None, None, None, "d0"),
    "kv_transfer": KvTransferServer,
}
PAIRS = [(f.name, feature) for f in FAMILIES for feature in REFUSALS]


def tiny(family: str) -> ModelConfig:
    return ModelConfig.tiny(**TINY[family])


def cell_hf(name: str) -> dict:
    with open(os.path.join(ROOT, "benchmark", "configs", name,
                           "config.json")) as f:
        return json.load(f)


def _assert_pair(cfg: ModelConfig, model_type, family: str, module: str):
    assert family_of(cfg) is BY_NAME[family]
    assert get_model_module(cfg).__name__ == f"dynamo_tpu.models.{module}"
    # the family that claims the model_type is the one its reader's
    # result is of
    assert reader_of(model_type) in BY_NAME[family].readers.values()


# ------------------------------------------------ (i) who reads, who runs


def test_every_configuration_of_the_benchmark_is_listed():
    found = {p.split(os.sep)[-2] for p in glob.glob(os.path.join(
        ROOT, "benchmark", "configs", "*", "config.json"))}
    assert found == set(CELLS)


@pytest.mark.parametrize("name", sorted(CELLS))
def test_a_cell_config_gets_the_family_and_module_the_parent_picked(name):
    hf = cell_hf(name)
    _assert_pair(ModelConfig.from_hf_config(hf), hf["model_type"],
                 *CELLS[name])


@pytest.mark.parametrize("model_type", TYPES, ids=str)
def test_a_model_type_gets_the_family_and_module_the_parent_picked(
        model_type):
    hf = dict(_HF) if model_type is None else dict(_HF,
                                                  model_type=model_type)
    if model_type == "deepseek_v2":
        hf["norm_topk_prob"] = False    # refused with it (models/mla.py)
    _assert_pair(ModelConfig.from_hf_config(hf), model_type or "llama",
                 *TYPES[model_type])


def test_the_tables_claim_every_model_type_listed_here_and_no_other():
    claimed = {t for f in FAMILIES for t in f.readers}
    listed = {t for t in TYPES if t} | {
        cell_hf(name)["model_type"] for name in CELLS}
    assert claimed == listed
    # no model_type is claimed twice
    assert len(claimed) == sum(len(f.readers) for f in FAMILIES)


# ----------------------------------------------------- (ii) who is refused


def test_a_model_type_nobody_claims_is_refused_by_name():
    with pytest.raises(NotImplementedError) as e:
        ModelConfig.from_hf_config(dict(_HF, model_type="mystery"))
    said = str(e.value)
    assert "'mystery'" in said and "not read as llama" in said
    for f in FAMILIES:
        for model_type in f.readers:
            assert re.search(rf"\b{model_type}\b", said), model_type


def test_an_absent_model_type_is_llama():
    cfg = ModelConfig.from_hf_config(dict(_HF))
    assert cfg == ModelConfig.from_hf_config(dict(_HF, model_type="llama"))
    assert cfg.model_type == "llama" and family_of(cfg).name == "llama"


# ------------------------------------- (iii) family x feature: the table


def _mesh():
    if len(jax.devices()) < 2:
        pytest.skip("needs two devices")
    return jax.sharding.Mesh(np.asarray(jax.devices()[:2]).reshape(1, 2),
                             ("data", "model"))


class _Engine:
    """Stands for a JaxEngine of a family, for the classes of llm/disagg:
    ``family`` is all they read before they refuse."""

    def __init__(self, family):
        self.family = family


def _build(family: str, feature: str):
    """What switches ``feature`` on for a tiny model of ``family``."""
    if feature in DISAGG:
        return DISAGG[feature](_Engine(BY_NAME[family]))
    ecfg = EngineConfig(page_size=16, num_pages=16, decode_steps=8,
                        **ENGINE_FEATURES[feature])
    return JaxEngine(tiny(family), ecfg,
                     mesh=_mesh() if feature == "mesh" else None)


@pytest.mark.parametrize("family,feature", PAIRS)
def test_a_feature_is_refused_where_the_table_has_a_reason(family, feature):
    """Every (family, feature): where REFUSALS holds a reason for a
    capability the family has, switching the feature on raises that
    sentence, whole; where it holds none, the table serves it (and what
    is cheap to build is built below)."""
    fam = BY_NAME[family]
    assert family_of(tiny(family)) is fam
    what, whys = REFUSALS[feature]
    mine = [c for c, (has, _) in CAPABILITIES.items()
            if has(fam) and c in whys]
    said = fam.refusal(feature)
    if not mine:
        assert said is None
        return
    assert what in said and whys[mine[0]] in said
    assert said == CAPABILITIES[mine[0]][1].format(what=what,
                                                   why=whys[mine[0]])
    if feature == "sampling_penalty":
        return      # made by the request: tests/test_sdar.py
    with pytest.raises(NotImplementedError, match=re.escape(said)):
        _build(family, feature)


@pytest.mark.parametrize("family", sorted(BY_NAME))
def test_the_transfer_server_is_built_for_whom_the_table_serves(family):
    """The one class of llm/disagg that a stand-in can build: built for
    a family the table serves, and for an engine that is no JaxEngine's
    (the echo engines, a test's sink: no ``family``, nothing asked)."""
    assert KvTransferServer(object()).engine is not None
    fam = BY_NAME[family]
    if fam.refusal("kv_transfer") is None:
        assert _build(family, "kv_transfer").engine.family is fam
    else:
        assert family not in ("llama", "mla")


def test_the_engine_is_built_where_the_table_serves_the_features():
    """The engine's features on at once for the plain family, and the
    one feature the table serves a family with state: built, not
    refused."""
    ecfg = dict(page_size=16, num_pages=16, max_batch=2,
                batch_buckets=(2,), prefill_buckets=(16,),
                page_buckets=(16,))
    eng = JaxEngine(tiny("llama"), EngineConfig(
        host_pages=8, spec_decode=True, long_prefill_threshold=64, **ecfg))
    assert eng.family is BY_NAME["llama"] and eng.verify_fn is not None
    assert eng.state is None and eng.wkv is None and eng.block == 1
    eng = JaxEngine(tiny("jamba"), EngineConfig(long_prefill_threshold=64,
                                                **ecfg))
    assert eng.family is BY_NAME["jamba"] and eng.state is not None
    assert not eng.pm.prefix_reuse


def test_the_sentence_of_recurrent_state_lists_the_modules_that_keep_it():
    sentence = CAPABILITIES["state"][1]
    for f in FAMILIES:
        name = f"models/{f.module.__name__.rpartition('.')[2]}.py"
        assert (name in sentence) == (f.init_state is not None), f.name
    for capability, roadmap in (("state", "B7"), ("pool_by_kind", "B6"),
                                ("by_blocks", "B10")):
        assert f"ROADMAP {roadmap}" in CAPABILITIES[capability][1]
    # every reason is of a capability the table knows
    for _, whys in REFUSALS.values():
        assert set(whys) <= set(CAPABILITIES)


# ------------------------------- (iv) a record says what its module has


@pytest.mark.parametrize("family", sorted(BY_NAME))
def test_a_record_declares_what_its_module_has(family):
    fam = BY_NAME[family]
    for name in ("init_state", "init_state_snapshots", "make_verify_fn"):
        assert getattr(fam, name) is getattr(fam.module, name, None), name
    assert fam.window_counts == getattr(fam.module, "WINDOW_COUNTS", ())
    for name in ("init_params", "init_kv_cache", "make_step_fns",
                 "make_decode_window_fn"):
        assert callable(getattr(fam.module, name)), name
    if fam.pool_by_kind:
        assert callable(fam.module.init_window_kv_cache)
        assert callable(fam.module.window_table_slots)
    for reader in fam.readers.values():
        assert reader.__module__ in (fam.module.__name__,
                                     "dynamo_tpu.models.config")


def _kept_state_as_the_parent_spelled_it(cfg: ModelConfig) -> bool:
    return (cfg.mamba_d_state > 0 or "conv" in cfg.layer_types
            or cfg.kda_n_heads > 0)


@pytest.mark.parametrize("family", sorted(BY_NAME))
def test_has_recurrent_state_is_the_tables_answer(family):
    cfg = tiny(family)
    assert cfg.has_recurrent_state == (
        BY_NAME[family].init_state is not None)
    assert cfg.has_recurrent_state == _kept_state_as_the_parent_spelled_it(
        cfg)


@pytest.mark.parametrize("name", sorted(CELLS))
def test_has_recurrent_state_of_a_cell_config_is_the_parents(name):
    cfg = ModelConfig.from_hf_config(cell_hf(name))
    assert cfg.has_recurrent_state == _kept_state_as_the_parent_spelled_it(
        cfg)
    assert cfg.has_recurrent_state == (
        BY_NAME[CELLS[name][0]].init_state is not None)
