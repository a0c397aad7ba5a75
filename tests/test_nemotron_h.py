"""Nemotron-H as Nemotron 3 Super states it (models/nemotron_h.py: a
layer that is ONE sub-block, Mamba-2 with groups of B and C, experts
that are not gated, in a latent, beside a full-width shared expert): the
step programs, the chip's share of a layer's experts and the engine's
state pool against the plain reference
(benchmark/configs/nemotron-3-super-120b-a12b/reference.py), on the CPU
at a small size with every kind of layer: float32, the published
pattern's first period ``MEMEMEM*EME`` (a mixer with no second half in
``M*E``, a last layer that is experts), hidden 64, 8 Mamba heads of 16 x
16 in 2 groups, chunk 8, 12 experts top-3 of which 6 are held at a latent
of 32, seeded random weights at the cell's weight scales.

Tolerance. Both sides are float32 and compute the same sums in another
order (the program in chunks of matrix products with a carried state and
in rows, the reference token by token from zero), so logits of magnitude
~3 differ by a few 1e-6; ATOL = 1e-4 leaves room and is far under what a
head reading another group's B and C, a norm over all of d_inner, a
dropped state or an expert of the wrong share moves (1e-3 and more: the
tests that provoke them)."""

import asyncio
import importlib.util
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import weights
from dynamo_tpu.engine.jax_engine import EngineConfig, JaxEngine
from dynamo_tpu.models import granite, jamba, llama, nemotron_h
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.models.registry import (REFUSALS, family_of,
                                        get_model_module)
from dynamo_tpu.ops.moe_grouped import moe_grouped_mlp
from tests.test_granite import PS, Pools as GranitePools, _gen

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG_DIR = os.path.join(ROOT, "benchmark", "configs",
                          "nemotron-3-super-120b-a12b")
ATOL = 1e-4
PATTERN = "MEMEMEM*EME"


def _reference():
    spec = importlib.util.spec_from_file_location(
        "nemotron_h_reference", os.path.join(CONFIG_DIR, "reference.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()
with open(os.path.join(CONFIG_DIR, "about.json")) as _f:
    ABOUT = json.load(_f)

TINY = dict(
    model_type="nemotron_h", vocab_size=512, hidden_size=64,
    intermediate_size=32, num_hidden_layers=len(PATTERN),
    hybrid_override_pattern=PATTERN + "MEM*E", num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, mamba_num_heads=8,
    mamba_head_dim=16, ssm_state_size=16, n_groups=2, conv_kernel=4,
    expand=2, chunk_size=8, moe_intermediate_size=32, moe_latent_size=32,
    moe_shared_expert_intermediate_size=48, n_shared_experts=1,
    n_routed_experts=6, router_num_experts=12, first_local_expert=0,
    num_experts_per_tok=3, routed_scaling_factor=5.0, norm_topk_prob=True,
    mlp_hidden_act="relu2", mamba_hidden_act="silu", n_group=1,
    topk_group=1, layer_norm_epsilon=1e-5, tie_word_embeddings=False,
    use_conv_bias=True, num_nextn_predict_layers=0)


def tiny(**over) -> ModelConfig:
    hf = dict(TINY)
    if "hybrid_override_pattern" in over:
        hf["num_hidden_layers"] = len(over["hybrid_override_pattern"])
    hf.update(over)
    cfg = ModelConfig.from_hf_config(hf)
    cfg.dtype = "float32"
    return cfg


def make_params(cfg, seed=0):
    """The cell's weights at this size: the harness's rule and the
    configuration's scales (embed of unit RMS at this width)."""
    scales = dict(ABOUT["weight_scales"], embed=math.sqrt(cfg.hidden_size))
    return weights.build_tree(nemotron_h, cfg, weights.seed_key(seed),
                              scales)


def ref_logits(params, cfg, tokens, ref=REF):
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.reference_logits(params, cfg, tokens))


class Pools(GranitePools):
    """tests/test_granite.py's pages and state slot of one sequence (the
    pools' shapes are granite.py's), on this module's programs."""

    def __init__(self, cfg, **kw):
        super().__init__(cfg, **kw)
        self.prefill, self.decode = nemotron_h.make_step_fns(cfg)


# -------------------------------------------------- the reader, the layout


def test_from_hf_config_on_the_catalog_config():
    """The published config and the file as it is run: every width as
    published, the pattern's three kinds counted, the share beside the
    router's width, the family found before granite's (both have Mamba-2
    heads); and the file as published is refused for its drafting head
    alone."""
    published = ABOUT["published"]
    with pytest.raises(NotImplementedError,
                       match="num_nextn_predict_layers 1"):
        ModelConfig.from_hf_config(published)
    cfg = ModelConfig.from_hf_config(
        dict(published, num_nextn_predict_layers=0))
    kinds = cfg.layer_types
    assert cfg.num_layers == len(kinds) == 88
    assert [kinds.count(k) for k in ("mamba", "moe", "attention")] \
        == [40, 40, 8]
    assert (cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state,
            cfg.mamba_n_groups, cfg.mamba_d_conv, cfg.mamba_chunk_size,
            cfg.mamba_d_inner) == (128, 64, 128, 8, 4, 128, 8192)
    assert granite.conv_width(cfg) == 8192 + 2 * 8 * 128
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_) == (32, 2, 128)
    assert cfg.attn_scale == 1 / math.sqrt(128)
    assert (cfg.num_experts, cfg.router_width, cfg.num_experts_per_tok,
            cfg.moe_intermediate_size, cfg.moe_latent_size,
            cfg.shared_intermediate_size, cfg.routed_scaling_factor) \
        == (512, 512, 22, 2688, 1024, 5376, 5.0)
    assert cfg.moe_router == "deepseek_v3" and cfg.norm_topk_prob
    assert (cfg.n_group, cfg.topk_group) == (0, 0)      # no group limit
    assert cfg.hidden_act == "relu2" and cfg.rms_norm_eps == 1e-5
    assert not cfg.tie_word_embeddings and cfg.has_recurrent_state
    assert family_of(cfg).name == "nemotron_h"
    assert get_model_module(cfg) is nemotron_h
    # the parameters the name states: 120.6 B, by the shapes
    shapes = jax.eval_shape(
        lambda: nemotron_h.init_params(cfg, jax.random.PRNGKey(0)))
    total = sum(math.prod(s.shape) for s in shapes.values())
    assert 120.0e9 < total < 121.0e9
    assert "w_gate" not in shapes and "w_gate_s" not in shapes

    run = ModelConfig.from_local_path(CONFIG_DIR)
    assert run.num_layers == 11 and run.attn_layer_ids == (7,)
    assert "".join({"mamba": "M", "moe": "E", "attention": "*"}[k]
                   for k in run.layer_types) == PATTERN
    assert (run.num_experts, run.router_width, run.first_expert,
            run.vocab_size) == (128, 512, 0, 32768)
    assert nemotron_h.held_first(run) == 0
    ssm, conv = jax.eval_shape(lambda: nemotron_h.init_state(run, 129))
    assert ssm.shape == (129, 5, 128, 8192) and ssm.dtype == jnp.float32
    assert conv.shape == (5, 129, 3 * 10240)        # layer-major
    kv_k, _ = jax.eval_shape(lambda: nemotron_h.init_kv_cache(
        run, llama.KVCacheSpec(8, 128)))
    assert kv_k.shape == (1, 8, 2, 128, 128)        # ONE attending layer


@pytest.mark.parametrize("key,value,match", [
    ("hybrid_override_pattern", "ME-E" + PATTERN, "hybrid_override_pattern"),
    ("hybrid_override_pattern", "MEM", "hybrid_override_pattern"),
    ("mamba_proj_bias", True, "mamba_proj_bias"),
    ("attention_bias", True, "attention_bias"),
    ("mlp_bias", True, "mlp_bias"),
    ("use_conv_bias", False, "use_conv_bias"),
    ("n_group", 8, "n_group"),
    ("topk_group", 4, "topk_group"),
    ("moe_shared_expert_overlap", True, "moe_shared_expert_overlap"),
    ("num_nextn_predict_layers", 1, "num_nextn_predict_layers"),
    ("mlp_hidden_act", "silu", "mlp_hidden_act"),
    ("sliding_window", 4096, "sliding_window"),
    ("moe_latent_size", None, "moe_latent_size"),
    ("mamba_head_dim", 32, "mamba_num_heads x mamba_head_dim"),
    ("n_groups", 3, "n_groups"),
    ("first_local_expert", 7, "first_local_expert"),
    ("num_experts_per_tok", 13, "num_experts_per_tok"),
])
def test_read_config_refuses_what_it_does_not_compute(key, value, match):
    with pytest.raises(NotImplementedError, match=match):
        ModelConfig.from_hf_config(dict(TINY, **{key: value}))


@pytest.mark.parametrize("pattern,want", [
    (PATTERN, [("mamba", 0, 0, 3, 0), ("mamba", 3, 3, 1, None),
               ("attn", 0, 4, 3), ("mamba", 4, 5, 1, 4)]),
    ("EM*", [("ff", 0), ("mamba", 0, 0, 1, None), ("attn", 0, 1, None)]),
    ("MMEE*EMM", [("mamba", 0, 0, 1, None), ("mamba", 1, 1, 1, 0),
                  ("ff", 1), ("attn", 0, 2, 2), ("mamba", 2, 3, 2, None)]),
], ids=["published", "experts-first", "doubles"])
def test_the_pattern_as_runs(pattern, want):
    """A mixer takes the experts that follow it as its second half; like
    Mamba-2 layers next to each other are one run (one trace); the M of
    ``M*E`` stands alone; experts no mixer precedes stand alone. Every
    index counts its own stack."""
    cfg = tiny(hybrid_override_pattern=pattern)
    assert nemotron_h.segments(cfg) == want
    shapes = jax.eval_shape(
        lambda: nemotron_h.init_params(cfg, jax.random.PRNGKey(0)))
    M, A, F = (pattern.count(k) for k in "M*E")
    assert shapes["ln_mixer"].shape[0] == M + A
    assert shapes["ln_mlp"].shape[0] == shapes["w_up"].shape[0] == F
    assert shapes["w_in"].shape[0] == M and shapes["wq"].shape[0] == A
    # the other families' layers keep both halves, at the layer's index
    assert jamba.MAMBA1.segments is jamba.segments is granite.BLOCKS.segments


# ------------------------------------------- the programs, the reference


@pytest.mark.parametrize("pattern,interpret", [
    (PATTERN, False), (PATTERN, True), ("EM*MME", False)],
    ids=["published", "pallas_interpret", "experts-first"])
def test_prefill_in_chunks_then_windows_match_reference(pattern, interpret):
    """A 37-token prompt in prefill chunks of 13 + 16 + 8 tokens (the
    first two end off the scan's chunk of 8, so a chunk edge is crossed
    inside a program and between programs, the state carried through
    the pool), then two decode windows through pages and the pool,
    against the reference's ONE full forward, on logits (the window's
    top-8 log-probabilities at each of its steps). ``pallas_interpret``:
    the window's kernels under interpretation, the matrix state advanced
    in the pool by groups (ssd_step). Patterns: the published one (a
    mixer with no second half in ``M*E``, experts last) and one that
    starts with experts and has two mixers in a row."""
    cfg = tiny(hybrid_override_pattern=pattern)
    params = make_params(cfg)
    pools = Pools(cfg)
    prompt = np.random.default_rng(0).integers(1, 512, 37)
    for a, b in ((0, 13), (13, 29), (29, 37)):
        logits = pools.run_prefill(params, prompt[a:b], a, 16)
    want = ref_logits(params, cfg, prompt)
    assert np.abs(want).max() > 1.0             # logits of unit scale
    assert np.abs(logits - want[-1]).max() < ATOL
    assert float(jnp.abs(pools.state[0][pools.drop]).max()) == 0.0

    window = nemotron_h.make_decode_window_fn(cfg, True, 64,
                                              pallas_interpret=interpret)
    B, K = 2, 4
    first = int(np.argmax(logits))
    carry = (jnp.asarray([first, 0], jnp.int32),
             jnp.asarray([len(prompt), -1], jnp.int32), jnp.zeros(B, bool),
             jnp.zeros(B, jnp.int32), jnp.asarray([100, 1], jnp.int32))
    kv_k, kv_v, state = pools.kv_k, pools.kv_v, pools.state
    toks, vals, ids = [], [], []
    n_moe = pattern.count("E")
    for _ in range(2):
        t, emitted, aux, carry, kv_k, kv_v, counted, state = window(
            params, *carry, kv_k, kv_v, pools.table(B), jnp.zeros(B),
            jnp.zeros(B, jnp.int32), jnp.ones(B), jnp.zeros(B, jnp.uint32),
            jnp.full((B, 8), -1, jnp.int32), None, state,
            jnp.asarray([pools.slot, pools.drop], jnp.int32),
            k_steps=K, logprobs_topn=8)
        assert list(np.asarray(emitted)) == [K, 0]
        # the live row's K steps chose 3 experts in each ``moe`` layer
        assert int(counted[0]) == K * n_moe * 3
        assert 0 < int(counted[1]) < K * n_moe * 3
        toks += [int(x) for x in t[0]]
        vals += list(np.asarray(aux[1][0]))
        ids += list(np.asarray(aux[2][0]))
    assert float(jnp.abs(state[0][pools.drop]).max()) == 0.0
    seq = list(prompt) + [first] + toks
    want = np.asarray(jax.nn.log_softmax(
        ref_logits(params, cfg, seq[:-1]), -1))
    for j in range(2 * K):
        at = len(prompt) + j
        assert np.abs(vals[j] - want[at][ids[j]]).max() < ATOL
        assert toks[j] == int(np.argmax(want[at]))


def test_decode_steps_through_the_pool_match_reference():
    """decode_step (K = 1, the rows' state gathered) after a one-chunk
    prefill: the other program that advances a stored state."""
    cfg = tiny()
    params = make_params(cfg, 1)
    pools = Pools(cfg)
    seq = np.random.default_rng(1).integers(1, 512, 24)
    pools.run_prefill(params, seq[:19], 0, 32)
    want = ref_logits(params, cfg, seq)
    for at in range(19, 24):
        got = pools.run_decode(params, int(seq[at]), at)
        assert np.abs(got - want[at]).max() < ATOL


def _with_group_0_for_every_head(cfg, params):
    """The parameters of a model whose every head reads group 0's B and
    C: the columns of w_in, conv_w and b_conv that make the other groups'
    are group 0's. The sound program on THESE is the control on the
    sound ones."""
    di, N, G = cfg.mamba_d_inner, cfg.mamba_d_state, cfg.mamba_n_groups
    out = dict(params)
    for name, base in (("w_in", di + di), ("conv_w", di), ("b_conv", di)):
        w = params[name]
        for start in (base, base + G * N):          # B's columns, then C's
            for g in range(1, G):
                w = w.at[..., start + g * N:start + (g + 1) * N].set(
                    w[..., start:start + N])
        out[name] = w
    return out


def test_the_groups_and_the_grouped_norm_matter(monkeypatch):
    """Two controls the tolerance has to see: the reference whose every
    head reads group 0's B and C (the sound reference on parameters whose
    other groups' columns are group 0's), and the reference whose gated
    norm is over all of d_inner instead of a group's channels. The
    program stays on the sound side of both by more than 100 x ATOL."""
    cfg = tiny()
    params = make_params(cfg, 2)
    prompt = np.random.default_rng(2).integers(1, 512, 21)
    got = Pools(cfg).run_prefill(params, prompt, 0, 32)
    assert np.abs(got - ref_logits(params, cfg, prompt)[-1]).max() < ATOL
    shared = ref_logits(_with_group_0_for_every_head(cfg, params), cfg,
                        prompt)[-1]
    assert np.abs(got - shared).max() > 100 * ATOL

    sound = REF._rms

    def over_all_of_d_inner(x, w, eps):
        if x.ndim != 3:                 # [T, G, d_inner / G]: the gated norm
            return sound(x, w, eps)
        flat = sound(x.reshape(x.shape[0], -1), w.reshape(-1), eps)
        return flat.reshape(x.shape)

    monkeypatch.setattr(REF, "_rms", over_all_of_d_inner)
    whole = ref_logits(params, cfg, prompt)[-1]
    assert np.abs(got - whole).max() > 100 * ATOL


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


def test_the_router_is_float32_in_deed():
    """On bf16 weights the router's logits are ONE product of float32
    operands at HIGHEST precision (on a TPU the default is a bf16 pass
    over the activation), and its activation operand is no value that
    went through the weights' type: the 22nd and the 23rd of 512 scores
    lie 0.02 of the logits' spread apart, and the agreement with the
    float32 reference turns on which of them is chosen."""
    cfg = tiny()
    cfg.dtype = "bfloat16"
    params = jax.tree.map(
        lambda a: a.astype(jnp.bfloat16) if a.dtype == jnp.float32
        and a.ndim > 1 else a, make_params(cfg))
    h = jnp.zeros((2, 4, cfg.hidden_size), jnp.float32)

    def ff(params, h):
        return nemotron_h._moe_ff(params, cfg, None, h, 0,
                                  jnp.ones((2, 4), bool))[0]

    jaxpr = jax.make_jaxpr(ff)(params, h).jaxpr
    eqns = list(_eqns(jaxpr))
    router = [e for e in eqns if e.primitive.name == "dot_general"
              and e.outvars[0].aval.shape[-1] == cfg.router_width]
    assert len(router) == 1
    dot, = router
    assert [v.aval.dtype for v in dot.invars] == [jnp.float32] * 2
    prec = dot.params["precision"]
    assert prec is not None and set(
        prec if isinstance(prec, tuple) else (prec,)) == {
            jax.lax.Precision.HIGHEST}
    rounded = {e.outvars[0] for e in eqns
               if e.primitive.name == "convert_element_type"
               and e.invars[0].aval.dtype == jnp.bfloat16
               and e.invars[0].aval.shape == h.shape}
    assert dot.invars[0] not in rounded
    # every other product of the layer reads bf16 operands at the default
    others = [e for e in eqns if e.primitive.name == "dot_general"
              and e is not dot]
    assert others and all(e.params["precision"] is None for e in others)


@pytest.mark.parametrize("tag", ["state-8bit", "latent-8bit"])
def test_the_long_context_tools_controls_reach_the_programs(tag):
    """tools/nemotron_h_long_context_check.py's two controls change what
    the programs traced under them compute, by far more than ATOL (the
    state and the conv tails a mixer hands back; x, W_lat_in and their
    product), and are taken back on the way out."""
    spec = importlib.util.spec_from_file_location(
        "nemotron_h_long_context_check", os.path.join(
            ROOT, "tools", "nemotron_h_long_context_check.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    cfg = tiny()
    params = make_params(cfg, 3)
    prompt = np.random.default_rng(3).integers(1, 512, 21)

    def run():
        pools = Pools(cfg)
        pools.run_prefill(params, prompt, 0, 32)
        return pools.run_decode(params, 7, len(prompt))

    sound = run()
    blocks, latent = nemotron_h.BLOCKS, nemotron_h.latent_in
    with tool.control(tag):
        assert (nemotron_h.BLOCKS, nemotron_h.latent_in) != (blocks, latent)
        moved = run()
    assert (nemotron_h.BLOCKS, nemotron_h.latent_in) == (blocks, latent)
    assert "DYN_DISABLE_PALLAS" not in os.environ
    assert np.abs(moved - sound).max() > 100 * ATOL
    assert np.abs(run() - sound).max() == 0


def test_the_check_can_see_the_carried_state():
    """A second chunk that starts from zeros instead of the carried
    state, and a state pool rounded to bfloat16 between the chunks, are
    visible at this tolerance."""
    cfg = tiny()
    params = make_params(cfg, 3)
    prompt = np.random.default_rng(3).integers(1, 512, 32)
    want = ref_logits(params, cfg, prompt)[-1]
    for fault in ("none", "zeros", "bf16"):
        pools = Pools(cfg)
        pools.run_prefill(params, prompt[:16], 0, 16)
        ssm, conv = pools.state
        if fault == "zeros":
            pools.state = (jnp.zeros_like(ssm), conv)
        if fault == "bf16":
            pools.state = (ssm.astype(jnp.bfloat16).astype(jnp.float32),
                           conv)
        err = np.abs(pools.run_prefill(params, prompt[16:], 16, 16)
                     - want).max()
        assert (err < ATOL) == (fault == "none"), (fault, err)


# -------------------------------------------------- Mamba-2 by groups


def _scan_operands(rng, B, T, H, P, N, G):
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.05, 1.0, (B, T, H)), jnp.float32)
    return (dt, f(B, T, H, P), f(B, T, G, N), f(B, T, G, N),
            -jnp.exp(f(H)), f(B, N, H * P))


def _token_by_token(s, dt, x, b, c, a_neg):
    """The published recurrence with B and C by group, a token at a
    time, on [B, H, P, N]."""
    B, T, H, P = x.shape
    G, N = b.shape[2:]
    s = jnp.moveaxis(s.reshape(B, N, H, P), 1, 3)
    ys = []
    for t in range(T):
        bt = jnp.repeat(b[:, t], H // G, axis=1)            # [B, H, N]
        ct = jnp.repeat(c[:, t], H // G, axis=1)
        s = (jnp.exp(dt[:, t] * a_neg)[..., None, None] * s
             + (dt[:, t, :, None] * x[:, t])[..., None] * bt[:, :, None, :])
        ys.append(jnp.einsum("bhpn,bhn->bhp", s, ct))
    return (jnp.moveaxis(s, 3, 1).reshape(B, N, H * P),
            jnp.stack(ys, axis=1))


@pytest.mark.parametrize("T,G", [(3, 2), (8, 4), (24, 2), (20, 4), (24, 1)],
                         ids=["below", "at", "across", "no-multiple",
                              "one-group-as-a-group-axis"])
def test_the_chunked_form_by_groups_is_the_per_token_recurrence(T, G):
    """granite._ssd_chunk (chunk 8) with B and C [B, T, G, N] against
    the recurrence token by token, from a carried state, at lengths
    below, at and across the chunk; and with the group axis dropped at G
    = 1 it is the one-group form's result."""
    H, P, N, B = 4, 8, 16, 2
    dt, x, b, c, a_neg, s0 = _scan_operands(np.random.default_rng(T), B, T,
                                            H, P, N, G)
    s, y = granite._ssd_chunk(s0, dt, x, b, c, a_neg, 8)
    want_s, want_y = _token_by_token(s0, dt, x, b, c, a_neg)
    assert np.abs(np.asarray(y) - np.asarray(want_y)).max() < 1e-4
    assert np.abs(np.asarray(s) - np.asarray(want_s)).max() < 1e-4
    if G == 1:
        s1, y1 = granite._ssd_chunk(s0, dt, x, b[:, :, 0], c[:, :, 0],
                                    a_neg, 8)
        assert np.abs(np.asarray(y1) - np.asarray(y)).max() < 1e-5
        assert np.abs(np.asarray(s1) - np.asarray(s)).max() < 1e-5


@pytest.mark.parametrize("G", [2, 4])
def test_step_forms_by_groups_match_the_recurrence(G):
    """One token from a stored state with B and C [B, G, N]: the XLA
    step on gathered rows and the kernel on the pool (under
    interpretation) against the published recurrence; a row whose dt is
    0 keeps its state bit for bit; a fresh row starts from zeros."""
    from dynamo_tpu.ops.selective_scan import ssd_step

    S, M, H, P, N, B = 7, 2, 4, 32, 16, 3
    C = H * P
    rng = np.random.default_rng(G)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    pool, at = f(S, M, N, C), jnp.asarray([4, 1, 6], jnp.int32)
    dt = rng.uniform(0.05, 1.0, (B, H))
    dt[1] = 0.0                                     # a frozen row
    dt = jnp.asarray(dt, jnp.float32)
    x, b, c, a_neg = f(B, 1, H, P), f(B, 1, G, N), f(B, 1, G, N), \
        -jnp.exp(f(H))
    dec = jnp.repeat(jnp.exp(dt * a_neg), P, axis=-1)
    dtx = jnp.repeat(dt, P, axis=-1) * x.reshape(B, C)
    want_s, want_y = _token_by_token(pool[at, 1], dt[:, None], x, b, c,
                                     a_neg)
    want_y = want_y.reshape(B, C)
    s, y = granite._ssd_step(pool[at, 1], dec, dtx, b[:, 0], c[:, 0])
    assert np.abs(np.asarray(y) - np.asarray(want_y)).max() < 1e-4
    assert np.abs(np.asarray(s) - np.asarray(want_s)).max() < 1e-5
    before = np.asarray(pool)
    pool, y = ssd_step(pool, at, jnp.int32(1), dec, dtx, b[:, 0], c[:, 0],
                       interpret=True)
    got = np.asarray(pool)
    assert np.abs(np.asarray(y) - np.asarray(want_y)).max() < 1e-4
    assert np.abs(got[np.asarray(at), 1] - np.asarray(want_s)).max() < 1e-5
    assert (got[1, 1] == before[1, 1]).all()        # the frozen row
    assert (got[:, 0] == before[:, 0]).all()        # the other layer
    fresh = jnp.arange(B) == 0
    pool, y = ssd_step(pool, at, jnp.int32(0), dec, dtx, b[:, 0], c[:, 0],
                       fresh, interpret=True)
    zero_s, zero_y = _token_by_token(jnp.zeros((1, N, C)), dt[:1, None],
                                     x[:1], b[:1], c[:1], a_neg)
    assert np.abs(np.asarray(y[0]) - np.asarray(zero_y).reshape(C)).max() \
        < 1e-4
    assert np.abs(np.asarray(pool[4, 0]) - np.asarray(zero_s[0])).max() \
        < 1e-5


# ------------------------------------------ experts that are not gated


def _by_hand(x, w, idx, wu, wd, first=0):
    """sum_k w_k relu(x W_up[e_k])^2 W_down[e_k] over the held pairs, a
    loop over the pairs."""
    want = np.zeros(x.shape, np.float32)
    for t in range(x.shape[1]):
        for j in range(idx.shape[-1]):
            e = int(idx[0, t, j]) - first
            if 0 <= e < wu.shape[0]:
                y = jnp.square(jax.nn.relu(x[0, t] @ wu[e])) @ wd[e]
                want[0, t] += float(w[0, t, j]) * np.asarray(y)
    return want


def test_the_non_gated_expert_forms_agree(monkeypatch):
    """llama.moe_experts with ``w_gate`` None and relu2, told which
    experts it holds (experts 4-8 of a router of 12): dense over the held
    experts, sorted as the loop of small programs, and sorted as the
    grouped kernel under interpretation, each against a loop over the
    pairs by hand; a padding row gets zeros in the sorted forms; and it
    is NOT what a gate of w_up under relu would compute twice over."""
    rng = np.random.default_rng(7)
    D, I, E, k, first, T = 16, 8, 5, 3, 4, 12
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    wu, wd = f(E, D, I), f(E, I, D)
    idx = jnp.asarray(rng.integers(0, 12, (1, T, k)), jnp.int32)
    w, x = jax.nn.softmax(f(1, T, k), -1), f(1, T, D)
    live = jnp.ones((1, T), bool).at[0, 5].set(False)
    want = _by_hand(x, w, idx, wu, wd, first)
    assert np.abs(want).max() > 0.1
    dense = llama.moe_experts(x, w, idx, None, wu, wd, False, first=first,
                              act=llama.relu2)
    assert np.abs(np.asarray(dense) - want).max() < ATOL
    assert llama._moe_kernel_interpret(wu) is None
    loop = llama.moe_experts(x, w, idx, None, wu, wd, True, live=live,
                             first=first, width=12, act=llama.relu2)
    monkeypatch.setenv("DYN_PALLAS_INTERPRET", "1")
    assert llama._moe_kernel_interpret(wu) is True
    kernel = llama.moe_experts(x, w, idx, None, wu[None], wd[None], True,
                               live=live, layer=jnp.int32(0), first=first,
                               width=12, act=llama.relu2)
    for got in (loop, kernel):
        assert (np.asarray(got[0, 5]) == 0).all()
        rows = np.asarray(live[0])
        assert np.abs(np.asarray(got)[0, rows] - want[0, rows]).max() < ATOL
    # the same number as relu(a) * a, which is why the form has to be
    # told apart by what it READS: the gated form takes three stacks
    twice = llama.moe_experts(x, w, idx, wu, wu, wd, False, first=first,
                              act=jax.nn.relu)
    assert np.abs(np.asarray(twice) - want).max() < ATOL


def test_the_grouped_kernel_reads_two_matrices_an_expert():
    """ops/moe_grouped.py with ``w_gate`` None: two weight operands, the
    result a block's relu(x W_up)^2 W_down, with I in one tile and in
    two; with a gate it takes three as before."""
    rng = np.random.default_rng(8)
    L, E, D, I, block = 2, 3, 128, 256, 8
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    wu, wd, xs = f(L, E, D, I), f(L, E, I, D), f(4 * block, D)
    block_e = jnp.asarray([0, 2, 2, 1], jnp.int32)
    for tile in (None, 128):
        ys = moe_grouped_mlp(xs, None, wu, wd, jnp.int32(1), jnp.int32(3),
                             block_e, block=block, act=llama.relu2,
                             interpret=True, tile=tile)
        for j in range(3):
            rows = xs[j * block:(j + 1) * block]
            e = int(block_e[j])
            want = jnp.square(jax.nn.relu(rows @ wu[1, e])) @ wd[1, e]
            assert np.abs(np.asarray(ys[j * block:(j + 1) * block] - want)
                          ).max() < 2e-2 * float(jnp.abs(want).max())
    jaxpr = jax.make_jaxpr(lambda *a: moe_grouped_mlp(
        *a, block=block, act=llama.relu2, interpret=True))(
            xs, None, wu, wd, jnp.int32(1), jnp.int32(3), block_e)
    call = next(e for e in jaxpr.jaxpr.eqns[0].params["jaxpr"].eqns
                if e.primitive.name == "pallas_call")
    weights_in = [v for v in call.invars
                  if getattr(v.aval, "shape", None) in (wu.shape, wd.shape)]
    assert len(weights_in) == 2


# ------------------------------------- the chip's share of the experts


def _share(params, first, held, **over):
    cfg = tiny(n_routed_experts=held, first_local_expert=first, **over)
    cut = dict(params)
    for name in nemotron_h.EXPERT_KEYS:
        cut[name] = params[name][:, first:first + held]
    return cfg, cut


@pytest.mark.parametrize("tokens,is_sorted",
                         [(24, False), (256, True)],
                         ids=["dense", "sorted"])
def test_the_shares_add_up(tokens, is_sorted):
    """The guide's test of the cut (section 4): at the small size, the
    routed parts that the four shares (experts 0-2, 3-5, 6-8, 9-11)
    compute, EACH through W_lat_out, plus the shared expert counted
    once, equal what the UNCUT reference gives for the whole layer; in
    both execution forms (24 tokens run dense-over-experts, 256 the
    sorted dispatch: the first bucket past the chip's ridge, 288 before
    PR 66 moved the rule's edge there), with padding rows that count for nothing; and each
    share's program equals the reference given the same share."""
    uncut = tiny(n_routed_experts=12, router_num_experts=12)
    params = make_params(uncut, 3)
    assert params["w_up"].shape[1:] == (12, 32, 32)
    assert float(jnp.abs(params["router_bias"]).max()) > 0
    h = jnp.asarray(np.random.default_rng(3).normal(size=(2, tokens // 2,
                                                          64)), jnp.float32)
    valid = jnp.ones(h.shape[:2], bool).at[1, -5:].set(False)
    e = 3

    def norm(x, w):
        return llama.rms_norm(x, w.astype(jnp.float32), uncut.rms_norm_eps)

    def program(cfg, p):            # routed held through W_lat_out + shared
        out = nemotron_h._moe_ff(p, cfg, norm, h, jnp.int32(e), valid)[0] - h
        return jnp.where(valid[..., None], out, 0.0)    # padding: nothing

    def reference(cfg, p):
        with jax.default_matmul_precision("highest"):
            out = jnp.stack([
                REF._experts(cfg, p, norm(row, p["ln_mlp"][e]), e)
                for row in h])
        return jnp.where(valid[..., None], out, 0.0)

    assert llama._moe_use_blocked(None, tokens, 3, 3) is False
    assert llama._moe_use_blocked(None, tokens, 6, 3) is is_sorted
    whole = reference(uncut, params)
    parts = []
    for first in (0, 3, 6, 9):
        cfg, cut = _share(params, first, 3)
        assert nemotron_h.held_first(cfg) == first
        got = program(cfg, cut)
        assert np.abs(np.asarray(got - reference(cfg, cut))).max() < ATOL
        parts.append(got)
    x = norm(h, params["ln_mlp"][e])
    shared = jnp.where(
        valid[..., None],
        llama.relu2(x @ params["w_up_s"][e]) @ params["w_down_s"][e], 0.0)
    total = sum(parts) - 3 * shared
    assert np.abs(np.asarray(total - whole)).max() < ATOL
    assert np.abs(np.asarray(parts[0] - whole)).max() > 100 * ATOL
    assert np.abs(np.asarray(program(uncut, params) - whole)).max() < ATOL
    # two shares of six run the sorted form at the sorted case's tokens
    halves = [program(*_share(params, first, 6)) for first in (0, 6)]
    assert np.abs(np.asarray(sum(halves) - shared - whole)).max() < ATOL


def test_no_program_holds_a_gate_for_the_experts():
    """The window's program reads two matrices an expert: no leaf named
    w_gate in the tree, and of the einsums under ``moe.experts`` one
    goes into the experts' width and one comes out (the gated form has
    two going in)."""
    cfg = tiny()
    params = make_params(cfg)
    assert not [n for n in params if n.startswith("w_gate")]
    h = jnp.zeros((2, 1, 64), jnp.float32)

    def ff(h):
        return nemotron_h._moe_ff(
            params, cfg, lambda x, w: llama.rms_norm(
                x, w.astype(jnp.float32), cfg.rms_norm_eps),
            h, jnp.int32(0), jnp.ones((2, 1), bool))[0]

    from tests.test_sampling_topk import _eqns
    dots = [e for e in _eqns(jax.make_jaxpr(ff)(h).jaxpr)
            if e.primitive.name == "dot_general"
            and any(getattr(v.aval, "shape", ())[:1] == (6,)
                    for v in e.invars)]
    assert len(dots) == 2


# ------------------------------------------------------ through JaxEngine


def _engine(cfg=None, **over) -> JaxEngine:
    base = dict(page_size=PS, num_pages=64, max_batch=4, prefill_chunk=16,
                batch_buckets=(4,), prefill_buckets=(16,),
                page_buckets=(16,), max_prefill_batch=2, decode_steps=4,
                warmup_logprobs=False)
    base.update(over)
    cfg = cfg or tiny()
    return JaxEngine(cfg, EngineConfig(**base), params=make_params(cfg),
                     seed=0)


def test_generate_matches_reference_and_counts_the_pairs(run_async):
    """A 37-token prompt crosses three prefill chunks of 16 and then four
    windows, through JaxEngine, the page manager and the state pool: the
    engine's top-5 log-probabilities agree with the reference at every
    position; two sequences interleaved give what each gives alone; the
    window counts the pairs the router chose in the five ``moe`` layers
    beside those that lay in the held range; the chunks past a prompt's
    first are counted as carried."""
    eng = _engine()
    assert eng.family.name == "nemotron_h"
    assert isinstance(eng.state, tuple) and eng.state[0].shape[1:] == (
        5, 16, 128)
    rng = np.random.default_rng(2)
    p1, p2 = (rng.integers(1, 512, n).tolist() for n in (37, 11))

    async def main():
        a, tops = await _gen(eng, p1, 13, logprobs=5)
        b, _ = await _gen(eng, p2, 9)
        both = await asyncio.gather(_gen(eng, p1, 13), _gen(eng, p2, 9))
        stats = eng.stats()
        await eng.stop()
        return a, tops, b, both, stats

    a, tops, b, both, stats = run_async(main())
    want = np.asarray(jax.nn.log_softmax(
        ref_logits(eng.params, eng.cfg, p1 + a[:-1]), -1))
    for j, top in enumerate(tops):
        row = want[len(p1) - 1 + j]
        assert max(abs(row[i] - v) for i, v in top.items()) < ATOL
    assert both[0][0] == a and both[1][0] == b
    assert stats["state_slots_active"] == 0
    routed, held = (stats["moe_pairs_routed_total"],
                    stats["moe_pairs_held_total"])
    assert routed == 2 * (12 + 8) * 5 * 3
    assert 0.3 * routed < held < 0.7 * routed
    assert stats["prefill_row_chunks_carried_total"] == 2 * 2
    assert stats["prefill_row_chunks_total"] == 2 * (3 + 1)


@pytest.mark.parametrize("feature", ["spec_decode", "mesh", "host_pages",
                                     "disagg_prefill", "kv_transfer"])
def test_what_a_family_with_state_is_refused(feature):
    """The drafting head's verification, a mesh (the exchange of latent
    rows between the chips that share a layer's experts), the host tier
    and the movers of pages are refused by REFUSALS' ``state`` rows, in
    words."""
    said = family_of(tiny()).refusal(feature)
    assert said and REFUSALS[feature][1]["state"] in said
    assert "models/nemotron_h.py" in said
