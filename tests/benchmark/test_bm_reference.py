"""benchmark/reference.py (plain jnp, expert by expert) against the
program's own llama.reference_forward at tiny size, and the on-device
weights against the program's parameter tree."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bm_paths  # noqa: F401

from benchmark import reference
from benchmark.harness import weights
from dynamo_tpu.models import llama
from dynamo_tpu.models.config import ModelConfig

TINY = {
    "mixtral-like": {
        "model_type": "mixtral", "vocab_size": 512, "hidden_size": 64,
        "intermediate_size": 96, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "num_local_experts": 4, "num_experts_per_tok": 2,
        "rope_theta": 1e6, "rms_norm_eps": 1e-5},
    "qwen3-moe-like": {
        "model_type": "qwen3_moe", "vocab_size": 512, "hidden_size": 64,
        "intermediate_size": 256, "moe_intermediate_size": 32,
        "head_dim": 32, "num_hidden_layers": 3, "num_attention_heads": 4,
        "num_key_value_heads": 1, "num_experts": 16,
        "num_experts_per_tok": 4, "norm_topk_prob": True,
        "rope_theta": 1e6, "rms_norm_eps": 1e-6},
    "dense": {
        "model_type": "llama", "vocab_size": 512, "hidden_size": 64,
        "intermediate_size": 128, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 4},
}


# what the two configurations' about.json files give the weight rule
SCALES = {"w_router": 2.0}
# sha256 of the bf16 tree from seed 2**31 + 26, taken on the parent of
# PR 26 (22497b9, ROUTER_GAIN a constant of weights.py)
PARENT_TREES = {
    "dense":
        "5e89ea8e9e351c6975a2257637fb9350c0d6e714f8b8c5dacb1484d97177025f",
    "mixtral-like":
        "702d397cc0cbc502cd2999383cc447d1deb91ac28264b73b75fe9a109f8e0787",
    "qwen3-moe-like":
        "87047b600c5a78579296ca386d491438b56164054d2913b3f2313daf4005bfd1",
}


def _cfg(kind, dtype="float32"):
    import dataclasses

    return dataclasses.replace(ModelConfig.from_hf_config(dict(TINY[kind])),
                               dtype=dtype)


def _scales(cfg):
    return SCALES if cfg.num_experts > 0 else {}


def _params(cfg, seed):
    return weights.make_params(llama, cfg, seed, _scales(cfg))


@pytest.mark.parametrize("kind", sorted(TINY))
def test_reference_agrees_with_the_programs_full_forward(kind):
    cfg = _cfg(kind)
    params = _params(cfg, 2 ** 31 + 5)
    toks = np.random.RandomState(0).randint(1, cfg.vocab_size, 40)
    with jax.default_matmul_precision("highest"):
        mine = np.asarray(reference.reference_logits(params, cfg, toks))
        theirs = np.asarray(llama.reference_forward(
            params, cfg, jnp.asarray(toks[None], jnp.int32))[0])
    assert mine.shape == theirs.shape == (40, cfg.vocab_size)
    np.testing.assert_allclose(mine, theirs, atol=2e-4, rtol=1e-4)


def test_reference_notices_a_missing_expert():
    """The tolerance of the agreement check means something only if a
    wrong computation lands outside it."""
    cfg = _cfg("mixtral-like")
    params = _params(cfg, 3)
    toks = np.arange(1, 33)
    good = jax.nn.log_softmax(reference.reference_logits(params, cfg, toks))
    broken = dict(params, w_down=params["w_down"].at[:, 0].set(0.0))
    bad = jax.nn.log_softmax(reference.reference_logits(broken, cfg, toks))
    assert float(jnp.max(jnp.abs(good - bad))) > reference.AGREE_ATOL


def test_reference_refuses_what_it_does_not_cover():
    cfg = ModelConfig.from_hf_config({
        "model_type": "gemma2", "vocab_size": 512, "hidden_size": 64,
        "intermediate_size": 128, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16})
    with pytest.raises(NotImplementedError):
        reference.reference_logits({}, cfg, [1, 2, 3])


@pytest.mark.parametrize("kind", sorted(TINY))
def test_weights_match_the_programs_tree_and_follow_the_seed(kind):
    cfg = _cfg(kind, "bfloat16")
    want = jax.eval_shape(
        lambda: llama.init_params(cfg, jax.random.PRNGKey(0)))
    a = _params(cfg, 2 ** 31 + 9)
    assert jax.tree.map(lambda x: (x.shape, x.dtype), a) == jax.tree.map(
        lambda x: (x.shape, x.dtype), want)
    b = _params(cfg, 2 ** 31 + 9)
    c = _params(cfg, 2 ** 31 + 10)
    assert all(bool(jnp.array_equal(a[k], b[k])) for k in a)
    assert not bool(jnp.array_equal(a["wq"], c["wq"]))
    assert bool(jnp.all(a["ln_attn"] == 1))
    std = float(jnp.std(a["wq"].astype(jnp.float32)))
    assert std == pytest.approx(cfg.hidden_size ** -0.5, rel=0.1)
    # layers are drawn from different keys
    assert not bool(jnp.array_equal(a["wq"][0], a["wq"][1]))


def _tops(ref_row, ids, shift=0.0):
    return {int(i): float(ref_row[i]) + shift for i in ids}


def test_judge_takes_the_median_and_caps_every_position():
    rng = np.random.RandomState(0)
    ref = np.log(rng.dirichlet(np.ones(50), size=9))
    toks = [int(np.argmax(r)) for r in ref]
    ids = [np.argsort(-r)[:20] for r in ref]
    good = [_tops(r, i, 0.02) for r, i in zip(ref, ids)]
    ok = reference.judge(ref, toks, good)
    assert ok["ok"] and ok["positions"] == 9
    assert ok["median_abs_logprob_diff"] == pytest.approx(0.02)
    # a few positions a routing swap hit do not move the median ...
    tops = list(good)
    for i in (1, 4, 7):
        tops[i] = _tops(ref[i], ids[i], 0.9)
    res = reference.judge(ref, toks, tops)
    assert res["ok"] and res["max_abs_logprob_diff"] == pytest.approx(0.9)
    # ... wrong numbers at one position are refused
    tops[4] = _tops(ref[4], ids[4], reference.FLIP_ATOL + 0.5)
    res = reference.judge(ref, toks, tops)
    assert not res["ok"] and res["positions_over_flip_atol"] == [4]
    # and so is a fault that moves every position a little
    bad = [_tops(r, i, reference.AGREE_ATOL + 0.03) for r, i in zip(ref, ids)]
    res = reference.judge(ref, toks, bad)
    assert not res["ok"] and not res["positions_over_flip_atol"]


@pytest.mark.parametrize("kind", sorted(TINY))
def test_trees_are_the_parents_bit_for_bit(kind):
    """The router's gain moved from a constant of weights.py into the
    configurations' about.json (PR 26); the tree a seed gives did not
    move."""
    tree = _params(_cfg(kind, "bfloat16"), 2 ** 31 + 26)
    h = hashlib.sha256()
    for name in sorted(tree):
        a = np.asarray(tree[name])
        h.update(f"{name}:{a.dtype}:{a.shape}".encode())
        h.update(a.tobytes())
    assert h.hexdigest() == PARENT_TREES[kind]


def test_todays_configurations_give_the_pinned_scales():
    from benchmark.harness import cells

    for cell in ("mixtral-8x7b.chat-steady", "qwen3-30b-a3b.decode-heavy",
                 "mixtral-8x7b.shared-prefix"):
        assert cells.load_cell(cell)["weight_scales"] == SCALES


@pytest.mark.parametrize("scales, std, zero", [
    (SCALES, 2.0, False), ({}, 1.0, False), ({"w_router": 0.5}, 0.5, False),
    ({"w_router": "zeros"}, 0.0, True)])
def test_router_weights_are_drawn_at_the_configurations_gain(scales, std, zero):
    cfg = _cfg("qwen3-moe-like", "bfloat16")
    params = weights.make_params(llama, cfg, 11, scales)
    router = params["w_router"].astype(jnp.float32)
    assert bool(jnp.all(router == 0)) == zero
    assert float(jnp.std(router)) == pytest.approx(
        std * cfg.hidden_size ** -0.5, rel=0.1)
    # no other leaf moves with it
    base = weights.make_params(llama, cfg, 11, {})
    assert all(bool(jnp.array_equal(params[k], base[k]))
               for k in params if k != "w_router")


def test_a_scale_for_a_leaf_the_tree_lacks_is_refused():
    with pytest.raises(ValueError, match="router_bias"):
        weights.make_params(llama, _cfg("mixtral-like"), 1,
                            {"router_bias": "zeros"})


def test_weights_make_greedy_tokens_printable_ascii():
    """lm_head's printable columns outweigh the rest, so a greedy token
    is one ASCII character under the byte tokenizer and reaches a client
    as text without logprobs."""
    cfg = _cfg("qwen3-moe-like", "bfloat16")
    params = _params(cfg, 2 ** 31 + 4)
    head = params["lm_head"].astype(jnp.float32)
    lo, hi = weights.PRINTABLE
    inside = float(jnp.std(head[:, lo:hi]))
    outside = float(jnp.std(jnp.concatenate([head[:, :lo], head[:, hi:]], 1)))
    assert outside == pytest.approx(inside * weights.OTHER_IDS_SCALE, rel=0.1)
    toks = np.random.RandomState(1).randint(1, cfg.vocab_size, 64)
    best = np.asarray(jnp.argmax(
        reference.reference_logits(params, cfg, toks), -1))
    assert ((best >= lo) & (best < hi)).all()
