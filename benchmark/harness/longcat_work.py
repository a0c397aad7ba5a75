"""What the readers of ``longcat-flash-omni.omni-turns`` need of a
``longcat_flash`` ``config.json`` as it is run (two latent attentions and
two dense MLPs a layer with one shortcut MoE:
dynamo_tpu/models/longcat_flash.py): its shapes, and what ONE decode
step of ``rows`` rows has to move, a part at a time.

**The latent kernel's work.** Every layer calls the latent decode kernel
TWICE (a sub-block each, over pool entries 2l and 2l + 1), so a step's
latent attention is ``benchmark/harness/latent_work.py
latent_attention_decode`` of one pool entry times ``sub_blocks`` = 2 x
``num_layers``, at the published 64 heads: ``2 H (2r + d_r)`` = 139,264
operations a cached token over ``(r + d_r) x 2`` = 1,152 bytes, 121
operations a byte, half the v5e's ridge of 240.

**A step's bytes** (``decode_step_bytes``; a floor: activations, the
router, norms, the embedding row and the sampler are left out): every
weight a step reads is read once whatever the rows, but an expert's
three matrices only where a row's pair chose it: with ``held`` of
``real`` experts here and ``pairs`` = rows x top-k x (1 - the identity
share) real pairs a layer spread evenly over the ``real`` experts, an
expert of this chip is touched with probability 1 - (1 - 1/real)^pairs.
"""

from __future__ import annotations

from typing import Optional


def shapes(config: dict) -> Optional[dict]:
    """Of a ``longcat_flash`` configuration as it is run; None for any
    other."""
    if config.get("model_type") != "longcat_flash":
        return None
    real = config.get("router_num_experts", config["n_routed_experts"])
    return {"layers": config["num_layers"],
            "sub_blocks": 2 * config["num_layers"],
            "heads": config["num_attention_heads"],
            "hidden": config["hidden_size"],
            "ffn": config["ffn_hidden_size"],
            "expert_ffn": config["expert_ffn_hidden_size"],
            "q_rank": config["q_lora_rank"],
            "kv_rank": config["kv_lora_rank"],
            "rope": config["qk_rope_head_dim"],
            "nope": config["qk_nope_head_dim"],
            "v": config["v_head_dim"],
            "held": config["n_routed_experts"], "real": real,
            "zero": config.get("zero_expert_num", 0),
            "top_k": config["moe_topk"], "vocab": config["vocab_size"]}


def decode_step_bytes(s: dict, rows: int, mean_context: float,
                      identity_share: float, page_size: int = 128,
                      itemsize: int = 2) -> dict:
    """{part: bytes} of one decode step of ``rows`` rows at
    ``mean_context`` cached tokens a row, ``s`` = ``shapes(config)``."""
    D, H = s["hidden"], s["heads"]
    attn = (D * s["q_rank"] + s["q_rank"] * H * (s["nope"] + s["rope"])
            + D * (s["kv_rank"] + s["rope"])
            + s["kv_rank"] * H * (s["nope"] + s["v"]) + H * s["v"] * D)
    pairs = rows * s["top_k"] * (1.0 - identity_share)
    touched = s["held"] * (1.0 - (1.0 - 1.0 / s["real"]) ** pairs)
    pages = -(-mean_context // page_size) * page_size
    return {
        "attn_proj": s["sub_blocks"] * attn * itemsize,
        "dense_ffn": s["sub_blocks"] * 3 * D * s["ffn"] * itemsize,
        "held_experts": s["layers"] * touched * 3 * D * s["expert_ffn"]
        * itemsize,
        "latent_pages": s["sub_blocks"] * rows * pages
        * (s["kv_rank"] + s["rope"]) * itemsize,
        "head": D * s["vocab"] * itemsize,
    }
