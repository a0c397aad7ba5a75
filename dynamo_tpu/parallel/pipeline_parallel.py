"""Pipeline parallelism over the mesh's ``stage`` axis.

SURVEY §2.4's last unbuilt row. The reference inherits pipeline
parallelism from its engines (vLLM ``--pipeline-parallel-size``, which
its own disagg deployments force to 1 — reference
docs/disagg_serving.md); the TPU-native shape is not NCCL
point-to-points between per-rank processes but a single SPMD program:
layers are stacked on a leading axis (models/llama.py init_params), so
stage-sharding is nothing more than ``P("stage")`` on that axis, and the
GPipe-style schedule is a ``lax.scan`` whose carry rotates activations
one stage forward with ``lax.ppermute`` each tick.

Schedule: with S stages and M microbatches (split over the batch dim),
the scan runs S+M-1 ticks; at tick t stage s computes microbatch t-s
(bubble fraction (S-1)/(S+M-1), amortized by M). Stage 0 embeds fresh
microbatches; the last stage collects hidden states, applies the final
norm + LM head, and a masked ``psum`` replicates the logits to every
stage so the caller sees a plain array.

This module provides the forward plane (full-attention prefill → logits,
the compute that dominates PP deployments) + param shardings; paged
decode under PP would additionally stage-shard the KV pool's layer axis
and is deliberately out of scope until a deployment needs it (the
reference ships PP=1 everywhere it matters).
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.config import ModelConfig
from ..models.llama import (Params, _layer_keys, _window_flag,
                            embed_tokens, full_attention_layer,
                            project_logits, rms_norm, rope_freqs)

# params stacked on a leading layer axis get that axis stage-sharded;
# everything else (embed, final norm, head) is replicated
# every per-layer param name any config can produce (superset of
# llama._layer_keys across configs — pp_param_specs has no cfg in hand,
# it shards whatever per-layer keys are present in the pytree)
_STACKED = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
            "ln_attn", "ln_mlp", "ln_attn_post", "ln_mlp_post",
            "q_norm", "k_norm", "bq", "bk", "bv", "w_router")


def pp_param_specs(params: Params) -> Dict[str, P]:
    return {k: (P("stage") if k in _STACKED else P())
            for k in params}


def shard_params_pp(params: Params, mesh: Mesh) -> Params:
    specs = pp_param_specs(params)
    return {k: jax.device_put(v, NamedSharding(mesh, specs[k]))
            for k, v in params.items()}


def make_pp_forward(cfg: ModelConfig, mesh: Mesh,
                    num_microbatches: int = 4):
    """Jitted pipelined forward: ``fn(params, tokens[B, T]) -> logits
    [B, T, V]`` (float32), numerically matching
    ``models.llama.reference_forward``.

    B must divide into ``num_microbatches`` equal microbatches and
    ``cfg.num_layers`` into ``mesh.shape['stage']`` equal stages.
    """
    S = mesh.shape["stage"]
    if cfg.num_layers % S != 0:
        raise ValueError(f"num_layers {cfg.num_layers} not divisible by "
                         f"{S} stages")
    if cfg.num_experts > 0:
        raise NotImplementedError("PP forward covers dense models; "
                                  "stage-shard MoE when a deployment "
                                  "needs both PP and EP")
    M = num_microbatches
    inv_freq = rope_freqs(cfg)
    scale = cfg.attn_scale

    def _local_layers(h, lp_stack, layer_off):
        """Run this stage's layer slice (leading axis L/S) over h
        [b, T, D] — the shared full-attention layer body. ``layer_off``
        is the stage's global layer offset (Gemma-2's sliding-window
        parity is indexed by GLOBAL layer, not stage-local)."""
        b, T = h.shape[:2]
        n_local = cfg.num_layers // S
        pos = jnp.broadcast_to(jnp.arange(T)[None, :], (b, T))

        def layer(h, xs):
            lp, li = xs
            return full_attention_layer(
                cfg, h, lp, pos, inv_freq, scale,
                is_sliding=_window_flag(cfg, layer_off + li)), None

        h, _ = lax.scan(layer, h,
                        (lp_stack, jnp.arange(n_local)))
        return h

    # the per-layer key set is owned by llama._layer_keys — PP stages
    # scan exactly the params the shared layer body consumes
    stacked_keys = _layer_keys(cfg)

    def _fwd(params, tokens):
        """Per-stage body (under shard_map over 'stage'): tokens
        [M, b, T] replicated; stacked params arrive as the local
        [L/S, ...] slice."""
        ax = lax.axis_index("stage")
        lp_stack = {k: params[k] for k in stacked_keys}
        _, b, T = tokens.shape
        D = params["embed"].shape[1]
        dt = params["embed"].dtype

        def tick(carry, t):
            recv, outbuf = carry
            # stage 0 injects microbatch t (clamped once the injection
            # phase is over; the result is masked out by collection)
            emb = embed_tokens(params, cfg,
                               tokens[jnp.clip(t, 0, M - 1)])
            my_in = jnp.where(ax == 0, emb, recv)
            out = _local_layers(my_in, lp_stack,
                                ax * (cfg.num_layers // S))
            # last stage collects microbatch t-(S-1) once it emerges
            oidx = t - (S - 1)
            oidx_c = jnp.clip(oidx, 0, M - 1)
            valid = (oidx >= 0) & (ax == S - 1)
            cur = lax.dynamic_index_in_dim(outbuf, oidx_c, 0,
                                           keepdims=False)
            outbuf = lax.dynamic_update_index_in_dim(
                outbuf, jnp.where(valid, out, cur), oidx_c, 0)
            # rotate activations one stage forward
            nxt = lax.ppermute(out, "stage",
                               [(i, i + 1) for i in range(S - 1)])
            return (nxt, outbuf), None

        recv0 = jnp.zeros((b, T, D), dt)
        outbuf0 = jnp.zeros((M, b, T, D), dt)
        (_, outbuf), _ = lax.scan(tick, (recv0, outbuf0),
                                  jnp.arange(S + M - 1))

        h = rms_norm(outbuf, params["ln_final"], cfg.rms_norm_eps,
                     cfg.norm_unit_offset)
        logits = project_logits(params, cfg, h)
        # only the last stage holds real outputs; masked psum replicates
        logits = jnp.where(ax == S - 1, logits, 0.0)
        return lax.psum(logits, "stage")

    def forward(params, tokens):
        B, T = tokens.shape
        if B % M != 0:
            raise ValueError(f"batch {B} not divisible into {M} "
                             f"microbatches")
        mb = tokens.reshape(M, B // M, T)
        in_specs = (pp_param_specs(params), P())
        fn = shard_map(_fwd, mesh=mesh, in_specs=in_specs, out_specs=P(),
                       check_vma=False)
        out = fn(params, mb)           # [M, b, T, V]
        return out.reshape(B, T, -1)

    return jax.jit(forward)
