"""int8 compression for KV pages crossing a slow boundary.

KV pages leave HBM in two places: the host-DRAM tier (engine/kv_manager
multi-tier pool — reference KV block manager V2's host tier) and the
disaggregation transfer plane (llm/disagg/transfer.py — the NIXL
replacement). Both move whole pages ``[L, n, KV, ps, hd]`` over links
that are orders of magnitude slower than HBM (PCIe for D2H, DCN TCP for
disagg; how much slower is unmeasured on a directly attached chip).
Quantizing per (token, head) row to int8 with an f32 amax/127 scale halves the bytes on those links (hd bytes + 4 vs 2·hd
bf16) at a per-element error ≤ s/2 — the LMCache/CacheGen-style KV
compression the GPU stacks apply at the same boundary.

Lossy ⇒ strictly OPT-IN (EngineConfig.host_tier_int8, PrefillWorker
compress_kv / DYN_KV_TRANSFER_INT8): restored pages round-trip through
int8, so decode on them is no longer bit-identical to a run that never
offloaded. Pages inside HBM always stay in the pool dtype.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np


@jax.jit
def quantize_pages(pages: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Device-side: [L, n, KV, ps, hd] → (int8 same shape, f32 scales
    [L, n, KV, ps, 1]). Runs BEFORE the D2H copy so the slow link moves
    int8, not bf16."""
    a32 = pages.astype(jnp.float32)
    amax = jnp.max(jnp.abs(a32), axis=-1, keepdims=True)
    s = jnp.maximum(amax / 127.0, 1e-12)
    q = jnp.clip(jnp.rint(a32 / s), -127, 127).astype(jnp.int8)
    return q, s


@jax.jit
def dequantize_pages(q: jax.Array, s: jax.Array) -> jax.Array:
    """Device-side inverse (f32; the pool scatter casts to pool dtype).
    Runs AFTER the H2D copy, for the same reason."""
    return q.astype(jnp.float32) * s


def quantize_pages_np(pages: np.ndarray
                      ) -> Tuple[np.ndarray, np.ndarray]:
    """Host-side variant for the transfer plane (pages are already host
    arrays there — extract_pages staged them)."""
    a32 = np.asarray(pages, np.float32)
    amax = np.max(np.abs(a32), axis=-1, keepdims=True)
    s = np.maximum(amax / 127.0, 1e-12).astype(np.float32)
    q = np.clip(np.rint(a32 / s), -127, 127).astype(np.int8)
    return q, s


def dequantize_pages_np(q: np.ndarray, s: np.ndarray,
                        dtype) -> np.ndarray:
    return (np.asarray(q, np.float32) * s).astype(dtype)
