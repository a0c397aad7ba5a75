"""Weight-only int8 quantization (per-output-channel symmetric).

Decode is HBM-bound: at batch sizes a single chip serves, every decode
step streams the full weight set from HBM, so int8 storage halves
bytes/token (and is the only way ~8B parameters fit beside a KV pool in
a 16 GB v5e). Activations stay bf16 — the MXU matmul runs exactly as in
the bf16 path; only the weight operand is stored quantized and widened
in VMEM (XLA fuses the convert+scale into the consumer dot, so the bf16
weights are never materialized in HBM).

Scheme: for a weight ``w[..., in, out]``, ``q = round(w / s)`` in int8
with per-output-channel scales ``s[..., 1, out] = amax(|w|, in) / 127``.
``x @ w`` is computed as ``(x @ q) * s`` — exactly equal to dequantizing
first (the scale is constant along the contraction), and slightly more
accurate since int8 values are exact in bf16.

``QuantInt8`` is a registered pytree whose leaves (q, s) both carry the
stacked-layer leading axis, so ``lax.scan`` over layers, pipeline-stage
sharding (P("stage") applies to both leaves via spec-prefixing), and
jit argument passing all work unchanged. It duck-types the few array
operations the model code applies to weights (``x @ w``, ``.astype``,
``.reshape``, ``.shape``) so models/llama.py and models/mla.py need no
int8 branches.

Reference parity: the reference's flagship configs serve FP8 engines
(docs/architecture.md:57-61, examples/llm/configs/disagg_router.yaml);
int8 weight-only is the TPU-native analog (v5e has no FP8 MXU mode).
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import tree_util

# Params quantized under --dtype int8: every large projection matrix.
# Excluded: embed (gather table), routers + router_bias (tiny,
# routing-precision-critical), norms and biases (1-D).
QUANT_KEYS = frozenset({
    # llama/qwen/gemma stack
    "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "lm_head",
    # MLA (DeepSeek) stack: q path, latent projections, output
    "w_q", "w_dq", "w_uq", "w_dkv", "w_uk", "w_uv", "w_o",
    # DeepSeek MoE segments: dense first-k, routed experts, shared
    "w_gate_d", "w_up_d", "w_down_d",
    "w_gate_e", "w_up_e", "w_down_e",
    "w_gate_s", "w_up_s", "w_down_s",
})


class QuantInt8:
    """int8 weight + per-output-channel scale; see module docstring."""

    __slots__ = ("q", "s")

    def __init__(self, q, s):
        self.q, self.s = q, s

    # ---- duck-typed array surface (only what model code uses on weights)

    @property
    def shape(self):
        return self.q.shape

    @property
    def ndim(self):
        return self.q.ndim

    def dequant(self, dtype=None):
        w = self.q.astype(self.s.dtype) * self.s
        return w.astype(dtype) if dtype is not None else w

    def astype(self, dtype):
        return self.dequant(dtype)

    def reshape(self, *shape):
        return self.dequant().reshape(*shape)

    def __getitem__(self, idx):
        # leading-(layer-)axis indexing only — q and s share that axis
        # (scale reduces axis -2, never axis 0, for every quantized key)
        return QuantInt8(self.q[idx], self.s[idx])

    def __rmatmul__(self, x):
        # (x @ q) * s — exact (scale constant along the contraction).
        # jax.Array.__matmul__ defers to unrecognized right operands.
        y = x @ self.q.astype(x.dtype)
        return y * jnp.squeeze(self.s, -2).astype(x.dtype)

    def __repr__(self):
        return f"QuantInt8(shape={tuple(self.q.shape)}, s={self.s.shape})"


tree_util.register_pytree_node(
    QuantInt8,
    lambda t: ((t.q, t.s), None),
    lambda aux, children: QuantInt8(*children),
)


def quantize_int8_np(w: np.ndarray) -> QuantInt8:
    """Host-side (numpy) quantization — used at checkpoint load so bf16
    weights never hit the device."""
    w32 = np.asarray(w, np.float32)
    amax = np.max(np.abs(w32), axis=-2, keepdims=True)
    s = np.maximum(amax / 127.0, 1e-12).astype(np.float32)
    q = np.clip(np.rint(w32 / s), -127, 127).astype(np.int8)
    return QuantInt8(q, s)


def quantize_int8(w: jax.Array) -> QuantInt8:
    w32 = jnp.asarray(w, jnp.float32)
    amax = jnp.max(jnp.abs(w32), axis=-2, keepdims=True)
    s = jnp.maximum(amax / 127.0, 1e-12)
    q = jnp.clip(jnp.rint(w32 / s), -127, 127).astype(jnp.int8)
    return QuantInt8(q, s)


def quantize_params(params: Dict, keys=QUANT_KEYS) -> Dict:
    """Quantize the standard projection weights of a loaded params tree
    (leaves already on device or host; non-listed keys untouched)."""
    out = {}
    for k, v in params.items():
        if k in keys and not isinstance(v, QuantInt8):
            out[k] = (quantize_int8_np(v) if isinstance(v, np.ndarray)
                      else quantize_int8(v))
        else:
            out[k] = v
    return out


def synthetic_int8_params(model, cfg,
                          device: Optional[jax.Device] = None) -> Dict:
    """Shape-faithful int8 params with MEANINGLESS values, built in
    milliseconds — for throughput benchmarking only.

    ``host_init_quantized`` draws a full Gaussian tree on the host; at
    8B on a single-core bench host that costs minutes of a chip run's
    budget for values the throughput measurement never looks
    at. Here: ``jax.eval_shape`` gives the exact tree without computing
    it, quantized keys get UNINITIALIZED int8 (always finite) with
    fan-in scales, norms get ones and everything else zeros (finite
    activations throughout — XLA does no value-dependent shortcuts, so
    the timing is identical to real weights)."""
    shapes = jax.eval_shape(lambda key: model.init_params(cfg, key),
                            jax.random.PRNGKey(0))
    out = {}
    for k, sd in shapes.items():
        if k in QUANT_KEYS:
            q = np.empty(sd.shape, np.int8)
            s = np.full(sd.shape[:-2] + (1,) + sd.shape[-1:],
                        1.0 / np.sqrt(sd.shape[-2]) / 127.0, np.float32)
            out[k] = QuantInt8(q, s)
        elif k.startswith(("ln_", "q_norm", "k_norm", "kv_norm")):
            out[k] = np.ones(sd.shape, np.float32)
        else:
            out[k] = np.zeros(sd.shape,
                              np.float32 if sd.dtype == jnp.float32
                              else jnp.bfloat16)
    dev = device or jax.devices()[0]
    return jax.device_put(out, dev)


def host_init_quantized(model, cfg, seed: int = 0,
                        device: Optional[jax.Device] = None) -> Dict:
    """Random-init on the host CPU backend, quantize there, then ship
    int8 to the accelerator — the bf16 tree never exists in HBM, which
    is what lets an 8B-shaped model start up on a 16 GB chip."""
    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        params = model.init_params(cfg, jax.random.PRNGKey(seed))
        params = quantize_params(params)
    dev = device or jax.devices()[0]
    return jax.device_put(params, dev)
