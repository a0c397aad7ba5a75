"""Batched token sampling (jitted, per-request parameters).

The sampling stage runs on-device right after the forward pass so only the
sampled token ids (a few bytes per sequence) cross back to the host — the
TPU-native replacement for the reference engines' sampler (vLLM
SamplingParams ← our SamplingOptions, lib/llm/src/protocols/common.rs).

Per-row temperature/top-k/top-p live in device arrays so one jitted function
serves heterogeneous batches (no recompile per request mix). Greedy rows are
temperature=0.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclass
class SamplingBatch:
    """Per-row sampling parameters, padded to the decode batch size."""

    temperature: np.ndarray  # [B] float32; 0 → greedy
    top_k: np.ndarray        # [B] int32; 0 → disabled
    top_p: np.ndarray        # [B] float32; 1.0 → disabled
    seeds: np.ndarray        # [B] uint32 per-row RNG streams
    # OpenAI/HF penalties; neutral values disable each
    rep: np.ndarray          # [B] float32; 1.0 → disabled (HF semantics)
    freq: np.ndarray         # [B] float32; 0.0 → disabled
    pres: np.ndarray         # [B] float32; 0.0 → disabled

    @classmethod
    def build(cls, rows, pad_to: int) -> "SamplingBatch":
        """rows: list of SamplingOptions-like objects with .temperature,
        .top_k, .top_p, .seed (+ the penalty fields)."""
        B = pad_to
        temperature = np.zeros(B, np.float32)
        top_k = np.zeros(B, np.int32)
        top_p = np.ones(B, np.float32)
        seeds = np.zeros(B, np.uint32)
        rep = np.ones(B, np.float32)
        freq = np.zeros(B, np.float32)
        pres = np.zeros(B, np.float32)
        for i, s in enumerate(rows):
            temperature[i] = s.temperature if s.temperature is not None else 0.0
            top_k[i] = s.top_k or 0
            top_p[i] = s.top_p if s.top_p is not None else 1.0
            seeds[i] = (s.seed if s.seed is not None
                        else np.random.randint(0, 2**31)) & 0xFFFFFFFF
            rep[i] = (s.repetition_penalty
                      if getattr(s, "repetition_penalty", None) else 1.0)
            freq[i] = getattr(s, "frequency_penalty", None) or 0.0
            pres[i] = getattr(s, "presence_penalty", None) or 0.0
        return cls(temperature, top_k, top_p, seeds, rep, freq, pres)

    @property
    def has_penalties(self) -> bool:
        return bool((self.rep != 1.0).any() or (self.freq != 0.0).any()
                    or (self.pres != 0.0).any())


def apply_penalties(logits: jax.Array, counts: jax.Array,
                    presence: jax.Array, rep: jax.Array,
                    freq: jax.Array, pres: jax.Array,
                    bias=None) -> jax.Array:
    """Sampling penalties on raw logits (before temperature), vLLM
    order and semantics:

    - repetition (HF `RepetitionPenaltyLogitsProcessor`): tokens present
      ANYWHERE in the context (prompt + generated) get positive logits
      divided / negative logits multiplied by the penalty;
    - frequency/presence (OpenAI): subtract ``freq·count`` and
      ``pres·(count>0)`` where ``count`` is over GENERATED tokens only.

    counts: [B, V] generated-token counts; presence: [B, V] context
    presence (bool-ish); penalties are per-row [B].
    """
    present = presence > 0
    rp = rep[:, None]
    logits = jnp.where(
        present & (rp != 1.0),
        jnp.where(logits > 0, logits / rp, logits * rp), logits)
    cf = counts.astype(jnp.float32)
    logits = logits - freq[:, None] * cf - pres[:, None] * (cf > 0)
    if bias is not None:
        # OpenAI logit_bias [B, V]: plain additive, before sampling
        logits = logits + bias
    return logits


def update_penalty_state(penalties, sampled: jax.Array, done: jax.Array):
    """Fold a window step's sampled tokens into the penalty state, for
    the one loop that samples (models/window.py make_window), before its
    carry_step_update. ``done`` is the PRE-step mask: tokens sampled
    while a row was live are the ones the host will append. Returns the
    updated tuple (or None through the penalty-free path)."""
    if penalties is None:
        return None
    counts, presence, rest = penalties[0], penalties[1], penalties[2:]
    if counts.shape[1] == 1:
        # bias-only placeholder state ([B, 1]): counts are unused by
        # apply_penalties (neutral rep/freq/pres) — nothing to fold in,
        # and a real scatter would index out of bounds
        return penalties
    with jax.named_scope("sample"):
        rows = jnp.arange(counts.shape[0])
        live = jnp.logical_not(done).astype(counts.dtype)
        counts = counts.at[rows, sampled].add(live)
        presence = presence.at[rows, sampled].max(
            live.astype(presence.dtype))
    return (counts, presence) + rest


_CHUNK = 128  # lanes of one TPU vector register: a chunk is one lane row


def _rank_bits(bits: jax.Array) -> jax.Array:
    """float32 bits <-> the int32 of the same rank (its own inverse):
    ``lax.top_k``'s total order, -0.0 below 0.0, a NaN past the
    infinity of its sign."""
    return bits ^ ((bits >> 31) & 0x7FFFFFFF)


def _top_k_two_key(x: jax.Array, ids: jax.Array, k: int
                   ) -> Tuple[jax.Array, jax.Array]:
    """The k best of every (narrow) float32 row with their ``ids``, by
    one two-key sort: (value, -id) ascending, read from the end, so
    equal values come out by lower id and no tie is left to the sort.
    The value key is the float's bits mapped to the int32 of the same
    rank, which is ``lax.top_k``'s total order (-0.0 below 0.0) where
    ``lax.sort`` on floats would call the two zeros equal.
    ``lax.top_k`` itself would do on paper; on a v5e its single-row
    lowering does not keep equal values in index order (PERF.md, PR 25).
    """
    key = _rank_bits(jax.lax.bitcast_convert_type(x, jnp.int32))
    key, neg_ids = jax.lax.sort((key, -ids), dimension=1, num_keys=2)
    vals = jax.lax.bitcast_convert_type(_rank_bits(key[:, :-k - 1:-1]),
                                        jnp.float32)
    return vals, -neg_ids[:, :-k - 1:-1]


@partial(jax.jit, static_argnames=("k",))
def exact_top_k(x: jax.Array, k: int) -> Tuple[jax.Array, jax.Array]:
    """``jax.lax.top_k(x, k)`` for x [B, V], to the letter: values
    descending, equal values by lower vocabulary index, float32
    compares, nothing approximate -- without sorting the vocabulary.

    1. the row is N = ceil(V / 128) chunks of 128 (tail padded with
       -inf); take every chunk's maximum: one read of the logits;
    2. the k best of the N maxima (lower chunk first among equals) name
       k chunks. An element of the global top k sits in one of them:
       were its chunk left out, k chunks would precede it (larger
       maximum, or equal maximum and lower index), and each holds an
       element that precedes it;
    3. gather those chunks and take the k best of the k * 128
       candidates, each under its vocabulary id, so ties break as they
       did. The padding has the highest ids and the lowest value: an
       id >= V is never returned.

    The shape decides at trace time: with N <= 2k (tiny vocabularies)
    this is the plain call. Jitted, so that an eager caller
    (``logprob_aux`` after an eager prefill) dispatches one program and
    not two dozen.
    """
    B, V = x.shape
    N = -(-V // _CHUNK)
    if N <= 2 * k:
        return jax.lax.top_k(x, k)
    dtype, x = x.dtype, x.astype(jnp.float32)
    if N * _CHUNK != V:
        x = jnp.pad(x, ((0, 0), (0, N * _CHUNK - V)),
                    constant_values=-jnp.inf)
    chunks = x.reshape(B, N, _CHUNK)
    iota = partial(jax.lax.broadcasted_iota, jnp.int32)
    _, picked = _top_k_two_key(jnp.max(chunks, axis=-1),
                               iota((B, N), 1), k)              # [B, k]
    cand = jnp.take_along_axis(chunks, picked[:, :, None], axis=1,
                               mode="promise_in_bounds")
    ids = picked[:, :, None] * _CHUNK + iota((B, k, _CHUNK), 2)
    vals, ids = _top_k_two_key(cand.reshape(B, k * _CHUNK),
                               ids.reshape(B, k * _CHUNK), k)
    return vals.astype(dtype), ids


def greedy_tokens(logits: jax.Array) -> jax.Array:
    """The greedy token of every row of logits [B, V], int32: the token
    ``exact_top_k(logits, k)[1][:, 0]`` names, to the letter (the
    maximum in :func:`_rank_bits`' total order, the lowest vocabulary id
    among equals), by one argmax over that int32 key: one read of the
    logits, no sort, no chunks, no pad, no copy. "To the letter" as far
    as ``exact_top_k`` is itself defined: a row holding a NaN with the
    sign bit set is not (its first stage is a float ``max``, which hands
    on a NaN of either sign, where this key ranks that one below
    ``-inf``), and with N <= 2k chunks ``exact_top_k`` is the plain
    ``lax.top_k``, whose order among equals is the lowering's (lowest id
    on the CPU; one row on a v5e not: PERF.md, PR 25) where this is
    lowest id always. What a temperature-0 row draws wherever it is
    drawn: :func:`sample_tokens`' all-greedy arm and
    :func:`verify_greedy_draft` call this, the sampled arm's greedy rows
    read the same token off ``exact_top_k``. No scope of its own, like
    ``exact_top_k``: the caller's ``sample`` names it (a scope inside
    itself would count an op twice in the trace's sums)."""
    key = _rank_bits(jax.lax.bitcast_convert_type(
        logits.astype(jnp.float32), jnp.int32))
    return jnp.argmax(key, axis=-1).astype(jnp.int32)


@partial(jax.jit, static_argnames=("max_top_k",))
def sample_tokens(logits: jax.Array, temperature: jax.Array,
                  top_k: jax.Array, top_p: jax.Array, seeds: jax.Array,
                  step: jax.Array, max_top_k: int = 64,
                  penalties=None) -> jax.Array:
    """Sample one token per row. logits: [B, V] float32; ``step`` is a
    scalar or per-row [B] decode-step counter (advances the RNG stream).

    Greedy rows (temperature==0) take argmax. Sampled rows apply
    [penalties →] temperature → top-k (static bound ``max_top_k``,
    per-row effective k) → top-p (nucleus) → categorical draw from a
    per-row fold_in'd key. ``penalties``, when given, is the tuple
    ``(counts [B,V], presence [B,V], rep [B], freq [B], pres [B])``
    consumed by :func:`apply_penalties`; None (the default and the only
    pre-compiled variant) keeps the penalty-free program.

    The program branches at run time on what the batch asks for: a
    batch of greedy rows alone (padding rows are temperature 0) is
    :func:`greedy_tokens`, the candidates are selected only where a row
    is sampled. Same tokens either way.
    """
    with jax.named_scope("sample"):
        if penalties is not None:
            logits = apply_penalties(logits, *penalties)
        step = jnp.broadcast_to(step, temperature.shape)
        B, V = logits.shape

        # above the branch, so that behind a head the divide stays the
        # root of the head's output fusion (a branch cannot be reached
        # into); a greedy row divides by 1.0 and keeps its bits
        temp = jnp.where(temperature > 0, temperature, 1.0)[:, None]
        scaled = logits / temp

        def sampled_arm():
            # top-k within a static bound: take max_top_k once, mask
            # per-row k. Greedy rows of a batch with a sampled row reuse
            # this pass: argmax == top-1
            k_vals, k_idx = exact_top_k(scaled, max_top_k)  # [B, K]
            greedy = k_idx[:, 0]
            ranks = jnp.arange(max_top_k)[None, :]
            eff_k = jnp.where(top_k[:, None] > 0,
                              jnp.minimum(top_k[:, None], max_top_k),
                              max_top_k)
            k_vals = jnp.where(ranks < eff_k, k_vals, -jnp.inf)

            # top-p over the (sorted) top-k candidates
            probs = jax.nn.softmax(k_vals, axis=-1)
            cum = jnp.cumsum(probs, axis=-1)
            # always keep the first candidate
            keep = (cum - probs) < top_p[:, None]
            k_vals = jnp.where(keep, k_vals, -jnp.inf)

            def row_sample(i):
                key = jax.random.fold_in(
                    jax.random.fold_in(jax.random.PRNGKey(0), seeds[i]),
                    step[i])
                choice = jax.random.categorical(key, k_vals[i])
                return k_idx[i, choice]

            sampled = jax.vmap(row_sample)(jnp.arange(B))
            return jnp.where(temperature > 0, sampled,
                             greedy).astype(jnp.int32)

        # Device time on a v5e, float32 (tools/sampler_op_timing.py;
        # PERF.md, PR 47), us, behind a bfloat16 head (the matmul and
        # this function in one program): the program without the branch
        # on any batch | this one on a batch of greedy rows | on a batch
        # whose last row alone is sampled: [256, 151936] 3,631 | 1,385 |
        # 3,636, [64, 151936] 1,320 | 853 | 1,322, [128, 65536] 1,035 |
        # 476 | 1,036. The greedy arm alone 209 / 55 / 48 (one read of
        # the logits at 819 GB/s: 190 / 48 / 41), the predicate 0.5.
        # (On lax.top_k the function took 12.46 ms at [64, 151936]:
        # there it compiles to a stable sort of the whole row; PR 25.)
        return jax.lax.cond(jnp.any(temperature > 0), sampled_arm,
                            lambda: greedy_tokens(scaled))


@jax.jit
def verify_greedy_draft(logits: jax.Array, draft: jax.Array,
                        draft_len: jax.Array
                        ) -> Tuple[jax.Array, jax.Array]:
    """Vectorized accept-mask + bonus-token draw for self-speculative
    decode (greedy rows only — the engine bypasses speculation for
    sampled/penalized/logprobs requests).

    logits: [B, K+1, V] from the multi-token verify forward, where
    position j's logits predict the token AFTER input j (input 0 is the
    row's pending decode token, inputs 1..K the draft); draft: [B, K];
    draft_len: [B] valid draft tokens per row (rows ride with shorter —
    or padded-empty — drafts in the same static program).

    Returns (out_tokens [B, K+1], accepted [B]): row i emits
    ``out_tokens[i, :accepted[i] + 1]`` — the accepted draft prefix plus
    the bonus token greedily drawn at the first divergent (or final)
    position; entries past that are -1.

    The greedy target is :func:`greedy_tokens`, what
    :func:`sample_tokens` draws for a greedy row on either arm, so
    speculation on/off is token-identical by construction, tie-breaking
    included.
    """
    B, K1, V = logits.shape
    K = K1 - 1
    greedy = greedy_tokens(logits.reshape(B * K1, V)).reshape(B, K1)
    match = jnp.logical_and(draft == greedy[:, :K],
                            jnp.arange(K)[None, :] < draft_len[:, None])
    # longest all-true prefix: cumprod zeroes everything past a miss
    accepted = jnp.sum(jnp.cumprod(match.astype(jnp.int32), axis=1), axis=1)
    bonus = jnp.take_along_axis(greedy, accepted[:, None], axis=1)
    steps = jnp.arange(K1)[None, :]
    draft_ext = jnp.concatenate(
        [draft.astype(jnp.int32), jnp.zeros((B, 1), jnp.int32)], axis=1)
    out = jnp.where(steps < accepted[:, None], draft_ext,
                    jnp.where(steps == accepted[:, None], bonus, -1))
    return out.astype(jnp.int32), accepted


def _gather_rows(logp: jax.Array, chosen: jax.Array) -> jax.Array:
    return logp[jnp.arange(logp.shape[0]), chosen]


def compute_logprobs(logits: jax.Array, chosen: jax.Array) -> jax.Array:
    """Log-probability of the chosen tokens: logits [B, V], chosen [B]."""
    return _gather_rows(jax.nn.log_softmax(logits, axis=-1), chosen)


def logprob_aux(logits: jax.Array, chosen: jax.Array, topn: int):
    """(chosen_logprob [B], top_vals [B, topn], top_ids [B, topn]) over
    the RAW model logits — OpenAI logprobs describe the model's
    distribution, so penalties/temperature are not reflected (vLLM's
    default differs; this is the documented contract here)."""
    with jax.named_scope("sample"):
        logp = jax.nn.log_softmax(logits, axis=-1)
        tv, ti = exact_top_k(logp, topn)
        return _gather_rows(logp, chosen), tv, ti


# ------------------------------------------- generation by diffusion (blocks)


def sample_with_confidence(logits: jax.Array, temperature: jax.Array,
                           top_k: jax.Array, top_p: jax.Array,
                           seeds: jax.Array, step: jax.Array,
                           max_top_k: int = 64
                           ) -> Tuple[jax.Array, jax.Array]:
    """A draw a POSITION for a model that generates by diffusion over
    blocks (models/llama.py block window): logits [B * L, V] float32 (a
    row's L positions one after another: made flat, because turning
    [B, L, V] into rows afterwards is a copy of all of it on a TPU), the
    per-row sampling parameters [B], ``step`` [B, L] the RNG step of each
    position (its absolute position, so a draw does not depend on which
    forward made it). Returns (ids [B, L] int32, p [B, L] float32) with
    ``p = softmax(logits)[id]``: the confidence the unmasking orders by,
    of the RAW distribution as ``logprob_aux`` reports it (the family
    publishes it so; temperature and top-k shape the draw only); by the
    row's log-sum-exp, so no [B * L, V] array of probabilities is made."""
    B, L = step.shape

    def rep(a):
        return jnp.repeat(a, L)

    ids = sample_tokens(logits, rep(temperature), rep(top_k), rep(top_p),
                        rep(seeds), step.reshape(B * L),
                        max_top_k=max_top_k, penalties=None)
    with jax.named_scope("sample"):
        p = jnp.exp(_gather_rows(logits, ids)
                    - jax.nn.logsumexp(logits, axis=-1))
    return ids.reshape(B, L), p.reshape(B, L)


def unmask(strategy: str, masked: jax.Array, conf: jax.Array,
           n: jax.Array, threshold: float, last) -> jax.Array:
    """Which masked positions of a block one denoising forward makes
    final. masked [B, L] bool; conf [B, L] the sampled ids'
    probabilities; n [B] the positions a forward of this block takes
    (ceil(masked at block start / denoising_steps)); ``last`` (traced
    bool) is the schedule's last forward, which takes what is left.

    - ``sequential``: the leftmost n masked;
    - ``low_confidence_static``: the n masked of highest confidence
      (equal confidences by lower position);
    - ``low_confidence_dynamic``: every masked position whose confidence
      passes ``threshold`` if those are at least n, else as static.

    A row with nothing masked picks nothing."""
    if strategy not in ("sequential", "low_confidence_static",
                        "low_confidence_dynamic"):
        raise ValueError(f"unknown remasking strategy {strategy!r}")
    L = masked.shape[1]
    if strategy == "sequential":
        rank = jnp.cumsum(masked.astype(jnp.int32), axis=1) - 1
    else:
        c = jnp.where(masked, conf, -1.0)
        j = jnp.arange(L)
        before = (c[:, None, :] > c[:, :, None]) | (
            (c[:, None, :] == c[:, :, None]) & (j[None, None, :]
                                                < j[None, :, None]))
        rank = jnp.sum(before & masked[:, None, :], axis=2)
    pick = masked & (rank < n[:, None])
    if strategy == "low_confidence_dynamic":
        high = masked & (conf > threshold)
        enough = jnp.sum(high, axis=1) >= n
        pick = jnp.where(enough[:, None], high, pick)
    return jnp.where(last, masked, pick)
