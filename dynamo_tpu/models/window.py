"""The fused decode window: K decode steps in one jitted program, with
the sequence carry (tok, pos, done, steps, remaining) on the device so
that the engine dispatches window N+1 before it reads window N back.

What every family's window has in common is here and nowhere else: the
carry update and its stop rules (``carry_step_update``;
``block_carry_update`` for a step that yields a block), the program
(``make_window``: the ``jax.jit`` line, the operands, the unrolled loop,
the sampler, the ``emitted`` count, the ``kv_carry`` scope around the
commit) and the order of its results (``WindowResults.pack`` writes it,
``unpack`` reads it). The host's bookkeeping
(``JaxEngine._process_window``) assumes the same stop semantics on every
path, which holds because there is one path.

A family (llama.py, mla.py, jamba.py with granite.py on it, lfm2.py)
writes a ``Family`` and no loop; ``w`` is the program's ``Operands``:

- ``begin(w) -> bufs``: what it allocates or gathers before the first
  step (window buffers for the steps' K/V, the rows' conv tails or state);
- ``step(w, bufs, tok, pos, active, i) -> (logits [B, V], bufs,
  counted)``: ONE decode step of every row, ``i`` the unrolled step. The
  pools are read-only: the step's K/V go to the buffers. A row that is
  not ``active`` (frozen, padding) flows through the matmuls; its state
  must not move. ``counted``: an int32 vector the builder sums over the
  steps and returns before the state (``WINDOW_COUNTS`` names it), or None;
- ``commit(w, bufs, pos) -> (kv_k, kv_v, state)``: the buffers into the
  pools, once, under the builder's ``kv_carry`` scope. ``pos`` is the
  carry's last: entry i of a row's buffer is valid iff ``start + i < pos``;
- ``settle(w, bufs, state) -> state``, optional: what is written after
  the commit under a scope of its own (lfm2.py's page snapshots, which
  the harness reads as ``state.snapshot``, not as ``kv_carry``).

The block window (llama.py ``_make_block_window_fn``) keeps its own step
and ``lax.while_loop``; it shares the carry's rules, the program's name
and call form, and ``pack``.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..engine.sampling import logprob_aux, sample_tokens, update_penalty_state


def carry_active(done: jax.Array, pos: jax.Array) -> jax.Array:
    """Rows still generating: not stopped, not padding (pos < 0)."""
    return jnp.logical_and(jnp.logical_not(done), pos >= 0)


def carry_step_update(nxt, tok, pos, done, steps, remaining, eos_table):
    """The on-device sequence-carry update for one step of one token a
    row: freeze rows that sample a stop token or exhaust their budget.
    The block window, whose step yields a block a row, uses
    ``block_carry_update`` in its place: the same three stop conditions
    (a stop id is emitted and freezes the row, the budget counts emitted
    tokens, a frozen row neither advances nor commits) applied to the
    block's new positions in order, so what the host assumes
    (``emitted`` tokens are the row's next tokens, ``done`` says the row
    froze in this window, the last emitted token decides
    stop-versus-length) holds for both."""
    active = carry_active(done, pos)
    hit_stop = jnp.any(nxt[:, None] == eos_table, axis=1)
    remaining = jnp.where(active, remaining - 1, remaining)
    tok = jnp.where(active, nxt, tok)
    pos = jnp.where(active, pos + 1, pos)
    steps = jnp.where(active, steps + 1, steps)
    done = jnp.logical_or(
        done, jnp.logical_and(active, jnp.logical_or(
            hit_stop, remaining <= 0)))
    return tok, pos, done, steps, remaining


def block_carry_update(tok, new, pos, done, steps, remaining, eos_table,
                       block: int):
    """The block window's carry update after one block is final: what
    ``carry_step_update`` does for a step of one token, for a step that
    yields a block.

    tok [B, L]: the block's final tokens; new [B, L]: positions that were
    masked when the block began (the others were the prompt's tail). A
    live row emits its new positions in order while its budget lasts and
    up to and including the first stop id; what follows in the block is
    dropped. The row advances (and its block becomes the pending one,
    which the next block's first forward commits) only if every new
    position was emitted; it freezes if it hit a stop id, spent its
    budget, or dropped anything. Returns (emit [B, L] bool, pos, done,
    steps, remaining)."""
    active = carry_active(done, pos)
    new = jnp.logical_and(new, active[:, None])
    count = jnp.cumsum(new.astype(jnp.int32), axis=1)
    stop = jnp.logical_and(
        new, jnp.any(tok[:, :, None] == eos_table[:, None, :], axis=2))
    stops_before = jnp.cumsum(stop.astype(jnp.int32), axis=1) \
        - stop.astype(jnp.int32)
    emit = new & (count <= remaining[:, None]) & (stops_before == 0)
    n_emit = jnp.sum(emit.astype(jnp.int32), axis=1)
    whole = n_emit == jnp.sum(new.astype(jnp.int32), axis=1)
    remaining = remaining - n_emit
    steps = steps + n_emit
    pos = jnp.where(active & whole, pos + block, pos)
    done = done | (active & (jnp.any(emit & stop, axis=1)
                             | (remaining <= 0) | ~whole))
    return emit, pos, done, steps, remaining


# ------------------------------------------------- the window's results


class WindowResults(NamedTuple):
    """What a window program returns, in the order it returns it, less
    what it does not have (None): ``aux`` without ``logprobs_topn``,
    ``counts`` where nothing counts, ``state`` where none is kept."""
    toks: jax.Array         # [B, K] a step's token (the block window: by
    #                         position); a row's new ones: ``emitted`` [B]
    emitted: jax.Array
    aux: Optional[tuple]    # (logprob [B, K], top values, top ids [B, K, N])
    carry: tuple            # (tok, pos, done, steps, remaining)
    kv_k: jax.Array
    kv_v: jax.Array
    counts: Optional[jax.Array]
    state: Any

    def pack(self) -> tuple:
        """The tuple the jitted program returns."""
        return tuple(x for x in self if x is not None)


def unpack(out, topn: int, counts: bool, state: bool) -> WindowResults:
    """A window program's results by name: the one reader of their
    order. ``topn``: the program's ``logprobs_topn``; ``counts``: whether
    it counts (the block window, a module with ``WINDOW_COUNTS``);
    ``state``: whether the model keeps state."""
    out = list(out)
    st = out.pop() if state else None
    cn = out.pop() if counts else None
    aux = out.pop(2) if topn else None
    return WindowResults(out[0], out[1], aux, *out[2:], cn, st)


# ------------------------------------------------------------ the program


class Operands(NamedTuple):
    """What a window program was called with, for a family's functions:
    the pools as at entry, ``start`` [B] each row's first position of
    the window (-1: padding)."""
    params: Any
    kv_k: jax.Array
    kv_v: jax.Array
    page_table: jax.Array
    start: jax.Array
    state: Any
    state_slots: Optional[jax.Array]
    k_steps: int


class Family(NamedTuple):
    """A family's part of the window (the module's docstring)."""
    begin: Callable
    step: Callable
    commit: Callable
    settle: Optional[Callable] = None


def make_window(family: Family, max_top_k: int):
    """The jitted window of a family: ``decode_window`` (the benchmark's
    ``window_ms_mean`` finds the program by that name), ``k_steps`` and
    ``logprobs_topn`` static, the pools donated."""

    @partial(jax.jit, static_argnames=("k_steps", "logprobs_topn"),
             donate_argnames=("kv_k", "kv_v", "state"))
    def decode_window(params, tokens, positions, done, steps, remaining,
                      kv_k, kv_v, page_table, temperature, top_k, top_p,
                      seeds, eos_table, penalties=None, state=None,
                      state_slots=None, *, k_steps: int,
                      logprobs_topn: int = 0):
        w = Operands(params, kv_k, kv_v, page_table, positions, state,
                     state_slots, k_steps)
        bufs = family.begin(w)
        tok, pos = tokens, positions
        toks, aux, tally = [], [], []
        # per-row count of tokens this window actually produced: a row
        # that freezes (stop token / budget) mid-window stops counting, so
        # the host can slice toks[i, :emitted[i]] without a per-step scan
        emitted = jnp.zeros((tokens.shape[0],), jnp.int32)
        # UNROLLED (k_steps is static): an outer lax.scan would carry the
        # window buffers, and XLA double-buffers a scan's carries
        for i in range(k_steps):
            active = carry_active(done, pos)
            logits, bufs, counted = family.step(w, bufs, tok, pos, active, i)
            if counted is not None:
                tally.append(counted)
            nxt = sample_tokens(logits, temperature, top_k, top_p, seeds,
                                steps, max_top_k=max_top_k,
                                penalties=penalties)
            if logprobs_topn:
                aux.append(logprob_aux(logits, nxt, logprobs_topn))
            penalties = update_penalty_state(penalties, nxt, done)
            emitted = emitted + active.astype(jnp.int32)
            tok, pos, done, steps, remaining = carry_step_update(
                nxt, tok, pos, done, steps, remaining, eos_table)
            toks.append(tok)

        with jax.named_scope("kv_carry"):
            kv_k, kv_v, state = family.commit(w, bufs, pos)
        if family.settle is not None:
            state = family.settle(w, bufs, state)
        out_toks = jnp.stack(toks, axis=1)
        counts = sum(tally) if tally else None
        stacked = (tuple(jnp.stack(a, axis=1) for a in zip(*aux))
                   if logprobs_topn else None)
        return WindowResults(out_toks, emitted, stacked,
                             (tok, pos, done, steps, remaining), kv_k, kv_v,
                             counts, state).pack()

    return decode_window
