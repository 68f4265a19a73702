"""Fused device-resident verify rounds (draft → verify → accept in ONE
dispatch).

The engine's round, whenever its drafter drafts on the device (scopes
``problem`` and ``global``): the draft walk and the model forward both
run on device, so nothing of the round needs the host but the budgets
going in and the emitted tokens coming out. Host per-row draft sessions
(scope ``problem+request``) keep the split round: host proposals, a
host-built block, ``verify_step``, a host emit scan.

This module fuses the whole steady-state round into one jitted program
per (K-bucket, forest geometry):

    propose (XLA suffix-match core over the packed forest)
      → build the (B, K+1) verify block on device
      → model forward + ``verify_block`` acceptance
      → cache commit (the block's ring slots, written in place inside the
        layer scan / staged recurrent gather)
      → EOS/limit emit scan
      → next-round session state (head, context tails, emitted, active)

The per-row session state (``RoundState``) lives on device between
rounds: heads are verify outputs, context tails are shift-registers
updated from the accepted tokens, and the matcher's registers
(``MatchRegs``) persist too. A round feeds each row only the tail
tokens the rounds since its last feed appended (``feed_from`` on), from
the registers that feed left; resumed this way, the same
``match_propose_row`` core under the same tail cap reaches the
registers a feed of the whole tail from the root reaches, so proposals
— and therefore sampled tokens under a shared PRNG stream — are
bit-identical to a whole-tail feed's. Registers are only good against the
tree they were fed in: the engine resets them (``forget_matches``)
whenever it uploads a new forest or new roots, and for the rows it
admits or evicts; a reset row is fed its whole tail from the root.

The host uploads one (B,) budget vector per round and downloads one
packed (B, K+5) result: ``[cand tokens | accepted | n_take | alive |
n_prop]`` — everything consume-side bookkeeping needs, double-buffered
by the engine.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.verify import verify_block
from repro.kernels.suffix_match import ops as sm_ops
from repro.kernels.suffix_match.kernel import MatchRegs
from repro.models import model as M


class RoundState(NamedTuple):
    """Device-resident per-slot session state carried across rounds."""

    head: jnp.ndarray  # (B,) i32 last emitted-but-unverified token
    tails: jnp.ndarray  # (B, m) i32 context tails, -1 = left pad/reset
    active: jnp.ndarray  # (B,) bool
    emitted: jnp.ndarray  # (B,) i32 tokens emitted so far
    max_new: jnp.ndarray  # (B,) i32 per-row token limit
    # (B,) i32 each: the matcher's registers for the row's context before
    # tails[:, feed_from], in the row's tree of the current forest. A row
    # is carried while its node is >= 0; node -1 = not carried (the next
    # feed starts at the root, from tail index feed_from).
    match: MatchRegs
    # (B,) i32 first tail index the row's next feed takes: for a row not
    # carried 0, or m while its tree is not uploaded yet (admitted since
    # the last sync: fed only once it has a budget).
    feed_from: jnp.ndarray


def _root_regs(n: int) -> MatchRegs:
    """``n`` rows of registers that are not carried (at the root)."""
    return MatchRegs(*(jnp.full((n,), v, jnp.int32) for v in (-1, -1, 0, 0)))


def make_state(head, tails, active, emitted, max_new) -> RoundState:
    """Build a device ``RoundState`` from host arrays (one-time upload
    at pool/batch construction; afterwards the state only lives on
    device). No row's matcher is carried: the first round feeds whole
    tails."""
    B = np.shape(head)[0]
    return RoundState(
        head=jnp.asarray(np.asarray(head, np.int32)),
        tails=jnp.asarray(np.asarray(tails, np.int32)),
        active=jnp.asarray(np.asarray(active, bool)),
        emitted=jnp.asarray(np.asarray(emitted, np.int32)),
        max_new=jnp.asarray(np.asarray(max_new, np.int32)),
        match=_root_regs(B),
        feed_from=jnp.zeros((B,), jnp.int32),
    )


def forget_matches(state: RoundState, slots=None) -> RoundState:
    """Reset matchers to not carried: every row's (``slots=None``: after
    a new forest or new roots), or the given rows' (eviction). ``slots``
    may hold out-of-range pads, which drop."""
    B = state.feed_from.shape[0]
    if slots is None:
        return state._replace(match=_root_regs(B),
                              feed_from=jnp.zeros((B,), jnp.int32))
    return state._replace(
        match=MatchRegs(*(r.at[slots].set(v)
                          for r, v in zip(state.match, (-1, -1, 0, 0)))),
        feed_from=state.feed_from.at[slots].set(0),
    )


def matcher_feeds(active, has_tree, budgets, carried, feed_from, m):
    """Rows a fused round with K > 0 feeds: active rows with a tree,
    budget or not, except rows still waiting for their tree's upload
    (``feed_from == m``, not carried) that have no budget. Takes jnp
    arrays on device, numpy ones for the host's mirror of the state."""
    waiting = ~carried & (feed_from >= m)
    return active & has_tree & ~(waiting & (budgets <= 0))


def advance_feed(xp, fed, carried, feed_from, alive, n_take, m):
    """(carried, feed_from) after a round that appended ``n_take`` tokens
    to every row's tail: fed rows resume after the tail's end, unfed
    carried rows where they were; both shift left with the tail, and a
    row stays carried while it lives and its resume index stays in the
    tail. Rows dropped keep waiting if they waited, else restart at 0.
    ``xp`` is ``jnp`` on device, ``np`` for the host's mirror."""
    nxt = xp.where(fed, m, feed_from) - n_take
    keep = (fed | carried) & alive & (nxt >= 0)
    waiting = ~fed & ~carried & (feed_from >= m)
    return keep, xp.where(keep, nxt, xp.where(waiting, m, 0))


# das: hot-path — shared verify core, traced inside every round dispatch
def verify_step(
    params, cfg, cache, block, budgets, active, key,
    *, temperature: float, recurrent: bool, attn_impl: str,
) -> Tuple[Any, Any]:
    """One verify forward + acceptance + cache commit (traceable).

    Shared by the host-draft round's per-K jitted verify and the fused
    round program so both paths run the exact same ops (token parity by
    construction). Returns (VerifyResult, committed cache).
    """
    B = block.shape[0]
    valid = jnp.broadcast_to(active[:, None], block.shape)
    # Single pass: attention caches commit via the ring-slot overwrite
    # trick; recurrent layers emit staged per-step states
    # (collect_states) that are gathered at the acceptance count below —
    # no second forward.
    with jax.named_scope("forward"):
        logits, cache1, _ = M.forward(
            params, cfg, block, cache=cache, valid=valid,
            commit_upto=None if recurrent else jnp.zeros((B,), jnp.int32),
            attn_impl=attn_impl, collect_states=recurrent,
        )
    with jax.named_scope("accept"):
        res = verify_block(
            logits[:, :, : cfg.vocab_size], block, budgets,
            temperature=temperature, key=key, active=active,
        )
    with jax.named_scope("commit"):
        n_commit = jnp.where(active, 1 + res.accepted, 0)
        if recurrent:
            cache1 = M.commit_staged_cache(cfg, cache1, n_commit)
        cache1 = cache1._replace(
            lengths=cache1.lengths + n_commit.astype(jnp.int32)
        )
    return res, cache1


# das: hot-path
def emit_scan_device(
    cand: jnp.ndarray,  # (B, K+1) candidate emissions per row
    n_new: jnp.ndarray,  # (B,) accepted + 1
    remaining: jnp.ndarray,  # (B,) max_new - emitted before this round
    eos: int,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Device twin of ``spec_engine._emit_scan`` (append-then-check)."""
    B, K1 = cand.shape
    idx = jnp.arange(K1)[None, :]
    valid = idx < n_new[:, None]
    eos_hit = (cand == eos) & valid
    has_eos = eos_hit.any(axis=1)
    first_eos = jnp.where(has_eos, jnp.argmax(eos_hit, axis=1), K1)
    cap = jnp.maximum(remaining, 1)  # append-then-check: >=1 lands
    n_take = jnp.minimum(jnp.minimum(n_new, cap),
                         jnp.where(has_eos, first_eos + 1, K1 + 1))
    last = jnp.take_along_axis(
        cand, jnp.maximum(n_take - 1, 0)[:, None], axis=1
    )[:, 0]
    alive = (n_take == n_new) & (last != eos) & (n_take < remaining)
    return n_take.astype(jnp.int32), alive


# das: hot-path — the entire steady-state round, one jitted dispatch
def fused_round_core(
    params, cfg, forest, cache, state: RoundState, roots, budgets, key,
    *, K: int, temperature: float, eos_token: int, recurrent: bool,
    attn_impl: str, min_match: int,
):
    """One fused round (traceable): propose → verify → commit → state.

    Returns (cache', state', out (B, K+5) i32). ``out`` packs
    everything the host consume path needs into ONE download:
    ``[cand (K+1) | accepted | n_take | alive | n_prop]``. Rows outside
    ``state.active`` carry zeros in the bookkeeping columns and leave
    cache/state untouched.

    The round's phases run under ``jax.named_scope``: ``propose`` (draft
    walk and block build), ``forward`` (layer scan and cache ring write),
    ``accept`` (acceptance, candidates, emit scan) and ``commit``
    (cache lengths, tail shift register, next state, output pack). The
    scopes reach every HLO instruction's ``op_name`` metadata, so a
    device trace splits the round's time by phase; they change no
    computation.
    """
    B, m = state.tails.shape
    i32 = jnp.int32
    carried = state.match.node >= 0
    with jax.named_scope("propose"):
        if K > 0:
            # Feed rows from their carried registers (the tokens since
            # their last feed) or, not carried, their whole tail from the
            # root; rows without budget are fed too, to stay carried.
            # Rows without a packed tree (root < 0) or without budget
            # propose nothing and take a plain AR step — same as the
            # host-draft round.
            fed = matcher_feeds(state.active, roots >= 0, budgets,
                                carried, state.feed_from, m)
            first = jnp.where(carried, state.feed_from, 0)
            _, n_prop, props, regs = sm_ops.propose_device(
                forest, state.tails, jnp.where(fed, roots, -1), budgets,
                n_prop_max=K, min_match=min_match,
                start=(state.match, first),
            )
            n_prop = n_prop.astype(i32)
            drafts = jnp.where(
                jnp.arange(K)[None, :] < n_prop[:, None], props, 0
            ).astype(i32)
        else:
            fed = jnp.zeros((B,), bool)
            regs = state.match
            n_prop = jnp.zeros((B,), i32)
            drafts = jnp.zeros((B, 0), i32)
        block = jnp.concatenate([state.head[:, None], drafts], axis=1)
    res, cache = verify_step(
        params, cfg, cache, block, n_prop, state.active, key,
        temperature=temperature, recurrent=recurrent, attn_impl=attn_impl,
    )
    with jax.named_scope("accept"):
        accepted = res.accepted.astype(i32)
        next_tok = res.next_token.astype(i32)
        cand = jnp.concatenate([block[:, 1:], jnp.zeros((B, 1), i32)],
                               axis=1)
        cand = cand.at[jnp.arange(B), accepted].set(next_tok)
        n_take, alive = emit_scan_device(
            cand, accepted + 1, state.max_new - state.emitted, eos_token
        )
        alive = alive & state.active
        n_take_eff = jnp.where(state.active, n_take, 0).astype(i32)
    with jax.named_scope("commit"):
        # Context-tail shift register: the last m of (tail ++ taken
        # tokens). The gather window ends exactly at the last taken
        # token, so junk cand positions past n_take never enter the tail.
        comb = jnp.concatenate([state.tails, cand], axis=1)
        idx = n_take_eff[:, None] + jnp.arange(m)[None, :]
        fed_tails = jnp.take_along_axis(comb, idx, axis=1)
        keep, feed_from = advance_feed(jnp, fed, carried, state.feed_from,
                                       alive, n_take_eff, m)
        state2 = RoundState(
            head=jnp.where(alive, next_tok, state.head),
            tails=jnp.where(alive[:, None], fed_tails, state.tails),
            active=alive,
            emitted=state.emitted + n_take_eff,
            max_new=state.max_new,
            match=MatchRegs(*(jnp.where(keep, r, z) for r, z in
                              zip(regs, _root_regs(B)))),
            feed_from=feed_from,
        )
        out = jnp.concatenate(
            [
                cand,
                accepted[:, None],
                n_take_eff[:, None],
                alive.astype(i32)[:, None],
                jnp.where(state.active, n_prop, 0)[:, None],
            ],
            axis=1,
        )
    return cache, state2, out


def build_fused_round(
    cfg, *, K: int, temperature: float, eos_token: int, recurrent: bool,
    attn_impl: str, min_match: int,
):
    """Jitted fused-round program for one K-bucket:

        fused(params, forest, cache, state, roots, budgets, key)
          -> (cache', state', out (B, K+5))

    ``cache`` and ``state`` are donated — the round is an in-place
    update of the pool.
    """
    @functools.partial(jax.jit, donate_argnums=(2, 3))
    def fused(params, forest, cache, state, roots, budgets, key):
        return fused_round_core(
            params, cfg, forest, cache, state, roots, budgets, key,
            K=K, temperature=temperature, eos_token=eos_token,
            recurrent=recurrent, attn_impl=attn_impl, min_match=min_match,
        )

    return fused


def unpack_round_out(out_row: np.ndarray, K: int):
    """Split one (B, K+5) host round result into its columns:
    (cand, accepted, n_take, alive, n_prop)."""
    K1 = K + 1
    return (
        out_row[:, :K1],
        out_row[:, K1].astype(np.int64),
        out_row[:, K1 + 1].astype(np.int64),
        out_row[:, K1 + 2].astype(bool),
        out_row[:, K1 + 3].astype(np.int64),
    )
