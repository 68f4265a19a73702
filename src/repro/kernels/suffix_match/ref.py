"""Pure-jnp reference for the suffix-match drafting kernel.

Runs the same scalar core as the pallas kernel (``kernel.match_propose_row``)
vmapped over batch rows — semantics are identical by construction, and
both are property-tested bit-identical to the host ``MatchState`` oracle
(tests/test_suffix_match_kernel.py). Besides being the oracle wiring,
this is the *compiled CPU fallback*: on hosts without a TPU the drafter
dispatches this jitted function instead of the pallas kernel, which is
still one batched XLA call per round instead of B Python tree walks.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .kernel import match_propose_row


@functools.partial(jax.jit, static_argnames=("n_prop_max", "min_match"))
def suffix_match_propose_ref(
    tails: jnp.ndarray,  # (B, m) int32, -1 = padding/reset
    roots: jnp.ndarray,  # (B,) int32, < 0 = inactive row
    budgets: jnp.ndarray,  # (B,) int32
    edge_node: jnp.ndarray,  # packed forest (see ops.pack_forest)
    edge_tok: jnp.ndarray,
    edge_child: jnp.ndarray,
    suffix_link: jnp.ndarray,
    edge_start: jnp.ndarray,
    edge_len: jnp.ndarray,
    first_tok: jnp.ndarray,
    best_child: jnp.ndarray,
    corpus: jnp.ndarray,
    start=None,  # ((B,) MatchRegs, (B,) first tail index) to resume from
    *,
    n_prop_max: int,
    min_match: int,
):
    """(match_len, n_prop, props); with ``start``, also the rows' final
    ``MatchRegs`` (see ``kernel.match_propose_row``)."""
    def one(tail, root, budget, st):
        return match_propose_row(
            edge_node, edge_tok, edge_child, suffix_link, edge_start,
            edge_len, first_tok, best_child, corpus, tail, root, budget,
            st, n_prop_max=n_prop_max, min_match=min_match,
        )

    out = jax.vmap(one)(tails, roots, budgets, start)
    return out if start is not None else out[:3]
