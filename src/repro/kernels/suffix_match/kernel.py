"""Pallas TPU kernel: batched longest-suffix-match drafting over packed
suffix trees.

Design notes (mirroring ``kernels/spec_verify``)
------------------------------------------------
The DAS drafter's per-round hot path is nonparametric: for every active
row, find the longest suffix of the decode context that occurs in the
row's (per-problem) suffix tree, then emit up to ``budget`` tokens along
the highest-weight continuation path. The seed did this as B per-row
Python walks per verify round — at large batch the host round-trip, not
the model, bounds the round rate. This kernel does the whole batch in
one device call over the flat export of ``SuffixTree.pack()``:

  grid = (B,)             — one program per batch row.

  per-row blocks          — the row's context tail ``(m,)`` (left-padded
                            with -1 = reset, exactly the host
                            ``MatchState`` semantics for separator
                            tokens), plus scalar root / budget.
  shared blocks           — the packed *forest* (every distinct
                            per-problem tree concatenated by
                            ``ops.pack_forest``): a lexicographically
                            sorted (node, token) → child edge table,
                            per-node suffix links / edge spans /
                            precomputed greedy continuation children,
                            and the packed token corpus. These are
                            broadcast to every grid step (index maps pin
                            them to block 0) and live in VMEM for the
                            duration of the row.

The algorithm is Chang–Lawler matching statistics (the same streaming
suffix-link descent as the host ``MatchState``): feed the m tail tokens
one at a time, follow suffix links on mismatch (amortized O(m) total),
then walk the greedy continuation from the deepest match, falling back
to shorter suffixes (more link hops) when the deepest match has no
continuation. ``best_child`` is baked host-side at pack time from the
epoch-decayed weights, so the device walk is pure pointer-chasing — no
floats cross the host/device boundary.

Matching statistics are a stream, so the feed can resume: given the
registers (``MatchRegs``) a previous feed left against the same tree,
feeding only the tail tokens appended since reaches the registers a
full feed of the m-token tail reaches. The registers after a feed are
the canonical locus of the longest suffix of the last m context tokens
that occurs in the tree; a resumed feed keeps ``mlen <= m`` by taking
one suffix-link hop before it feeds a token while ``mlen == m`` (a full
feed from the root never meets that cap). The fused round
(``core/fused_round.py``) carries the registers across rounds this way;
every other caller starts at the root.

Control-flow shape matters more than FLOPs here. Two deliberate choices
keep the core fast both vmapped on CPU (the fallback in ``ref.py``) and
as a per-row pallas program:

* **flat loops** — feed and propose are each ONE ``lax.while_loop``
  whose body is straight-line code; the suffix-link re-descent runs as
  an interleaved micro-step (a ``mode`` register) instead of a nested
  loop. Nested data-dependent loops under ``vmap`` re-materialize their
  carried state per level and were measured ~50x slower.
* **edge table, not child lists** — child lookup is a binary search
  over the sorted (node, token) edge table, unrolled to the static
  ``ceil(log2(E))`` steps (separator edges are excluded at pack time,
  so a context token can never match one). This bounds every loop body
  to a fixed instruction count — no inner scan whose trip count depends
  on a node's fan-out.

This is scalar-unit work, not MXU/VPU work: the win is not FLOPs but
removing B synchronous host walks (and their resync re-feeds after
every tree mutation) from the verify loop, so the propose dispatch
overlaps the in-flight verify in the double-buffered continuous loop.
The scalar core (``match_propose_row``) is shared verbatim with the
pure-jnp reference (``ref.py``), which is what the main path runs on
every backend; the pallas path is validated in interpret mode on CPU.
The TPU lowering refuses it: the ``(None, m)`` / ``(1,)`` blocks break
the 8x128 tiling, and the core indexes tables loaded as vector values
(``en_ref[...]``) at data-dependent positions, a ``dynamic_slice``
Mosaic cannot lower. The same lookup compiles from an SMEM ref, so a
TPU kernel means moving the tables into SMEM.

Invariants inherited from ``SuffixTree.pack()``:
* canonical positions are kept eagerly normalized: the matcher is
  either exactly at a node (``child == -1``) or strictly inside an edge
  (``0 < epos < edge_len[child]``);
* suffix links are valid for the root (self-link) and every internal
  node, and a matcher can never sit exactly on a leaf (the corpus ends
  with a separator), so no re-descend fallback is needed;
* separators are -1 in the packed corpus and context tokens are >= 0,
  so a separator can never match and resets the matcher when fed;
* suffix-link re-descents only ever probe tokens of already-matched
  text, hence never a separator — the separator-free edge table is
  complete for every lookup the core performs.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_FEED = 0  # consume the next tail token / walk the continuation
_DESC = 1  # mid suffix-link re-descent (skip/count, one segment a step)


def _i32(x):
    return jnp.asarray(x, jnp.int32)


class MatchRegs(NamedTuple):
    """A matcher's registers: exactly at ``node`` (``child == -1``) or
    ``epos`` tokens into the edge that leads to ``child``, with ``mlen``
    context tokens matched. ``node == -1`` stands for the row's root
    (a fresh matcher). Scalars per row, or ``(B,)`` arrays batched."""

    node: jnp.ndarray
    child: jnp.ndarray
    epos: jnp.ndarray
    mlen: jnp.ndarray


def match_propose_row(
    e_node, e_tok, e_child,  # (E,) sorted (node, token) -> child edges
    sl, es, el, ft, bc,  # (N,) node table
    corpus,  # (C,) packed tokens, separators = -1
    tail,  # (m,) int32 context tail, -1 = padding/reset
    root,  # scalar int32 root node of this row's tree; < 0 = inactive
    budget,  # scalar int32 draft budget for this row
    start=None,  # (MatchRegs, first tail index to feed); None = root, 0
    *,
    n_prop_max: int,
    min_match: int,
):
    """Scalar core shared by the pallas kernel and the jnp reference.

    Returns (match_len, n_prop, props[(n_prop_max,)], regs) — the first
    three bit-identical to the host ``MatchState`` fed the same tail
    followed by ``propose(budget, min_match)``; ``regs`` are the
    ``MatchRegs`` the feed ended on. ``start`` resumes a feed: the
    registers an earlier feed of this row's context left in this tree,
    and the index of the first tail token it has not fed. Resumed from
    the registers of the context before ``tail[first]``, the feed ends
    on the registers a feed of the whole tail from the root ends on.
    """
    active = root >= 0
    root_s = jnp.maximum(_i32(root), 0)
    budget = jnp.minimum(_i32(budget), n_prop_max)
    m = tail.shape[0]
    C = corpus.shape[0]
    E = e_node.shape[0]
    n_steps = max(int(E - 1).bit_length(), 1) + 1

    def find_child(node, tok):
        """Child of `node` whose edge starts with `tok` (-1 if none):
        unrolled lower-bound binary search on the sorted edge table."""
        lo, hi = _i32(0), _i32(E)
        for _ in range(n_steps):
            mid = (lo + hi) // 2
            mid_c = jnp.minimum(mid, E - 1)
            en, et = e_node[mid_c], e_tok[mid_c]
            less = (en < node) | ((en == node) & (et < tok))
            upd = lo < hi
            lo = jnp.where(upd & less, mid + 1, lo)
            hi = jnp.where(upd & ~less, mid, hi)
        lo_c = jnp.minimum(lo, E - 1)
        found = (lo < E) & (e_node[lo_c] == node) & (e_tok[lo_c] == tok)
        return jnp.where(found, e_child[lo_c], _i32(-1))

    # ---- streaming longest-suffix match (matching statistics) --------
    # One flat while_loop; a failed step starts a suffix-link hop whose
    # skip/count re-descent runs one segment per iteration (mode=_DESC),
    # then the same tail token is retried.
    def fcond(st):
        i, _, _, _, _, mode, _, _, _ = st
        return (i < m) | (mode == _DESC)

    def fbody(st):
        i, node, child, epos, mlen, mode, dnode, dpos, drem = st
        in_desc = mode == _DESC
        t = tail[jnp.minimum(i, m - 1)]
        # shared child lookup (descent probe or at-node step)
        q_node = jnp.where(in_desc, dnode, node)
        q_tok = jnp.where(in_desc, corpus[jnp.minimum(dpos, C - 1)], t)
        c_found = find_child(q_node, q_tok)
        c_s = jnp.maximum(c_found, 0)
        # -- descent micro-step ----------------------------------------
        d_end = drem == 0
        ell = el[c_s]
        d_full = ~d_end & (drem >= ell)
        desc_node = jnp.where(d_end, dnode, jnp.where(d_full, node, dnode))
        desc_child = jnp.where(d_end | d_full, _i32(-1), c_s)
        desc_epos = jnp.where(d_end | d_full, _i32(0), drem)
        desc_mode = jnp.where(d_full, _DESC, _FEED)
        desc_dnode = jnp.where(d_full, c_s, dnode)
        desc_dpos = dpos + jnp.where(d_full, ell, 0)
        desc_drem = drem - jnp.where(d_full, ell, 0)
        # -- feed micro-step -------------------------------------------
        is_reset = t < 0
        on_edge = child >= 0
        ch_s = jnp.maximum(child, 0)
        tok_edge = corpus[jnp.minimum(es[ch_s] + epos, C - 1)]
        step_ok = jnp.where(on_edge, tok_edge == t, c_found >= 0)
        new_child = jnp.where(on_edge, child, c_found)
        new_epos = jnp.where(on_edge, epos + 1, _i32(1))
        full = new_epos == el[jnp.maximum(new_child, 0)]
        s_node = jnp.where(full, jnp.maximum(new_child, 0), node)
        s_child = jnp.where(full, _i32(-1), new_child)
        s_epos = jnp.where(full, _i32(0), new_epos)
        dead = mlen == 0
        # at the cap the match first drops its oldest token (one hop),
        # so it never spans more than the last m context tokens
        step_ok = step_ok & (mlen < m)
        hop = ~is_reset & ~step_ok & ~dead
        shift = (on_edge & (node == root_s)).astype(jnp.int32)
        feed_node = jnp.where(is_reset, root_s, jnp.where(step_ok, s_node, node))
        feed_child = jnp.where(is_reset, _i32(-1), jnp.where(step_ok, s_child, child))
        feed_epos = jnp.where(is_reset, _i32(0), jnp.where(step_ok, s_epos, epos))
        feed_mlen = jnp.where(
            is_reset, _i32(0),
            jnp.where(step_ok, mlen + 1, jnp.where(dead, mlen, mlen - 1)),
        )
        feed_i = i + (is_reset | step_ok | dead).astype(jnp.int32)
        feed_mode = jnp.where(hop, _DESC, _FEED)
        feed_dnode = sl[node]
        feed_dpos = es[ch_s] + shift
        feed_drem = jnp.where(on_edge, epos - shift, _i32(0))
        # -- merge -----------------------------------------------------
        return (
            jnp.where(in_desc, i, feed_i),
            jnp.where(in_desc, desc_node, feed_node),
            jnp.where(in_desc, desc_child, feed_child),
            jnp.where(in_desc, desc_epos, feed_epos),
            jnp.where(in_desc, mlen, feed_mlen),
            jnp.where(in_desc, desc_mode, feed_mode),
            jnp.where(in_desc, desc_dnode, feed_dnode),
            jnp.where(in_desc, desc_dpos, feed_dpos),
            jnp.where(in_desc, desc_drem, feed_drem),
        )

    z = _i32(0)
    if start is None:
        regs0, first = MatchRegs(root_s, _i32(-1), z, z), z
    else:
        (node0, child0, epos0, mlen0), first = start
        regs0 = MatchRegs(jnp.where(node0 < 0, root_s, _i32(node0)),
                          _i32(child0), _i32(epos0), _i32(mlen0))
    i0 = jnp.where(active, first, m).astype(jnp.int32)  # inactive rows skip
    _, node, child, epos, mlen, _, _, _, _ = jax.lax.while_loop(
        fcond, fbody,
        (i0, *regs0, _i32(_FEED), root_s, z, z),
    )
    regs = MatchRegs(node, child, epos, mlen)

    # ---- greedy continuation walk with shorter-suffix fallback -------
    # Same flat shape: walk micro-steps emit tokens; an empty walk hops
    # one suffix link (descent micro-steps) and retries, until a token
    # lands or the match falls below min_match.
    minm = max(int(min_match), 1)
    props0 = jnp.full((n_prop_max,), -1, jnp.int32)
    done0 = jnp.logical_not(active) | (budget <= 0) | (mlen < minm)

    def pcond(st):
        return jnp.logical_not(st[10])

    def pbody(st):
        wn, wc, we, k, props, pmlen, mode, dnode, dpos, drem, _ = st
        in_desc = mode == _DESC
        c_found = find_child(
            jnp.where(in_desc, dnode, 0),
            corpus[jnp.minimum(dpos, C - 1)],
        )
        c_s = jnp.maximum(c_found, 0)
        # -- descent micro-step ----------------------------------------
        d_end = drem == 0
        ell = el[c_s]
        d_full = ~d_end & (drem >= ell)
        desc_wn = jnp.where(d_end, dnode, jnp.where(d_full, wn, dnode))
        desc_wc = jnp.where(d_end | d_full, _i32(-1), c_s)
        desc_we = jnp.where(d_end | d_full, _i32(0), drem)
        desc_mode = jnp.where(d_full, _DESC, _FEED)
        desc_dnode = jnp.where(d_full, c_s, dnode)
        desc_dpos = dpos + jnp.where(d_full, ell, 0)
        desc_drem = drem - jnp.where(d_full, ell, 0)
        # -- walk micro-step -------------------------------------------
        hit = k >= budget
        on_edge = wc >= 0
        wc_s = jnp.maximum(wc, 0)
        at_end = on_edge & (we == el[wc_s])
        tok_e = corpus[jnp.minimum(es[wc_s] + we, C - 1)]
        bcx = bc[wn]
        tok = jnp.where(on_edge, tok_e, ft[jnp.maximum(bcx, 0)])
        brk = (on_edge & ~at_end & (tok_e < 0)) | (~on_edge & (bcx < 0))
        stop = hit | brk
        succeed = stop & (k > 0)
        pml2 = pmlen - 1
        give_up = stop & (k == 0) & (pml2 < minm)
        hop = stop & (k == 0) & ~give_up
        norm = ~stop & at_end
        emit = ~stop & ~norm
        shift = (on_edge & (wn == root_s)).astype(jnp.int32)
        k_c = jnp.minimum(k, n_prop_max - 1)
        props2 = props.at[k_c].set(jnp.where(emit, tok, props[k_c]))
        walk_wn = jnp.where(norm, wc_s, wn)
        walk_wc = jnp.where(
            norm, _i32(-1),
            jnp.where(emit & ~on_edge, jnp.maximum(bcx, 0), wc),
        )
        walk_we = jnp.where(
            norm, _i32(0),
            jnp.where(emit, jnp.where(on_edge, we + 1, _i32(1)), we),
        )
        walk_mode = jnp.where(hop, _DESC, _FEED)
        walk_dnode = jnp.where(hop, sl[wn], dnode)
        walk_dpos = jnp.where(hop, es[wc_s] + shift, dpos)
        walk_drem = jnp.where(hop, jnp.where(on_edge, we - shift, z), drem)
        walk_pmlen = jnp.where(hop | give_up, pml2, pmlen)
        walk_done = succeed | give_up
        # -- merge -----------------------------------------------------
        return (
            jnp.where(in_desc, desc_wn, walk_wn),
            jnp.where(in_desc, desc_wc, walk_wc),
            jnp.where(in_desc, desc_we, walk_we),
            k + (~in_desc & emit).astype(jnp.int32),
            jnp.where(in_desc, props, props2),
            jnp.where(in_desc, pmlen, walk_pmlen),
            jnp.where(in_desc, desc_mode, walk_mode),
            jnp.where(in_desc, desc_dnode, walk_dnode),
            jnp.where(in_desc, desc_dpos, walk_dpos),
            jnp.where(in_desc, desc_drem, walk_drem),
            jnp.where(in_desc, jnp.bool_(False), walk_done),
        )

    _, _, _, n_prop, props, _, _, _, _, _, _ = jax.lax.while_loop(
        pcond, pbody,
        (node, child, epos, z, props0, mlen, _i32(_FEED), root_s, z, z,
         done0),
    )

    match_len = jnp.where(active, mlen, 0).astype(jnp.int32)
    n_prop = jnp.where(active, n_prop, 0).astype(jnp.int32)
    props = jnp.where(active, props, -1).astype(jnp.int32)
    return match_len, n_prop, props, regs


def _suffix_match_kernel(
    tail_ref,  # (m,) int32         this row's context tail
    root_ref,  # (1,) int32         root node of this row's tree
    budget_ref,  # (1,) int32       this row's draft budget
    en_ref, et_ref, ec_ref,  # (E,) sorted edge table
    sl_ref, es_ref, el_ref, ft_ref, bc_ref,  # (N,) node table
    corpus_ref,  # (C,) int32       packed forest corpus
    mlen_ref,  # (1,) int32 out     longest-suffix match length
    nprop_ref,  # (1,) int32 out    number of proposed tokens
    props_ref,  # (K,) int32 out    proposed tokens (-1 padded)
    *,
    n_prop_max: int,
    min_match: int,
):
    match_len, n_prop, props, _ = match_propose_row(
        en_ref[...], et_ref[...], ec_ref[...],
        sl_ref[...], es_ref[...], el_ref[...], ft_ref[...], bc_ref[...],
        corpus_ref[...],
        tail_ref[...], root_ref[0], budget_ref[0],
        n_prop_max=n_prop_max, min_match=min_match,
    )
    mlen_ref[0] = match_len
    nprop_ref[0] = n_prop
    props_ref[...] = props


def suffix_match_propose_kernel(
    tails: jnp.ndarray,  # (B, m) int32
    roots: jnp.ndarray,  # (B,) int32
    budgets: jnp.ndarray,  # (B,) int32
    edge_node: jnp.ndarray,  # (E,) packed forest …
    edge_tok: jnp.ndarray,
    edge_child: jnp.ndarray,
    suffix_link: jnp.ndarray,
    edge_start: jnp.ndarray,
    edge_len: jnp.ndarray,
    first_tok: jnp.ndarray,
    best_child: jnp.ndarray,
    corpus: jnp.ndarray,  # (C,) int32
    *,
    n_prop_max: int,
    min_match: int,
    interpret: bool = False,
):
    """Low-level entry; see ops.suffix_match_propose for the public API."""
    B, m = tails.shape
    E = edge_node.shape[0]
    N = suffix_link.shape[0]
    C = corpus.shape[0]
    kernel = functools.partial(
        _suffix_match_kernel, n_prop_max=n_prop_max, min_match=min_match
    )
    row = pl.BlockSpec((None, m), lambda b: (b, 0))
    scalar = pl.BlockSpec((1,), lambda b: (b,))
    shared_e = pl.BlockSpec((E,), lambda b: (0,))
    shared_n = pl.BlockSpec((N,), lambda b: (0,))
    out = pl.pallas_call(
        kernel,
        grid=(B,),
        in_specs=[
            row, scalar, scalar,
            shared_e, shared_e, shared_e,
            shared_n, shared_n, shared_n, shared_n, shared_n,
            pl.BlockSpec((C,), lambda b: (0,)),
        ],
        out_specs=[
            pl.BlockSpec((1,), lambda b: (b,)),
            pl.BlockSpec((1,), lambda b: (b,)),
            pl.BlockSpec((None, n_prop_max), lambda b: (b, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B,), jnp.int32),
            jax.ShapeDtypeStruct((B,), jnp.int32),
            jax.ShapeDtypeStruct((B, n_prop_max), jnp.int32),
        ],
        interpret=interpret,
    )(
        tails, roots, budgets,
        edge_node, edge_tok, edge_child,
        suffix_link, edge_start, edge_len, first_tok, best_child,
        corpus,
    )
    return out


def _suffix_match_kernel_chunked(
    tidx_ref,  # scalar-prefetch: (B,) tree ordinal per row
    tail_ref, root_ref, budget_ref,
    en_ref, et_ref, ec_ref,
    sl_ref, es_ref, el_ref, ft_ref, bc_ref,
    corpus_ref,
    mlen_ref, nprop_ref, props_ref,
    *,
    n_prop_max: int,
    min_match: int,
):
    # The BlockSpec index maps already streamed this row's tree into
    # VMEM (tidx_ref drove the DMA); in-kernel the core is identical to
    # the flat variant, just on tree-local indices (root 0).
    del tidx_ref
    _suffix_match_kernel(
        tail_ref, root_ref, budget_ref,
        en_ref, et_ref, ec_ref,
        sl_ref, es_ref, el_ref, ft_ref, bc_ref,
        corpus_ref,
        mlen_ref, nprop_ref, props_ref,
        n_prop_max=n_prop_max, min_match=min_match,
    )


def suffix_match_propose_kernel_chunked(
    tails: jnp.ndarray,  # (B, m) int32
    roots: jnp.ndarray,  # (B,) int32 tree ordinal (< 0 = inactive row)
    budgets: jnp.ndarray,  # (B,) int32
    edge_node: jnp.ndarray,  # (T, Es) per-tree chunked forest …
    edge_tok: jnp.ndarray,
    edge_child: jnp.ndarray,
    suffix_link: jnp.ndarray,  # (T, Ns)
    edge_start: jnp.ndarray,
    edge_len: jnp.ndarray,
    first_tok: jnp.ndarray,
    best_child: jnp.ndarray,
    corpus: jnp.ndarray,  # (T, Cs) int32
    *,
    n_prop_max: int,
    min_match: int,
    interpret: bool = False,
):
    """HBM→VMEM streamed variant for forests past VMEM capacity.

    The flat kernel holds the whole packed forest in VMEM for every grid
    step, which caps the forest at a few MB. Here the forest is packed
    *per tree* (``ops.pack_forest_chunked``: node/edge/corpus indices
    are tree-local, rows padded to a common stride) and the grid streams
    exactly ONE tree's chunk per row: a scalar-prefetched ``tree`` index
    drives the BlockSpec index maps, so pallas DMAs the row's tree from
    HBM into VMEM ahead of the grid step (consecutive rows drafting from
    the same problem reuse the resident chunk). VMEM then holds one
    tree-stride instead of the whole forest — the forest scales with
    HBM, the stride with the largest single tree.
    """
    B, m = tails.shape
    T, Es = edge_node.shape
    Ns = suffix_link.shape[1]
    Cs = corpus.shape[1]
    tidx = jnp.clip(roots, 0, T - 1).astype(jnp.int32)
    root_local = jnp.where(roots >= 0, 0, -1).astype(jnp.int32)
    kernel = functools.partial(
        _suffix_match_kernel_chunked,
        n_prop_max=n_prop_max, min_match=min_match,
    )
    row = pl.BlockSpec((None, m), lambda b, t: (b, 0))
    scalar = pl.BlockSpec((1,), lambda b, t: (b,))
    tree_e = pl.BlockSpec((None, Es), lambda b, t: (t[b], 0))
    tree_n = pl.BlockSpec((None, Ns), lambda b, t: (t[b], 0))
    tree_c = pl.BlockSpec((None, Cs), lambda b, t: (t[b], 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B,),
        in_specs=[
            row, scalar, scalar,
            tree_e, tree_e, tree_e,
            tree_n, tree_n, tree_n, tree_n, tree_n,
            tree_c,
        ],
        out_specs=[
            pl.BlockSpec((1,), lambda b, t: (b,)),
            pl.BlockSpec((1,), lambda b, t: (b,)),
            pl.BlockSpec((None, n_prop_max), lambda b, t: (b, 0)),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B,), jnp.int32),
            jax.ShapeDtypeStruct((B,), jnp.int32),
            jax.ShapeDtypeStruct((B, n_prop_max), jnp.int32),
        ],
        interpret=interpret,
    )(
        tidx,
        tails, root_local, budgets,
        edge_node, edge_tok, edge_child,
        suffix_link, edge_start, edge_len, first_tok, best_child,
        corpus,
    )
    return out
