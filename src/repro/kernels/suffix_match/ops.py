"""Public wrapper for the suffix-match drafting kernel.

Handles the host-side plumbing between the drafter's per-problem
``PackedSuffixTree`` exports and the kernel's flat batched layout:

* ``pack_forest`` — concatenate the distinct per-problem packed trees of
  one batch into a single node table + corpus (indices offset per tree,
  sizes padded to power-of-two buckets so jit recompiles stay rare as
  windows grow), returning the per-tree root indices;
* ``suffix_match_propose`` — one device call for a ``(B, m)`` batch of
  context tails: longest-suffix match length + up to ``n_prop_max``
  greedy continuation tokens per row.

The main path drafts with the XLA scalar core (``impl="ref"``, the
vmapped ``match_propose_row``) on every backend: it is the default of
``propose_device`` (inside the fused round) and of
``suffix_match_propose``, and nothing on the main path passes another
``impl``. The TPU lowering refuses the Pallas
kernel: its ``(None, m)`` / ``(1,)`` blocks break the 8x128 tiling
rule, and the core indexes tables loaded as vector values at
data-dependent positions (a ``dynamic_slice`` Mosaic cannot lower) — a
working TPU kernel needs the tables in SMEM refs. ``impl="pallas"``
with ``interpret=True`` keeps the kernel validated against the core in
the CPU tests.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .kernel import (
    MatchRegs,
    match_propose_row,
    suffix_match_propose_kernel,
    suffix_match_propose_kernel_chunked,
)
from .ref import suffix_match_propose_ref

_MIN_NODES = 1024
_MIN_EDGES = 1024
_MIN_CORPUS = 2048
_MIN_STRIDE = 256
_SENTINEL = np.int32(np.iinfo(np.int32).max)  # sorts past every real edge


class PackedForest(NamedTuple):
    """Concatenated ``PackedSuffixTree`` exports, ready for the device."""

    edge_node: jnp.ndarray
    edge_tok: jnp.ndarray
    edge_child: jnp.ndarray
    suffix_link: jnp.ndarray
    edge_start: jnp.ndarray
    edge_len: jnp.ndarray
    first_tok: jnp.ndarray
    best_child: jnp.ndarray
    corpus: jnp.ndarray


class ChunkedForest(NamedTuple):
    """Per-tree chunked export: row ``t`` holds tree ``t`` (tree-local
    node/edge/corpus indices, padded to a common stride). The pallas
    kernel streams one row from HBM to VMEM per grid step (scalar-
    prefetch driven), so the forest may exceed VMEM as long as the
    largest single tree fits. ``roots`` for this layout are tree
    ordinals (row indices), not node ids."""

    edge_node: jnp.ndarray  # (T, Es)
    edge_tok: jnp.ndarray
    edge_child: jnp.ndarray
    suffix_link: jnp.ndarray  # (T, Ns)
    edge_start: jnp.ndarray
    edge_len: jnp.ndarray
    first_tok: jnp.ndarray
    best_child: jnp.ndarray
    corpus: jnp.ndarray  # (T, Cs)


def _bucket(n: int, floor: int) -> int:
    b = floor
    while b < n:
        b *= 2
    return b


def pack_forest(
    packs: Sequence, *, min_nodes: int = _MIN_NODES,
    min_edges: int = _MIN_EDGES, min_corpus: int = _MIN_CORPUS,
) -> Tuple[PackedForest, np.ndarray]:
    """Concatenate packed trees; returns (forest, root index per tree).

    Node indices (edge-table children / links / best children) are
    shifted by each tree's node offset and edge spans by its corpus
    offset, so every tree keeps its exact host semantics — including
    ``suffix_link[root] == root``, which the kernel's root-edge hop
    relies on. The per-tree edge tables are lexicographic in (node,
    token) and node ranges are disjoint and increasing, so the
    concatenation stays globally sorted. Padding slots are inert (edge
    sentinels sort last, padding nodes have no edges and self-link), and
    array lengths are padded to power-of-two buckets with generous
    floors: growing windows then cross a bucket (and recompile) only on
    doublings.
    """
    n_total = sum(p.n_nodes for p in packs)
    e_total = sum(p.n_edges for p in packs)
    c_total = sum(len(p.corpus) for p in packs)
    # 25% headroom before bucketing: a sliding window fluctuates a few
    # percent per refresh, which must not straddle a bucket boundary
    # (every new bucket is a kernel recompile)
    N = _bucket(max(n_total + n_total // 4, 1), min_nodes)
    E = _bucket(max(e_total + e_total // 4, 1), min_edges)
    C = _bucket(max(c_total + c_total // 4, 1), min_corpus)
    en = np.full(E, _SENTINEL, np.int32)
    et = np.full(E, _SENTINEL, np.int32)
    ec = np.full(E, -1, np.int32)
    sl = np.zeros(N, np.int32)
    es = np.zeros(N, np.int32)
    el = np.zeros(N, np.int32)
    ft = np.full(N, -1, np.int32)
    bc = np.full(N, -1, np.int32)
    corpus = np.full(C, -1, np.int32)
    roots = np.zeros(len(packs), np.int32)
    noff = eoff = coff = 0
    for i, p in enumerate(packs):
        n, e, c = p.n_nodes, p.n_edges, len(p.corpus)
        roots[i] = noff
        en[eoff:eoff + e] = p.edge_node + noff
        et[eoff:eoff + e] = p.edge_tok
        ec[eoff:eoff + e] = p.edge_child + noff
        bc[noff:noff + n] = np.where(p.best_child >= 0,
                                     p.best_child + noff, -1)
        sl[noff:noff + n] = p.suffix_link + noff
        es[noff:noff + n] = p.edge_start + coff
        el[noff:noff + n] = p.edge_len
        ft[noff:noff + n] = p.first_tok
        corpus[coff:coff + c] = p.corpus
        noff += n
        eoff += e
        coff += c
    # Inert padding nodes self-link so a (masked) hop can never escape.
    sl[noff:] = np.arange(noff, N, dtype=np.int32)
    forest = PackedForest(
        edge_node=jnp.asarray(en), edge_tok=jnp.asarray(et),
        edge_child=jnp.asarray(ec),
        suffix_link=jnp.asarray(sl), edge_start=jnp.asarray(es),
        edge_len=jnp.asarray(el), first_tok=jnp.asarray(ft),
        best_child=jnp.asarray(bc), corpus=jnp.asarray(corpus),
    )
    return forest, roots


def forest_nbytes(packs: Sequence) -> int:
    """Approximate device bytes of a flat forest over ``packs`` (pre-
    bucketing): 3 int32 edge arrays, 5 node arrays, 1 corpus array."""
    n = sum(p.n_nodes for p in packs)
    e = sum(p.n_edges for p in packs)
    c = sum(len(p.corpus) for p in packs)
    return 4 * (3 * e + 5 * n + c)


def pack_forest_chunked(
    packs: Sequence, *, min_stride_nodes: int = _MIN_STRIDE,
    min_stride_edges: int = _MIN_STRIDE, min_stride_corpus: int = _MIN_STRIDE,
    min_trees: int = 1,
) -> Tuple[ChunkedForest, np.ndarray]:
    """Pack trees into the per-tree chunked layout; returns
    (forest, tree ordinal per tree).

    Unlike ``pack_forest`` nothing is offset: every row keeps the
    tree-local indices of its ``PackedSuffixTree`` (root = node 0), so
    the kernel can operate on a single streamed-in row. Strides are the
    bucketed maximum single-tree sizes (25% headroom, power-of-two with
    generous floors) and the tree count is bucketed too, so sliding-
    window growth recompiles only on doublings. Padding is inert: edge
    sentinels sort last, padding nodes self-link *locally*, padded
    corpus is separators (-1), and padded tree rows are never selected
    (inactive rows clamp to tree 0 with root -1).
    """
    n_max = max((p.n_nodes for p in packs), default=1)
    e_max = max((p.n_edges for p in packs), default=1)
    c_max = max((len(p.corpus) for p in packs), default=1)
    Ns = _bucket(n_max + n_max // 4, min_stride_nodes)
    Es = _bucket(e_max + e_max // 4, min_stride_edges)
    Cs = _bucket(c_max + c_max // 4, min_stride_corpus)
    T = _bucket(max(len(packs), 1), max(min_trees, 1))
    en = np.full((T, Es), _SENTINEL, np.int32)
    et = np.full((T, Es), _SENTINEL, np.int32)
    ec = np.full((T, Es), -1, np.int32)
    sl = np.broadcast_to(np.arange(Ns, dtype=np.int32), (T, Ns)).copy()
    es = np.zeros((T, Ns), np.int32)
    el = np.zeros((T, Ns), np.int32)
    ft = np.full((T, Ns), -1, np.int32)
    bc = np.full((T, Ns), -1, np.int32)
    corpus = np.full((T, Cs), -1, np.int32)
    for i, p in enumerate(packs):
        n, e, c = p.n_nodes, p.n_edges, len(p.corpus)
        en[i, :e] = p.edge_node
        et[i, :e] = p.edge_tok
        ec[i, :e] = p.edge_child
        sl[i, :n] = p.suffix_link
        es[i, :n] = p.edge_start
        el[i, :n] = p.edge_len
        ft[i, :n] = p.first_tok
        bc[i, :n] = p.best_child
        corpus[i, :c] = p.corpus
    forest = ChunkedForest(
        edge_node=jnp.asarray(en), edge_tok=jnp.asarray(et),
        edge_child=jnp.asarray(ec),
        suffix_link=jnp.asarray(sl), edge_start=jnp.asarray(es),
        edge_len=jnp.asarray(el), first_tok=jnp.asarray(ft),
        best_child=jnp.asarray(bc), corpus=jnp.asarray(corpus),
    )
    return forest, np.arange(len(packs), dtype=np.int32)


def _propose_chunked_ref(forest, tails, roots, budgets, start=None, *,
                         n_prop_max, min_match):
    """Chunked-layout jnp fallback: vmap the scalar core over rows,
    gathering each row's tree chunk (the CPU/oracle twin of the
    scalar-prefetch streamed pallas variant). Registers are tree-local."""
    T = forest.edge_node.shape[0]
    tidx = jnp.clip(roots, 0, T - 1).astype(jnp.int32)
    root_local = jnp.where(roots >= 0, 0, -1).astype(jnp.int32)

    def one(t, tail, root, budget, st):
        return match_propose_row(
            forest.edge_node[t], forest.edge_tok[t], forest.edge_child[t],
            forest.suffix_link[t], forest.edge_start[t], forest.edge_len[t],
            forest.first_tok[t], forest.best_child[t], forest.corpus[t],
            tail, root, budget, st,
            n_prop_max=n_prop_max, min_match=min_match,
        )

    out = jax.vmap(one)(tidx, tails, root_local, budgets, start)
    return out if start is not None else out[:3]


# das: hot-path — trace-time dispatch, composed inside the fused round
def propose_device(forest, tails, roots, budgets, *, n_prop_max,
                   min_match, impl="ref", interpret=False, start=None):
    """Trace-time propose dispatch — usable standalone *or inside a
    larger jitted program* (the fused verify round composes it with the
    model forward). Routes on forest layout: flat forests use the
    shared-block kernel / vmapped reference, chunked forests the
    scalar-prefetch streamed kernel / per-row gather reference.

    Returns ``(match_len, n_prop, props)``. ``start`` — ``((B,)
    MatchRegs, (B,) first tail index)`` — resumes each row's feed from
    carried registers (XLA core only) and appends the rows' final
    ``MatchRegs`` to the result."""
    if start is not None and impl != "ref":
        raise ValueError("a resumed feed runs on the XLA core (impl='ref')")
    if isinstance(forest, ChunkedForest):
        if impl == "ref":
            return _propose_chunked_ref(
                forest, tails, roots, budgets, start,
                n_prop_max=n_prop_max, min_match=min_match,
            )
        return suffix_match_propose_kernel_chunked(
            tails, roots, budgets, *forest,
            n_prop_max=n_prop_max, min_match=min_match, interpret=interpret,
        )
    if impl == "ref":
        return suffix_match_propose_ref(
            tails, roots, budgets, *forest, start,
            n_prop_max=n_prop_max, min_match=min_match,
        )
    return suffix_match_propose_kernel(
        tails, roots, budgets, *forest,
        n_prop_max=n_prop_max, min_match=min_match, interpret=interpret,
    )


# das: hot-path
@functools.partial(
    jax.jit,
    static_argnames=("n_prop_max", "min_match", "impl", "interpret"),
)
def _dispatch(query, forest, *, n_prop_max, min_match, impl, interpret):
    # `query` packs (tails | roots | budgets) into one (B, m+2) array so
    # the per-round host cost is a single host->device transfer.
    tails = query[:, :-2]
    roots = query[:, -2]
    budgets = query[:, -1]
    return propose_device(
        forest, tails, roots, budgets,
        n_prop_max=n_prop_max, min_match=min_match,
        impl=impl, interpret=interpret,
    )


def pack_query(tails, roots, budgets) -> np.ndarray:
    """Fuse per-round inputs into the single (B, m+2) transfer array."""
    return np.concatenate(
        [
            np.asarray(tails, np.int32),
            np.asarray(roots, np.int32)[:, None],
            np.asarray(budgets, np.int32)[:, None],
        ],
        axis=1,
    )


def suffix_match_propose(
    forest: PackedForest,
    tails,  # (B, m) int context tails, -1 = padding/reset
    roots,  # (B,) int per-row root node index (< 0 = inactive row)
    budgets,  # (B,) int per-row draft budget
    *,
    n_prop_max: int,
    min_match: int = 1,
    impl: str = "ref",
    interpret: bool = False,
    query: np.ndarray | None = None,  # pre-packed (B, m+2) override
):
    """Batched longest-suffix match + greedy continuation proposal.

    Returns ``(match_len (B,), n_prop (B,), props (B, n_prop_max))`` as
    device arrays (callers keep the dispatch/consume split to overlap
    with the in-flight verify). ``impl``: "ref" (the XLA scalar core,
    the main path) | "pallas" (tests only, with ``interpret=True``).
    """
    if query is None:
        query = pack_query(tails, roots, budgets)
    # the numpy query crosses into jax inside the jitted call (the C++
    # conversion path is ~5x cheaper than a python-level jnp.asarray)
    return _dispatch(
        query, forest,
        n_prop_max=int(n_prop_max), min_match=int(min_match),
        impl=str(impl), interpret=bool(interpret),
    )
