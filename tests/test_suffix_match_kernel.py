"""Device-side batched suffix-match drafting vs the host oracle.

The contract under test: for the same packed history and the same
context tail, the kernel's (match length, proposals) are bit-identical
to the host ``MatchState`` fed that tail followed by
``propose(budget, min_match)`` — across random corpora, epoch decay,
document removal, and interleaved extend/evict via the drafter window.
"""

import functools

import jax
import numpy as np
import pytest
from conftest import hypothesis_or_stub

given, settings, st = hypothesis_or_stub()

from repro.core.drafter import DrafterConfig, SuffixDrafter
from repro.core.length_policy import (
    LengthPolicy,
    LengthPolicyConfig,
    LONG,
    MEDIUM,
    SHORT,
)
from repro.core.suffix_tree import SuffixTree
from repro.kernels.suffix_match import (
    pack_forest,
    pack_forest_chunked,
    suffix_match_propose,
)

TAIL = 16  # fixed shapes -> the jitted core compiles once per impl
B = 4
KMAX = 8


def _host_oracle(tree, ctx, budget, min_match):
    """MatchState fed the same (tail-truncated) context, then propose."""
    stt = tree.match_state()
    for t in ctx[-TAIL:]:
        stt.feed(int(t))
    return stt.match_len, stt.propose(int(budget), min_match)


def _device(trees, ctxs, budgets, min_match, impl="ref", roots_neg=()):
    packs = [t.pack() for t in trees]
    forest, troots = pack_forest(packs)
    n = len(ctxs)
    tails = np.full((n, TAIL), -1, np.int32)
    roots = np.zeros(n, np.int32)
    for b, ctx in enumerate(ctxs):
        tail = [int(t) for t in ctx[-TAIL:]]
        if tail:
            tails[b, TAIL - len(tail):] = tail
        roots[b] = -1 if b in roots_neg else troots[b % len(trees)]
    ml, npr, props = suffix_match_propose(
        forest, tails, roots, np.asarray(budgets, np.int32),
        n_prop_max=KMAX, min_match=min_match, impl=impl,
        interpret=impl == "pallas",
    )
    ml, npr, props = np.asarray(ml), np.asarray(npr), np.asarray(props)
    return ml, [props[b, : npr[b]].tolist() for b in range(n)]


def _check_parity(trees, ctxs, budgets, min_match, impl="ref"):
    ml, props = _device(trees, ctxs, budgets, min_match, impl=impl)
    for b, ctx in enumerate(ctxs):
        h_ml, h_prop = _host_oracle(
            trees[b % len(trees)], ctx, budgets[b], min_match
        )
        assert h_ml == ml[b], (b, ctx, h_ml, int(ml[b]))
        assert h_prop == props[b], (b, ctx, h_prop, props[b])


def _mk_tree(docs, decay=1.0, epochs=None, remove=()):
    tree = SuffixTree(epoch_decay=decay)
    for i, d in enumerate(docs):
        tree.add_document(list(d), epoch=epochs[i] if epochs else 0)
    for d in remove:
        tree.remove_document(d)
    return tree


def test_kernel_matches_host_basic():
    tree = _mk_tree([[1, 2, 3, 4, 5], [1, 2, 3, 9, 9], [7, 1, 2, 3, 9]])
    ctxs = [[1, 2, 3], [2, 3], [9], [5, 5, 5]]
    _check_parity([tree], ctxs, [4, 4, 4, 4], 1)


def test_kernel_matches_host_epoch_decay_and_removal():
    tree = _mk_tree(
        [[1, 2, 3, 4], [1, 2, 3, 8], [1, 2, 3, 8], [1, 2, 3, 4]],
        decay=0.5, epochs=[0, 1, 2, 3], remove=(1,),
    )
    tree.current_epoch = 5
    tree._dirty = True
    ctxs = [[1, 2, 3], [2, 3], [3], [1, 2]]
    _check_parity([tree], ctxs, [3, 3, 3, 3], 1)


def test_kernel_min_match_and_budgets():
    tree = _mk_tree([[4, 5, 6, 7, 8, 9]])
    ctxs = [[4, 5], [5], [4, 5, 6], [0]]
    for mm in (1, 2, 3):
        _check_parity([tree], ctxs, [2, 0, 8, 5], mm)


def test_kernel_multi_tree_forest_and_inactive_rows():
    t1 = _mk_tree([[1, 2, 3, 4, 5]])
    t2 = _mk_tree([[1, 2, 3, 9, 9], [6, 6, 1, 2]])
    ctxs = [[1, 2, 3], [1, 2, 3], [2, 3], [6, 1, 2]]
    ml, props = _device([t1, t2], ctxs, [4] * 4, 1)
    assert props[0] == [4, 5]  # row 0 -> tree 1
    assert props[1] == [9, 9]  # row 1 -> tree 2
    # inactive rows (root < 0) produce nothing
    ml, props = _device([t1, t2], ctxs, [4] * 4, 1, roots_neg=(1, 3))
    assert ml[1] == 0 and props[1] == []
    assert ml[3] == 0 and props[3] == []
    assert props[0] == [4, 5]


def test_pallas_interpret_matches_ref():
    tree = _mk_tree(
        [[1, 2, 3, 4, 5], [1, 2, 3, 9, 9], [5, 4, 1, 2, 3]], decay=0.9,
        epochs=[0, 1, 2],
    )
    ctxs = [[1, 2, 3], [4, 1, 2], [3, 4], [9]]
    ml_r, props_r = _device([tree], ctxs, [4, 3, 8, 2], 1, impl="ref")
    ml_p, props_p = _device(
        [tree], ctxs, [4, 3, 8, 2], 1, impl="pallas"
    )
    assert np.array_equal(ml_r, ml_p)
    assert props_r == props_p
    _check_parity([tree], ctxs, [4, 3, 8, 2], 1, impl="pallas")


def test_pack_is_version_gated():
    tree = _mk_tree([[1, 2, 3]])
    p1 = tree.pack()
    assert tree.pack() is p1  # cache hit while unmutated
    tree.add_document([2, 3, 4])
    p2 = tree.pack()
    assert p2 is not p1
    # decay-epoch moves also invalidate (weights change, version doesn't)
    tree.current_epoch += 1
    tree._dirty = True
    assert tree.pack() is not p2


def test_pack_rejects_incomplete_trees():
    tree = SuffixTree()
    tree.extend(1)
    tree.extend(2)
    with pytest.raises(RuntimeError):
        tree.pack()


def test_batched_sessions_match_per_row_sessions():
    d = SuffixDrafter(DrafterConfig(scope="problem", min_match=1))
    d.observe_rollout("p1", [1, 2, 3, 4, 5], 0)
    d.observe_rollout("p1", [1, 2, 3, 4, 6], 1)
    d.observe_rollout("p2", [1, 2, 3, 9, 9], 0)
    ctxs = {0: ("p1", [1, 2, 3]), 1: ("p2", [1, 2, 3]), 2: ("p1", [9, 9])}
    bds = d.batched_sessions(3)
    assert bds.device
    host = []
    for row, (pid, ctx) in ctxs.items():
        bds.open(row, pid, ctx)
        host.append(d.new_session(pid, list(ctx)).propose(4))
    props = bds.propose_batch([4, 4, 4])
    assert props == host
    # feeds keep rows independent; closed rows propose nothing
    bds.feed(0, [4])
    bds.close(1)
    props = bds.propose_batch([4, 4, 4])
    assert props[0] == d.new_session("p1", [1, 2, 3, 4]).propose(4)
    assert props[1] == []


def test_batched_sessions_host_fallback_for_request_scope():
    d = SuffixDrafter(DrafterConfig(scope="problem+request", min_match=2))
    bds = d.batched_sessions(1)
    assert not bds.device  # request trees stay host-side
    bds.open(0, "new-problem", [5, 6])
    bds.feed(0, [1, 2, 3, 1, 2, 3, 1, 2])
    prop = bds.propose_batch([3])[0]
    assert prop[:1] == [3]  # same as DraftSession (self-repetition)


def test_engine_device_draft_parity(tiny_dense):
    """Device drafting must not change emitted tokens (T=0 losslessness)
    against the host sessions on the same drafts, and must actually take
    the batched device path."""
    import jax
    from conftest import host_drafts, make_params
    from repro.core.spec_engine import EngineConfig, SpecEngine

    params = make_params(tiny_dense)
    prompts = [[3, 4, 5], [6, 7], [8, 9, 10, 11]]
    outs = {}
    for mode in ("on", "off"):
        eng = SpecEngine(
            params, tiny_dense,
            EngineConfig(max_new_tokens=24, max_draft=4,
                         block_buckets=(0, 2, 4)),
        )
        if mode == "off":
            host_drafts(eng)
        for it in range(2):  # second pass drafts from first-pass history
            eng.begin_iteration(it)
            outs[(mode, it)], _ = eng.generate(
                prompts, key=jax.random.key(0)
            )
        if mode == "on":
            assert eng.drafter.stats["batched_proposes"] > 0
    for it in range(2):
        assert outs[("on", it)] == outs[("off", it)]


# ---------------------------------------------------------------------------
# chunked (HBM→VMEM streamed) forest layout
# ---------------------------------------------------------------------------
def _device_chunked(trees, ctxs, budgets, min_match, impl="ref"):
    """Chunked-layout twin of ``_device`` (tree ordinal roots)."""
    packs = [t.pack() for t in trees]
    forest, troots = pack_forest_chunked(
        packs, min_stride_nodes=64, min_stride_edges=64,
        min_stride_corpus=64,
    )
    n = len(ctxs)
    tails = np.full((n, TAIL), -1, np.int32)
    roots = np.zeros(n, np.int32)
    for b, ctx in enumerate(ctxs):
        tail = [int(t) for t in ctx[-TAIL:]]
        if tail:
            tails[b, TAIL - len(tail):] = tail
        roots[b] = troots[b % len(trees)]
    ml, npr, props = suffix_match_propose(
        forest, tails, roots, np.asarray(budgets, np.int32),
        n_prop_max=KMAX, min_match=min_match, impl=impl,
        interpret=impl == "pallas",
    )
    ml, npr, props = np.asarray(ml), np.asarray(npr), np.asarray(props)
    return ml, [props[b, : npr[b]].tolist() for b in range(n)]


def test_chunked_forest_exceeds_single_block_limit():
    """A forest whose flat packing would blow the kernel's single
    shared-block budget still drafts correctly chunked: each row only
    ever needs ITS tree's stride resident, so the per-row block stays at
    the (tiny) stride while the total forest exceeds the configured
    limit by an order of magnitude."""
    from repro.kernels.suffix_match import ops as sm_ops

    rng = np.random.default_rng(3)
    trees = []
    for t in range(48):
        docs = [list(rng.integers(0, 6, size=12)) for _ in range(2)]
        trees.append(_mk_tree(docs, decay=0.9, epochs=[0, 1]))
    packs = [t.pack() for t in trees]
    budget_bytes = 4 << 10  # pretend VMEM caps at 4 KiB
    assert sm_ops.forest_nbytes(packs) > 10 * budget_bytes
    forest, _ = pack_forest_chunked(
        packs, min_stride_nodes=64, min_stride_edges=64,
        min_stride_corpus=64,
    )
    # per-row residency = one stride of each table, under the limit
    per_row = 4 * (
        3 * forest.edge_node.shape[1] + 5 * forest.suffix_link.shape[1]
        + forest.corpus.shape[1]
    )
    assert per_row < budget_bytes
    ctxs = [list(rng.integers(0, 6, size=rng.integers(1, 12)))
            for _ in range(len(trees))]
    budgets = [int(b) for b in rng.integers(0, KMAX, size=len(trees))]
    ml, props = _device_chunked(trees, ctxs, budgets, 1)
    for b, ctx in enumerate(ctxs):
        h_ml, h_prop = _host_oracle(trees[b], ctx, budgets[b], 1)
        assert h_ml == ml[b], (b, ctx, h_ml, int(ml[b]))
        assert h_prop == props[b], (b, ctx, h_prop, props[b])


def test_chunked_pallas_interpret_matches_ref():
    """The scalar-prefetch streamed kernel ≡ the chunked jnp reference
    (and both ≡ the flat layout) on a multi-tree forest with inactive
    rows."""
    t1 = _mk_tree([[1, 2, 3, 4, 5], [1, 2, 3, 9, 9]], decay=0.9,
                  epochs=[0, 1])
    t2 = _mk_tree([[7, 1, 2, 8], [6, 6, 1, 2]])
    ctxs = [[1, 2, 3], [1, 2], [6, 1, 2], [5, 5]]
    budgets = [4, 3, 8, 2]
    ml_f, props_f = _device([t1, t2], ctxs, budgets, 1)
    ml_r, props_r = _device_chunked([t1, t2], ctxs, budgets, 1, impl="ref")
    ml_p, props_p = _device_chunked(
        [t1, t2], ctxs, budgets, 1, impl="pallas"
    )
    assert np.array_equal(ml_f, ml_r) and props_f == props_r
    assert np.array_equal(ml_r, ml_p) and props_r == props_p


def test_batched_sessions_chunked_layout_parity():
    """forest_layout="chunked" through the BatchedDraftSessions surface
    proposes exactly what the host sessions do."""
    d = SuffixDrafter(
        DrafterConfig(scope="problem", min_match=1,
                      forest_layout="chunked")
    )
    d.observe_rollout("p1", [1, 2, 3, 4, 5], 0)
    d.observe_rollout("p1", [1, 2, 3, 4, 6], 1)
    d.observe_rollout("p2", [1, 2, 3, 9, 9], 0)
    ctxs = {0: ("p1", [1, 2, 3]), 1: ("p2", [1, 2, 3]), 2: ("p1", [9, 9])}
    bds = d.batched_sessions(3)
    assert bds.device
    host = []
    for row, (pid, ctx) in ctxs.items():
        bds.open(row, pid, ctx)
        host.append(d.new_session(pid, list(ctx)).propose(4))
    assert bds.propose_batch([4, 4, 4]) == host
    from repro.kernels.suffix_match.ops import ChunkedForest

    assert isinstance(bds._forest, ChunkedForest)


def test_engine_fused_with_chunked_forest_parity(tiny_dense):
    """Fused rounds compose with the chunked forest layout: outputs stay
    token-identical to the flat-layout engine."""
    import jax
    from conftest import make_params
    from repro.core.spec_engine import EngineConfig, SpecEngine

    params = make_params(tiny_dense)
    prompts = [[3, 4, 5], [6, 7], [8, 9, 10, 11]]
    outs = {}
    for layout in ("flat", "chunked"):
        eng = SpecEngine(
            params, tiny_dense,
            EngineConfig(max_new_tokens=20, max_draft=4,
                         block_buckets=(0, 2, 4)),
            drafter=SuffixDrafter(
                DrafterConfig(scope="problem", min_match=1,
                              forest_layout=layout)
            ),
        )
        for it in range(2):
            eng.begin_iteration(it)
            outs[(layout, it)], _ = eng.generate(
                prompts, key=jax.random.key(0)
            )
    for it in range(2):
        assert outs[("flat", it)] == outs[("chunked", it)]


# ---------------------------------------------------------------------------
# property test: parity across random corpora, decay, interleaved
# extend/evict (window eviction exercises remove_document + repack)
# ---------------------------------------------------------------------------
tok = st.integers(min_value=0, max_value=6)
doc = st.lists(tok, min_size=1, max_size=24)


@settings(max_examples=25, deadline=None)
@given(
    docs=st.lists(doc, min_size=1, max_size=10),
    ctxs=st.lists(st.lists(tok, min_size=0, max_size=24),
                  min_size=B, max_size=B),
    window=st.integers(2, 4),
    decay=st.sampled_from([1.0, 0.9, 0.5]),
    budgets=st.lists(st.integers(0, KMAX), min_size=B, max_size=B),
    min_match=st.integers(1, 2),
)
def test_kernel_parity_property(docs, ctxs, window, decay, budgets,
                                min_match):
    d = SuffixDrafter(
        DrafterConfig(scope="problem", window_size=window,
                      epoch_decay=decay, min_match=min_match,
                      max_draft=KMAX, device_tail=TAIL)
    )
    for e, dd in enumerate(docs):
        d.observe_rollout("p", dd, epoch=e)  # evicts beyond the window
        if e % 3 == 2:
            d.begin_iteration(e + 1)  # decay reference moves
    tree = d.index.tree(d._key("p"))
    assert tree is not None
    _check_parity([tree], ctxs, budgets, min_match)
    # and through the batched-sessions surface (DraftSession oracle)
    bds = d.batched_sessions(B)
    host = []
    for b, ctx in enumerate(ctxs):
        bds.open(b, "p", ctx)
        host.append(d.new_session("p", list(ctx[-TAIL:])).propose(budgets[b]))
    assert bds.propose_batch(budgets) == host


# ---------------------------------------------------------------------------
# carried registers: a feed resumed from the registers the previous feed
# left ≡ a feed of the whole tail from the root
# ---------------------------------------------------------------------------
CARRY_TAIL = 8  # small tail: copied text outgrows it, so the cap binds


@functools.lru_cache(maxsize=None)
def _propose_fn(resumed: bool):
    from repro.kernels.suffix_match import propose_device

    def fn(forest, tails, roots, budgets, regs, first):
        return propose_device(
            forest, tails, roots, budgets, n_prop_max=KMAX, min_match=1,
            start=(regs, first) if resumed else None,
        )

    return jax.jit(fn)


def _root_regs(n):
    from repro.kernels.suffix_match import MatchRegs

    return MatchRegs(*(np.full(n, v, np.int32) for v in (-1, -1, 0, 0)))


def _stream(plan, docs):
    """Tokens from ``plan`` segments: copies of corpus text (the match
    grows past the tail), tokens outside the corpus alphabet (the match
    breaks), resets (-1)."""
    out = []
    for seg in plan:
        if seg[0] == "copy":
            d = docs[seg[1] % len(docs)]
            i = seg[2] % len(d)
            out += [int(t) for t in d[i:i + seg[3]]]
        elif seg[0] == "tok":
            out += [int(t) for t in seg[1]]
        else:
            out.append(-1)
    return out


def _check_carried_feed(docs, plans, chunks, budgets, layout):
    """Feed every row's stream in chunks from carried registers and, after
    each chunk, compare with a feed of the last ``CARRY_TAIL`` tokens from
    the root: registers, match length and proposals identical. A chunk
    longer than the tail restarts the row at the root, as the fused round
    does. Returns how often the match spanned the whole tail."""
    m = CARRY_TAIL
    trees = [_mk_tree(docs[: len(docs) // 2 + 1]), _mk_tree(docs)]
    packs = [t.pack() for t in trees]
    if layout == "flat":
        forest, troots = pack_forest(packs)
    else:
        forest, troots = pack_forest_chunked(
            packs, min_stride_nodes=64, min_stride_edges=64,
            min_stride_corpus=64,
        )
    n = len(plans)
    streams = [_stream(p, docs) for p in plans]
    roots = np.array([troots[b % 2] for b in range(n)], np.int32)
    budgets = np.asarray(budgets, np.int32)
    steps = max(len(c) for c in chunks)
    sizes = [[c[i % len(c)] for i in range(steps)] for c in chunks]
    # each row's stream, repeated to cover its chunks
    streams = [s * (sum(k) // len(s) + 1) for s, k in zip(streams, sizes)]
    regs, pos, n_cap = _root_regs(n), [0] * n, 0
    for step in range(steps):
        tails = np.full((n, m), -1, np.int32)
        first = np.zeros(n, np.int32)
        for b in range(n):
            k = sizes[b][step]
            pos[b] += k
            tail = streams[b][max(pos[b] - m, 0):pos[b]]
            tails[b, m - len(tail):] = tail
            first[b] = m - k if k <= m else 0
        restart = first == 0
        regs = type(regs)(*(np.where(restart, z, r)
                            for r, z in zip(regs, _root_regs(n))))
        got = _propose_fn(True)(forest, tails, roots, budgets, regs, first)
        want = _propose_fn(True)(forest, tails, roots, budgets,
                                 _root_regs(n), np.zeros(n, np.int32))
        plain = _propose_fn(False)(forest, tails, roots, budgets,
                                   regs, first)
        got, want = jax.tree.map(np.asarray, (got, want))
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            assert np.array_equal(g, w), (step, tails, g, w)
        for g, p in zip(got[:3], plain):
            assert np.array_equal(g, np.asarray(p))
        n_cap += int((got[0] == m).sum())
        regs = got[3]
    return n_cap


segment = st.one_of(
    st.tuples(st.just("copy"), st.integers(0, 9), st.integers(0, 23),
              st.integers(1, 24)),
    st.tuples(st.just("tok"), st.lists(st.integers(0, 9), min_size=1,
                                       max_size=4)),
    st.tuples(st.just("reset")),
)


@settings(max_examples=25, deadline=None)
@given(
    docs=st.lists(doc, min_size=2, max_size=6),
    plans=st.lists(st.lists(segment, min_size=1, max_size=6),
                   min_size=B, max_size=B),
    chunks=st.lists(st.lists(st.integers(1, KMAX + 1), min_size=1,
                             max_size=12), min_size=B, max_size=B),
    budgets=st.lists(st.integers(0, KMAX), min_size=B, max_size=B),
    layout=st.sampled_from(["flat", "chunked"]),
)
def test_carried_feed_matches_full_feed_property(docs, plans, chunks,
                                                 budgets, layout):
    _check_carried_feed(docs, plans, chunks, budgets, layout)


@pytest.mark.parametrize("layout", ["flat", "chunked"])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_carried_feed_matches_full_feed(layout, seed):
    """Seeded twin of the property test: long copies of corpus text so
    the tail cap binds, tokens that leave the tree, resets."""
    rng = np.random.default_rng(seed)
    docs = [list(rng.integers(0, 4, size=int(rng.integers(12, 24))))
            for _ in range(4)]
    plans = []
    for _ in range(B):
        plan = []
        for _ in range(6):
            r = rng.random()
            if r < 0.6:
                plan.append(("copy", int(rng.integers(0, 4)),
                             int(rng.integers(0, 4)),
                             int(rng.integers(CARRY_TAIL + 2, 24))))
            elif r < 0.9:
                plan.append(("tok", [int(t) for t in
                                     rng.integers(4, 10, size=2)]))
            else:
                plan.append(("reset",))
        plans.append(plan)
    chunks = [[int(k) for k in rng.integers(1, KMAX + 2, size=12)]
              for _ in range(B)]
    budgets = [int(b) for b in rng.integers(0, KMAX + 1, size=B)]
    assert _check_carried_feed(docs, plans, chunks, budgets, layout) > 0


# ---------------------------------------------------------------------------
# length-policy satellite fixes
# ---------------------------------------------------------------------------
def test_classify_length_medium_until_thresholds_exist():
    lp = LengthPolicy(LengthPolicyConfig(min_history=4))
    # seed regression: (inf, inf) thresholds classified everything SHORT
    # (budget 0 - speculation silently disabled for direct callers)
    assert lp.classify_length(5.0) == MEDIUM
    assert lp.classify_length(1e9) == MEDIUM
    assert lp.budget_for_class(lp.classify_length(50.0)) > 0
    for L in (10, 20, 200, 400):
        lp.observe("p", float(L))
    assert lp.classify_length(5.0) == SHORT  # real quantiles take over
    assert lp.classify_length(1e9) == LONG


def test_posterior_blends_global_survivors_when_history_thin():
    lp = LengthPolicy(LengthPolicyConfig(min_history=4, prior_weight=0.0))
    for _ in range(20):
        lp.observe("long_p", 500.0)
        lp.observe("med_p", 100.0)
    # one short sample: survivor pool of size <= 1 used to dominate
    lp.observe("thin_p", 20.0)
    post = lp.posterior("thin_p", 10.0)
    # global survivors (mass at MEDIUM/LONG) must still carry weight
    assert post[SHORT] < 1.0 - 1e-6
    assert post[MEDIUM] + post[LONG] > 0.25
    # with enough per-problem history the pool is per-problem again
    for _ in range(4):
        lp.observe("thin_p", 20.0)
    post2 = lp.posterior("thin_p", 10.0)
    assert post2[SHORT] > post[SHORT]
    # past every per-problem length but below global max: blending keeps
    # the degenerate "definitely Long" verdict from a 1-sample pool at bay
    lp2 = LengthPolicy(LengthPolicyConfig(min_history=4, prior_weight=0.0))
    for _ in range(20):
        lp2.observe("other", 100.0)
    lp2.observe("thin", 20.0)
    post3 = lp2.posterior("thin", 50.0)
    assert post3[LONG] < 1.0 - 1e-6  # global pool keeps MEDIUM alive
