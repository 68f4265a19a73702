"""Device-side batched suffix-match drafting vs the host oracle.

The contract under test: for the same packed history and the same
context tail, the kernel's (match length, proposals) are bit-identical
to the host ``MatchState`` fed that tail followed by
``propose(budget, min_match)`` — across random corpora, epoch decay,
document removal, and interleaved extend/evict via the drafter window.
"""

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
    and must actually take the batched device path."""
    import jax
    from conftest import make_params
    from repro.core.spec_engine import EngineConfig, SpecEngine

    params = make_params(tiny_dense)
    prompts = [[3, 4, 5], [6, 7], [8, 9, 10, 11]]
    outs = {}
    for mode in ("on", "off"):
        eng = SpecEngine(
            params, tiny_dense,
            EngineConfig(max_new_tokens=24, max_draft=4,
                         block_buckets=(0, 2, 4), device_draft=mode),
        )
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
                         block_buckets=(0, 2, 4), device_draft="on",
                         fuse_rounds="on"),
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
