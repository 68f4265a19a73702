"""Driving ``SpecEngine.serve`` for the traffic generators: one RL step
to its last token, and the set-up that compiles every shape a window can
meet (admission chunks, and the fused round at the next forest size).
"""

from __future__ import annotations

import time

import numpy as np


def requests(dicts):
    from repro.core.scheduler import Request

    return [Request(rid=d["rid"], problem_id=d["problem_id"],
                    prompt=list(d["prompt"]),
                    max_new_tokens=d["max_new_tokens"]) for d in dicts]


def serve_step(eng, dicts, slots: int, step: int, profile=None) -> dict:
    """Serve one RL step to its last token; returns its record. With a
    ``RoundProfile``, that profile runs inside the step (its start and
    stop are left out of the step's seconds)."""
    from repro.core.spec_engine import RolloutStats

    reqs = requests(dicts)
    st = RolloutStats()
    if profile is not None:
        profile.snapshot = lambda: snapshot(st, reqs)
        eng.telemetry.profile = profile
    t0 = time.perf_counter()
    try:
        done = list(eng.serve(reqs, slots=slots, stats=st,
                              collect_effective_batch=True))
    finally:
        if profile is not None:
            eng.telemetry.profile = None
    dt = time.perf_counter() - t0
    if profile is not None:
        dt -= profile.overhead_s
    return {"step": step, "seconds": dt, "stats": st, "requests": reqs,
            "finished": len(done)}


def snapshot(st, reqs) -> dict:
    """The counters a profile's rounds are read from, at one point."""
    return {"drafted": st.n_drafted, "accepted": st.n_accepted,
            "n_eff": len(st.effective_batch),
            "rows": [(q.rounds, len(q.output)) for q in reqs]}


def profiled_work(reqs, st, a: dict, b: dict) -> dict:
    """What the rounds between snapshots ``a`` and ``b`` verified: rows,
    block tokens, and each row's valid cache read (its prompt and
    emitted tokens, at the mean over those rounds)."""
    eff = st.effective_batch[a["n_eff"]:b["n_eff"]]
    row_rounds = float(sum(eff))
    block = row_rounds + (b["drafted"] - a["drafted"])
    ctx = 0.0
    for q, (r0, o0), (r1, o1) in zip(reqs, a["rows"], b["rows"]):
        if r1 > r0:
            ctx += (r1 - r0) * (len(q.prompt) + (o0 + o1) / 2.0)
    return {"rounds": len(eff), "block_tokens": block,
            "context_reads": ctx,
            "attn_pairs": ctx * block / row_rounds if row_rounds else 0.0}


def warm_admissions(eng, slots: int) -> int:
    """Compile every admission shape the window can meet: prefill, the
    cache-row copy, the first-token pick and the round-state write, for
    each power-of-two admission chunk up to ``slots`` and each prompt
    bucket and pool length the warm steps used. Returns the shapes
    warmed."""
    import jax

    from repro.core.verify import sample_token_rows

    geoms = sorted(eng._prefill_jit)
    copy_rows = eng._get_copy_rows()
    admit_state = eng._get_admit_state()
    tail = eng.drafter.cfg.device_tail
    V = eng.cfg.vocab_size
    n = 0
    for max_len in sorted({m for _, m in geoms}):
        cache = eng._init_pool(slots, max_len)
        state = _empty_state(eng, slots)
        for Tp in sorted({t for t, m in geoms if m == max_len}):
            k = 1
            while k <= slots:
                toks = np.zeros((k, Tp), np.int32)
                mask = np.ones((k, Tp), bool)
                logits, rows = eng._get_prefill(Tp, max_len)(
                    eng.params, toks, mask)
                cache = copy_rows(cache, rows, np.full(k, slots, np.int32))
                np.asarray(sample_token_rows(
                    logits[:, :V], temperature=eng.engine.temperature))
                state = admit_state(
                    state, np.full(k, slots, np.int32),
                    np.zeros(k, np.int32), np.full((k, tail), -1, np.int32),
                    np.ones(k, np.int32), np.ones(k, np.int32))
                n += 1
                k *= 2
        jax.block_until_ready((cache, state))
        del cache, state
    return n


def _empty_state(eng, slots: int):
    from repro.core.fused_round import make_state

    tail = eng.drafter.cfg.device_tail
    return eng._to_device(make_state(
        np.zeros(slots, np.int32), np.full((slots, tail), -1, np.int32),
        np.zeros(slots, bool), np.zeros(slots, np.int64),
        np.ones(slots, np.int64)))


class FusedGeometry:
    """Records, while installed, the forest and pool geometry of every
    fused-round call, per K bucket. ``remove()`` puts the engine's own
    method back, so nothing of it stays on the timed path."""

    def __init__(self, eng) -> None:
        import jax

        self.eng = eng
        self.seen: dict = {}
        orig = type(eng)._get_fused.__get__(eng)

        def get(K, R):
            fn = orig(K, R)

            def call(params, forest, cache, *rest):
                shapes = tuple(tuple(x.shape) for x in forest)
                self.seen.setdefault((K, R), {})[shapes] = (
                    jax.tree.map(lambda a: jax.ShapeDtypeStruct(
                        a.shape, a.dtype), cache), rest[-1])
                return fn(params, forest, cache, *rest)

            return call

        eng._get_fused = get

    def remove(self) -> None:
        del self.eng._get_fused


def warm_next_forest(eng, geom: FusedGeometry, slots: int) -> int:
    """Run each fused-round program (K bucket) the warm steps used at
    the largest packed forest they met, and at the next power of two of
    it in every dimension, where the warm steps did not: a window whose
    drafter history grows one bucket then loads its program from the
    compile cache in set-up, not inside the window. All rows inactive.
    Returns the programs run."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.suffix_match import ops as sm_ops

    every = {s: v for by in geom.seen.values() for s, v in by.items()}
    if not every:
        return 0
    big = max(every, key=lambda s: s[-1][0])  # the largest corpus
    cache_avals, key = every[big]
    sizes = dict(zip(sm_ops.PackedForest._fields, (s[0] for s in big)))
    n = 0
    for (K, R), by_shape in sorted(geom.seen.items()):
        for mult in (1, 2):
            forest, _ = sm_ops.pack_forest(
                [], min_nodes=mult * sizes["suffix_link"],
                min_edges=mult * sizes["edge_node"],
                min_corpus=mult * sizes["corpus"])
            if tuple(tuple(x.shape) for x in forest) in by_shape:
                continue
            forest = eng._to_device(forest)
            with jax.default_device(eng.device):
                cache = eng._to_device(jax.tree.map(
                    lambda a: jnp.zeros(a.shape, a.dtype), cache_avals))
            out = type(eng)._get_fused(eng, K, R)(
                eng.params, forest, cache, _empty_state(eng, slots),
                eng._to_device(np.full(slots, -1, np.int32)),
                np.zeros(slots, np.int32), key)
            jax.block_until_ready(out)
            del out, cache, forest
            n += 1
    return n
