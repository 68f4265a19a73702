"""Cached decode == full forward, for every block family (the invariant
that makes speculative verification exact)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import make_params
from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import model as M

BASE = dict(
    num_layers=2, d_model=64, num_heads=4, num_kv_heads=2, d_ff=128,
    vocab_size=97, vocab_pad_multiple=8, dtype="float32",
)

FAMILIES = {
    "dense": dict(family="dense"),
    "dense-swa": dict(family="dense", sliding_window=6),
    "gqa-bias-partial-rope": dict(
        family="dense", attn_bias=True, rope="partial", rope_fraction=0.5
    ),
    "parallel-layernorm": dict(family="dense", parallel_block=True, norm="layer"),
    "moe": dict(
        family="moe", num_experts=4, experts_per_token=2, capacity_factor=4.0
    ),
    "moe-dense-residual": dict(
        family="moe", num_experts=4, experts_per_token=2, capacity_factor=4.0,
        moe_dense_residual=True,
    ),
    "hybrid-rglru": dict(
        family="hybrid", block_pattern=("rglru", "rglru", "local_attn"),
        num_layers=5, local_window=6, rnn_width=64,
    ),
    "xlstm": dict(
        family="ssm", block_pattern=("mlstm", "slstm"), d_ff=0,
        num_layers=4, rnn_width=64,
    ),
    "mrope": dict(family="vlm", rope="mrope", mrope_sections=(4, 2, 2)),
}


@pytest.mark.parametrize("name", sorted(FAMILIES))
def test_cached_decode_matches_full_forward(name):
    kw = {**BASE, **FAMILIES[name]}
    cfg = ModelConfig(name=name, **kw)
    params = make_params(cfg)
    B, T = 3, 12
    toks = jax.random.randint(jax.random.key(1), (B, T), 0, cfg.vocab_size)
    logits_full, _, _ = M.forward(params, cfg, toks)
    assert not bool(jnp.isnan(logits_full).any())
    plens = [5, 7, 12]
    Tp = 12
    pad = np.zeros((B, Tp), np.int32)
    mask = np.zeros((B, Tp), bool)
    for b, pl in enumerate(plens):
        pad[b, Tp - pl :] = np.asarray(toks[b, :pl])
        mask[b, Tp - pl :] = True
    last, cache = M.prefill(
        params, cfg, jnp.asarray(pad), jnp.asarray(mask), max_len=32
    )
    assert list(np.asarray(cache.lengths)) == plens
    for b, pl in enumerate(plens):
        np.testing.assert_allclose(
            np.asarray(last[b]), np.asarray(logits_full[b, pl - 1]),
            atol=2e-2, rtol=1e-2,
        )
    lengths = np.array(plens)
    for _ in range(T - min(plens)):
        feed = np.zeros((B, 1), np.int32)
        val = np.zeros((B, 1), bool)
        for b in range(B):
            if lengths[b] < T:
                feed[b, 0] = int(toks[b, lengths[b]])
                val[b, 0] = True
        logits, cache, _ = M.forward(
            params, cfg, jnp.asarray(feed), cache=cache,
            valid=jnp.asarray(val),
            commit_upto=jnp.asarray(val[:, 0].astype(np.int32)),
        )
        cache = cache._replace(
            lengths=cache.lengths + jnp.asarray(val[:, 0].astype(np.int32))
        )
        for b in range(B):
            if val[b, 0]:
                np.testing.assert_allclose(
                    np.asarray(logits[b, 0]),
                    np.asarray(logits_full[b, lengths[b]]),
                    atol=2e-2, rtol=1e-2, err_msg=f"{name} b={b}",
                )
        lengths = lengths + val[:, 0]


def test_verify_block_partial_acceptance_commit():
    """A multi-token verify block with partial acceptance must leave the
    cache equivalent to having decoded only the accepted prefix."""
    cfg = ModelConfig(
        name="hyb",
        **{**BASE, **FAMILIES["hybrid-rglru"]},
    )
    params = make_params(cfg)
    B = 2
    prompt = jax.random.randint(jax.random.key(2), (B, 5), 0, cfg.vocab_size)
    last, cache = M.prefill(
        params, cfg, prompt, jnp.ones((B, 5), bool), max_len=32
    )
    # feed a 4-token block, accept only `a` per row
    block = jax.random.randint(jax.random.key(3), (B, 4), 0, cfg.vocab_size)
    accepted = jnp.asarray([1, 3], jnp.int32)
    _, cache_blk, _ = M.forward(
        params, cfg, block, cache=cache, valid=jnp.ones((B, 4), bool),
        commit_upto=accepted,
    )
    cache_blk = cache_blk._replace(lengths=cache_blk.lengths + accepted)
    # reference: decode the accepted tokens one by one
    cache_ref = cache
    for t in range(4):
        live = (jnp.arange(B) * 0 + t) < accepted
        _, cache_ref, _ = M.forward(
            params, cfg, block[:, t : t + 1], cache=cache_ref,
            valid=live[:, None],
            commit_upto=live.astype(jnp.int32),
        )
        cache_ref = cache_ref._replace(
            lengths=cache_ref.lengths + live.astype(jnp.int32)
        )
    # next-step logits from both caches must agree
    nxt = jax.random.randint(jax.random.key(4), (B, 1), 0, cfg.vocab_size)
    l1, _, _ = M.forward(
        params, cfg, nxt, cache=cache_blk, valid=jnp.ones((B, 1), bool),
        commit_upto=jnp.ones((B,), jnp.int32),
    )
    l2, _, _ = M.forward(
        params, cfg, nxt, cache=cache_ref, valid=jnp.ones((B, 1), bool),
        commit_upto=jnp.ones((B,), jnp.int32),
    )
    np.testing.assert_allclose(
        np.asarray(l1), np.asarray(l2), atol=2e-2, rtol=1e-2
    )


def test_encoder_decoder_consistency():
    cfg = ModelConfig(
        name="ed", family="audio", num_layers=2, d_model=64, num_heads=4,
        num_kv_heads=4, d_ff=128, vocab_size=97, vocab_pad_multiple=8,
        dtype="float32", is_encoder_decoder=True, num_encoder_layers=2,
        mlp="gelu", modality="audio",
    )
    params = make_params(cfg)
    B, S, T = 2, 9, 8
    enc_embeds = jax.random.normal(jax.random.key(2), (B, S, cfg.d_model))
    enc_mask = jnp.asarray(np.array([[1] * 9, [1] * 6 + [0] * 3], bool))
    enc_out = M.encode(params, cfg, enc_embeds, enc_mask)
    toks = jax.random.randint(jax.random.key(3), (B, T), 0, cfg.vocab_size)
    full, _, _ = M.forward(params, cfg, toks, enc_out=enc_out, enc_mask=enc_mask)
    assert not bool(jnp.isnan(full).any())
    last, cache = M.prefill(
        params, cfg, toks[:, :3], jnp.ones((B, 3), bool), max_len=16,
        enc_out=enc_out, enc_mask=enc_mask,
    )
    np.testing.assert_allclose(
        np.asarray(last), np.asarray(full[:, 2]), atol=2e-2, rtol=1e-2
    )


def test_ring_cache_wraparound_matches_full():
    """long_500k semantics at CPU scale: decode 40 tokens through a
    13-slot ring cache (window 8 + headroom 4 + trash) — every slot is
    overwritten multiple times; logits must track windowed full
    attention exactly."""
    cfg = ModelConfig(
        name="swa-ring", family="dense", sliding_window=8,
        **{k: v for k, v in BASE.items()},
    )
    params = make_params(cfg)
    B, T = 2, 40
    toks = jax.random.randint(jax.random.key(1), (B, T), 0, cfg.vocab_size)
    full, _, _ = M.forward(params, cfg, toks)
    _, cache = M.prefill(
        params, cfg, toks[:, :4], jnp.ones((B, 4), bool), max_len=64,
        headroom=4,
    )
    for step in range(4, T):
        logits, cache, _ = M.forward(
            params, cfg, toks[:, step : step + 1], cache=cache,
            valid=jnp.ones((B, 1), bool),
            commit_upto=jnp.ones((B,), jnp.int32),
        )
        cache = cache._replace(lengths=cache.lengths + 1)
        np.testing.assert_allclose(
            np.asarray(logits[:, 0]), np.asarray(full[:, step]),
            atol=2e-2, rtol=1e-2, err_msg=f"step {step}",
        )


def test_cross_cache_matches_recompute():
    """§Perf pair A: the precomputed cross-KV path must be numerically
    identical to re-projecting enc_out every step."""
    cfg = ModelConfig(
        name="ed", family="audio", num_layers=2, d_model=64, num_heads=4,
        num_kv_heads=4, d_ff=128, vocab_size=97, vocab_pad_multiple=8,
        dtype="float32", is_encoder_decoder=True, num_encoder_layers=2,
        mlp="gelu", modality="audio",
    )
    params = make_params(cfg)
    B, S = 2, 9
    enc_embeds = jax.random.normal(jax.random.key(2), (B, S, cfg.d_model))
    enc_mask = jnp.ones((B, S), bool)
    enc_out = M.encode(params, cfg, enc_embeds, enc_mask)
    cross = M.build_cross_cache(params, cfg, enc_out)
    toks = jax.random.randint(jax.random.key(3), (B, 4), 0, cfg.vocab_size)
    _, cache = M.prefill(
        params, cfg, toks[:, :2], jnp.ones((B, 2), bool), max_len=16,
        enc_out=enc_out, enc_mask=enc_mask,
    )
    blk = toks[:, 2:4]
    l_re, _, _ = M.forward(
        params, cfg, blk, cache=cache, valid=jnp.ones((B, 2), bool),
        commit_upto=jnp.zeros((B,), jnp.int32),
        enc_out=enc_out, enc_mask=enc_mask,
    )
    l_cc, _, _ = M.forward(
        params, cfg, blk, cache=cache, valid=jnp.ones((B, 2), bool),
        commit_upto=jnp.zeros((B,), jnp.int32),
        cross_cache=cross, enc_mask=enc_mask,
    )
    np.testing.assert_allclose(
        np.asarray(l_re), np.asarray(l_cc), atol=1e-5, rtol=1e-5
    )
    # axes tree mirrors structure
    ax = M.cross_cache_logical_axes(cfg)
    assert len(jax.tree.leaves(cross)) == len(
        jax.tree.leaves(
            ax,
            is_leaf=lambda x: isinstance(x, tuple)
            and all(isinstance(e, (str, type(None))) for e in x),
        )
    )


def test_flash_attention_matches_dense():
    import repro.models.layers as L

    cfg = ModelConfig(name="t", family="dense", **BASE)
    B, S, Hq, Hkv, hd = 2, 2304, 4, 2, 16
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(B, S, Hq, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, Hkv, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, Hkv, hd)), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S)).astype(jnp.int32)
    for window in (0, 257):
        flash = L._flash_attn_train(
            q, k, v, pos, cfg, window=window, valid=None,
            q_chunk=256, kv_chunk=512,
        )
        qp, kp = pos[:, :, None], pos[:, None, :]
        mask = kp <= qp
        if window:
            mask &= kp > qp - window
        ref = L._attn_core(q, k, v, mask[:, None], cfg)
        np.testing.assert_allclose(
            np.asarray(flash), np.asarray(ref), atol=3e-5, rtol=1e-4
        )


def _layer_loop_forward(params, cfg, block, cache, valid, collect):
    """The cached forward as a Python loop over unstacked layers."""
    B, T = block.shape
    x = params["embed"][block].astype(jnp.dtype(cfg.dtype))
    positions = cache.lengths[:, None] + jnp.arange(T)[None]
    commit_upto = None if collect else jnp.zeros((B,), jnp.int32)
    stages = []
    for si, (unit, repeats) in enumerate(cfg.scan_stages):
        sp, sc = params["stages"][si], cache.stages[si]
        per_layer = []
        for r in range(repeats):
            def take(tree, r=r, stacked=repeats > 1):
                return jax.tree.map(lambda a: a[r], tree) if stacked else tree

            outs = []
            for ui, kind in enumerate(unit):
                x, c, _ = M._run_block(
                    take(sp[ui]), kind, x, cfg, positions=positions,
                    cache=take(sc[ui]), valid=valid, commit_upto=commit_upto,
                    collect_states=collect,
                )
                outs.append(c)
            per_layer.append(tuple(outs))
        stages.append(
            jax.tree.map(lambda *a: jnp.stack(a), *per_layer)
            if repeats > 1 else per_layer[0]
        )
    x = L.apply_norm(params["final_norm"], x, cfg)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("btd,dv->btv", x, head).astype(jnp.float32)
    return logits, M.Cache(tuple(stages), cache.lengths)


IN_PLACE_UNITS = {
    "attn": dict(family="dense", num_layers=3),
    "local_attn+attn": dict(
        family="dense", block_pattern=("local_attn", "attn"), num_layers=4,
        local_window=6,
    ),
    "rglru+local_attn": dict(
        family="hybrid", block_pattern=("rglru", "rglru", "local_attn"),
        num_layers=6, local_window=6, rnn_width=64,
    ),
}


@pytest.mark.parametrize("name", sorted(IN_PLACE_UNITS))
def test_scanned_cache_update_equals_layer_loop(name):
    """The layer scan's in-place update of the stacked attention cache
    gives exactly what a loop over the unstacked layers gives — logits
    and every cache leaf, recurrent staged states included — and leaves
    every slot outside the verify block as it was."""
    cfg = ModelConfig(name=name, **{**BASE, **IN_PLACE_UNITS[name]})
    collect = name.startswith("rglru")
    params = make_params(cfg)
    B, Tp, K1 = 3, 8, 5
    toks = jax.random.randint(jax.random.key(1), (B, Tp), 0, cfg.vocab_size)
    mask = jnp.arange(Tp)[None] >= jnp.asarray([[3], [0], [5]])
    _, cache = M.prefill(params, cfg, toks, mask, max_len=32, headroom=8)
    block = jax.random.randint(jax.random.key(2), (B, K1), 0, cfg.vocab_size)
    valid = jnp.broadcast_to(jnp.asarray([True, False, True])[:, None],
                             (B, K1))

    @jax.jit
    def scanned(params, block, cache):
        logits, new, _ = M.forward(
            params, cfg, block, cache=cache, valid=valid,
            commit_upto=None if collect else jnp.zeros((B,), jnp.int32),
            collect_states=collect,
        )
        return logits, new

    looped = jax.jit(functools.partial(
        _layer_loop_forward, cfg=cfg, valid=valid, collect=collect
    ))
    logits, new = scanned(params, block, cache)
    logits_ref, new_ref = looped(params, block=block, cache=cache)
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(logits_ref))
    leaves, ref_leaves = jax.tree.leaves(new), jax.tree.leaves(new_ref)
    assert jax.tree.structure(new) == jax.tree.structure(new_ref)
    for a, b in zip(leaves, ref_leaves):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # attention leaves: only the block's slots (valid rows) or the trash
    # slot (invalid rows) may differ from the cache before the forward
    pos = np.asarray(cache.lengths)[:, None] + np.arange(K1)[None]
    for si, (unit, repeats) in enumerate(cfg.scan_stages):
        for ui, kind in enumerate(unit):
            if kind not in ("attn", "local_attn"):
                continue
            for old, upd in zip(cache.stages[si][ui], new.stages[si][ui]):
                old, upd = np.asarray(old), np.asarray(upd)
                if repeats == 1:
                    old, upd = old[None], upd[None]
                S = old.shape[2] - 1
                kept = np.ones(old.shape[1:3], bool)  # (B, S+1)
                for b in range(B):
                    kept[b, pos[b] % S if bool(valid[b, 0]) else S] = False
                np.testing.assert_array_equal(upd[:, kept], old[:, kept])
