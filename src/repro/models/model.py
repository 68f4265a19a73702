"""Model assembly: embedding → scan-staged blocks → head.

One code path serves all four workload shapes:

* ``train``    — full-sequence causal forward, no cache (train_4k).
* ``prefill``  — full-sequence compute + cache construction from the
  computed K/V (left-padded prompts; pads masked everywhere).
* ``verify``   — cached path: a K+1-token draft block is appended at
  per-row offsets. Attention caches commit via ring-slot overwrite
  (speculative rollback is free). Recurrent layers (rglru/mlstm/slstm)
  support two commit schemes: dual-carry scans (the *dynamic* state
  advances for correct per-position logits while the *committed* state
  stops at `commit_upto` — needs a second gated forward when the
  acceptance count isn't known up front), and the single-pass
  ``collect_states`` scheme — staged per-step state candidates are
  emitted and `commit_staged_cache` gathers at the acceptance count
  afterwards (§Perf pair D: −46% verify flops on recurrentgemma).
  Plain decode is verify with K=0.

Layers are grouped into ``cfg.scan_stages`` and executed under
``jax.lax.scan`` with stacked parameters to keep HLO size and compile
time bounded at 64-layer scale.
"""

from __future__ import annotations

from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.launch.sharding import constrain
from repro.models import layers as L
from repro.models.layers import Param, split_tree, stack_params


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _init_block(key, cfg: ModelConfig, kind: str):
    """One block = pre-norm + mixer (+ cross-attn) (+ post-norm + MLP/MoE)."""
    ks = jax.random.split(key, 6)
    p: Dict[str, Any] = {"norm": L.init_norm(cfg)}
    if kind in ("attn", "local_attn"):
        p["attn"] = L.init_attention(ks[0], cfg)
        if cfg.is_encoder_decoder:
            p["cross_norm"] = L.init_norm(cfg)
            p["cross"] = L.init_attention(ks[1], cfg, cross=True)
    elif kind == "rglru":
        p["rglru"] = L.init_rglru(ks[0], cfg)
    elif kind == "mlstm":
        p["mlstm"] = L.init_mlstm(ks[0], cfg)
    elif kind == "slstm":
        p["slstm"] = L.init_slstm(ks[0], cfg)
    else:
        raise ValueError(f"unknown block kind {kind}")
    if cfg.num_experts > 0 and kind in ("attn", "local_attn"):
        p["mlp_norm"] = L.init_norm(cfg)
        p["moe"] = L.init_moe(ks[2], cfg)
    elif cfg.d_ff > 0 and not cfg.parallel_block:
        p["mlp_norm"] = L.init_norm(cfg)
        p["mlp"] = L.init_mlp(ks[2], cfg)
    elif cfg.d_ff > 0 and cfg.parallel_block:
        p["mlp"] = L.init_mlp(ks[2], cfg)  # shares `norm` (command-r)
    return p


def _init_enc_block(key, cfg: ModelConfig):
    ks = jax.random.split(key, 2)
    return {
        "norm": L.init_norm(cfg),
        "attn": L.init_attention(ks[0], cfg),
        "mlp_norm": L.init_norm(cfg),
        "mlp": L.init_mlp(ks[1], cfg),
    }


def init_params(cfg: ModelConfig, key) -> Dict[str, Any]:
    """Returns a Param tree (values + logical axes)."""
    n_keys = cfg.num_layers + cfg.num_encoder_layers + 4
    keys = jax.random.split(key, n_keys)
    dt = jnp.dtype(cfg.dtype)
    params: Dict[str, Any] = {
        "embed": L._dense_init(
            keys[0], (cfg.padded_vocab, cfg.d_model), ("vocab", "embed"), dt,
            scale=0.02,
        ),
        "final_norm": L.init_norm(cfg),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = L._dense_init(
            keys[1], (cfg.d_model, cfg.padded_vocab), ("embed", "vocab"), dt
        )
    ki = 2
    stages: List[Any] = []
    for unit, repeats in cfg.scan_stages:
        reps = []
        for _ in range(repeats):
            unit_p = []
            for kind in unit:
                unit_p.append(_init_block(keys[ki], cfg, kind))
                ki += 1
            reps.append(tuple(unit_p))
        stages.append(stack_params(reps) if repeats > 1 else reps[0])
    params["stages"] = stages
    if cfg.is_encoder_decoder:
        enc = [
            _init_enc_block(keys[(ki + i) % n_keys], cfg)
            for i in range(cfg.num_encoder_layers)
        ]
        params["encoder"] = {
            "blocks": stack_params(enc),
            "final_norm": L.init_norm(cfg),
        }
    return params


def param_shapes(cfg: ModelConfig):
    """Abstract Param tree (ShapeDtypeStructs) — used by the dry-run."""
    return jax.eval_shape(lambda k: init_params(cfg, k), jax.random.key(0))


# ---------------------------------------------------------------------------
# cache
# ---------------------------------------------------------------------------

class Cache(NamedTuple):
    stages: Tuple[Any, ...]  # per-stage pytrees (stacked when scanned)
    lengths: jnp.ndarray  # (B,) committed tokens per row


def _block_cache(cfg: ModelConfig, kind: str, batch: int, max_len: int,
                 headroom: int, slot_multiple: int = 1):
    if kind == "attn":
        return L.init_kv_cache(
            cfg, batch, max_len, cfg.sliding_window, headroom, slot_multiple
        )
    if kind == "local_attn":
        return L.init_kv_cache(
            cfg, batch, max_len, cfg.local_window, headroom, slot_multiple
        )
    W = cfg.rnn_width
    H = max(cfg.num_heads, 1)
    hd = W // H
    if kind == "rglru":
        return {
            "h": jnp.zeros((batch, W), jnp.float32),
            "conv": jnp.zeros((batch, cfg.conv_width - 1, W), jnp.dtype(cfg.dtype)),
        }
    if kind == "mlstm":
        return (
            jnp.zeros((batch, H, hd, hd), jnp.float32),
            jnp.zeros((batch, H, hd), jnp.float32),
            jnp.full((batch, H), -jnp.inf, jnp.float32),
        )
    if kind == "slstm":
        return tuple(jnp.zeros((batch, W), jnp.float32) for _ in range(3)) + (
            jnp.full((batch, W), -jnp.inf, jnp.float32),
        )
    raise ValueError(kind)


def init_cache(
    cfg: ModelConfig, batch: int, max_len: int, headroom: int = 64,
    slot_multiple: int = 1,
) -> Cache:
    stages = []
    for unit, repeats in cfg.scan_stages:
        unit_c = tuple(
            _block_cache(cfg, k, batch, max_len, headroom, slot_multiple)
            for k in unit
        )
        if repeats > 1:
            unit_c = jax.tree.map(
                lambda x: jnp.broadcast_to(x, (repeats,) + x.shape).copy(),
                unit_c,
            )
        stages.append(unit_c)
    return Cache(tuple(stages), jnp.zeros((batch,), jnp.int32))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _run_block(
    p, kind, x, cfg: ModelConfig, *, positions, cache, valid, commit_upto,
    mrope_positions=None, enc_out=None, enc_mask=None, attn_impl="xla",
    cross_kv=None, collect_states=False, layer=None,
):
    """Returns (x, new_cache, aux). ``layer`` indexes a stacked attention
    cache (see ``L.attention_forward``)."""
    aux = jnp.zeros((), jnp.float32)
    h = L.apply_norm(p["norm"], x, cfg)
    new_cache = cache
    if kind in ("attn", "local_attn"):
        window = cfg.local_window if kind == "local_attn" else cfg.sliding_window
        y, kv_out = L.attention_forward(
            p["attn"], h, cfg, positions=positions, window=window,
            kv_cache=cache, valid=valid, mrope_positions=mrope_positions,
            attn_impl=attn_impl if cache is not None else "xla",
            layer=layer,
        )
        new_cache = kv_out
        x = x + y
        if cfg.is_encoder_decoder and (enc_out is not None or cross_kv is not None):
            hc = L.apply_norm(p["cross_norm"], x, cfg)
            if cross_kv is not None:
                # precomputed cross K/V (build_cross_cache): avoids
                # re-projecting enc_out every decode step (§Perf pair A)
                ck, cv = cross_kv
            else:
                ck = jnp.einsum("bsd,dhk->bshk", enc_out, p["cross"]["wk"])
                cv = jnp.einsum("bsd,dhk->bshk", enc_out, p["cross"]["wv"])
            yc, _ = L.attention_forward(
                p["cross"], hc, cfg, positions=positions,
                cross_kv=(ck, cv, enc_mask),
            )
            x = x + yc
    elif kind == "rglru":
        state = cache["h"] if cache is not None else None
        conv = cache["conv"] if cache is not None else None
        y, h_fin, conv_new = L.apply_rglru(
            p["rglru"], h, cfg, state, conv,
            update_mask=valid, commit_upto=commit_upto,
            collect=collect_states,
        )
        x = x + y
        new_cache = {"h": h_fin, "conv": conv_new}
    elif kind == "mlstm":
        y, new_state = L.apply_mlstm(
            p["mlstm"], h, cfg, cache, update_mask=valid,
            commit_upto=commit_upto, collect=collect_states,
        )
        x = x + y
        new_cache = new_state
    elif kind == "slstm":
        y, new_state = L.apply_slstm(
            p["slstm"], h, cfg, cache, update_mask=valid,
            commit_upto=commit_upto, collect=collect_states,
        )
        x = x + y
        new_cache = new_state
    # MLP / MoE
    if "moe" in p:
        hm = L.apply_norm(p["mlp_norm"], x, cfg)
        y, aux = L.apply_moe(p["moe"], hm, cfg)
        x = x + y
    elif "mlp" in p:
        hm = h if cfg.parallel_block else L.apply_norm(p["mlp_norm"], x, cfg)
        x = x + L.apply_mlp(p["mlp"], hm, cfg)
    return x, new_cache, aux


def build_cross_cache(params, cfg: ModelConfig, enc_out):
    """Precompute every decoder layer's cross-attention K/V from the
    encoder output — static per request, so recomputing it each decode
    step (2·L·S_enc·d² flops + traffic) is pure waste. §Perf pair A:
    this one change moved seamless decode_32k's useful-flops ratio from
    0.03 toward 1. Returns a per-stage tuple aligned with cfg.scan_stages
    (None entries for non-attention kinds)."""
    stages = []
    for si, (unit, repeats) in enumerate(cfg.scan_stages):
        stage_p = params["stages"][si]
        unit_out = []
        for ui, kind in enumerate(unit):
            if kind in ("attn", "local_attn") and cfg.is_encoder_decoder:
                pc = stage_p[ui]["cross"]
                if repeats > 1:
                    ck = jnp.einsum("bsd,rdhk->rbshk", enc_out, pc["wk"])
                    cv = jnp.einsum("bsd,rdhk->rbshk", enc_out, pc["wv"])
                else:
                    ck = jnp.einsum("bsd,dhk->bshk", enc_out, pc["wk"])
                    cv = jnp.einsum("bsd,dhk->bshk", enc_out, pc["wv"])
                unit_out.append((ck, cv))
            else:
                unit_out.append(None)
        stages.append(tuple(unit_out))
    return tuple(stages)


def cross_cache_logical_axes(cfg: ModelConfig):
    """Axes tree matching build_cross_cache's output."""
    stages = []
    for unit, repeats in cfg.scan_stages:
        unit_out = []
        for kind in unit:
            if kind in ("attn", "local_attn") and cfg.is_encoder_decoder:
                ax = ("batch", None, "kv_heads", "head_dim")
                if repeats > 1:
                    ax = ("layers",) + ax
                unit_out.append((ax, ax))
            else:
                unit_out.append(None)
        stages.append(tuple(unit_out))
    return tuple(stages)


def encode(params, cfg: ModelConfig, enc_embeds, enc_mask):
    """Bidirectional encoder over stub frontend embeddings (audio)."""
    pe = params["encoder"]
    x = enc_embeds.astype(jnp.dtype(cfg.dtype))
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))

    def body(x, pb):
        h = L.apply_norm(pb["norm"], x, cfg)
        y, _ = L.attention_forward(
            pb["attn"], h, cfg, positions=positions, bidirectional=True,
            valid=enc_mask,
        )
        y = jnp.where(enc_mask[:, :, None], y, 0.0)
        x = x + y
        hm = L.apply_norm(pb["mlp_norm"], x, cfg)
        x = x + L.apply_mlp(pb["mlp"], hm, cfg)
        return x, None

    x, _ = jax.lax.scan(body, x, pe["blocks"])
    return L.apply_norm(pe["final_norm"], x, cfg)


def forward(
    params,  # raw value tree (no Param wrappers)
    cfg: ModelConfig,
    tokens: Optional[jnp.ndarray] = None,  # (B, T) int32
    *,
    embeds: Optional[jnp.ndarray] = None,  # (B, T, d) modality stub
    cache: Optional[Cache] = None,
    positions: Optional[jnp.ndarray] = None,
    valid: Optional[jnp.ndarray] = None,  # (B, T) bool
    commit_upto: Optional[jnp.ndarray] = None,  # (B,) acceptance prefix
    mrope_positions=None,
    enc_out=None,
    enc_mask=None,
    cross_cache=None,  # build_cross_cache output (decode fast path)
    attn_impl: str = "xla",
    remat: bool = False,
    return_hidden: bool = False,
    collect_states: bool = False,  # single-pass speculative verify
):
    """Returns (logits (B,T,V_padded) f32, new_cache | kv_list, aux).

    With return_hidden=True, returns the final-norm hidden states
    (B,T,D) instead of logits — callers then use a *chunked* logprob
    computation (rl.grpo.chunked_token_logprobs) so the (B,S,V) fp32
    logits tensor is never materialized (large-vocab training).

    A scanned stage with a cache carries its stacked *attention* caches
    in the scan's carry, and each layer updates them in place at its
    index (``jnp.arange(R)`` comes in as ``xs``): only the block's slots
    are written, and the result aliases the donated pool. Passed as
    ``xs``/``ys`` instead, XLA gives ``ys`` a fresh pool-sized buffer,
    writes every layer's whole slice into it, and copies it into the
    donated pool afterwards. Recurrent caches stay in ``xs``/``ys``:
    they are small, and with ``collect_states`` their ``ys`` is a staged
    (B, T+1, ...) shape that ``commit_staged_cache`` gathers from."""
    if embeds is None:
        emb = params["embed"]
        x = emb[tokens].astype(jnp.dtype(cfg.dtype))
    else:
        x = embeds.astype(jnp.dtype(cfg.dtype))
    B, T = x.shape[0], x.shape[1]
    if positions is None:
        if cache is not None:
            positions = cache.lengths[:, None] + jnp.arange(T)[None]
        else:
            positions = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
    aux_total = jnp.zeros((), jnp.float32)
    new_stages = []
    for si, (unit, repeats) in enumerate(cfg.scan_stages):
        stage_p = params["stages"][si]
        stage_c = cache.stages[si] if cache is not None else None
        stage_x = cross_cache[si] if cross_cache is not None else None
        if repeats > 1:
            held = tuple(
                stage_c is not None and kind in ("attn", "local_attn")
                for kind in unit
            )
            c_carry = tuple(
                stage_c[ui] if h else None for ui, h in enumerate(held)
            )
            c_xs = tuple(
                None if h or stage_c is None else stage_c[ui]
                for ui, h in enumerate(held)
            )

            def scan_body(carry, xs, *, unit=unit, held=held):
                x, aux, c_carry = carry
                p_slice, layer, c_slice, x_slice = xs
                c_carry, c_ys = list(c_carry), []
                for ui, kind in enumerate(unit):
                    xc = x_slice[ui] if x_slice is not None else None
                    x, cu_new, a = _run_block(
                        p_slice[ui], kind, x, cfg, positions=positions,
                        cache=c_carry[ui] if held[ui] else c_slice[ui],
                        valid=valid, commit_upto=commit_upto,
                        mrope_positions=mrope_positions, enc_out=enc_out,
                        enc_mask=enc_mask, attn_impl=attn_impl,
                        cross_kv=xc, collect_states=collect_states,
                        layer=layer if held[ui] else None,
                    )
                    if held[ui]:
                        c_carry[ui] = cu_new
                    c_ys.append(None if held[ui] else cu_new)
                    aux = aux + a
                x = constrain(x)  # sequence-parallel residual (training)
                return (x, aux, tuple(c_carry)), tuple(c_ys)

            if remat:
                scan_body = jax.checkpoint(scan_body, prevent_cse=False)
            (x, aux_total, c_carry), c_ys = jax.lax.scan(
                scan_body, (x, aux_total, c_carry),
                (stage_p, jnp.arange(repeats), c_xs, stage_x),
            )
            new_stages.append(tuple(
                c_carry[ui] if h else c_ys[ui] for ui, h in enumerate(held)
            ))
        else:
            c_new_unit = []
            for ui, kind in enumerate(unit):
                cu = stage_c[ui] if stage_c is not None else None
                xc = stage_x[ui] if stage_x is not None else None
                x, cu_new, a = _run_block(
                    stage_p[ui], kind, x, cfg, positions=positions,
                    cache=cu, valid=valid, commit_upto=commit_upto,
                    mrope_positions=mrope_positions, enc_out=enc_out,
                    enc_mask=enc_mask, attn_impl=attn_impl,
                    cross_kv=xc, collect_states=collect_states,
                )
                c_new_unit.append(cu_new)
                aux_total = aux_total + a
            x = constrain(x)
            new_stages.append(tuple(c_new_unit))
    x = L.apply_norm(params["final_norm"], x, cfg)
    if return_hidden:
        logits = x
    elif cfg.tie_embeddings:
        logits = jnp.einsum("btd,vd->btv", x, params["embed"]).astype(jnp.float32)
    else:
        logits = jnp.einsum("btd,dv->btv", x, params["lm_head"]).astype(jnp.float32)
    if cache is not None:
        new_cache = Cache(tuple(new_stages), cache.lengths)
    else:
        new_cache = tuple(new_stages)  # train: per-stage (k, v, pos) lists
    return logits, new_cache, aux_total


# ---------------------------------------------------------------------------
# prefill: full-sequence compute, then scatter computed K/V into a cache
# ---------------------------------------------------------------------------

def prefill(
    params, cfg: ModelConfig, tokens, pad_mask, max_len: int,
    *, embeds=None, headroom: int = 64, mrope_positions=None,
    enc_out=None, enc_mask=None,
):
    """Left-padded prompt prefill.

    tokens/embeds: (B, Tp) / (B, Tp, d); pad_mask (B, Tp) bool (False =
    left pad). Returns (last_logits (B, V), cache) where cache.lengths =
    per-row prompt lengths and the last valid position's logits feed the
    first decode/draft step (all rows are right-aligned, so the last
    column is each row's final prompt token).
    """
    B, Tp = (tokens.shape if tokens is not None else embeds.shape[:2])
    plen = pad_mask.sum(-1).astype(jnp.int32)  # (B,)
    positions = jnp.cumsum(pad_mask, axis=-1) - 1  # (B, Tp); pads < 0
    positions = jnp.where(pad_mask, positions, -1).astype(jnp.int32)
    logits, kv_stages, _ = forward(
        params, cfg, tokens, embeds=embeds, cache=None, positions=positions,
        valid=pad_mask, mrope_positions=mrope_positions, enc_out=enc_out,
        enc_mask=enc_mask,
    )
    cache = init_cache(cfg, B, max_len, headroom)
    new_stages = []
    li = 0
    kinds_by_stage = []
    for unit, repeats in cfg.scan_stages:
        kinds_by_stage.append((unit, repeats))
    for si, (unit, repeats) in enumerate(kinds_by_stage):
        stage_kv = kv_stages[si]
        stage_c = cache.stages[si]
        unit_new = []
        for ui, kind in enumerate(unit):
            c0 = stage_c[ui]
            kv = stage_kv[ui]
            if kind in ("attn", "local_attn"):
                ck, cv, cpos = c0
                k, v, _pos = kv  # (B,Tp,H,hd) or (R,B,Tp,H,hd)
                S = ck.shape[-3] - 1
                n_keep = min(Tp, S)
                ksl = k[..., Tp - n_keep :, :, :]
                vsl = v[..., Tp - n_keep :, :, :]
                psl = positions[:, Tp - n_keep :]
                msl = pad_mask[:, Tp - n_keep :]
                slots = jnp.where(msl, psl % S, S)  # (B, n_keep)
                bidx = jnp.arange(B)[:, None]
                posw = jnp.where(msl, psl, -1)
                if ksl.ndim == 5:  # scanned stage: vmap the scatter over R
                    def scat(ck1, cv1, cp1, k1, v1):
                        return (
                            ck1.at[bidx, slots].set(k1.astype(ck1.dtype)),
                            cv1.at[bidx, slots].set(v1.astype(cv1.dtype)),
                            cp1.at[bidx, slots].set(posw),
                        )
                    ck, cv, cpos = jax.vmap(scat)(ck, cv, cpos, ksl, vsl)
                else:
                    ck = ck.at[bidx, slots].set(ksl.astype(ck.dtype))
                    cv = cv.at[bidx, slots].set(vsl.astype(cv.dtype))
                    cpos = cpos.at[bidx, slots].set(posw)
                unit_new.append((ck, cv, cpos))
            else:
                # recurrent: forward already produced the committed state
                unit_new.append(kv)
            li += repeats
        new_stages.append(tuple(unit_new))
    last_logits = logits[:, -1, :]  # rows are right-aligned
    return last_logits, Cache(tuple(new_stages), plen)


def copy_cache_rows(cfg: ModelConfig, dst: Cache, src: Cache, slots) -> Cache:
    """Write batch rows ``0..k-1`` of ``src`` into rows ``slots`` of
    ``dst`` — the slot-recycling admission primitive: finished rows'
    slots in the continuous-batching pool are overwritten with the
    freshly (batch-)prefilled caches of the next pending requests, one
    scatter per cache leaf for the whole coalesced admission chunk.
    Both caches must share the same geometry (``max_len``/``headroom``);
    the batch axis is leading for unstacked stages and second (after
    the scan-repeat axis) for stacked ones. ``slots`` is a (k,) index
    array (may be traced); out-of-range entries (e.g. ``n_slots``
    padding) are dropped by XLA scatter semantics, so callers can pad
    ``slots`` to a bucketed size without masking.
    """

    def write(d, s, stacked: bool):
        def one(dl, sl):
            if stacked:  # (R, B, ...): scatter along the batch axis
                return dl.at[:, slots].set(sl.astype(dl.dtype))
            return dl.at[slots].set(sl.astype(dl.dtype))

        return jax.tree.map(one, d, s)

    new_stages = []
    for si, (unit, repeats) in enumerate(cfg.scan_stages):
        unit_new = tuple(
            write(dst.stages[si][ui], src.stages[si][ui], repeats > 1)
            for ui in range(len(unit))
        )
        new_stages.append(unit_new)
    lengths = dst.lengths.at[slots].set(src.lengths)
    return Cache(tuple(new_stages), lengths)


def has_recurrent(cfg: ModelConfig) -> bool:
    return any(k in ("rglru", "mlstm", "slstm") for k in cfg.layer_kinds)


def commit_staged_cache(cfg: ModelConfig, cache: Cache, n_commit) -> Cache:
    """Gather staged recurrent states at the acceptance count.

    `cache` came from forward(collect_states=True): recurrent entries
    have an extra per-step dim (B, T+1, ...) — index t = state after t
    committed tokens. `n_commit` (B,) selects per row (0 for frozen
    rows). Attention entries pass through (ring-slot overwrite already
    committed them). This turns the 2-forward recurrent verify into a
    single pass (§Perf beyond-paper: 2× verify compute for SSM/hybrid).
    """
    n_commit = n_commit.astype(jnp.int32)

    def gather(staged, stacked: bool):
        def one(x):
            # x: (B, T+1, ...) or (R, B, T+1, ...)
            ax = 2 if stacked else 1
            idx = n_commit.reshape(
                (1,) * (ax - 1) + (-1, 1) + (1,) * (x.ndim - ax - 1)
            )
            idx = jnp.broadcast_to(
                idx, x.shape[: ax] + (1,) + x.shape[ax + 1 :]
            )
            return jnp.take_along_axis(x, idx, axis=ax).squeeze(ax)

        return jax.tree.map(one, staged)

    new_stages = []
    for si, (unit, repeats) in enumerate(cfg.scan_stages):
        stage_c = cache.stages[si]
        unit_new = []
        for ui, kind in enumerate(unit):
            entry = stage_c[ui]
            if kind in ("attn", "local_attn"):
                unit_new.append(entry)
            else:
                unit_new.append(gather(entry, stacked=repeats > 1))
        new_stages.append(tuple(unit_new))
    return Cache(tuple(new_stages), cache.lengths)


# ---------------------------------------------------------------------------
# logical axes for cache pytrees (mirrors _block_cache structure)
# ---------------------------------------------------------------------------

def _block_cache_axes(cfg: ModelConfig, kind: str, mesh_model: int):
    """Logical-axes tree matching _block_cache's arrays.

    kv layout preference: shard kv_heads over the model axis when it
    divides; otherwise shard the slot (sequence) dim — context-parallel
    decode, XLA inserts the partial-softmax collectives."""
    if kind in ("attn", "local_attn"):
        if mesh_model > 0 and cfg.num_kv_heads % mesh_model == 0:
            kv = ("batch", None, "kv_heads", "head_dim")
            cp = ("batch", None)
        else:
            kv = ("batch", "kv_seq", "kv_heads", "head_dim")
            cp = ("batch", "kv_seq")
        return (kv, kv, cp)
    if kind == "rglru":
        return {
            "h": ("batch", "mlp"),
            "conv": ("batch", None, "mlp"),
        }
    if kind == "mlstm":
        return (
            ("batch", "heads", None, None),
            ("batch", "heads", None),
            ("batch", "heads"),
        )
    if kind == "slstm":
        return tuple(("batch", "mlp") for _ in range(4))
    raise ValueError(kind)


def cache_logical_axes(cfg: ModelConfig, mesh_model: int = 16):
    """Axes pytree for init_cache's Cache (stacked stages get a leading
    'layers' axis)."""
    stages = []
    for unit, repeats in cfg.scan_stages:
        unit_a = tuple(_block_cache_axes(cfg, k, mesh_model) for k in unit)
        if repeats > 1:
            unit_a = jax.tree.map(
                lambda a: ("layers",) + a,
                unit_a,
                is_leaf=lambda x: isinstance(x, tuple)
                and all(isinstance(e, (str, type(None))) for e in x),
            )
        stages.append(unit_a)
    return Cache(tuple(stages), ("batch",))
