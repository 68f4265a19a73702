"""Composable model layers (pure functional JAX).

Every `init_*` returns a pytree whose leaves are `Param(value, axes)` —
the value plus its *logical axis names* — so the sharding layer
(`repro.launch.sharding`) can map logical axes to mesh axes without a
parallel bookkeeping tree. `split_tree` separates values from axes.

Layer kinds (cfg.block_pattern): attn, local_attn, rglru, mlstm, slstm.
All attention layers support three execution modes through one code path:
  * train/prefill: full sequence, causal (+window) mask from positions;
  * cached verify/decode: T-token block appended to a (possibly ring)
    KV cache with absolute-position bookkeeping (`cache_pos`), which
    makes speculative *rollback free*: rejected tokens' slots are simply
    overwritten by the next verify block (see spec_engine).
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.launch import sharding as _sh


class Param:
    """A parameter leaf: array value + static logical axis names.

    Registered as a pytree node with `axes` as aux data so Param trees
    pass through jit/eval_shape (the dry-run builds abstract Param trees
    with ShapeDtypeStruct values and real axis metadata)."""

    __slots__ = ("value", "axes")

    def __init__(self, value: Any, axes: Tuple[Optional[str], ...]):
        self.value = value
        self.axes = tuple(axes)

    def __repr__(self) -> str:
        return f"Param({self.value!r}, axes={self.axes})"


jax.tree_util.register_pytree_node(
    Param,
    lambda p: ((p.value,), p.axes),
    lambda axes, children: Param(children[0], axes),
)


def is_param(x) -> bool:
    return isinstance(x, Param)


def split_tree(tree):
    """(params, axes) from a Param tree."""
    vals = jax.tree.map(lambda p: p.value, tree, is_leaf=is_param)
    axes = jax.tree.map(lambda p: p.axes, tree, is_leaf=is_param)
    return vals, axes


def stack_params(trees):
    """Stack per-layer Param trees along a new leading 'layers' axis."""
    def _stack(*ps):
        return Param(
            jnp.stack([p.value for p in ps], axis=0),
            ("layers",) + ps[0].axes,
        )
    return jax.tree.map(_stack, *trees, is_leaf=is_param)


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------

def _dense_init(key, shape, axes, dtype, scale: Optional[float] = None) -> Param:
    fan_in = shape[0] if len(shape) >= 2 else max(shape[0], 1)
    if len(shape) == 3:  # (in, heads, hd) or (experts, in, out)
        fan_in = shape[0] if axes[0] != "experts" else shape[1]
    s = scale if scale is not None else 1.0 / math.sqrt(fan_in)
    v = (jax.random.normal(key, shape, jnp.float32) * s).astype(dtype)
    return Param(v, axes)


def _zeros(shape, axes, dtype) -> Param:
    return Param(jnp.zeros(shape, dtype), axes)


def _ones(shape, axes, dtype) -> Param:
    return Param(jnp.ones(shape, dtype), axes)


# ---------------------------------------------------------------------------
# normalization
# ---------------------------------------------------------------------------

def init_norm(cfg: ModelConfig):
    p = {"scale": _ones((cfg.d_model,), (None,), jnp.float32)}
    if cfg.norm == "layer":
        p["bias"] = _zeros((cfg.d_model,), (None,), jnp.float32)
    return p


def apply_norm(p, x, cfg: ModelConfig):
    xf = x.astype(jnp.float32)
    if cfg.norm == "layer":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + cfg.norm_eps)
        y = y * p["scale"] + p["bias"]
    else:
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(ms + cfg.norm_eps) * p["scale"]
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# rotary embeddings: standard / partial (chatglm "2d") / M-RoPE (qwen2-vl)
# ---------------------------------------------------------------------------

def _rope_freqs(dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))


def apply_rope(x, positions, cfg: ModelConfig, mrope_positions=None):
    """x: (B, T, H, hd); positions: (B, T) int32 absolute positions.

    * standard: rotate the whole head_dim.
    * partial:  rotate only rope_fraction of head_dim (ChatGLM applies
      RoPE to half the dims — its "2d" scheme — the rest are NoPE).
    * mrope:    3 position streams (t, h, w) own interleaved frequency
      sections of the rotary half (Qwen2-VL §M-RoPE). For text tokens the
      three streams coincide and M-RoPE reduces to standard RoPE.
    """
    if cfg.rope == "none":
        return x
    hd = x.shape[-1]
    rot = int(hd * (cfg.rope_fraction if cfg.rope == "partial" else 1.0))
    rot -= rot % 2
    freqs = _rope_freqs(rot, cfg.rope_theta)  # (rot/2,)
    if cfg.rope == "mrope":
        # mrope_positions: (3, B, T). Each frequency index is owned by one
        # of the (t, h, w) streams according to cfg.mrope_sections.
        if mrope_positions is None:
            mrope_positions = jnp.broadcast_to(
                positions[None], (3,) + positions.shape
            )
        sec = cfg.mrope_sections
        n = rot // 2
        owner = jnp.concatenate([
            jnp.full((sec[0],), 0), jnp.full((sec[1],), 1), jnp.full((sec[2],), 2)
        ])[:n]  # (n,) — which stream owns each frequency
        pos3 = mrope_positions.astype(jnp.float32)  # (3, B, T)
        pos_f = pos3[owner]  # (n, B, T)
        ang = jnp.einsum("nbt,n->btn", pos_f, freqs)  # (B, T, n)
    else:
        ang = positions.astype(jnp.float32)[..., None] * freqs  # (B, T, n)
    cos = jnp.cos(ang)[..., None, :].astype(x.dtype)  # (B, T, 1, n)
    sin = jnp.sin(ang)[..., None, :].astype(x.dtype)
    x_rot, x_pass = x[..., :rot], x[..., rot:]
    x1, x2 = x_rot[..., : rot // 2], x_rot[..., rot // 2 :]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return jnp.concatenate([out, x_pass], axis=-1)


# ---------------------------------------------------------------------------
# attention (GQA, sliding window, cached verify blocks)
# ---------------------------------------------------------------------------

def init_attention(key, cfg: ModelConfig, cross: bool = False):
    ks = jax.random.split(key, 4)
    hd, Hq, Hkv, d = cfg.head_dim, cfg.num_heads, cfg.num_kv_heads, cfg.d_model
    dt = jnp.dtype(cfg.dtype)
    p = {
        "wq": _dense_init(ks[0], (d, Hq, hd), ("embed", "heads", "head_dim"), dt),
        "wk": _dense_init(ks[1], (d, Hkv, hd), ("embed", "kv_heads", "head_dim"), dt),
        "wv": _dense_init(ks[2], (d, Hkv, hd), ("embed", "kv_heads", "head_dim"), dt),
        "wo": _dense_init(ks[3], (Hq, hd, d), ("heads", "head_dim", "embed"), dt),
    }
    if cfg.attn_bias and not cross:
        p["bq"] = _zeros((Hq, hd), ("heads", "head_dim"), dt)
        p["bk"] = _zeros((Hkv, hd), ("kv_heads", "head_dim"), dt)
        p["bv"] = _zeros((Hkv, hd), ("kv_heads", "head_dim"), dt)
    return p


_NEG = -1e30


def _flash_mask(qp, kp, kval, window: int):
    """(B, qc, kc) bool from float position chunks."""
    m = (kp[:, None, :] <= qp[:, :, None]) & (kval[:, None, :] > 0)
    if window > 0:
        m &= kp[:, None, :] > (qp[:, :, None] - window)
    return m


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _flash(q, k, v, qpos, kpos, kval, window, qc, kc):
    """Flash attention with hand-written VJP (O(S) memory fwd AND bwd).

    q: (B, Sq, Hkv, G, hd); k/v: (B, Sk, Hkv, hd); qpos/kpos/kval are
    FLOAT arrays (so custom_vjp cotangents are well-defined zeros).
    Returns out (B, Sq, Hkv, G, hd). Saved residuals: out + lse only —
    the backward recomputes P per (q-chunk, kv-chunk) tile, which is
    what keeps the 64-layer 104B train_4k step inside HBM.
    """
    out, _ = _flash_fwd_impl(q, k, v, qpos, kpos, kval, window, qc, kc)
    return out


def _flash_fwd_impl(q, k, v, qpos, kpos, kval, window, qc, kc):
    B, Sq, Hkv, G, hd = q.shape
    Sk = k.shape[1]
    scale = 1.0 / math.sqrt(hd)
    nq, nk = Sq // qc, Sk // kc
    q_c = jnp.moveaxis(q.reshape(B, nq, qc, Hkv, G, hd), 1, 0)
    qp_c = jnp.moveaxis(qpos.reshape(B, nq, qc), 1, 0)
    k_c = jnp.moveaxis(k.reshape(B, nk, kc, Hkv, hd), 1, 0)
    v_c = jnp.moveaxis(v.reshape(B, nk, kc, Hkv, hd), 1, 0)
    kp_c = jnp.moveaxis(kpos.reshape(B, nk, kc), 1, 0)
    kv_c = jnp.moveaxis(kval.reshape(B, nk, kc), 1, 0)

    def q_step(_, qin):
        q_blk, qp = qin

        def kv_step(carry, kin):
            m, l, acc = carry
            k_blk, v_blk, kp, kok = kin
            s = jnp.einsum(
                "bqkgh,bckh->bkgqc", q_blk, k_blk,
                preferred_element_type=jnp.float32,
            ) * scale
            s = jnp.where(_flash_mask(qp, kp, kok, window)[:, None, None], s, _NEG)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            m_safe = jnp.where(m_new <= _NEG, 0.0, m_new)
            p = jnp.exp(s - m_safe[..., None])
            alpha = jnp.where(m <= _NEG, 0.0, jnp.exp(m - m_safe))
            l = alpha * l + p.sum(-1)
            acc = alpha[..., None] * acc + jnp.einsum(
                "bkgqc,bckh->bkgqh", p.astype(v_blk.dtype), v_blk,
                preferred_element_type=jnp.float32,
            )
            return (m_new, l, acc), None

        init = (
            jnp.full((B, Hkv, G, qc), _NEG, jnp.float32),
            jnp.zeros((B, Hkv, G, qc), jnp.float32),
            jnp.zeros((B, Hkv, G, qc, hd), jnp.float32),
        )
        (m, l, acc), _ = jax.lax.scan(kv_step, init, (k_c, v_c, kp_c, kv_c))
        o = acc / jnp.maximum(l[..., None], 1e-20)
        lse = m + jnp.log(jnp.maximum(l, 1e-20))  # (B,Hkv,G,qc)
        return None, (jnp.moveaxis(o, 3, 1).astype(q.dtype), lse)

    _, (outs, lses) = jax.lax.scan(q_step, None, (q_c, qp_c))
    out = jnp.moveaxis(outs, 0, 1).reshape(B, Sq, Hkv, G, hd)
    lse = jnp.moveaxis(lses, 0, 3).reshape(B, Hkv, G, Sq)
    return out, lse


def _flash_fwd(q, k, v, qpos, kpos, kval, window, qc, kc):
    out, lse = _flash_fwd_impl(q, k, v, qpos, kpos, kval, window, qc, kc)
    return out, (q, k, v, qpos, kpos, kval, out, lse)


def _flash_bwd(window, qc, kc, res, dout):
    q, k, v, qpos, kpos, kval, out, lse = res
    B, Sq, Hkv, G, hd = q.shape
    Sk = k.shape[1]
    scale = 1.0 / math.sqrt(hd)
    nq, nk = Sq // qc, Sk // kc
    # D = rowsum(dO ∘ O)  (B, Hkv, G, Sq)
    Drow = jnp.einsum(
        "bskgh,bskgh->bkgs", dout.astype(jnp.float32), out.astype(jnp.float32)
    )
    q_c = jnp.moveaxis(q.reshape(B, nq, qc, Hkv, G, hd), 1, 0)
    do_c = jnp.moveaxis(dout.reshape(B, nq, qc, Hkv, G, hd), 1, 0)
    qp_c = jnp.moveaxis(qpos.reshape(B, nq, qc), 1, 0)
    lse_c = jnp.moveaxis(lse.reshape(B, Hkv, G, nq, qc), 3, 0)
    D_c = jnp.moveaxis(Drow.reshape(B, Hkv, G, nq, qc), 3, 0)
    k_c = jnp.moveaxis(k.reshape(B, nk, kc, Hkv, hd), 1, 0)
    v_c = jnp.moveaxis(v.reshape(B, nk, kc, Hkv, hd), 1, 0)
    kp_c = jnp.moveaxis(kpos.reshape(B, nk, kc), 1, 0)
    kv_c = jnp.moveaxis(kval.reshape(B, nk, kc), 1, 0)

    def q_step(carry, qin):
        dk_full, dv_full = carry  # (nk, B, kc, Hkv, hd) f32
        q_blk, do_blk, qp, lse_q, D_q = qin

        def kv_step(inner, idx):
            dq_acc, dk_full, dv_full = inner
            k_blk, v_blk, kp, kok = (
                k_c[idx], v_c[idx], kp_c[idx], kv_c[idx]
            )
            s = jnp.einsum(
                "bqkgh,bckh->bkgqc", q_blk, k_blk,
                preferred_element_type=jnp.float32,
            ) * scale
            msk = _flash_mask(qp, kp, kok, window)[:, None, None]
            p = jnp.where(msk, jnp.exp(s - lse_q[..., None]), 0.0)
            dv_blk = jnp.einsum(
                "bkgqc,bqkgh->bckh", p, do_blk.astype(jnp.float32)
            )
            dp = jnp.einsum(
                "bqkgh,bckh->bkgqc", do_blk.astype(jnp.float32),
                v_blk.astype(jnp.float32),
            )
            ds = p * (dp - D_q[..., None]) * scale
            dq_acc = dq_acc + jnp.einsum(
                "bkgqc,bckh->bqkgh", ds, k_blk.astype(jnp.float32)
            )
            dk_blk = jnp.einsum("bkgqc,bqkgh->bckh", ds, q_blk.astype(jnp.float32))
            dk_full = dk_full.at[idx].add(dk_blk)
            dv_full = dv_full.at[idx].add(dv_blk)
            return (dq_acc, dk_full, dv_full), None

        dq0 = jnp.zeros((B, qc, Hkv, G, hd), jnp.float32)
        (dq_blk, dk_full, dv_full), _ = jax.lax.scan(
            kv_step, (dq0, dk_full, dv_full), jnp.arange(nk)
        )
        return (dk_full, dv_full), dq_blk

    dk0 = jnp.zeros((nk, B, kc, Hkv, hd), jnp.float32)
    dv0 = jnp.zeros((nk, B, kc, Hkv, hd), jnp.float32)
    (dk_full, dv_full), dq_chunks = jax.lax.scan(
        q_step, (dk0, dv0), (q_c, do_c, qp_c, lse_c, D_c)
    )
    dq = jnp.moveaxis(dq_chunks, 0, 1).reshape(B, Sq, Hkv, G, hd)
    dk = jnp.moveaxis(dk_full, 0, 1).reshape(B, Sk, Hkv, hd)
    dv = jnp.moveaxis(dv_full, 0, 1).reshape(B, Sk, Hkv, hd)
    zq = jnp.zeros_like(qpos)
    zk = jnp.zeros_like(kpos)
    zv = jnp.zeros_like(kval)
    return (
        dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
        zq, zk, zv,
    )


_flash.defvjp(_flash_fwd, _flash_bwd)


def _flash_attn_train(
    q, k, v, positions, cfg: ModelConfig, *, window: int, valid,
    q_chunk: int = 512, kv_chunk: int = 1024,
):
    """Memory-bounded causal attention for long full-sequence forwards
    (training / prefill). Custom-VJP flash: O(S·hd) residuals instead of
    O(S²) scores. positions: (B, S) absolute (left-pad aware); valid:
    (B, S) key-validity or None. softcap unsupported here (no assigned
    arch trains with softcap)."""
    assert cfg.logit_softcap == 0.0, "flash train path: softcap unsupported"
    B, S, Hq, hd = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    qc = min(q_chunk, S)
    kc = min(kv_chunk, S)
    Sq = ((S + qc - 1) // qc) * qc
    Sk = ((S + kc - 1) // kc) * kc
    qq = jnp.pad(q, ((0, 0), (0, Sq - S), (0, 0), (0, 0)))
    kk = jnp.pad(k, ((0, 0), (0, Sk - S), (0, 0), (0, 0)))
    vv = jnp.pad(v, ((0, 0), (0, Sk - S), (0, 0), (0, 0)))
    posf = positions.astype(jnp.float32)
    qpos = jnp.pad(posf, ((0, 0), (0, Sq - S)), constant_values=-1e30)
    kpos = jnp.pad(posf, ((0, 0), (0, Sk - S)), constant_values=-1e30)
    kval = (
        valid.astype(jnp.float32)
        if valid is not None
        else jnp.ones((B, S), jnp.float32)
    )
    kval = jnp.pad(kval, ((0, 0), (0, Sk - S)))
    qq = qq.reshape(B, Sq, Hkv, G, hd)
    out = _flash(qq, kk, vv, qpos, kpos, kval, window, qc, kc)
    return out[:, :S].reshape(B, S, Hq, hd)


_FLASH_THRESHOLD = 2048


def _attn_core(q, k, v, mask, cfg: ModelConfig):
    """q: (B,T,Hq,hd), k/v: (B,S,Hkv,hd), mask: (B,1,T,S) or (1,1,T,S)."""
    Hq, Hkv = q.shape[2], k.shape[2]
    group = Hq // Hkv
    B, T, _, hd = q.shape
    S = k.shape[1]
    qg = q.reshape(B, T, Hkv, group, hd)
    scores = jnp.einsum(
        "btkgh,bskh->bkgts", qg, k, preferred_element_type=jnp.float32
    ) / math.sqrt(hd)
    if cfg.logit_softcap > 0:
        c = cfg.logit_softcap
        scores = jnp.tanh(scores / c) * c
    # mask: (B, 1, T, S) → broadcast over (B, Hkv, group, T, S)
    scores = jnp.where(mask[:, :, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgts,bskh->btkgh", probs, v)
    return out.reshape(B, T, Hq, hd)


def attention_forward(
    p,
    x,
    cfg: ModelConfig,
    *,
    positions,  # (B, T) absolute positions of the x tokens
    window: int = 0,  # 0 = full
    kv_cache: Optional[Tuple] = None,  # (k, v, cache_pos) or None
    valid=None,  # (B, T) bool — False rows/tokens are pads / frozen
    bidirectional: bool = False,
    mrope_positions=None,
    cross_kv: Optional[Tuple] = None,  # (k, v, valid_mask) for cross-attn
    attn_impl: str = "xla",  # xla | pallas (cached path only)
    layer=None,  # index into a stacked kv_cache's leading layer axis
):
    """Returns (y, new_kv_cache).

    Cached path: kv_cache = (k, v, cache_pos) with k/v (B, S+1, Hkv, hd)
    and cache_pos (B, S+1) int32 (-1 = empty). Slot S is a *trash slot*:
    invalid tokens write there and it is never read (its cache_pos stays
    masked). Valid tokens write at ring slot ``pos % S`` *before* the
    attention read, so stale (rejected-draft) entries are overwritten —
    speculative rollback is free for attention layers. For windowed
    caches S = window + headroom (headroom >= max draft block) so a
    multi-token block never clobbers in-window entries.

    With ``layer`` given, kv_cache is the whole stacked cache of a
    scanned stage, (R, B, S+1, ...), carried through the layer scan: the
    block's K/V and positions are scattered at ``[layer, row, slot]``,
    the attention reads that layer, and the stacked triple comes back.
    Only the block's slots are written, so the update happens in place
    in the (donated) pool instead of rewriting a whole layer per step.
    """
    B, T, _ = x.shape
    q = jnp.einsum("btd,dhk->bthk", x, p["wq"])
    if "bq" in p:
        q = q + p["bq"]
    if cross_kv is not None:
        k, v, kvalid = cross_kv
        mask = jnp.broadcast_to(
            kvalid[:, None, None, :], (B, 1, T, k.shape[1])
        )
        out = _attn_core(q, k, v, mask, cfg)
        y = jnp.einsum("bthk,hkd->btd", out, p["wo"])
        return y, None
    k = jnp.einsum("btd,dhk->bthk", x, p["wk"])
    v = jnp.einsum("btd,dhk->bthk", x, p["wv"])
    if "bk" in p:
        k = k + p["bk"]
        v = v + p["bv"]
    q = apply_rope(q, positions, cfg, mrope_positions)
    k = apply_rope(k, positions, cfg, mrope_positions)

    if kv_cache is None:
        # Full-sequence (train / prefill compute).
        if not bidirectional and T >= _FLASH_THRESHOLD:
            out = _flash_attn_train(
                q, k, v, positions, cfg, window=window, valid=valid
            )
        else:
            qpos = positions[:, :, None]  # (B,T,1)
            kpos = positions[:, None, :]  # (B,1,T)
            if bidirectional:
                mask = jnp.ones((B, T, T), bool)
            else:
                mask = kpos <= qpos
                if window > 0:
                    mask &= kpos > qpos - window
            if valid is not None:
                mask &= valid[:, None, :]
            out = _attn_core(q, k, v, mask[:, None], cfg)
        y = jnp.einsum("bthk,hkd->btd", out, p["wo"])
        return y, (k, v, positions)

    ck, cv, cpos = kv_cache
    S = ck.shape[-3] - 1  # last slot is the trash slot
    if valid is None:
        slots = positions % S
        pos_write = positions
    else:
        slots = jnp.where(valid, positions % S, S)
        pos_write = jnp.where(valid, positions, -1)
    at = (jnp.arange(B)[:, None], slots)
    if layer is not None:
        at = (layer,) + at
    ck = ck.at[at].set(k.astype(ck.dtype))
    cv = cv.at[at].set(v.astype(cv.dtype))
    cpos = cpos.at[at].set(pos_write)
    lk, lv, lpos = (ck, cv, cpos) if layer is None else (
        ck[layer], cv[layer], cpos[layer]
    )
    if attn_impl == "pallas":
        from repro.kernels.spec_verify import ops as sv_ops  # lazy

        out = sv_ops.spec_verify_attention(
            q, lk, lv, lpos, positions, window=window,
            softcap=cfg.logit_softcap,
        )
    else:
        qpos = positions[:, :, None]  # (B,T,1)
        kpos = lpos[:, None, :]  # (B,1,S+1)
        mask = (kpos >= 0) & (kpos <= qpos)
        if window > 0:
            mask &= kpos > qpos - window
        out = _attn_core(
            q, lk.astype(q.dtype), lv.astype(q.dtype), mask[:, None], cfg
        )
    y = jnp.einsum("bthk,hkd->btd", out, p["wo"])
    return y, (ck, cv, cpos)


def init_kv_cache(
    cfg: ModelConfig, batch: int, max_len: int, window: int = 0,
    headroom: int = 64, slot_multiple: int = 1,
):
    """Zero cache for one attention layer (+1 trash slot); ring-sized
    (window + headroom) when windowed. ``slot_multiple`` rounds the slot
    count up (e.g. to 256) so the slot dim can shard over the mesh model
    axis when kv_heads cannot; extra slots are never written (the ring
    modulus is ``slots - 1`` >= the required retention) and stay masked
    (cache_pos = -1)."""
    S = min(max_len, window + headroom) if window > 0 else max_len
    slots = S + 1
    if slot_multiple > 1:
        slots = ((slots + slot_multiple - 1) // slot_multiple) * slot_multiple
    hd, Hkv = cfg.head_dim, cfg.num_kv_heads
    dt = jnp.dtype(cfg.dtype)
    return (
        jnp.zeros((batch, slots, Hkv, hd), dt),
        jnp.zeros((batch, slots, Hkv, hd), dt),
        jnp.full((batch, slots), -1, jnp.int32),
    )


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def init_mlp(key, cfg: ModelConfig, d_ff: Optional[int] = None):
    d_ff = d_ff or cfg.d_ff
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 3)
    d = cfg.d_model
    if cfg.mlp == "swiglu":
        return {
            "wi": _dense_init(ks[0], (d, d_ff), ("embed", "mlp"), dt),
            "wg": _dense_init(ks[1], (d, d_ff), ("embed", "mlp"), dt),
            "wo": _dense_init(ks[2], (d_ff, d), ("mlp", "embed"), dt),
        }
    return {
        "wi": _dense_init(ks[0], (d, d_ff), ("embed", "mlp"), dt),
        "wo": _dense_init(ks[2], (d_ff, d), ("mlp", "embed"), dt),
    }


def apply_mlp(p, x, cfg: ModelConfig):
    h = jnp.einsum("btd,df->btf", x, p["wi"])
    if cfg.mlp == "swiglu":
        g = jnp.einsum("btd,df->btf", x, p["wg"])
        h = jax.nn.silu(g) * h
    else:
        h = jax.nn.gelu(h)
    return jnp.einsum("btf,fd->btd", h, p["wo"])


# ---------------------------------------------------------------------------
# Mixture of Experts (top-k, capacity-based GShard-style dispatch)
# ---------------------------------------------------------------------------

def init_moe(key, cfg: ModelConfig):
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 5)
    d, f, E = cfg.d_model, cfg.d_ff, cfg.num_experts
    p = {
        "router": _dense_init(ks[0], (d, E), ("embed", None), jnp.float32),
        "wi": _dense_init(ks[1], (E, d, f), ("experts", "embed", "mlp"), dt),
        "wg": _dense_init(ks[2], (E, d, f), ("experts", "embed", "mlp"), dt),
        "wo": _dense_init(ks[3], (E, f, d), ("experts", "mlp", "embed"), dt),
    }
    if cfg.moe_dense_residual:
        p["dense"] = init_mlp(ks[4], cfg)
    return p


def apply_moe(p, x, cfg: ModelConfig):
    """Returns (y, aux_loss). Scatter/gather capacity-based dispatch:
    tokens are scattered into per-expert capacity buffers (O(N·d) data
    movement — under expert sharding XLA lowers this to the all-to-all
    of real expert parallelism), experts run batched matmuls, outputs
    gather back with top-k gate weights. Overflow beyond capacity drops
    (Switch/GShard semantics). The earlier one-hot einsum dispatch cost
    N·E·cap·d FLOPs — 10-15× the expert compute itself at Mixtral scale
    (caught by the roofline's useful-FLOPs ratio) — hence this path.
    """
    B, T, d = x.shape
    E, K = cfg.num_experts, cfg.experts_per_token
    N = B * T
    xt = x.reshape(N, d)
    logits = jnp.einsum("nd,de->ne", xt.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)  # (N, E)
    gate_vals, gate_idx = jax.lax.top_k(probs, K)  # (N, K)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)
    cap = max(1, int(cfg.capacity_factor * N * K / E))
    # slot of each (token, k) within its expert's capacity buffer
    onehot = jax.nn.one_hot(gate_idx, E, dtype=jnp.int32).reshape(N * K, E)
    pos = (jnp.cumsum(onehot, axis=0) - onehot)  # (N*K, E)
    slot = (pos * onehot).sum(-1)  # (N*K,)
    e_flat = gate_idx.reshape(N * K)
    keep = slot < cap
    # scatter tokens into (E*cap [+1 trash], d)
    dest = jnp.where(keep, e_flat * cap + slot, E * cap)
    buf = jnp.zeros((E * cap + 1, d), xt.dtype)
    buf = buf.at[dest].set(jnp.repeat(xt, K, axis=0))
    xin = _sh.constrain_moe(buf[: E * cap].reshape(E, cap, d))
    h = _sh.constrain_moe(jnp.einsum("ecd,edf->ecf", xin, p["wi"]))
    g = jnp.einsum("ecd,edf->ecf", xin, p["wg"])
    h = jax.nn.silu(g) * h
    eout = _sh.constrain_moe(jnp.einsum("ecf,efd->ecd", h, p["wo"]))
    eout = eout.reshape(E * cap, d)
    eout = jnp.concatenate([eout, jnp.zeros((1, d), eout.dtype)], axis=0)
    # gather back, weight by gates (dropped tokens contribute 0)
    y_flat = eout[dest] * (gate_vals.reshape(N * K, 1).astype(x.dtype))
    y = y_flat.reshape(N, K, d).sum(1).reshape(B, T, d)
    if cfg.moe_dense_residual and "dense" in p:
        y = y + apply_mlp(p["dense"], x, cfg)
    # Switch-style load-balance aux loss
    me = probs.mean(0)  # (E,)
    ce = jax.nn.one_hot(gate_idx[:, 0], E, dtype=jnp.float32).mean(0)
    aux = E * jnp.sum(me * ce) * cfg.router_aux_weight
    return y, aux


# ---------------------------------------------------------------------------
# RG-LRU recurrent block (RecurrentGemma, arXiv:2402.19427)
# ---------------------------------------------------------------------------

def init_rglru(key, cfg: ModelConfig):
    dt = jnp.dtype(cfg.dtype)
    d, w = cfg.d_model, cfg.rnn_width
    ks = jax.random.split(key, 6)
    # Λ init so that a = sigmoid(Λ)^(c·r) starts near 0.9..0.999
    lam = jnp.log(jnp.expm1(jnp.linspace(0.9, 0.999, w) ** (1.0 / 8.0)))
    return {
        "wx": _dense_init(ks[0], (d, w), ("embed", "mlp"), dt),  # branch in
        "wy": _dense_init(ks[1], (d, w), ("embed", "mlp"), dt),  # gate branch
        "wo": _dense_init(ks[2], (w, d), ("mlp", "embed"), dt),
        "conv": _dense_init(ks[3], (cfg.conv_width, w), (None, "mlp"), dt, scale=0.5),
        "w_a": _dense_init(ks[4], (w,), ("mlp",), jnp.float32, scale=1.0),
        "w_i": _dense_init(ks[5], (w,), ("mlp",), jnp.float32, scale=1.0),
        "lam": Param(lam.astype(jnp.float32), ("mlp",)),
    }


def _gate_masks(B: int, T: int, update_mask, commit_upto):
    """(upd (T,B), com (T,B)) gating masks for the recurrent scans.

    * ``update_mask`` (B,T) gates the *dynamic* state — False for pads
      (left-padded prefill) and for frozen (finished) rows.
    * ``commit_upto`` (B,) gates the *committed* state: step t commits
      iff t < commit_upto (speculative-verify acceptance prefix). None
      commits every updated step (train / prefill).
    """
    upd = (
        jnp.ones((T, B), bool)
        if update_mask is None
        else jnp.transpose(update_mask)
    )
    if commit_upto is None:
        com = upd
    else:
        com = upd & (jnp.arange(T)[:, None] < commit_upto[None, :])
    return upd, com


def _rglru_scan(x, a_gate, i_gate, lam, h0, upd, com):
    """h_t = a_t h_{t-1} + sqrt(1-a_t^2) (i_t * x_t); a_t = a^(c r_t).

    Dual-carry semantics for speculative verify: the *dynamic* state
    advances through every updated step (so each draft position's output
    sees the correct recurrent context), while the *committed* state
    stops at the acceptance prefix — it becomes the new cache if later
    draft tokens are rejected. Returns (h_seq (B,T,W), h_committed).
    """
    c = 8.0
    a_base = jnp.log(jax.nn.sigmoid(lam))  # log a  (negative)
    log_a = c * a_gate * a_base[None, None, :]  # (B,T,W), r_t = sigmoid(..)
    a = jnp.exp(log_a)
    gated_x = i_gate * x
    mult = jnp.sqrt(jnp.clip(1.0 - jnp.exp(2.0 * log_a), 1e-9, 1.0))

    def step(carry, inp):
        dyn, comm = carry
        a_t, gx_t, m_t, u_t, c_t = inp
        new = a_t * dyn + m_t * gx_t
        dyn = jnp.where(u_t[:, None], new, dyn)
        comm = jnp.where(c_t[:, None], dyn, comm)
        return (dyn, comm), dyn

    xs = (
        jnp.moveaxis(a, 1, 0),
        jnp.moveaxis(gated_x, 1, 0),
        jnp.moveaxis(mult, 1, 0),
        upd,
        com,
    )
    (_, h_com), hs = jax.lax.scan(step, (h0, h0), xs)
    return jnp.moveaxis(hs, 0, 1), h_com


def apply_rglru(
    p, x, cfg: ModelConfig, state=None, conv_state=None,
    update_mask=None, commit_upto=None, use_kernel: bool = False,
    collect: bool = False,
):
    """RecurrentGemma recurrent block. state: (B, W) fp32; conv_state:
    (B, conv_width-1, W). Returns (y, new_state, new_conv_state).

    collect=True (single-pass speculative verify): instead of one
    committed state, returns STAGED per-step candidates — new_state
    (B, T+1, W) and new_conv_state (B, T+1, cw-1, W) where index t is
    the state after t updates; the engine gathers at the acceptance
    count after verification (model.commit_staged_cache)."""
    B, T, _ = x.shape
    W = cfg.rnn_width
    gate_in = jnp.einsum("btd,dw->btw", x, p["wy"])
    xr = jnp.einsum("btd,dw->btw", x, p["wx"])
    if update_mask is not None:
        # pads / frozen rows contribute nothing to conv or recurrence
        xr = jnp.where(update_mask[:, :, None], xr, 0.0)
    # temporal conv with cached left context
    cw = cfg.conv_width
    if conv_state is None:
        conv_state = jnp.zeros((B, cw - 1, W), xr.dtype)
    xr_pad = jnp.concatenate([conv_state, xr], axis=1)  # (B, T+cw-1, W)
    if collect and cw > 1:
        # staged conv contexts: candidate t = xr_pad[:, t : t+cw-1]
        new_conv_state = jnp.stack(
            [xr_pad[:, t : t + cw - 1] for t in range(T + 1)], axis=1
        )
    elif cw > 1:
        if commit_upto is None:
            new_conv_state = xr_pad[:, -(cw - 1):]
        else:
            # committed conv context = the cw-1 inputs preceding the
            # accepted boundary: xr_pad[:, upto : upto+cw-1]
            idx = commit_upto[:, None] + jnp.arange(cw - 1)[None]
            new_conv_state = jnp.take_along_axis(
                xr_pad, idx[:, :, None], axis=1
            )
    else:
        new_conv_state = conv_state
    xc = sum(
        xr_pad[:, i : i + T] * p["conv"][i][None, None, :] for i in range(cw)
    )
    xf = xc.astype(jnp.float32)
    r = jax.nn.sigmoid(xf * p["w_a"])  # recurrence gate r_t
    i = jax.nn.sigmoid(xf * p["w_i"])  # input gate i_t
    if state is None:
        state = jnp.zeros((B, W), jnp.float32)
    upd, com = _gate_masks(B, T, update_mask, commit_upto)
    if use_kernel and update_mask is None and commit_upto is None and not collect:
        from repro.kernels.rglru import ops as rglru_ops  # lazy import

        hs, h_fin = rglru_ops.rglru_scan(xf, r, i, p["lam"], state)
    else:
        hs, h_fin = _rglru_scan(xf, r, i, p["lam"], state, upd, com)
    y = hs.astype(x.dtype) * jax.nn.gelu(gate_in)
    y = jnp.einsum("btw,wd->btd", y, p["wo"])
    if collect:
        # rglru's per-step state IS hs (with update gating folded in by
        # the scan's upd mask); prepend the initial state
        h_fin = jnp.concatenate([state[:, None], hs], axis=1)
    return y, h_fin, new_conv_state


# ---------------------------------------------------------------------------
# xLSTM blocks (arXiv:2405.04517): mLSTM (matrix memory) and sLSTM
# ---------------------------------------------------------------------------

def init_mlstm(key, cfg: ModelConfig):
    dt = jnp.dtype(cfg.dtype)
    d, w, H = cfg.d_model, cfg.rnn_width, max(cfg.num_heads, 1)
    ks = jax.random.split(key, 7)
    return {
        "wq": _dense_init(ks[0], (d, w), ("embed", "mlp"), dt),
        "wk": _dense_init(ks[1], (d, w), ("embed", "mlp"), dt),
        "wv": _dense_init(ks[2], (d, w), ("embed", "mlp"), dt),
        "wi": _dense_init(ks[3], (d, H), ("embed", None), jnp.float32, scale=0.1),
        "wf": _dense_init(ks[4], (d, H), ("embed", None), jnp.float32, scale=0.1),
        "bf": Param(jnp.ones((H,), jnp.float32) * 3.0, (None,)),
        "wo_gate": _dense_init(ks[5], (d, w), ("embed", "mlp"), dt),
        "wo": _dense_init(ks[6], (w, d), ("mlp", "embed"), dt),
    }


def apply_mlstm(
    p, x, cfg: ModelConfig, state=None, update_mask=None, commit_upto=None,
    collect: bool = False,
):
    """mLSTM with exponential gating and matrix memory.

    state = (C (B,H,hd,hd), n (B,H,hd), m (B,H)) fp32 stabilizer.
    Sequential lax.scan over time (TPU-friendly: per-step outer products).
    collect=True returns staged per-step states (B, T+1, ...) for the
    single-pass speculative commit (see apply_rglru docstring).
    """
    B, T, d = x.shape
    H = max(cfg.num_heads, 1)
    W = cfg.rnn_width
    hd = W // H
    q = jnp.einsum("btd,dw->btw", x, p["wq"]).reshape(B, T, H, hd)
    k = jnp.einsum("btd,dw->btw", x, p["wk"]).reshape(B, T, H, hd) / math.sqrt(hd)
    v = jnp.einsum("btd,dw->btw", x, p["wv"]).reshape(B, T, H, hd)
    i_pre = jnp.einsum("btd,dh->bth", x.astype(jnp.float32), p["wi"])
    f_pre = jnp.einsum("btd,dh->bth", x.astype(jnp.float32), p["wf"]) + p["bf"]
    if state is None:
        state = (
            jnp.zeros((B, H, hd, hd), jnp.float32),
            jnp.zeros((B, H, hd), jnp.float32),
            jnp.full((B, H), -jnp.inf, jnp.float32),
        )

    def _sel(flag, new, old):
        """Broadcast (B,) bool over trailing dims of new/old."""
        f = flag.reshape((-1,) + (1,) * (new.ndim - 1))
        return jnp.where(f, new, old)

    def step(carry, inp):
        (C, n, m), com = carry
        q_t, k_t, v_t, i_t, f_t, u_t, c_t = inp
        logf = jax.nn.log_sigmoid(f_t)
        m_new = jnp.maximum(logf + m, i_t)
        m_new = jnp.where(jnp.isfinite(m_new), m_new, i_t)
        fg = jnp.where(jnp.isfinite(m), jnp.exp(logf + m - m_new), 0.0)
        ig = jnp.exp(i_t - m_new)
        C_new = fg[..., None, None] * C + ig[..., None, None] * (
            k_t[..., :, None] * v_t[..., None, :]
        )
        n_new = fg[..., None] * n + ig[..., None] * k_t
        qn = jnp.einsum("bhk,bhk->bh", q_t.astype(jnp.float32), n_new)
        denom = jnp.maximum(jnp.abs(qn), jnp.exp(-m_new))[..., None]
        h_t = jnp.einsum("bhk,bhkv->bhv", q_t.astype(jnp.float32), C_new) / denom
        dyn = (
            _sel(u_t, C_new, C), _sel(u_t, n_new, n), _sel(u_t, m_new, m)
        )
        com = tuple(_sel(c_t, d, o) for d, o in zip(dyn, com))
        return (dyn, com), ((h_t, dyn) if collect else h_t)

    upd, com_m = _gate_masks(B, T, update_mask, commit_upto)
    xs = (
        jnp.moveaxis(q, 1, 0), jnp.moveaxis(k, 1, 0), jnp.moveaxis(v, 1, 0),
        jnp.moveaxis(i_pre, 1, 0), jnp.moveaxis(f_pre, 1, 0), upd, com_m,
    )
    (_, new_state), ys = jax.lax.scan(step, (state, state), xs)
    if collect:
        hs, staged = ys
        new_state = jax.tree.map(
            lambda s0, ss: jnp.concatenate(
                [s0[:, None], jnp.moveaxis(ss, 0, 1)], axis=1
            ),
            state, staged,
        )
    else:
        hs = ys
    h = jnp.moveaxis(hs, 0, 1).reshape(B, T, W).astype(x.dtype)
    gate = jax.nn.silu(jnp.einsum("btd,dw->btw", x, p["wo_gate"]))
    y = jnp.einsum("btw,wd->btd", h * gate, p["wo"])
    return y, new_state


def init_slstm(key, cfg: ModelConfig):
    dt = jnp.dtype(cfg.dtype)
    d, w, H = cfg.d_model, cfg.rnn_width, max(cfg.num_heads, 1)
    hd = w // H
    ks = jax.random.split(key, 6)
    return {
        "wz": _dense_init(ks[0], (d, w), ("embed", "mlp"), dt),
        "wi": _dense_init(ks[1], (d, w), ("embed", "mlp"), jnp.float32, scale=0.05),
        "wf": _dense_init(ks[2], (d, w), ("embed", "mlp"), jnp.float32, scale=0.05),
        "wo_g": _dense_init(ks[3], (d, w), ("embed", "mlp"), dt),
        # head-wise recurrent kernel (block-diagonal R)
        "r": _dense_init(ks[4], (H, hd, hd), (None, None, None), jnp.float32, scale=0.2),
        "bf": Param(jnp.ones((w,), jnp.float32) * 2.0, ("mlp",)),
        "wo": _dense_init(ks[5], (w, d), ("mlp", "embed"), dt),
    }


def apply_slstm(
    p, x, cfg: ModelConfig, state=None, update_mask=None, commit_upto=None,
    collect: bool = False,
):
    """sLSTM with scalar memory, exponential gating, head-wise recurrence.

    state = (c, n, h, m) each (B, W) fp32. collect=True returns staged
    per-step states (B, T+1, W) for the single-pass speculative commit.
    """
    B, T, d = x.shape
    H = max(cfg.num_heads, 1)
    W = cfg.rnn_width
    hd = W // H
    z_in = jnp.einsum("btd,dw->btw", x, p["wz"]).astype(jnp.float32)
    i_in = jnp.einsum("btd,dw->btw", x.astype(jnp.float32), p["wi"])
    f_in = jnp.einsum("btd,dw->btw", x.astype(jnp.float32), p["wf"]) + p["bf"]
    o_in = jnp.einsum("btd,dw->btw", x, p["wo_g"]).astype(jnp.float32)
    if state is None:
        state = tuple(jnp.zeros((B, W), jnp.float32) for _ in range(3)) + (
            jnp.full((B, W), -jnp.inf, jnp.float32),
        )

    R = p["r"]  # (H, hd, hd)

    def step(carry, inp):
        (c, n, h, m), com = carry
        z_t, i_t, f_t, o_t, u_t, c_t = inp
        hr = h.reshape(B, H, hd)
        rec = jnp.einsum("bhk,hkj->bhj", hr, R).reshape(B, W)
        z = jnp.tanh(z_t + rec)
        logf = jax.nn.log_sigmoid(f_t)
        m_new = jnp.maximum(logf + m, i_t)
        m_new = jnp.where(jnp.isfinite(m_new), m_new, i_t)
        fg = jnp.where(jnp.isfinite(m), jnp.exp(logf + m - m_new), 0.0)
        ig = jnp.exp(i_t - m_new)
        c_new = fg * c + ig * z
        n_new = fg * n + ig
        h_new = jax.nn.sigmoid(o_t) * c_new / jnp.maximum(n_new, 1e-6)
        uv = u_t[:, None]
        dyn = tuple(
            jnp.where(uv, d, o)
            for d, o in zip((c_new, n_new, h_new, m_new), (c, n, h, m))
        )
        cv = c_t[:, None]
        com = tuple(jnp.where(cv, d, o) for d, o in zip(dyn, com))
        return (dyn, com), ((dyn[2], dyn) if collect else dyn[2])

    upd, com_m = _gate_masks(B, T, update_mask, commit_upto)
    xs = (
        jnp.moveaxis(z_in, 1, 0), jnp.moveaxis(i_in, 1, 0),
        jnp.moveaxis(f_in, 1, 0), jnp.moveaxis(o_in, 1, 0), upd, com_m,
    )
    (_, new_state), ys = jax.lax.scan(step, (state, state), xs)
    if collect:
        hs, staged = ys
        new_state = jax.tree.map(
            lambda s0, ss: jnp.concatenate(
                [s0[:, None], jnp.moveaxis(ss, 0, 1)], axis=1
            ),
            state, staged,
        )
    else:
        hs = ys
    h = jnp.moveaxis(hs, 0, 1).astype(x.dtype)
    y = jnp.einsum("btw,wd->btd", h, p["wo"])
    return y, new_state
