"""Plain float32 forward of the dense GQA decoders in the benchmark,
written from the published model descriptions, for the comparison that
decides a run's ``correct``.

Qwen2 (arXiv:2407.10671; HF ``Qwen2ForCausalLM``) and ChatGLM3
(THUDM ChatGLM3-6B, ``modeling_chatglm.py``) share one block:

    h = RMSNorm(x);  q, k, v = h Wq + bq, h Wk + bk, h Wv + bv
    q, k = RoPE(q), RoPE(k)            (grouped KV heads, causal)
    x = x + softmax(q k^T / sqrt(head_dim)) v Wo
    x = x + (silu(RMSNorm(x) Wg) * (RMSNorm(x) Wi)) Wdown
    logits = RMSNorm(x) E^T   (tied, Qwen2)  or  RMSNorm(x) W_head

Qwen2 rotates the whole head dimension with rotate-half pairs. ChatGLM3
rotates only the first half of each head (``rope_dims`` = head_dim / 2)
and pairs neighbouring dimensions (0,1), (2,3), ...; this reference, as
the program, pairs the two halves of the rotated part instead. The two
differ by a fixed permutation of the q and k columns, which random
weights cannot tell apart. That is the one departure.

Everything is float32 under ``jax.default_matmul_precision("highest")``.
The forward runs layer by layer over a batch of right-padded sequences
(causal attention makes right padding inert), so it fits on the chip
after the program's state is freed. Weights are read from the
benchmark's own generator (``weights.py``), never from the program.

The control (``quant="fp8"``) is the same forward with every linear
layer computed in float8 e4m3: weights scaled per output channel and
activations per token, both rounded to e4m3, and the key/value cache
stored in e4m3; products accumulate in float32.
"""

from __future__ import annotations

import functools
import math

import numpy as np

E4M3_MAX = 448.0


def shape_of(spec) -> dict:
    """The reference's view of a configuration file: published keys of
    Qwen2 (``num_hidden_layers``...) or ChatGLM (``num_layers``...)."""
    c = spec["config"]
    d = c["hidden_size"]
    heads = c["num_attention_heads"]
    head_dim = c.get("kv_channels", c.get("head_dim", d // heads))
    glm = "num_layers" in c
    return {
        "layers": c["num_layers"] if glm else c["num_hidden_layers"],
        "d": d,
        "heads": heads,
        "kv_heads": c["multi_query_group_num"] if glm
        else c["num_key_value_heads"],
        "head_dim": head_dim,
        "ff": c["ffn_hidden_size"] if glm else c["intermediate_size"],
        "vocab": c["padded_vocab_size"] if glm else c["vocab_size"],
        "eps": c["layernorm_epsilon"] if glm else c["rms_norm_eps"],
        "rope_theta": 10000.0 * c.get("rope_ratio", 1) if glm
        else c["rope_theta"],
        "rope_dims": head_dim // 2 if glm else head_dim,
        "tied": bool(c.get("tie_word_embeddings", False)),
        "qkv_bias": bool(c["add_qkv_bias"]) if glm else True,
    }


def block_weights(params) -> dict:
    """Every layer's weights, stacked on a leading layer axis, out of the
    parameter tree the benchmark generates (the program's layout: one
    scanned stage of attention blocks)."""
    blk = params["stages"][0][0]
    a, m = blk["attn"], blk["mlp"]
    w = {
        "norm": blk["norm"]["scale"],
        "wq": a["wq"], "wk": a["wk"], "wv": a["wv"], "wo": a["wo"],
        "mlp_norm": blk["mlp_norm"]["scale"],
        "wi": m["wi"], "wg": m["wg"], "wd": m["wo"],
    }
    if "bq" in a:
        w.update(bq=a["bq"], bk=a["bk"], bv=a["bv"])
    return w


def _e4m3(x):
    """Round float32 ``x`` to the nearest float8 e4m3 value (3 mantissa
    bits, normal exponents from -6, subnormal step 2^-9, saturating at
    448), in float32 arithmetic so any backend computes it alike."""
    import jax.numpy as jnp

    a = jnp.abs(x)
    e = jnp.floor(jnp.log2(jnp.maximum(a, 2.0 ** -9)))
    step = jnp.exp2(jnp.maximum(e, -6.0) - 3.0)
    q = jnp.round(a / step) * step
    return jnp.sign(x) * jnp.minimum(q, E4M3_MAX)


def _q8(x, axis):
    """Float8 e4m3 with one scale per slice along ``axis``."""
    import jax.numpy as jnp

    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / E4M3_MAX
    s = jnp.where(s > 0, s, 1.0)
    return _e4m3(x / s) * s


def _rms(x, scale, eps):
    import jax.numpy as jnp

    return x * jnp.reciprocal(jnp.sqrt(jnp.mean(x * x, -1, keepdims=True)
                                       + eps)) * scale


def _rope(x, theta, dims):
    """Rotate the first ``dims`` of each head (rotate-half pairing);
    x: (B, T, H, hd), positions 0..T-1."""
    import jax.numpy as jnp

    T = x.shape[1]
    inv = 1.0 / theta ** (jnp.arange(0, dims, 2, dtype=jnp.float32) / dims)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    xr, xp = x[..., :dims], x[..., dims:]
    x1, x2 = xr[..., : dims // 2], xr[..., dims // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, xp],
                           axis=-1)


@functools.lru_cache(maxsize=None)
def _layer_fn(shape_items: tuple, quant: str):
    import jax
    import jax.numpy as jnp

    s = dict(shape_items)
    H, Hk, hd = s["heads"], s["kv_heads"], s["head_dim"]
    f8 = quant == "fp8"

    def lin(x, w):  # x (..., d_in), w (d_in, ...out)
        if f8:
            x = _q8(x, -1)
            w = _q8(w, 0)  # one scale per output channel
        return jnp.tensordot(x, w, axes=1)

    def layer(x, w_all, li):
        w = jax.tree.map(lambda a: a[li].astype(jnp.float32), w_all)
        B, T, _ = x.shape
        h = _rms(x, w["norm"], s["eps"])
        q, k, v = lin(h, w["wq"]), lin(h, w["wk"]), lin(h, w["wv"])
        if "bq" in w:
            q, k, v = q + w["bq"], k + w["bk"], v + w["bv"]
        q = _rope(q, s["rope_theta"], s["rope_dims"])
        k = _rope(k, s["rope_theta"], s["rope_dims"])
        if f8:
            k, v = _q8(k, -1), _q8(v, -1)
        qg = q.reshape(B, T, Hk, H // Hk, hd)
        sc = jnp.einsum("btkgh,bskh->bkgts", qg, k) / math.sqrt(hd)
        causal = jnp.tril(jnp.ones((T, T), bool))
        sc = jnp.where(causal, sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        o = jnp.einsum("bkgts,bskh->btkgh", p, v).reshape(B, T, H, hd)
        if f8:
            o = _q8(o.reshape(B, T, H * hd), -1).reshape(B, T, H, hd)
            wo = _q8(w["wo"].reshape(H * hd, -1), 0).reshape(w["wo"].shape)
        else:
            wo = w["wo"]
        x = x + jnp.einsum("bthk,hkd->btd", o, wo)
        h = _rms(x, w["mlp_norm"], s["eps"])
        g = jax.nn.silu(lin(h, w["wg"])) * lin(h, w["wi"])
        return x + lin(g, w["wd"])

    return jax.jit(layer)


@functools.lru_cache(maxsize=None)
def _head_fn(shape_items: tuple, quant: str):
    """(hidden rows (N, d), final norm, head (d, V)) -> (best logit,
    logit of the given token, argmax) per row, without keeping (N, V)."""
    import jax
    import jax.numpy as jnp

    s = dict(shape_items)
    f8 = quant == "fp8"

    def head(x, norm, w, tok):
        x = _rms(x, norm.astype(jnp.float32), s["eps"])
        w = w.astype(jnp.float32)
        if f8:
            x, w = _q8(x, -1), _q8(w, 0)
        lg = x @ w
        return (jnp.max(lg, -1), jnp.take_along_axis(lg, tok[:, None], 1)[:, 0],
                jnp.argmax(lg, -1).astype(jnp.int32))

    return jax.jit(head)


def hidden_states(shape, params, seqs, *, quant: str = "none",
                  bucket: int = 256):
    """Final-layer hidden states (before the final norm) of right-padded
    ``seqs`` (lists of token ids): ``(B, T_pad, d)`` float32."""
    import jax
    import jax.numpy as jnp

    T = max(len(q) for q in seqs)
    T = -(-T // bucket) * bucket
    toks = np.zeros((len(seqs), T), np.int32)
    for i, q in enumerate(seqs):
        toks[i, : len(q)] = q
    items = tuple(sorted(shape.items()))
    with jax.default_matmul_precision("highest"):
        x = params["embed"][:shape["vocab"]][jnp.asarray(toks)].astype(
            jnp.float32)
        fn = _layer_fn(items, quant)
        w_all = block_weights(params)
        for layer in range(shape["layers"]):
            x = fn(x, w_all, jnp.int32(layer))
    return x


def head_matrix(shape, params):
    """(d, V) output matrix: the embedding's transpose when tied."""
    if shape["tied"]:
        return params["embed"][: shape["vocab"]].T
    return params["lm_head"][:, : shape["vocab"]]


def _rows_logits(shape, params, hid, pos, tokens, quant, rows):
    """(best, logit of ``tokens``, argmax) at hidden rows ``pos``."""
    import jax
    import jax.numpy as jnp

    rows_h = hid[jnp.asarray(pos[:, 0]), jnp.asarray(pos[:, 1])]
    head = _head_fn(tuple(sorted(shape.items())), quant)
    W = head_matrix(shape, params)
    norm = params["final_norm"]["scale"]
    out = [[], [], []]
    with jax.default_matmul_precision("highest"):
        for a in range(0, len(pos), rows):
            n = len(tokens[a: a + rows])
            pad = rows - n
            xr = jnp.pad(rows_h[a: a + rows], ((0, pad), (0, 0)))
            tk = jnp.asarray(np.pad(tokens[a: a + rows], (0, pad)))
            for o, r in zip(out, head(xr, norm, W, tk)):
                o.append(np.asarray(r)[:n])
    return [np.concatenate(o) for o in out]


def logit_gaps(shape, params, seqs, starts, *, control: bool = False,
               rows: int = 512):
    """Gaps in the float32 forward, teacher-forced on ``seqs``.

    At every position ``p`` that predicts a served token (``p + 1 >=
    starts[i]``), the gap is the float32 best logit minus the float32
    logit of the served token ``seqs[i][p + 1]``: 0 where the served
    token is the reference's greedy choice. With ``control``, also the
    gap of the token that the float8 forward (the control) puts first
    at the same positions. Returns ``(served, control)``: lists of
    float64 arrays, one per sequence (``control`` is None without it).
    """
    pos = np.array([(i, p) for i, q in enumerate(seqs)
                    for p in range(starts[i] - 1, len(q) - 1)], np.int32)
    served = np.array([seqs[i][p + 1] for i, p in pos], np.int32)
    hid = hidden_states(shape, params, seqs)
    best, at, _ = _rows_logits(shape, params, hid, pos, served, "none", rows)
    split = np.cumsum([len(q) - s for q, s in zip(seqs, starts)])[:-1]
    out_served = np.split((best - at).astype(np.float64), split)
    if not control:
        return out_served, None
    hid8 = hidden_states(shape, params, seqs, quant="fp8")
    _, _, pick = _rows_logits(shape, params, hid8, pos, served, "fp8", rows)
    del hid8
    best, at, _ = _rows_logits(shape, params, hid, pos, pick, "none", rows)
    return out_served, np.split((best - at).astype(np.float64), split)


def logits(shape, params, seqs):
    """Full float32 logits ``(B, T_pad, V)`` of right-padded ``seqs``:
    for small sizes (tests)."""
    import jax
    import jax.numpy as jnp

    hid = hidden_states(shape, params, seqs)
    with jax.default_matmul_precision("highest"):
        x = _rms(hid, params["final_norm"]["scale"].astype(jnp.float32),
                 shape["eps"])
        return x @ head_matrix(shape, params).astype(jnp.float32)
