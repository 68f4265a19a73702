"""ChatGLM3-6B pipeline stage 1 (``configs/chatglm3-6b.stage1.json``):
the program computes the published model, and the configuration file is
one the harness runs.

ChatGLM3's published rotary embedding (``modeling_chatglm.py``,
``apply_rotary_pos_emb``) rotates the first ``rope_dims`` of each head
in neighbouring pairs (0,1), (2,3), ...; the program (``models/layers.py``,
``rope="partial"``) and ``reference.py`` pair the two halves of the
rotated part. Permuting the rotated columns of ``wq``, ``wk``, ``bq``
and ``bk`` by ``interleave_to_halves`` turns one into the other: it is
the map a loader of the published checkpoint applies.
"""

import json
import math
import os

import numpy as np

from bench import counts, harness, reference, weights

BENCH = os.path.dirname(os.path.abspath(__file__))

TINY = {"name": "tiny-chatglm3", "arch": "chatglm3-6b", "slots": 4,
        "dtype": "float32", "config": {
            "hidden_size": 64, "ffn_hidden_size": 160,
            "num_attention_heads": 4, "num_layers": 3,
            "multi_query_group_num": 2, "kv_channels": 16,
            "layernorm_epsilon": 1e-5, "add_qkv_bias": True,
            "tie_word_embeddings": False, "padded_vocab_size": 512}}


def _stage1():
    with open(os.path.join(BENCH, "configs", "chatglm3-6b.stage1.json")) as f:
        return json.load(f)


def interleave_to_halves(head_dim: int, rope_dims: int) -> np.ndarray:
    """Column ``i`` of a program head is column ``perm[i]`` of the
    published head: the even then the odd rotated columns, then the
    unrotated ones in place."""
    return np.concatenate([np.arange(0, rope_dims, 2),
                           np.arange(1, rope_dims, 2),
                           np.arange(rope_dims, head_dim)])


def _published_rope(x, theta, dims):
    """``apply_rotary_pos_emb`` of ``modeling_chatglm.py`` on (T, H, hd):
    pair (2i, 2i+1) of the first ``dims`` turns by position x theta^(-2i/dims)."""
    T = x.shape[0]
    inv = 1.0 / theta ** (np.arange(0, dims, 2) / dims)
    ang = np.arange(T)[:, None] * inv[None, :]
    cos, sin = np.cos(ang)[:, None, :], np.sin(ang)[:, None, :]
    xr = x[..., :dims].reshape(*x.shape[:-1], dims // 2, 2)
    a, b = xr[..., 0], xr[..., 1]
    out = np.stack([a * cos - b * sin, b * cos + a * sin], -1)
    return np.concatenate([out.reshape(*x.shape[:-1], dims),
                           x[..., dims:]], -1)


def _published_logits(s, params, tokens):
    """ChatGLM3's forward as published, in float64 numpy, on one
    sequence: RMSNorm, fused QKV with bias, interleaved partial RoPE,
    grouped (multi-query) causal attention, SwiGLU, untied head."""
    def f64(a):
        return np.asarray(a, np.float64)

    def rms(x, w):
        return x / np.sqrt(np.mean(x * x, -1, keepdims=True) + s["eps"]) * w

    H, Hk, hd = s["heads"], s["kv_heads"], s["head_dim"]
    w_all = {k: f64(v) for k, v in reference.block_weights(params).items()}
    x = f64(params["embed"])[np.asarray(tokens)]
    T = len(tokens)
    causal = np.tril(np.ones((T, T), bool))
    for li in range(s["layers"]):
        w = {k: v[li] for k, v in w_all.items()}
        h = rms(x, w["norm"])
        q = np.einsum("td,dhk->thk", h, w["wq"]) + w["bq"]
        k = np.einsum("td,dhk->thk", h, w["wk"]) + w["bk"]
        v = np.einsum("td,dhk->thk", h, w["wv"]) + w["bv"]
        q = _published_rope(q, s["rope_theta"], s["rope_dims"])
        k = _published_rope(k, s["rope_theta"], s["rope_dims"])
        k, v = np.repeat(k, H // Hk, axis=1), np.repeat(v, H // Hk, axis=1)
        sc = np.einsum("thk,shk->hts", q, k) / math.sqrt(hd)
        sc = np.where(causal, sc, -np.inf)
        p = np.exp(sc - sc.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        o = np.einsum("hts,shk->thk", p, v)
        x = x + np.einsum("thk,hkd->td", o, w["wo"])
        h = rms(x, w["mlp_norm"])
        g = h @ w["wg"]
        x = x + (g / (1.0 + np.exp(-g)) * (h @ w["wi"])) @ w["wd"]
    x = rms(x, f64(params["final_norm"]["scale"]))
    return x @ f64(params["lm_head"])[:, : s["vocab"]]


def _numpy_params(cfg, seed):
    """The program's parameter tree in float32 numpy, drawn by
    ``weights.leaf_rule`` (no device program to compile)."""
    import jax

    shapes, stacked = weights.abstract_params(cfg)
    rng = np.random.default_rng(seed)

    def draw(path, sd, st):
        kind, std = weights.leaf_rule(weights._path_names(path), sd.shape, st)
        x = rng.normal(0.0, std, sd.shape).astype(np.float32)
        return x + 1.0 if kind == "norm" else x

    return jax.tree_util.tree_map_with_path(draw, shapes, stacked)


def _permute_rotated(params, perm):
    """``params`` with the last axis of every q and k weight and bias
    taken in ``perm`` order."""
    import jax

    def walk(path, leaf):
        name = str(getattr(path[-1], "key", ""))
        return leaf[..., perm] if name in ("wq", "wk", "bq", "bk") else leaf

    return jax.tree_util.tree_map_with_path(walk, params)


def test_program_is_published_chatglm3_up_to_the_rope_column_map():
    import jax
    import jax.numpy as jnp

    from repro.models import model as M

    cfg = harness.program_config(TINY)
    s = reference.shape_of(TINY)
    params = _numpy_params(cfg, 3)
    tokens = np.random.default_rng(0).integers(4, s["vocab"], 13)
    want = _published_logits(s, params, tokens)
    perm = interleave_to_halves(s["head_dim"], s["rope_dims"])

    fwd = jax.jit(lambda p, t: M.forward(p, cfg, t)[0])

    def program(p):
        with jax.default_matmul_precision("highest"):
            lg = fwd(p, jnp.asarray(tokens[None], jnp.int32))
        return np.asarray(lg)[0, :, : s["vocab"]]

    np.testing.assert_allclose(program(_permute_rotated(params, perm)), want,
                               rtol=0, atol=1e-5)
    # without the map the program computes another function
    assert np.abs(program(params) - want).max() > 1e-3


def test_stage1_builds_and_counts_the_published_stage():
    spec = _stage1()
    cfg = harness.program_config(spec)
    s = reference.shape_of(spec)
    assert (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
            cfg.head_dim, cfg.d_ff, cfg.vocab_size) == (
        14, 4096, 32, 2, 128, 13696, 65024)
    assert cfg.rope == "partial" and cfg.attn_bias and not cfg.tie_embeddings
    assert round(counts.param_count(s) / 1e9, 2) == 3.39
    assert counts.kv_bytes_per_token(s) == 14336
    # only the keys it lists differ from the published configuration
    assert sorted(spec["published"]) == sorted(spec["reduced"])
    assert all(spec["config"][k] != v for k, v in spec["published"].items())


def test_stage1_has_a_numeric_correctness_limit():
    limit = _stage1()["correct"]["max_logit_gap"]
    assert isinstance(limit, (int, float)) and not isinstance(limit, bool)
    assert 0.0 < limit < math.inf

