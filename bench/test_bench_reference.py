"""The reference forward against the program at a small size on the
CPU: prefill followed by a cached verify block through ``models/`` must
give the reference's logits, for a Qwen2-like and a ChatGLM-like
configuration (tied and untied heads, full and partial RoPE, QKV bias).
"""

import numpy as np
import pytest

from bench import harness, reference, weights

QWEN = {"name": "tiny-qwen2", "arch": "qwen2-1.5b", "slots": 4,
        "dtype": "float32", "config": {
            "hidden_size": 64, "intermediate_size": 128,
            "num_attention_heads": 4, "num_hidden_layers": 2,
            "num_key_value_heads": 2, "head_dim": 16, "rms_norm_eps": 1e-6,
            "rope_theta": 1e6, "tie_word_embeddings": True,
            "vocab_size": 500}}
GLM = {"name": "tiny-chatglm3", "arch": "chatglm3-6b", "slots": 4,
       "dtype": "float32", "config": {
           "hidden_size": 64, "ffn_hidden_size": 160,
           "num_attention_heads": 4, "num_layers": 3,
           "multi_query_group_num": 2, "kv_channels": 16,
           "layernorm_epsilon": 1e-5, "add_qkv_bias": True,
           "tie_word_embeddings": False, "padded_vocab_size": 512}}


@pytest.mark.parametrize("spec", [QWEN, GLM], ids=["qwen2", "chatglm3"])
def test_reference_matches_prefill_then_cached_verify(spec):
    import jax
    import jax.numpy as jnp

    from repro.models import model as M

    cfg = harness.program_config(spec)
    shape = reference.shape_of(spec)
    params = weights.with_step_norms(weights.make_params(cfg, 5), 5, 1, 0.1)
    rng = np.random.default_rng(0)
    prompt = rng.integers(4, shape["vocab"], 11).tolist()
    block = rng.integers(4, shape["vocab"], 4).tolist()
    Tp = 16
    toks = np.zeros((1, Tp), np.int32)
    mask = np.zeros((1, Tp), bool)
    toks[0, Tp - len(prompt):] = prompt
    mask[0, Tp - len(prompt):] = True
    with jax.default_matmul_precision("highest"):
        last, cache = M.prefill(params, cfg, toks, mask,
                                max_len=64)
        got, _, _ = M.forward(params, cfg, jnp.asarray([block], jnp.int32),
                              cache=cache, valid=jnp.ones((1, 4), bool),
                              commit_upto=jnp.zeros((1,), jnp.int32))
    want = np.asarray(reference.logits(shape, params, [prompt + block]))
    V = shape["vocab"]
    n = len(prompt)
    np.testing.assert_allclose(np.asarray(last)[0, :V], want[0, n - 1],
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(got)[0, :, :V],
                               want[0, n: n + 4], rtol=2e-4, atol=2e-4)


def test_logit_gaps_zero_on_greedy_tokens_and_control_reads_its_own():
    """Tokens the reference itself picks greedily have gap 0; a token
    below the best reads its distance; the float8 control reads the gap
    of its own first choice, never below 0."""
    spec = dict(QWEN, dtype="bfloat16")
    cfg = harness.program_config(spec)
    shape = reference.shape_of(spec)
    params = weights.make_params(cfg, 9)
    prompt = list(range(7, 19))
    seq = list(prompt)
    for _ in range(12):
        lg = np.asarray(reference.logits(shape, params, [seq]))[0, len(seq) - 1]
        seq.append(int(lg.argmax()))
    lg = np.asarray(reference.logits(shape, params, [seq]))[0]
    worst = int(lg[len(seq) - 1].argmin())
    bad = seq + [worst]
    served, ctrl = reference.logit_gaps(shape, params, [seq, bad],
                                        [len(prompt)] * 2, control=True)
    assert served[0].max() == 0.0
    np.testing.assert_allclose(served[1][-1], lg[len(seq) - 1].max()
                               - lg[len(seq) - 1].min(), rtol=1e-5)
    assert all((c >= 0).all() for c in ctrl)
    assert len(ctrl[1]) == len(served[1]) == 13
