"""Pallas kernels vs pure-jnp oracles, run through the Pallas interpreter
(each call asks for it: the wrappers compile by default), with hypothesis
shape/dtype sweeps."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import hypothesis_or_stub

# Property-based tests are skipped when hypothesis is unavailable
# (offline CI image); the plain tests below still run.
given, settings, st = hypothesis_or_stub()

from repro.kernels.rglru import rglru_scan, rglru_scan_ref
from repro.kernels.spec_verify import (
    spec_verify_attention,
    spec_verify_attention_ref,
)


def _cache_pos(rng, B, S, wrap=True):
    lengths = rng.integers(1, S - 1, size=B)
    cpos = np.full((B, S), -1, np.int64)
    for b in range(B):
        lo = max(0, lengths[b] - (S - 1)) if wrap else 0
        for pos in range(lo, lengths[b]):
            cpos[b, pos % (S - 1)] = pos
    return lengths, cpos


def _run_case(B, T, Hq, Hkv, hd, S, window, softcap, dtype, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(B, T, Hq, hd)), dtype)
    k = jnp.asarray(rng.normal(size=(B, S, Hkv, hd)), dtype)
    v = jnp.asarray(rng.normal(size=(B, S, Hkv, hd)), dtype)
    lengths, cpos = _cache_pos(rng, B, S)
    positions = lengths[:, None] + np.arange(T)[None]
    args = (
        q, k, v, jnp.asarray(cpos, jnp.int32),
        jnp.asarray(positions, jnp.int32),
    )
    out = spec_verify_attention(
        *args, window=window, softcap=softcap, chunk=128, interpret=True
    )
    ref = spec_verify_attention_ref(*args, window=window, softcap=softcap)
    atol = 3e-2 if dtype == "bfloat16" else 3e-5
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=atol, rtol=1e-2,
    )


@pytest.mark.parametrize(
    "B,T,Hq,Hkv,hd,S,window,softcap,dtype",
    [
        (2, 9, 8, 2, 64, 257, 0, 0.0, "float32"),
        (1, 1, 4, 4, 128, 129, 0, 0.0, "float32"),
        (3, 5, 6, 2, 64, 130, 48, 0.0, "float32"),
        (2, 17, 8, 4, 128, 513, 0, 30.0, "bfloat16"),
        (2, 4, 12, 2, 64, 300, 100, 0.0, "bfloat16"),
        (1, 2, 16, 1, 32, 70, 0, 0.0, "float32"),  # MQA
    ],
)
def test_spec_verify_kernel_cases(B, T, Hq, Hkv, hd, S, window, softcap, dtype):
    _run_case(B, T, Hq, Hkv, hd, S, window, softcap, dtype)


@settings(max_examples=12, deadline=None)
@given(
    B=st.integers(1, 3),
    T=st.integers(1, 9),
    group=st.integers(1, 4),
    Hkv=st.integers(1, 3),
    hd=st.sampled_from([32, 64]),
    S=st.integers(40, 200),
    window=st.sampled_from([0, 33]),
    dtype=st.sampled_from(["float32", "bfloat16"]),
)
def test_spec_verify_kernel_hypothesis(B, T, group, Hkv, hd, S, window, dtype):
    _run_case(B, T, Hkv * group, Hkv, hd, S, window, 0.0, dtype, seed=B + S)


@pytest.mark.parametrize("B,T,W", [(2, 16, 128), (1, 7, 130), (3, 128, 256)])
def test_rglru_kernel_cases(B, T, W):
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(B, T, W)), jnp.float32)
    r = jnp.asarray(rng.uniform(size=(B, T, W)), jnp.float32)
    i = jnp.asarray(rng.uniform(size=(B, T, W)), jnp.float32)
    lam = jnp.asarray(rng.normal(size=(W,)), jnp.float32)
    h0 = jnp.asarray(rng.normal(size=(B, W)), jnp.float32)
    hs, hf = rglru_scan(x, r, i, lam, h0, interpret=True)
    hs_r, hf_r = rglru_scan_ref(x, r, i, lam, h0)
    np.testing.assert_allclose(np.asarray(hs), np.asarray(hs_r), atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(hf), np.asarray(hf_r), atol=1e-5, rtol=1e-5)


@settings(max_examples=10, deadline=None)
@given(
    B=st.integers(1, 3), T=st.integers(1, 40), W=st.sampled_from([64, 128, 200])
)
def test_rglru_kernel_hypothesis(B, T, W):
    rng = np.random.default_rng(B * 100 + T)
    x = jnp.asarray(rng.normal(size=(B, T, W)), jnp.float32)
    r = jnp.asarray(rng.uniform(size=(B, T, W)), jnp.float32)
    i = jnp.asarray(rng.uniform(size=(B, T, W)), jnp.float32)
    lam = jnp.asarray(rng.normal(size=(W,)), jnp.float32)
    h0 = jnp.asarray(rng.normal(size=(B, W)), jnp.float32)
    hs, hf = rglru_scan(x, r, i, lam, h0, interpret=True)
    hs_r, hf_r = rglru_scan_ref(x, r, i, lam, h0)
    np.testing.assert_allclose(np.asarray(hs), np.asarray(hs_r), atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(hf), np.asarray(hf_r), atol=1e-5, rtol=1e-5)


def test_kernel_matches_model_attention_layer():
    """attention_forward(attn_impl='pallas') must agree with the XLA path.
    The model layer calls the kernel compiled; on CPU the test asks for
    the TPU interpreter around the call."""
    from jax.experimental.pallas import tpu as pltpu

    from conftest import make_params
    from repro.configs.base import ModelConfig
    from repro.models import model as M

    cfg = ModelConfig(
        name="t", family="dense", num_layers=2, d_model=64, num_heads=4,
        num_kv_heads=2, d_ff=128, vocab_size=64, vocab_pad_multiple=8,
        dtype="float32",
    )
    params = make_params(cfg)
    B = 2
    prompt = jax.random.randint(jax.random.key(1), (B, 6), 0, cfg.vocab_size)
    _, cache = M.prefill(params, cfg, prompt, jnp.ones((B, 6), bool), max_len=64)
    block = jax.random.randint(jax.random.key(2), (B, 4), 0, cfg.vocab_size)
    outs = {}
    for impl in ("xla", "pallas"):
        with pltpu.force_tpu_interpret_mode():
            logits, _, _ = M.forward(
                params, cfg, block, cache=cache,
                valid=jnp.ones((B, 4), bool),
                commit_upto=jnp.zeros((B,), jnp.int32), attn_impl=impl,
            )
        outs[impl] = np.asarray(logits)
    np.testing.assert_allclose(outs["xla"], outs["pallas"], atol=3e-4, rtol=1e-3)


def _suffix_match_pallas(interpret):
    from repro.kernels.suffix_match.ops import pack_forest, suffix_match_propose

    forest, _ = pack_forest([])
    return suffix_match_propose(
        forest, np.full((2, 8), -1, np.int32), np.full(2, -1, np.int32),
        np.zeros(2, np.int32), n_prop_max=4, impl="pallas",
        interpret=interpret,
    )


def _rglru(interpret):
    x = jnp.ones((1, 8, 128), jnp.float32)
    return rglru_scan(x, 0.5 * x, 0.5 * x, jnp.ones(128), jnp.zeros((1, 128)),
                      interpret=interpret)


def _spec_verify(interpret):
    q = jnp.ones((1, 2, 4, 64), jnp.float32)
    kv = jnp.ones((1, 128, 2, 64), jnp.float32)
    cpos = jnp.arange(128, dtype=jnp.int32)[None]
    return spec_verify_attention(q, kv, kv, cpos, jnp.full((1, 2), 128),
                                 chunk=128, interpret=interpret)


@pytest.mark.parametrize("call", [_suffix_match_pallas, _rglru, _spec_verify])
def test_kernels_compile_unless_interpret_is_asked(call):
    """No wrapper falls back to the interpreter by itself: on CPU the
    default (compiled) call is refused, and only an explicit
    ``interpret=True`` runs. On a TPU the same default compiles."""
    jax.block_until_ready(call(interpret=True))
    with pytest.raises(ValueError, match="interpret mode"):
        jax.block_until_ready(call(interpret=False))
