"""Public jit'd wrapper for the spec-verify attention kernel.

Handles layout plumbing between the model (B, T, Hq, hd)/(B, S, Hkv, hd)
world and the kernel's MXU-aligned tiles:

* GQA regrouping: queries (B,T,Hq,hd) → (B, T·G, Hkv, hd) rows so each
  kv head sees a contiguous (T·G, hd) query block;
* padding: query rows to the 8-row sublane tile, cache length to a
  multiple of the KV chunk (padded slots carry cpos = -1 → masked);
* compiled on TPU; ``interpret=True`` runs the kernel through the Pallas
  interpreter, which a CPU caller asks for explicitly.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .kernel import DEFAULT_CHUNK, spec_verify_attention_kernel


def _round_up(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


# das: hot-path
@functools.partial(
    jax.jit, static_argnames=("window", "softcap", "chunk", "interpret")
)
def spec_verify_attention(
    q: jnp.ndarray,  # (B, T, Hq, hd)
    k: jnp.ndarray,  # (B, S, Hkv, hd)  (S includes the trash slot)
    v: jnp.ndarray,
    cache_pos: jnp.ndarray,  # (B, S) int32
    positions: jnp.ndarray,  # (B, T) int32
    *,
    window: int = 0,
    softcap: float = 0.0,
    chunk: int = DEFAULT_CHUNK,
    interpret: bool = False,
) -> jnp.ndarray:
    B, T, Hq, hd = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    # --- regroup queries per kv head: rows = t*G + g ---
    qg = q.reshape(B, T, Hkv, G, hd).transpose(0, 1, 3, 2, 4)  # B,T,G,Hkv,hd
    qg = qg.reshape(B, T * G, Hkv, hd)
    qpos = jnp.repeat(positions, G, axis=1)  # (B, T*G)
    # --- pad query rows to the sublane tile ---
    TG = _round_up(T * G, 8)
    if TG != T * G:
        qg = jnp.pad(qg, ((0, 0), (0, TG - T * G), (0, 0), (0, 0)))
        qpos = jnp.pad(
            qpos, ((0, 0), (0, TG - T * G)), constant_values=-(1 << 30)
        )
    # --- pad cache length to a chunk multiple ---
    ch = min(chunk, _round_up(S, 128))
    Sp = _round_up(S, ch)
    if Sp != S:
        k = jnp.pad(k, ((0, 0), (0, Sp - S), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, Sp - S), (0, 0), (0, 0)))
        cache_pos = jnp.pad(
            cache_pos, ((0, 0), (0, Sp - S)), constant_values=-1
        )
    out = spec_verify_attention_kernel(
        qg, k, v, cache_pos, qpos,
        window=window, softcap=softcap, chunk=ch, interpret=interpret,
    )
    out = out[:, : T * G]  # strip row padding
    out = out.reshape(B, T, G, Hkv, hd).transpose(0, 1, 3, 2, 4)
    return out.reshape(B, T, Hq, hd)
