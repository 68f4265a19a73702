"""Operations and bytes that the rollout work needs, from a
configuration's shapes (``reference.shape_of``).

These count what the algorithm needs, whatever implements it:

- a verify round reads every weight once (the embedding table only for
  the rows it gathers, unless it is also the output head), reads each
  active row's *valid* key/value context (not the whole ring a masked
  implementation sweeps), writes the block's keys and values, and
  writes float32 logits for the block;
- its operations are two per weight per block token, plus attention
  (q k^T and p v over the valid context) and the output head;
- an emitted token needs one forward at its context; a rejected draft
  needs nothing.

All weights and the cache are bfloat16 (2 bytes).
"""

from __future__ import annotations

import json
import os

WEIGHT_BYTES = 2
KV_BYTES = 2
LOGIT_BYTES = 4


def load_peaks(device_kind: str) -> dict:
    """The chip's peaks from ``peaks.json``; an unknown kind is an
    error, never a default."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "peaks.json")
    with open(path) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def layer_params(s) -> int:
    d, H, Hk, hd, ff = s["d"], s["heads"], s["kv_heads"], s["head_dim"], s["ff"]
    attn = d * H * hd + 2 * d * Hk * hd + H * hd * d
    if s["qkv_bias"]:
        attn += (H + 2 * Hk) * hd
    return attn + 3 * d * ff + 2 * d


def param_count(s, vocab: int | None = None) -> int:
    """Every parameter: embedding, layers, final norm, untied head."""
    V = vocab or s["vocab"]
    n = V * s["d"] + s["layers"] * layer_params(s) + s["d"]
    if not s["tied"]:
        n += s["d"] * V
    return n


def kv_bytes_per_token(s) -> int:
    return s["layers"] * 2 * s["kv_heads"] * s["head_dim"] * KV_BYTES


def matmul_params(s) -> int:
    """Weights a token multiplies through (layers and the head)."""
    return s["layers"] * layer_params(s) + s["d"] * s["vocab"]


def verify_work(s, *, rounds: float, block_tokens: float,
                context_reads: float, attn_pairs: float) -> tuple:
    """``(flops, bytes)`` that ``rounds`` verify rounds need.

    ``block_tokens``: tokens verified, summed over rounds and active
    rows (each row's 1 + draft budget). ``context_reads``: each active
    row's valid cached context, summed over rounds and rows.
    ``attn_pairs``: (query, key) pairs the block tokens attend to,
    summed likewise. Weights (layers and head) are read once per round;
    an untied embedding only for the rows it gathers."""
    weights = matmul_params(s) * WEIGHT_BYTES
    kv = kv_bytes_per_token(s)
    bytes_ = (rounds * weights
              + block_tokens * s["d"] * WEIGHT_BYTES   # embedding rows
              + context_reads * kv                     # cache read
              + block_tokens * kv                      # block K/V written
              + block_tokens * s["vocab"] * LOGIT_BYTES)
    flops = (2.0 * matmul_params(s) * block_tokens
             + 4.0 * s["layers"] * s["heads"] * s["head_dim"] * attn_pairs)
    return flops, bytes_


def sequence_flops(s, prompt: int, output: int) -> float:
    """Forward operations a plain decoder needs to produce ``output``
    tokens after ``prompt``: one forward per position from the prompt's
    first token to the last output's predecessor, each attending to all
    positions up to its own."""
    n = prompt + output - 1
    return (2.0 * matmul_params(s) * n
            + 4.0 * s["layers"] * s["heads"] * s["head_dim"]
            * n * (n + 1) / 2.0)
