"""Pallas TPU kernels for the paper's compute hot-spots.

- spec_verify/: flash-decode attention for speculative verification
  (the DAS device hot-spot): (K+1)-query block vs position-tagged ring
  KV cache, GQA, sliding window, online softmax over VMEM-streamed
  chunks. kernel.py (pl.pallas_call + BlockSpec), ops.py (jit wrapper),
  ref.py (pure-jnp oracle).
- suffix_match/: batched longest-suffix-match drafting over packed
  suffix trees (the DAS host hot-spot moved on-device): grid over batch
  rows, Chang-Lawler suffix-link descent + greedy continuation walk
  over the flat export of ``SuffixTree.pack()``, one device call per
  verify round instead of B per-row Python walks. kernel.py
  (pl.pallas_call + the shared scalar core), ops.py (forest packing +
  jit wrapper), ref.py (the vmapped scalar core, which the main path
  runs on every backend).
- rglru/: blocked RG-LRU linear-recurrence scan (RecurrentGemma's
  recurrent half) with VMEM carry across sequence chunks.

Validated through the Pallas interpreter (``interpret=True``) in the CPU
tests. The TPU lowering refuses all three as written — block shapes off
the 8x128 tiling, and the suffix-match core indexing vector-loaded
tables at data-dependent positions — so no main-path code selects them.
Import the subpackages lazily — they pull in pallas.
"""
