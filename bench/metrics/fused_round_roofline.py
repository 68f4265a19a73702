"""Fused round (device): the least time the profiled verify rounds
need (``bench/counts.py``: the larger of their operations over the peak
and their bytes over the bandwidth, both summed over the rounds) over
the fused program's device time, in percent."""

from bench import counts


def read(run):
    tr, st = run["trace"], run.get("traced")
    if not tr or not st or not st["rounds"]:
        return None
    t = tr["programs"].get("jit_fused")
    if not t:
        return None
    flops, nbytes = counts.verify_work(
        run["shape"], rounds=st["rounds"], block_tokens=st["block_tokens"],
        context_reads=st["context_reads"], attn_pairs=st["attn_pairs"])
    pk = run["peaks"]
    least = max(flops / pk["bf16_flops_per_s"], nbytes / pk["hbm_bytes_per_s"])
    return 100.0 * least / t
