"""Whole step: operations that the window's served tokens need
(``bench/counts.py``: a plain forward over each finished rollout's
prompt and output; rejected drafts count nothing) over the window's
host-clock seconds and the chip's bf16 peak, in percent."""

from bench import counts


def read(run):
    if not run["sequences"] or not run["window_s"]:
        return None
    flops = sum(counts.sequence_flops(run["shape"], p, n)
                for p, n in run["sequences"] if n)
    return 100.0 * flops / (run["window_s"]
                            * run["peaks"]["bf16_flops_per_s"])
