"""The control that the correctness limit must fail: the float8
reference, put in the program's place on the same prompts and served
tokens, is judged by the harness's own comparison and comes out not
correct, while the program (bf16) is correct -- here at the small
cell's size, on three seeds, driving the whole harness on the CPU."""

import json
import os
import time

import pytest

from bench import harness, smallcell

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("seed", [11, 2**33 + 5, 4_000_000_007])
def test_program_passes_and_control_fails(seed):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    res = harness.run(
        bench["workloads"][0]["name"], seed, 0.5, False,
        t_start=time.perf_counter(), bench=bench, log=lambda m: None,
        spec=smallcell.SPEC, mix=smallcell.MIX, control=True)
    limit = smallcell.SPEC["correct"]["max_logit_gap"]
    assert res["correct"], res["compared"]
    assert res["compared"]["max_logit_gap"]["value"] <= limit
    assert res["control"]["correct"] is False
    assert res["control"]["compared"]["max_logit_gap"]["value"] > limit
    assert res["control"]["compared"]["unfinished"] == \
        res["compared"]["unfinished"]
    assert list(res["metrics"]) == ["step_s", "setup_s"]
    assert list(res)[-1] == "compared"
