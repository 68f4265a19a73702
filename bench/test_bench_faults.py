"""The harness, driven past its look for a chip with the timed path
broken underneath, reports ``correct`` false: once with a served token
altered where the round produces it, once with the round's cache writes
dropped (later tokens then attend to a cache without them)."""

import json
import os
import time

import numpy as np
import pytest

from bench import harness, smallcell

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _alter_token(mp):
    import repro.core.spec_engine as se

    orig = se.unpack_round_out

    def altered(out_row, K):
        cand, acc, n_take, alive, n_prop = orig(out_row, K)
        cand = cand.copy()
        cand[0, 0] = (cand[0, 0] + 7) % smallcell.SPEC["config"]["vocab_size"]
        return cand, acc, n_take, alive, n_prop

    mp.setattr(se, "unpack_round_out", altered)


def _drop_cache_writes(mp):
    import repro.core.fused_round as fr

    orig = fr.verify_step

    def no_commit(params, cfg, cache, *a, **kw):
        res, cache1 = orig(params, cfg, cache, *a, **kw)
        return res, cache._replace(lengths=cache1.lengths)

    mp.setattr(fr, "verify_step", no_commit)


@pytest.mark.parametrize("fault", [_alter_token, _drop_cache_writes],
                         ids=["token_altered", "cache_not_written"])
def test_broken_timed_path_is_not_correct(fault):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with pytest.MonkeyPatch.context() as mp:
        fault(mp)
        res = harness.run(
            bench["workloads"][0]["name"], 5, 0.5, False,
            t_start=time.perf_counter(), bench=bench, log=lambda m: None,
            spec=smallcell.SPEC, mix=smallcell.MIX)
    assert not res["correct"]
    gap = res["compared"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]
    assert np.isfinite(gap["value"])
