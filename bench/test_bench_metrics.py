"""Each per-layer metric's reader, on a run record whose numbers are
worked out by hand, and on one where it finds nothing to read."""

import pytest

from bench import counts, harness

SHAPE = {"layers": 2, "d": 8, "heads": 2, "kv_heads": 1, "head_dim": 4,
         "ff": 16, "vocab": 32, "tied": True, "qkv_bias": True,
         "eps": 1e-6, "rope_theta": 1e4, "rope_dims": 4}
PEAKS = {"bf16_flops_per_s": 1e6, "hbm_bytes_per_s": 1e6, "hbm_bytes": 1e9}


def _run(**kw):
    run = {
        "slots": 4, "steps": 2, "window_s": 10.0, "rounds": 5,
        "effective_batch": [4, 4, 2, 2, 0], "drafted": 20, "accepted": 5,
        "host_time_s": 0.05, "emitted_tokens": 30, "request_rounds": 12,
        "sequences": [(3, 4), (2, 0)], "shape": SHAPE, "peaks": PEAKS,
        "setup_s": 42.0,
        "traced": {"rounds": 4, "block_tokens": 16, "context_reads": 40,
                   "attn_pairs": 60},
        "trace": {"window_s": 2.0, "busy_s": 1.5,
                  "programs": {"jit_fused": 0.8, "jit_prefill_fn": 0.1,
                               "jit_write_fn": 0.02},
                  "program_counts": {"jit_fused": 4, "jit_prefill_fn": 2,
                                     "jit_write_fn": 4}},
    }
    run.update(kw)
    return run


@pytest.mark.parametrize("name,want", [
    ("step_s", 5.0),
    ("setup_s", 42.0),
    ("slot_occupancy", 100.0 * 12 / 20),
    ("draft_accept_rate", 25.0),
    ("tokens_per_row_round", 2.5),
    ("host_ms_per_round", 10.0),
    ("rounds_per_step", 2.5),
    ("fused_round_ms", 200.0),  # 0.8 s over 4 traced rounds
    ("prefill_share", 100.0 * 0.12 / 2.0),
    ("device_idle_share", 25.0),
])
def test_reader_values(name, want):
    assert harness.load_reader(name)(_run()) == pytest.approx(want)


def test_roofline_and_mfu_from_counts():
    run = _run()
    f, b = counts.verify_work(SHAPE, rounds=4, block_tokens=16,
                              context_reads=40, attn_pairs=60)
    want = 100.0 * max(f, b) / 1e6 / 0.8
    assert harness.load_reader("fused_round_roofline")(run) == \
        pytest.approx(want)
    mfu = 100.0 * counts.sequence_flops(SHAPE, 3, 4) / (10.0 * 1e6)
    assert harness.load_reader("rollout_mfu")(run) == pytest.approx(mfu)


@pytest.mark.parametrize("name", [
    "step_s", "slot_occupancy", "draft_accept_rate", "tokens_per_row_round",
    "host_ms_per_round", "rounds_per_step", "fused_round_ms",
    "fused_round_roofline", "prefill_share", "device_idle_share",
    "rollout_mfu"])
def test_reader_returns_nothing_when_nothing_to_read(name):
    empty = _run(effective_batch=[], drafted=0, request_rounds=0, rounds=0,
                 steps=0, sequences=[], traced=None, trace=None)
    assert harness.load_reader(name)(empty) is None
