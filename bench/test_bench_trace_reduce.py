"""The reduction from a profiler trace to device busy time, program
time and named idle gaps, on a small trace in the profiler's own
format (``testdata/small_trace.pbtxt``): a host thread holding the
benchmark's window and the engine's round-phase spans, and one TPU
plane with its XLA modules and ops. Times in milliseconds from the
window's start: fused rounds run at 1-3 and 5-7, a prefill at 8-9."""

import os

import pytest

from bench import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def reduced():
    return trace_reduce.reduce(trace_reduce.load(
        os.path.join(HERE, "testdata", "small_trace.pbtxt")))


def test_busy_and_window(reduced):
    assert reduced["window_s"] == pytest.approx(0.010)
    assert reduced["busy_s"] == pytest.approx(0.005)
    assert reduced["n_devices"] == 1


def test_program_time_by_module(reduced):
    assert reduced["programs"] == pytest.approx(
        {"jit_fused": 0.004, "jit_prefill_fn": 0.001})
    assert reduced["program_counts"] == {"jit_fused": 2, "jit_prefill_fn": 1}
    assert reduced["ops"][0] == ["fusion.12", pytest.approx(0.0035)]


def test_idle_gaps_named_by_innermost_host_span(reduced):
    # gaps 0-1 (serve_round open), 3-5 (consume nested in serve_round),
    # 7-8 (prefill), 9-10 (nothing open)
    assert dict(reduced["idle_gaps"]) == pytest.approx({
        "serve_round": 0.001, "consume": 0.002, "prefill": 0.001,
        "(no host span)": 0.001})
    assert reduced["n_idle_gaps"] == 4


def test_trace_without_a_tpu_plane_is_refused():
    from jax.profiler import ProfileData

    pd = ProfileData.from_text_proto('planes { id: 1 name: "/host:CPU" }')
    with pytest.raises(ValueError):
        trace_reduce.reduce(pd)


def test_a_bare_window_mark_opens_to_the_last_device_event():
    with open(os.path.join(HERE, "testdata", "small_trace.pbtxt")) as f:
        text = f.read()
    text = text.replace("events { metadata_id: 1 offset_ps: 0 duration_ps: "
                        "10000000000 }",
                        "events { metadata_id: 1 offset_ps: 0 duration_ps: 0 }")
    from jax.profiler import ProfileData

    r = trace_reduce.reduce(ProfileData.from_text_proto(text))
    assert r["window_s"] == pytest.approx(0.009)  # 0 to the prefill's end
    assert r["busy_s"] == pytest.approx(0.005)


def test_executions_must_match_what_the_run_dispatched():
    pd = trace_reduce.load(os.path.join(HERE, "testdata", "small_trace.pbtxt"))
    r = trace_reduce.reduce(pd, expect={"jit_fused": 2})
    assert r["program_counts"]["jit_fused"] == 2
    for want in (1, 3):  # a dropped event, or work outside the window
        with pytest.raises(ValueError, match="jit_fused"):
            trace_reduce.reduce(pd, expect={"jit_fused": want})


def test_a_window_without_device_work_is_refused():
    with open(os.path.join(HERE, "testdata", "small_trace.pbtxt")) as f:
        text = f.read()
    # the window moved past every device event
    text = text.replace("events { metadata_id: 1 offset_ps: 0 duration_ps: "
                        "10000000000 }",
                        "events { metadata_id: 1 offset_ps: 20000000000 "
                        "duration_ps: 10000000000 }")
    from jax.profiler import ProfileData

    with pytest.raises(ValueError, match="no device operation"):
        trace_reduce.reduce(ProfileData.from_text_proto(text))
