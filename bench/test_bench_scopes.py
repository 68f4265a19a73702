"""Device time per phase scope of the fused round, on a small trace in
the TPU profiler's format (``testdata/scoped_trace.pbtxt``): two fused
rounds at 1-3 and 5-7 ms whose operations carry the scope path in their
metadata's ``tf_op`` stat; the draft walk's and the layer scan's
``while`` carry none, the operations of their bodies do; a pool copy
with no scope; and a prefill at 8-9 ms."""

import os
import re

import pytest

from bench import scopes, trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
TRACE = os.path.join(HERE, "testdata", "scoped_trace.pbtxt")


@pytest.fixture(scope="module")
def space():
    return scopes.load(TRACE)


def test_seconds_per_scope_of_each_program(space):
    got = scopes.reduce(space)
    # round 1 leaves 0.05 ms of each loop, the copy and 0.1 ms of the
    # program unscoped; round 2's operations fill it
    assert got["jit_fused"] == pytest.approx({
        "propose": 0.00085, "forward": 0.00195, "accept": 0.0006,
        "commit": 0.0003, "unscoped": 0.0003})
    assert got["jit_prefill_fn"] == pytest.approx({"unscoped": 0.001})


def test_scopes_add_up_to_the_program_time(space):
    programs = trace_reduce.reduce(trace_reduce.load(TRACE))["programs"]
    for prog, times in scopes.reduce(space).items():
        assert sum(times.values()) == pytest.approx(programs[prog])


def test_top_ops_by_name_with_their_scope(space):
    top = {name: (scope, s) for name, scope, s in scopes.top_ops(space)}
    assert top["while.1"] == (None, pytest.approx(0.002))
    assert top["fusion.2"] == ("forward", pytest.approx(0.001))
    assert top["fusion.8"] == ("propose", pytest.approx(0.00085))
    assert top["copy.9"] == (None, pytest.approx(0.0001))


def test_a_fused_round_without_scopes_is_refused():
    from jax.profiler import ProfileData

    with open(TRACE) as f:
        text = re.sub(r"\s*stats \{ metadata_id: 1 [^}]*\}", "", f.read())
    space = scopes._space_class()()
    space.ParseFromString(ProfileData.text_proto_to_serialized_xspace(text))
    with pytest.raises(ValueError, match="jit_fused"):
        scopes.reduce(space)
    # a program not required to carry scopes reads as unscoped
    assert scopes.reduce(space, require=())["jit_fused"] == pytest.approx(
        {"unscoped": 0.004})


@pytest.mark.parametrize("op_name,scope", [
    ("jit(fused)/propose/jit(suffix_match_propose_ref)/vmap()/while",
     "propose"),
    ("jit(fused)/forward/while/body/closed_call/dot_general:", "forward"),
    ("jit(verify_fn)/commit/add", "commit"),
    ("jit(prefill_fn)/dot_general", None),
])
def test_scope_of_an_op_name(op_name, scope):
    assert scopes.scope_of(op_name) == scope
