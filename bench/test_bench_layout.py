"""What the yardstick counts, checked against the program's own shapes,
and the traffic's promise that every run seed serves the same work."""

import json
import os

import numpy as np
import pytest

from bench import counts, harness, reference
from bench.traffic import grpo

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def _spec(name):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name,billions", [("qwen2-1.5b", 1.54),
                                           ("chatglm3-6b.pp2", 3.39)])
def test_param_count_matches_the_program(name, billions):
    import jax

    from repro.models import model as M
    from repro.models.layers import split_tree

    spec = _spec(name)
    cfg = harness.program_config(spec)
    shapes = jax.eval_shape(lambda k: split_tree(M.init_params(cfg, k))[0],
                            jax.random.key(0))
    n_prog = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    s = reference.shape_of(spec)
    # the program pads the vocabulary to a multiple of its own
    assert counts.param_count(s, vocab=cfg.padded_vocab) == n_prog
    assert round(counts.param_count(s) / 1e9, 2) == billions


def test_every_cell_names_files_that_exist():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for cell in bench["workloads"]:
        assert os.path.isfile(os.path.join(BENCH, "configs",
                                           f"{cell['config']}.json"))
        mix = os.path.join(BENCH, "traffic", f"{cell['traffic']}.json")
        assert os.path.isfile(mix)
        with open(mix) as f:
            assert callable(harness.load_generator(json.load(f)["generator"]))
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(harness.load_reader(m["name"]))
    for c in bench["configs"]:
        spec = _spec(c["name"])
        assert spec["reduced"] == c["reduced"] and spec["source"] == c["source"]


def test_traffic_sizes_do_not_depend_on_the_seed():
    # the generator takes no run seed: every seed serves these steps
    with open(os.path.join(BENCH, "traffic", "grpo-recur.json")) as f:
        mix = json.load(f)
    a = grpo.Traffic(mix, 151936)
    first = a.step(0)
    for step in range(12):
        sa = a.step(step)
        assert sa == grpo.Traffic(mix, 151936).step(step)
        assert max(r["max_new_tokens"] for r in sa) == mix["scale"]["cap"]
        assert len(sa) == mix["problems"] * mix["group"]
        # the same problems every step, in the same order
        assert [(r["problem_id"], r["prompt"]) for r in sa] == [
            (r["problem_id"], r["prompt"]) for r in first]
    lens = np.concatenate([grpo.step_lengths(mix, s).ravel()
                           for s in range(64)])
    assert 120 <= np.median(lens) <= 160
    assert 0.03 <= (lens == mix["scale"]["cap"]).mean() <= 0.08


def test_verify_work_counts_weights_once_per_round():
    s = reference.shape_of(_spec("chatglm3-6b.pp2"))
    f1, b1 = counts.verify_work(s, rounds=1, block_tokens=64,
                                context_reads=0, attn_pairs=0)
    f2, b2 = counts.verify_work(s, rounds=2, block_tokens=64,
                                context_reads=0, attn_pairs=0)
    assert b2 - b1 == counts.matmul_params(s) * counts.WEIGHT_BYTES
    assert 6.2e9 < b1 < 6.4e9 and f1 == f2


def _run_cli(cwd, *args):
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "qwen2-1.5b.grpo-recur", "--seed", str(2**35), "--seconds", "1",
         "--trace", "0", *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_cli_refuses_without_a_tpu():
    p = _run_cli(ROOT)
    assert p.returncode != 0 and p.stdout == ""
    assert "no TPU" in p.stderr


def test_cli_refuses_without_the_program(tmp_path):
    import shutil

    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_cli(tmp_path)
    assert p.returncode != 0 and p.stdout == ""
