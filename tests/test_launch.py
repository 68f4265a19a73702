"""Entry points: the serving CLI, ``chip_smoke.py`` at CPU sizes, the
compile-cache helper, and what importing the package must not do."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402
from repro.launch import serve  # noqa: E402
from repro.launch.compile_cache import (  # noqa: E402
    DEFAULT_DIR,
    configure_compile_cache,
)


def _env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    env.update(extra)
    return env


def test_imports_start_no_backend_and_shards_stay_free_of_jax():
    """Importing the engine initializes no JAX backend (a process that
    imports it can still hand the chip to another), and a history shard
    process never imports JAX at all."""
    code = (
        "import sys\n"
        "import repro.history.service\n"
        "assert 'jax' not in sys.modules, 'history.service imported jax'\n"
        "import repro.core.spec_engine\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge.backends_are_initialized()\n"
    )
    subprocess.run([sys.executable, "-c", code], env=_env(), check=True,
                   timeout=120)


def test_compile_cache_honours_env_else_fixed_checkout_path(monkeypatch):
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert configure_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before  # untouched
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        assert configure_compile_cache() == str(DEFAULT_DIR)
        assert jax.config.jax_compilation_cache_dir == str(DEFAULT_DIR)
        assert DEFAULT_DIR == __import__("pathlib").Path(ROOT) / ".jax_cache"
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        compilation_cache.reset_cache()


def test_grpo_requests_shape_and_repeat():
    reqs = serve.grpo_requests(3, n_problems=8, vocab=1000,
                               **serve.GRPO_TRAFFIC)
    assert len(reqs) == 8 * serve.GROUP
    assert [r.rid for r in reqs] == list(range(len(reqs)))
    lo, hi = serve.GRPO_TRAFFIC["prompt_len"]
    caps = np.array([r.max_new_tokens for r in reqs])
    assert caps.min() >= 64 and caps.max() <= 2048
    assert np.median(caps) < 256 < caps.max()  # short bulk, long tail
    for p in range(8):
        group = reqs[p * serve.GROUP:(p + 1) * serve.GROUP]
        assert len({r.problem_id for r in group}) == 1
        assert all(r.prompt == group[0].prompt for r in group)
        assert lo <= len(group[0].prompt) <= hi
        assert min(group[0].prompt) >= 4 and max(group[0].prompt) < 1000
    again = serve.grpo_requests(3, n_problems=8, vocab=1000,
                                **serve.GRPO_TRAFFIC)
    assert [(r.prompt, r.max_new_tokens) for r in again] == \
        [(r.prompt, r.max_new_tokens) for r in reqs]


@pytest.mark.parametrize("mode", [
    ["--continuous", "--slots", "4"],
    ["--history-service", "--service-mode", "thread", "--workers", "2",
     "--slots", "4"],
])
def test_serve_cli_smoke(mode, tmp_path):
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--arch",
         "qwen2-1.5b", "--smoke", "--rounds", "2", "--drain-deadline", "0",
         *mode],
        env=_env(JAX_COMPILATION_CACHE_DIR=str(tmp_path)), cwd=tmp_path,
        capture_output=True, text=True, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    assert out.stderr.count("round 1:") == 1, out.stderr[-3000:]


def test_chip_smoke_refuses_without_tpu(capsys):
    assert chip_smoke.main([]) != 0
    assert capsys.readouterr().out == ""


def test_chip_smoke_one_chip_phase_at_cpu_size(capsys):
    """The single-chip schedule end to end on the reduced config: set-up
    pass, zero compiles in the warm window, fused rounds with drafts in
    epoch 2, and plain decoding token-identical (float32: no ties)."""
    cfg, params = serve.load_model("qwen2-1.5b", smoke=True)
    res = chip_smoke.one_chip(cfg, params, traffic=serve.SMOKE_TRAFFIC,
                              n_problems=2, slots=8)
    assert res == {"diverged": 0}
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    phases = [x["phase"] for x in lines]
    assert phases == ["setup", "warm_window", "epoch1", "epoch2", "plain",
                      "epoch2_vs_plain_identity"]
    assert lines[1]["new_compile_count"] == 0


def test_chip_smoke_four_workers_one_per_device():
    """The four-chip phase on four virtual CPU devices: one params
    replica per device and outputs identical to one worker."""
    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "import chip_smoke\n"
        "from repro.launch import serve\n"
        "cfg, params = serve.load_model('qwen2-1.5b', smoke=True)\n"
        "res = chip_smoke.four_chips(cfg, params, n_problems=4, slots=4,\n"
        "                            traffic=serve.SMOKE_TRAFFIC)\n"
        "assert res == {'diverged': 0}, res\n" % ROOT
    )
    out = subprocess.run(
        [sys.executable, "-c", code],
        env=_env(XLA_FLAGS="--xla_force_host_platform_device_count=4"),
        capture_output=True, text=True, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    workers = next(json.loads(x) for x in out.stdout.splitlines()
                   if '"workers"' in x)
    assert len(set(workers["live_array_bytes"])) == 1


def test_near_tie_judge_admits_only_tokens_at_the_best_logit():
    from repro.models import model as M

    cfg, params = serve.load_model("qwen2-1.5b", smoke=True)
    judge = chip_smoke.NearTieJudge(params, cfg, eos=1)
    prompt = [5, 6, 7, 8, 9]
    toks = np.zeros((1, 256), np.int32)
    toks[0, -len(prompt):] = prompt
    logits = np.asarray(M.prefill(
        params, cfg, toks, toks > 0, max_len=256
    )[0][0, :cfg.vocab_size])
    top, worst = int(logits.argmax()), int(logits.argmin())
    tie = judge(prompt, [top], [top], 0)
    assert tie["admitted"] and tie["below_best"] == [0.0, 0.0]
    far = judge(prompt, [top], [worst], 0)
    assert not far["admitted"]
    assert far["below_best"][1] > far["bound"]
