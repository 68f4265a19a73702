#!/usr/bin/env python3
"""Choose a traffic mix's ``policy_drift`` by one sweep on the chip.

    python3 bench/sweep_drift.py --workload qwen2-1.5b.grpo-recur \\
        --drifts 0,0.003,0.01,0.03 --seed 5

For each drift, in one process: a fresh engine serves the mix's warm
steps, then one recurring step (the same problems again, the policy
moved by that drift) and one step of problems never seen (new prompts,
the same sizes). Each prints one JSON line with the steps' tokens per
row-round, acceptance and seconds. Take the lowest drift at which the
recurring step's tokens per row-round sit clearly between the unseen
step's and drift 0's. Needs a TPU, like ``run.py``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--drifts", required=True)
    ap.add_argument("--seed", type=int, default=5)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    import jax
    import numpy as np

    if jax.devices()[0].platform != "tpu":
        print("sweep_drift: no TPU", file=sys.stderr)
        return 2
    from bench import harness, serving, weights
    from bench.traffic.grpo import Traffic
    from repro.launch import serve

    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = harness.find_cell(bench, args.workload)
    spec = harness.load_json(os.path.join(harness.BENCH_DIR, "configs",
                                          f"{cell['config']}.json"))
    mix = harness.load_json(os.path.join(harness.BENCH_DIR, "traffic",
                                         f"{cell['traffic']}.json"))
    cfg = harness.program_config(spec)
    slots = int(spec["slots"])
    wseed = int(spec["weights_seed"])
    base = weights.make_params(cfg, wseed)
    for drift in [float(x) for x in args.drifts.split(",")]:
        traffic = Traffic(mix, cfg.vocab_size)
        eng = serve.make_engine(
            weights.with_step_norms(base, wseed, 0, drift), cfg)
        W = int(mix["warm_steps"])
        for step in range(W + 1):
            eng.set_params(weights.with_step_norms(base, wseed, step, drift))
            rec = serving.serve_step(eng, traffic.step(step), slots, step)
            eng.begin_iteration(step + 1)
        rng = np.random.default_rng([args.seed, 99])
        fresh = traffic.step(W + 1)
        prompts = {}
        for r in fresh:
            pid = r["problem_id"]
            if pid not in prompts:
                prompts[pid] = rng.integers(4, cfg.vocab_size,
                                            len(r["prompt"])).tolist()
            r["problem_id"], r["prompt"] = "new-" + pid, prompts[pid]
        new = serving.serve_step(eng, fresh, slots, W + 1)
        out = {"drift": drift}
        for name, r in (("recurring", rec), ("unseen", new)):
            reqs, st = r["requests"], r["stats"]
            out[name] = {
                "seconds": r["seconds"], "rounds": st.n_rounds,
                "tokens_per_row_round": sum(len(q.output) for q in reqs)
                / max(sum(q.rounds for q in reqs), 1),
                "accept_rate": st.n_accepted / max(st.n_drafted, 1),
            }
        print(json.dumps(out), flush=True)
        del eng
    return 0


if __name__ == "__main__":
    t0 = time.perf_counter()
    rc = main()
    print(f"sweep_drift: {time.perf_counter() - t0:.1f} s", file=sys.stderr)
    sys.exit(rc)
