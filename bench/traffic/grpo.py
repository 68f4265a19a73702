"""GRPO rollout traffic: groups of rollouts that share a prompt, served
one RL step at a time through ``SpecEngine.serve``.

A mix file (``bench/traffic/<mix>.json``) names this generator
(``"generator": "grpo"``) and its parameters:

- ``problems`` x ``group``: rollouts per step (a GRPO group shares one
  prompt of ``prompt_len`` random tokens);
- ``scale``: each problem's length scale, drawn once per problem from
  a Pareto law (``alpha``, from ``low``, capped at ``cap``). The scales
  are the law's quantiles at ``(i + 0.5) / problems``, so every run
  holds the same set of problems;
- ``rollout_sigma``, ``min_new``: each rollout's length is
  ``clip(scale * LogNormal(0, rollout_sigma), min_new, cap)``, drawn
  again every step: lengths are stable per problem, spread per rollout;
- ``recurring``: the same problems come back every step, as RL epochs
  revisit a dataset;
- ``sizes_seed``, ``problems_seed``: the seeds of every length, and of
  the prompts' tokens and the problems' order within a step. Neither
  depends on the run's ``--seed``, so every seed serves the same
  problems at the same sizes in the same order, with the same policy
  (the configuration's ``weights_seed``): the work of a step is fixed.
  (The order alone moved a step's rounds by a quarter.) The run's seed
  draws the requests that the reference checks;
- ``warm_steps``: steps served in set-up before the window;
- ``policy_drift``: the per-step RL update, a redraw of the norm scales
  (``bench/weights.py``);
- ``profile``: with ``--trace 1``, the window's first step is profiled
  for ``rounds`` rounds after its first ``after`` (``bench/annotate.py``).

Random weights almost never emit EOS, so a rollout's length is carried
in its ``max_new_tokens``. The engine's length predictor reads its own
history, not ``max_new_tokens``.

``run(ctx)`` is the generator's whole run: set-up (weights, engine,
warm steps, every shape the window meets), then the window: whole RL
steps back to back, one ``serve`` call each, the policy moved and
``begin_iteration`` called between steps, as a trainer does. No step
starts after ``ctx.seconds``; the step in progress finishes.
"""

from __future__ import annotations

import time

import numpy as np

SAMPLE_REQUESTS = 8  # finished window requests compared with the reference


def problem_scales(mix) -> np.ndarray:
    """Length scale per problem: Pareto quantiles, capped."""
    sc = mix["scale"]
    n = int(mix["problems"])
    q = (np.arange(n) + 0.5) / n
    raw = sc["low"] * (1.0 - q) ** (-1.0 / sc["alpha"])
    return np.minimum(np.floor(raw), sc["cap"]).astype(np.int64)


def step_lengths(mix, step: int) -> np.ndarray:
    """(problems, group) ``max_new_tokens`` of RL step ``step``; the
    same for every run seed."""
    rng = np.random.default_rng([int(mix["sizes_seed"]), 1, int(step)])
    scale = problem_scales(mix).astype(np.float64)
    ln = rng.lognormal(0.0, mix["rollout_sigma"],
                       (len(scale), int(mix["group"])))
    cap = mix["scale"]["cap"]
    return np.clip(np.round(scale[:, None] * ln), mix["min_new"],
                   cap).astype(np.int64)


def prompt_lengths(mix) -> np.ndarray:
    lo, hi = mix["prompt_len"]
    rng = np.random.default_rng([int(mix["sizes_seed"]), 0])
    return rng.integers(lo, hi + 1, int(mix["problems"]))


class Traffic:
    """The requests of each RL step; the same for every run seed."""

    def __init__(self, mix, vocab: int) -> None:
        if not mix.get("recurring", True):
            raise ValueError("only recurring problems are built so far")
        self.mix = mix
        self.group = int(mix["group"])
        prng = np.random.default_rng([int(mix["problems_seed"]), 7])
        self.prompts = [prng.integers(4, vocab, int(n)).tolist()
                        for n in prompt_lengths(mix)]
        self.order = prng.permutation(int(mix["problems"]))
        cap = mix["scale"]["cap"]
        self.cap = int(cap)

    def step(self, step: int) -> list:
        """Request dicts of step ``step``: ``rid``, ``problem_id``,
        ``prompt``, ``max_new_tokens``. Every step holds a rollout at
        the cap, so the slot pool has one geometry for the whole run."""
        lens = step_lengths(self.mix, step)
        if lens.max() < self.cap:
            raise ValueError(
                f"step {step} has no rollout at the cap {self.cap}: pick "
                "another sizes_seed")
        reqs = []
        for p in self.order:
            for g in range(self.group):
                reqs.append({
                    "rid": step * 100_000 + len(reqs),
                    "problem_id": f"p{int(p)}",
                    "prompt": self.prompts[p],
                    "max_new_tokens": int(lens[p, g]),
                })
        return reqs


def _aggregate(steps, slots: int) -> dict:
    """Window totals from the steps' ``RolloutStats`` and requests."""
    eff = [b for r in steps for b in r["stats"].effective_batch]
    reqs = [q for r in steps for q in r["requests"]]
    return {
        "slots": slots,
        "steps": len(steps),
        "window_s": sum(r["seconds"] for r in steps),
        "rounds": sum(r["stats"].n_rounds for r in steps),
        "effective_batch": eff,
        "drafted": sum(r["stats"].n_drafted for r in steps),
        "accepted": sum(r["stats"].n_accepted for r in steps),
        "host_time_s": sum(r["stats"].host_time_s for r in steps),
        "emitted_tokens": sum(len(q.output) for q in reqs),
        "request_rounds": sum(q.rounds for q in reqs),
        "sequences": [(len(q.prompt), len(q.output)) for q in reqs],
    }


def _sample(steps, seed: int, n: int) -> list:
    """Finished window requests to compare: the longest, and others
    drawn from the seed."""
    reqs = [(rec["step"], r) for rec in steps for r in rec["requests"]
            if r.output]
    if not reqs:
        return []
    longest = max(range(len(reqs)), key=lambda i: (len(reqs[i][1].output),
                                                   -reqs[i][1].rid))
    rest = [i for i in range(len(reqs)) if i != longest]
    rng = np.random.default_rng([int(seed), 11])
    pick = [longest] + list(rng.choice(rest, min(n - 1, len(rest)),
                                       replace=False))
    return [reqs[i] for i in pick]


def run(ctx) -> dict:
    """Set-up and window of one run; returns the run record that the
    harness compares and the metric readers read."""
    import gc

    import jax

    from bench import serving, weights
    from repro.launch import serve

    spec, mix, cfg = ctx.spec, ctx.mix, ctx.cfg
    slots = int(spec["slots"])
    drift = float(mix["policy_drift"])
    wseed = int(spec["weights_seed"])
    base = weights.make_params(cfg, wseed)
    traffic = Traffic(mix, cfg.vocab_size)
    tel = None
    if ctx.trace_dir:
        from bench.annotate import Annotations

        tel = Annotations()
    eng = serve.make_engine(weights.with_step_norms(base, wseed, 0, drift),
                            cfg, telemetry=tel)
    geom = serving.FusedGeometry(eng)
    warm = []
    for step in range(int(mix["warm_steps"])):
        eng.set_params(weights.with_step_norms(base, wseed, step, drift))
        warm.append(serving.serve_step(eng, traffic.step(step), slots, step))
        eng.begin_iteration(step + 1)
    geom.remove()
    n_adm = serving.warm_admissions(eng, slots)
    n_fused = serving.warm_next_forest(eng, geom, slots)
    jax.block_until_ready(eng.params)
    mark = (ctx.meter.n, ctx.meter.cache_hits, eng.compile_count())
    setup_s = time.perf_counter() - ctx.t_start
    ctx.log(f"setup {setup_s:.2f} s: {ctx.meter.n} backend compiles "
            f"({ctx.meter.seconds:.1f} s), {ctx.meter.cache_hits} cache "
            f"hits, warm steps {[round(w['seconds'], 2) for w in warm]}, "
            f"{n_adm} admission shapes, {n_fused} fused rounds at the "
            f"next forest size")

    profile = None
    steps = []
    step = int(mix["warm_steps"])
    t_w0 = time.perf_counter()
    while not steps or time.perf_counter() - t_w0 < ctx.seconds:
        eng.set_params(weights.with_step_norms(base, wseed, step, drift))
        prof = None
        if ctx.trace_dir and profile is None:
            from bench.annotate import RoundProfile

            p = mix["profile"]
            prof = profile = RoundProfile(ctx.trace_dir, p["after"],
                                          p["rounds"], None)
        steps.append(serving.serve_step(eng, traffic.step(step), slots,
                                        step, profile=prof))
        eng.begin_iteration(step + 1)
        step += 1
    window_s = time.perf_counter() - t_w0 - (
        profile.overhead_s if profile else 0.0)
    compiles = ctx.meter.n - mark[0]
    loads = ctx.meter.cache_hits - mark[1]
    new_programs = eng.compile_count() - mark[2]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in ctx.devices)
    rec = _aggregate(steps, slots)
    rec.update(window_s=window_s, setup_s=setup_s,
               memory_peak_bytes=int(peak),
               attempted=sum(len(r["requests"]) for r in steps),
               failed=sum(len(r["requests"]) - r["finished"] for r in steps))
    rec["notes"] = {"compiles": compiles, "cache_loads": loads,
                    "new_programs": new_programs,
                    "step_seconds": [r["seconds"] for r in steps]}
    if profile is not None:
        if profile.state != "done":
            raise RuntimeError(
                f"the profile of rounds {profile.after}.."
                f"{profile.after + profile.rounds} did not run: the step "
                f"had {profile.consumed} rounds")
        first = steps[0]
        rec["traced"] = serving.profiled_work(
            first["requests"], first["stats"], profile.start, profile.end)
        rec["trace_dir"] = ctx.trace_dir
        rec["trace_expect"] = {"jit_fused": profile.dispatched}
        rec["notes"]["trace_stop_s"] = profile.stop_s
        rec["notes"]["trace_overhead_s"] = profile.overhead_s
    ctx.log(f"window {window_s:.2f} s, {len(steps)} steps "
            f"{[round(r['seconds'], 2) for r in steps]}, compiles in window "
            f"{compiles} (cache loads {loads}, new programs {new_programs})"
            + (f", profile of {profile.dispatched} rounds written in "
               f"{profile.stop_s:.1f} s" if profile else ""))

    sample = _sample(steps, ctx.seed, SAMPLE_REQUESTS)
    eos = eng.engine.eos_token
    eng.params = None
    del eng, base, warm, steps
    gc.collect()

    groups = []
    for st in sorted({s for s, _ in sample}):
        seqs, starts = [], []
        for s, r in sample:
            if s != st:
                continue
            served = list(r.output)
            if len(served) < r.max_new_tokens:
                served.append(eos)  # the EOS the engine stripped
            seqs.append(list(r.prompt) + served)
            starts.append(len(r.prompt))
        groups.append((_policy(cfg, wseed, st, drift), seqs, starts))
    rec["reference"] = groups
    return rec


def _policy(cfg, wseed: int, step: int, drift: float):
    """The weights of RL step ``step``, made again for the reference."""
    def make():
        from bench import weights

        return weights.with_step_norms(weights.make_params(cfg, wseed),
                                       wseed, step, drift)

    return make
