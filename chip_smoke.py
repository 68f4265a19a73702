#!/usr/bin/env python3
"""Bring-up check: Qwen2-1.5B at full width through the fused rollout
path on a TPU, driven through the functions ``repro.launch.serve`` uses.

    python3 chip_smoke.py             # one chip
    python3 chip_smoke.py --chips 4   # four workers, one replica per chip

One chip. The published ``qwen2-1.5b`` config (28 layers, d_model 1536,
vocab 151,936, bf16 weights drawn from a seed) serves GRPO-shaped
traffic through ``SpecEngine.serve`` with the suffix drafter at scope
``problem``: 8 problems x 8 rollouts in a 32-slot pool, prompts of
100–128 tokens, heavy-tailed ``max_new_tokens`` from 64 to 2,048. Epoch
1 is cold; epoch 2 serves the same problems, so the fused round drafts
from history. A plain-decoding engine (``spec_enabled=False``) then
serves the epoch-2 requests, and the two must agree token for token.

Set-up runs that whole schedule once, so every program it needs is
compiled (or loaded from the persistent compilation cache), then resets
both engines' history to cold. The measured window repeats the schedule
and must compile nothing.

Four chips. Four serving workers behind one history service (shards in
threads), each with its params and slot pool on its own device, serve
the GRPO requests concurrently; one worker then serves the same
requests alone, and the merged outputs must match it.

Near-tie rule. bf16 verify blocks and one-token decodes may compute a
logit differently in the last bits and so flip an argmax where two
tokens nearly tie. Logits come out of a bf16 matmul, so with random
weights exact ties at the top are common (and a tie may be three-way).
A divergence between two outputs is admitted only if, in a
teacher-forced forward of the prompt plus the reference output up to
the first differing position, both differing tokens score within
``NEAR_TIE_ULPS`` bf16 ulps of the best logit. Any other divergence
fails the run.

Random weights make acceptance degenerate (the G rollouts of a problem
are identical at temperature 0): it is printed, not interpreted.

Every phase prints one JSON line. The script exits non-zero on any
failure and when JAX finds no TPU; the last line of a passing run is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "qwen2-1.5b"
N_PROBLEMS = 8  # x serve.GROUP rollouts each
SLOTS = 32
NEAR_TIE_ULPS = 4
# Four-chip phase: shorter rollouts keep four extra compiles cheap.
FOUR_CHIP_MAX_NEW = (64, 512)
WORKER_SLOTS = 16
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class SmokeFailure(Exception):
    """A check of the bring-up run failed."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def report(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


class CompileMeter:
    """Backend compiles (or persistent-cache loads) seen by this process,
    with their seconds, from JAX's own monitoring events."""

    def __init__(self) -> None:
        import jax

        self.n = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if event == BACKEND_COMPILE:
            self.n += 1
            self.seconds += duration


def first_divergence(a, b):
    """First position where token lists ``a`` and ``b`` differ (a list
    that ends first differs there), or None when they are equal."""
    for i, (x, y) in enumerate(zip(a, b)):
        if x != y:
            return i
    return None if len(a) == len(b) else min(len(a), len(b))


class NearTieJudge:
    """Admits a divergence only at a near tie of the reference's logits
    (see the module docstring)."""

    def __init__(self, params, cfg, eos: int) -> None:
        import jax

        from repro.models import model as M

        self.params, self.cfg, self.eos = params, cfg, eos

        def last_logits(params, toks, mask):
            return M.prefill(params, cfg, toks, mask,
                             max_len=toks.shape[1])[0][0, :cfg.vocab_size]

        self._fn = jax.jit(last_logits)

    def __call__(self, prompt, ref_out, test_out, pos: int) -> dict:
        import numpy as np

        ctx = list(prompt) + list(ref_out[:pos])
        width = -(-len(ctx) // 256) * 256  # few shapes, few compiles
        toks = np.zeros((1, width), np.int32)
        mask = np.zeros((1, width), bool)
        toks[0, width - len(ctx):] = ctx
        mask[0, width - len(ctx):] = True
        logits = np.asarray(self._fn(self.params, toks, mask), np.float64)
        a = ref_out[pos] if pos < len(ref_out) else self.eos
        b = test_out[pos] if pos < len(test_out) else self.eos
        best = float(logits.max())
        ulp = 2.0 ** (np.floor(np.log2(max(abs(best), 1e-30))) - 7)  # bf16
        below = [best - float(logits[a]), best - float(logits[b])]
        return {
            "pos": pos, "ref_tok": int(a), "tok": int(b),
            "n_at_best": int((logits == best).sum()),
            "below_best": below, "bound": float(NEAR_TIE_ULPS * ulp),
            "admitted": bool(max(below) <= NEAR_TIE_ULPS * ulp),
        }


def compare(reqs_ref, reqs_test, judge, label: str) -> int:
    """Token-for-token comparison by rid under the near-tie rule; returns
    the number of diverging requests, raising on any not admitted."""
    test = {r.rid: r for r in reqs_test}
    check(sorted(test) == sorted(r.rid for r in reqs_ref),
          f"{label}: request sets differ")
    diverged = []
    for r in sorted(reqs_ref, key=lambda r: r.rid):
        pos = first_divergence(r.output, test[r.rid].output)
        if pos is not None:
            diverged.append(dict(rid=r.rid, **judge(
                r.prompt, r.output, test[r.rid].output, pos)))
    report(f"{label}_identity", requests=len(reqs_ref),
           diverged=len(diverged), where=diverged)
    bad = [d for d in diverged if not d["admitted"]]
    check(not bad, f"{label}: {len(bad)} divergence(s) outside the near-tie "
                   f"rule: {bad}")
    return len(diverged)


def epoch_fields(done, st, dt) -> dict:
    toks = sum(len(r.output) for r in done)
    return {
        "makespan_s": dt, "tokens": toks, "tokens_per_s": toks / dt,
        "verify_rounds": st.n_rounds,
        "accepted_per_round": st.acceptance_per_round,
        "drafted": st.n_drafted, "accepted": st.n_accepted,
    }


def one_chip(cfg, params, *, traffic, n_problems=N_PROBLEMS, slots=SLOTS,
             seed=0) -> dict:
    """Cold and warm speculative epochs plus the plain-decoding
    reference, after a set-up pass of the same schedule."""
    import jax

    from repro.history import persist
    from repro.launch import serve

    meter = CompileMeter()
    eng = serve.make_engine(params, cfg)
    ref = serve.make_engine(params, cfg, spec=False)
    cold = [persist.engine_state(e) for e in (eng, ref)]

    def schedule():
        out = {}
        for epoch in (1, 2):
            out[epoch] = serve.serve_epoch(
                eng, serve.grpo_requests(seed, n_problems=n_problems,
                                         vocab=cfg.vocab_size, **traffic),
                slots=slots, key=jax.random.key(epoch),
            )
            eng.begin_iteration(epoch)
        out["plain"] = serve.serve_epoch(
            ref, serve.grpo_requests(seed, n_problems=n_problems,
                                     vocab=cfg.vocab_size, **traffic),
            slots=slots, key=jax.random.key(2),
        )
        return out

    t0 = time.perf_counter()
    schedule()
    report("setup", seconds=time.perf_counter() - t0,
           compile_s=meter.seconds, compiles=meter.n,
           fused_programs=len(eng._fused_jit))
    for e, state in zip((eng, ref), cold):
        persist.restore_engine(e, state)

    n0 = eng.compile_count() + ref.compile_count()
    b0 = meter.n
    res = schedule()
    new_programs = eng.compile_count() + ref.compile_count() - n0
    report("warm_window", new_compile_count=new_programs,
           backend_compiles=meter.n - b0)
    for name in (1, 2, "plain"):
        report(f"epoch{name}" if name != "plain" else "plain",
               **epoch_fields(*res[name]))
    check(new_programs == 0 and meter.n == b0,
          "the warm window compiled new programs")
    check(not eng._verify_jit and eng._fused_jit,
          "speculative rounds did not run as fused device rounds")
    check(res[2][1].n_drafted > 0,
          "epoch 2 proposed no draft tokens from history")
    judge = NearTieJudge(params, cfg, eos=eng.engine.eos_token)
    diverged = compare(res["plain"][0], res[2][0], judge, "epoch2_vs_plain")
    return {"diverged": diverged}


def four_chips(cfg, params, *, traffic, n_problems=N_PROBLEMS,
               slots=WORKER_SLOTS, seed=0, n_workers=4) -> dict:
    """Four workers, one per device, behind one history service, against
    one worker serving the same requests."""
    import jax

    from repro.history.service import HistoryService
    from repro.launch import serve

    devs = jax.devices()
    check(len(devs) >= n_workers,
          f"{n_workers} workers need {n_workers} devices, have {len(devs)}")
    svc = HistoryService.spawn_in_process(2)
    engines, clients = serve.make_workers(params, cfg, svc.book,
                                          n_workers=n_workers)
    try:
        placed = [eng.device for eng in engines]
        check(placed == devs[:n_workers],
              f"workers are not one per device: {placed}")
        reqs = serve.grpo_requests(seed, n_problems=n_problems,
                                   vocab=cfg.vocab_size, **traffic)
        done, stats, dt = serve.serve_workers(
            engines, clients, reqs, slots=slots, key=jax.random.key(1),
        )
        # Bytes of distinct live buffers per device (arrays that alias
        # one buffer count once).
        live = [sum({x.unsafe_buffer_pointer(): x.nbytes
                     for x in jax.live_arrays()
                     if x.devices() == {d}}.values())
                for d in devs[:n_workers]]
        report("workers", n_workers=n_workers, makespan_s=dt,
               tokens=sum(len(r.output) for r in done),
               verify_rounds=[st.n_rounds for st in stats if st],
               accepted=[st.n_accepted for st in stats if st],
               live_array_bytes=live,
               bytes_in_use=[(d.memory_stats() or {}).get("bytes_in_use")
                             for d in devs[:n_workers]])
        param_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
        check(all(param_bytes <= b < 2 * param_bytes for b in live),
              f"not one params replica per device: {live}")
    finally:
        for c in clients:
            c.close()
        svc.stop()
    # The reference admits every request at once: slot count changes no
    # token at temperature 0, and one admission wave compiles fewest
    # prefill shapes.
    one = serve.make_engine(engines[0].params, cfg)
    ref, st, dt = serve.serve_epoch(
        one, serve.grpo_requests(seed, n_problems=n_problems,
                                 vocab=cfg.vocab_size, **traffic),
        slots=len(reqs), key=jax.random.key(1),
    )
    report("one_worker", makespan_s=dt, verify_rounds=st.n_rounds)
    judge = NearTieJudge(engines[0].params, cfg, eos=one.engine.eos_token)
    return {"diverged": compare(ref, done, judge, "workers_vs_one")}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the single-chip epochs and plain reference; "
                         "4: only the four-worker phase and its reference")
    args = ap.parse_args(argv)

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devs[0].platform}); "
              "nothing was run", file=sys.stderr)
        return 1
    from repro.launch import serve
    from repro.launch.compile_cache import configure_compile_cache

    report("device", kind=devs[0].device_kind, count=len(devs),
           compile_cache=configure_compile_cache())
    t0 = time.perf_counter()
    cfg, params = serve.load_model(ARCH, smoke=False)
    jax.block_until_ready(params)
    report("model", arch=cfg.name, layers=cfg.num_layers,
           d_model=cfg.d_model, vocab=cfg.vocab_size, dtype=cfg.dtype,
           param_bytes=sum(x.nbytes for x in jax.tree.leaves(params)),
           seconds=time.perf_counter() - t0)
    try:
        if args.chips == 4:
            traffic = dict(serve.GRPO_TRAFFIC, max_new=FOUR_CHIP_MAX_NEW)
            four_chips(cfg, params, traffic=traffic)
            n = 4
        else:
            one_chip(cfg, params, traffic=serve.GRPO_TRAFFIC)
            n = 1
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    report("memory", peak_bytes_in_use=[
        d.memory_stats()["peak_bytes_in_use"] for d in devs[:n]])
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
