"""Production launcher: serving entry point (decode/verify workloads).

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b \
        --continuous --slots 32 --requests 64
    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --smoke
    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --smoke \
        --continuous [--slots 4] [--requests 16]
    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-8b --dry-run \
        [--shape verify_8] [--multi-pod]

Without ``--smoke`` the server runs the **published config** of
``--arch`` at full width (every layer, the full vocabulary, bf16 weights
from a seed) under GRPO-shaped traffic: ``--requests`` rollouts in
groups of ``GROUP`` that share one prompt of 100–128 tokens, with
heavy-tailed ``max_new_tokens`` from 64 to 2,048. That is the shape for
an accelerator; ``chip_smoke.py`` at the repository root drives the same
functions on one TPU. ``--smoke`` serves the reduced variant
(``configs.smoke_variant``) with short prompts and outputs, which runs
on a CPU in seconds.

Each round serves the same problems again (RL epochs), so from the
second round on the suffix-tree drafter proposes from history. With
``--continuous`` the requests flow through the slot-recycling pool
(``SpecEngine.serve``: ``--slots`` device rows, longest-predicted-first
admission, fused device rounds with the default ``--scope problem``) and
completions are logged as they stream out; without it each round is one
lock-step ``generate`` batch of ``--batch`` requests. ``--dry-run``
lowers+compiles the full config's serve step on the production mesh.

``--history-dir DIR`` points the server at a persisted rollout history
(``repro.history.persist`` format): the drafter starts with warm suffix
trees and the length policy with warm per-problem priors, so the very
first requests draft against cross-epoch history instead of cold
trees. ``--save-history`` persists the (updated) history back to the
same directory on exit — run-to-run the server keeps learning.

``--history-service`` runs through the **sharded cross-worker history
service**: ``--shards`` shard subprocesses (each owning a problem range
behind the socket RPC) and ``--workers`` serving engines, one per
device round-robin over ``jax.devices()``, whose drafters publish
rollouts to — and replicate packed-forest deltas from — the shared
service, so every worker drafts from every worker's rollouts. Each round
partitions the problems across the workers (rotated), and the workers
serve their slices concurrently. Needs a tree-only ``--scope`` (problem
or global). Combined with ``--history-dir`` the service loads/saves the
sharded manifest format (``history_manifest.json`` +
``history.shard<k>.json``).

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b \
        --smoke --history-service --shards 2 --workers 2

**Observability** — ``--metrics-port P`` attaches a ``repro.obs``
``Telemetry`` (metrics registry + round-phase tracer + event log) and
serves Prometheus text on ``http://127.0.0.1:P/metrics`` (``P`` = 0
binds an ephemeral port; the chosen port is logged). Multi-worker runs
get ONE endpoint PER worker at ``P + w``, each aggregating that
worker's engine round phases, drafter/client counters and fault
gauges. ``--log-every N`` logs round-timing lines every N rounds
through ``logging`` (they also land in the structured event log).
"""

from __future__ import annotations

import argparse
import logging
import time

log = logging.getLogger("repro.launch.serve")

# GRPO group size: rollouts per problem, all sharing the problem's prompt.
GROUP = 8
# Generated traffic, inclusive (low, high) ranges per request. Random
# weights rarely emit EOS, so ``max_new_tokens`` sets each rollout's
# length: a Pareto(1) tail from the low end, capped at the high end.
SMOKE_TRAFFIC = {"prompt_len": (5, 8), "max_new": (8, 32)}
GRPO_TRAFFIC = {"prompt_len": (100, 128), "max_new": (64, 2048)}


def _setup_logging() -> None:
    if not logging.getLogger().handlers:
        logging.basicConfig(
            level=logging.INFO,
            format="%(asctime)s %(name)s %(levelname)s %(message)s",
        )


def load_model(arch: str, *, smoke: bool, seed: int = 0):
    """``(cfg, params)`` for ``arch``: the published config (full width,
    its own dtype — bf16 for the decoder configs) or, with ``smoke``,
    its reduced variant. Weights are random, drawn from ``seed`` on the
    default device op by op: one jitted program for the whole
    unrolled init compiles for over a minute at full width."""
    import jax

    from repro.configs import get_config, smoke_variant
    from repro.models import model as M
    from repro.models.layers import split_tree

    cfg = get_config(arch)
    if smoke:
        cfg = smoke_variant(cfg)
    if cfg.is_encoder_decoder:
        raise SystemExit(
            "enc-dec serving isn't wired through SpecEngine; use "
            "tests/test_models.py::test_encoder_decoder_consistency or "
            "the dry-run path"
        )
    params, _ = split_tree(M.init_params(cfg, jax.random.key(seed)))
    return cfg, params


def make_engine(params, cfg, *, scope: str = "problem", spec: bool = True,
                remote=None, telemetry=None):
    """The serving engine: greedy verification of up to 8 drafted tokens
    per round (K buckets 0/4/8) from a suffix-tree drafter. ``spec=False``
    is plain decoding through the same engine, the reference that
    speculative output must match token for token. The engine serves
    from the device its ``params`` are committed to."""
    from repro.core.drafter import DrafterConfig, SuffixDrafter
    from repro.core.spec_engine import EngineConfig, SpecEngine

    return SpecEngine(
        params, cfg,
        EngineConfig(spec_enabled=spec, max_new_tokens=32, eos_token=1,
                     max_draft=8, block_buckets=(0, 4, 8)),
        drafter=SuffixDrafter(
            DrafterConfig(scope=scope, min_match=2), remote=remote
        ),
        telemetry=telemetry,
    )


def grpo_requests(seed: int, *, n_problems: int, vocab: int,
                  prompt_len, max_new, group: int = GROUP):
    """GRPO-shaped requests: ``n_problems`` prompts of random tokens,
    each served as ``group`` rollouts that share it. The same ``seed``
    gives the same problems, so calling again serves the next epoch."""
    import numpy as np

    from repro.core.scheduler import Request

    rng = np.random.default_rng(seed)
    lo, hi = max_new
    reqs = []
    for p in range(n_problems):
        n = int(rng.integers(prompt_len[0], prompt_len[1] + 1))
        prompt = rng.integers(4, vocab, size=n).tolist()
        caps = np.minimum(hi, np.floor(lo * (1.0 + rng.pareto(1.0, group))))
        for cap in caps:
            reqs.append(Request(
                rid=len(reqs), problem_id=f"p{p}", prompt=prompt,
                max_new_tokens=int(cap),
            ))
    return reqs


def serve_epoch(eng, reqs, *, slots: int, key, watchdog=None, journal=None,
                drain=None, log_done: bool = False):
    """Serve ``reqs`` through the engine's slot pool to completion.

    Returns ``(finished requests in completion order, RolloutStats, wall
    seconds)``. The outputs are host token lists, so the wall time ends
    after the device produced the last token."""
    from repro.core.spec_engine import RolloutStats

    st = RolloutStats()
    done = []
    t0 = time.perf_counter()
    for fin in eng.serve(reqs, slots=slots, key=key, stats=st,
                         watchdog=watchdog, journal=journal, drain=drain):
        done.append(fin)
        if log_done:
            log.info(
                "  req %3d (%s) done: %4d toks, rounds %d->%d",
                fin.rid, fin.problem_id, len(fin.output), fin.admit_round,
                fin.finish_round,
            )
    return done, st, time.perf_counter() - t0


def make_workers(params, cfg, book, *, n_workers: int, scope: str = "problem",
                 telemetries=None):
    """One engine per worker, each with a drafter backed by the history
    service at ``book``. Worker ``w`` serves from ``jax.devices()[w]``
    (round-robin when there are fewer devices): its params copy and
    everything the engine allocates live on that device. Returns
    ``(engines, clients)``."""
    import jax

    from repro.history.client import HistoryClient

    devs = jax.devices()
    engines, clients = [], []
    for w in range(n_workers):
        client = HistoryClient(book, worker_id=f"w{w}")
        tel = telemetries[w] if telemetries is not None else None
        if tel is not None and tel.enabled:
            client.attach_telemetry(tel)
        engines.append(make_engine(
            jax.device_put(params, devs[w % len(devs)]), cfg, scope=scope,
            remote=client, telemetry=tel,
        ))
        clients.append(client)
    return engines, clients


def serve_workers(engines, clients, reqs, *, slots: int, key, rnd: int = 0,
                  watchdogs=None):
    """Serve ``reqs`` across the workers, one thread each: problem ``j``
    (in first-appearance order) goes to worker ``(j + rnd) % N`` — rotated
    per round, so every worker drafts from peers' history. Each worker
    flushes its publishes to the service after its slice. Returns
    ``(finished requests sorted by rid, per-worker RolloutStats, wall
    seconds)``."""
    import concurrent.futures

    import jax

    N = len(engines)
    order = list(dict.fromkeys(r.problem_id for r in reqs))
    owner = {pid: (j + rnd) % N for j, pid in enumerate(order)}
    slices = [[r for r in reqs if owner[r.problem_id] == w]
              for w in range(N)]
    keys = jax.random.split(key, N)

    def run(w):
        if not slices[w]:
            return [], None
        done, st, _ = serve_epoch(
            engines[w], slices[w], slots=slots, key=keys[w],
            watchdog=watchdogs[w] if watchdogs else None,
        )
        clients[w].flush()
        return done, st

    t0 = time.perf_counter()
    with concurrent.futures.ThreadPoolExecutor(N) as ex:
        results = [f.result() for f in [ex.submit(run, w) for w in range(N)]]
    dt = time.perf_counter() - t0
    finished = sorted((r for done, _ in results for r in done),
                      key=lambda r: r.rid)
    return finished, [st for _, st in results], dt


def _make_telemetry(args, worker: int = 0):
    """One (Telemetry, MetricsServer) pair per worker when
    ``--metrics-port`` is set; the NULL no-op telemetry otherwise.
    ``--trace-out`` forces a real telemetry (the flight recorder and
    span tracer feed the Perfetto export) even with metrics off."""
    from repro import obs

    trace_out = getattr(args, "trace_out", "")
    if args.metrics_port < 0 and not trace_out:
        return obs.NULL, None
    tel = obs.Telemetry()
    if trace_out:
        tel.attach_flight(worker=f"w{worker}")
    server = None
    if args.metrics_port >= 0:
        server = obs.MetricsServer(
            tel,
            port=(args.metrics_port + worker if args.metrics_port else 0),
        ).start()
        log.info("worker %d metrics at %s/metrics", worker, server.url)
    return tel, server


def _export_trace(args, tels, names=None) -> None:
    """Write the combined Perfetto/Chrome trace (``--trace-out``): one
    process track per worker, flow arrows across handoffs/resumes."""
    if not getattr(args, "trace_out", ""):
        return
    from repro import obs

    doc = obs.export_trace(args.trace_out, tels, names=names)
    log.info(
        "wrote trace: %d event(s) -> %s (open in ui.perfetto.dev)",
        len(doc.get("traceEvents", ())), args.trace_out,
    )


def main() -> None:
    ap = argparse.ArgumentParser(
        description="Serve an --arch config through the speculative "
                    "rollout engine: the published config at full width "
                    "(bf16, random weights), or its reduced variant "
                    "with --smoke."
    )
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="serve the reduced variant with short traffic "
                         "(CPU-sized); default: the published config at "
                         "full width with GRPO-shaped traffic")
    ap.add_argument("--dry-run", action="store_true")
    ap.add_argument("--shape", default="decode_32k",
                    choices=["decode_32k", "long_500k", "verify_8"])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--rounds", type=int, default=3,
                    help="epochs: each serves the same problems again")
    ap.add_argument("--batch", type=int, default=8,
                    help="requests per lock-step generate batch")
    ap.add_argument("--continuous", action="store_true",
                    help="serve through the slot-recycling pool")
    ap.add_argument("--slots", type=int, default=4,
                    help="device slots in the continuous pool")
    ap.add_argument("--requests", type=int, default=0,
                    help="requests per round in continuous and "
                         f"multi-worker mode, in groups of {GROUP} "
                         "(default: 2x --batch)")
    ap.add_argument("--scope", default="problem",
                    choices=["problem", "problem+request", "global"],
                    help="drafter scope: problem or global draft on "
                         "the device in one fused round; problem+request "
                         "drafts from host sessions")
    ap.add_argument("--history-dir", default="",
                    help="load persisted rollout history (warm trees + "
                         "warm length priors) from this directory")
    ap.add_argument("--save-history", action="store_true",
                    help="persist updated rollout history back to "
                         "--history-dir on exit")
    ap.add_argument("--history-service", action="store_true",
                    help="back the drafters with the sharded "
                         "cross-worker history service")
    ap.add_argument("--shards", type=int, default=2,
                    help="history-service shard count")
    ap.add_argument("--workers", type=int, default=2,
                    help="serving workers sharing the history service, "
                         "one per device round-robin")
    ap.add_argument("--service-mode", default="process",
                    choices=["process", "thread"],
                    help="spawn shards as subprocesses (real runs) or "
                         "in-process threads (debug)")
    ap.add_argument("--supervise", action="store_true",
                    help="run a shard supervisor: dead shards restart "
                         "with backoff and republish their addresses")
    ap.add_argument("--watchdog-deadline", type=float, default=120.0,
                    help="per-worker rollout watchdog deadline in "
                         "seconds (0 disables the watchdog)")
    ap.add_argument("--journal-dir", default="",
                    help="write-ahead token journal directory: every "
                         "consumed verify round is group-committed; on "
                         "startup unfinished sessions are recovered and "
                         "resumed token-identically (T=0)")
    ap.add_argument("--drain-deadline", type=float, default=30.0,
                    help="graceful-drain deadline in seconds: SIGTERM/"
                         "SIGINT stops admissions, residents past the "
                         "deadline journal-and-exit (0 disables the "
                         "handlers)")
    ap.add_argument("--metrics-port", type=int, default=-1,
                    help="serve Prometheus /metrics on this port "
                         "(0 = ephemeral; multi-worker runs bind one "
                         "endpoint per worker at PORT+w; default off)")
    ap.add_argument("--log-every", type=int, default=1,
                    help="log round-timing lines every N rounds "
                         "(0 silences them; events still recorded)")
    ap.add_argument("--trace-out", default="",
                    help="write a Perfetto/Chrome trace-event JSON of "
                         "the run (spans + per-rollout flight events, "
                         "one track per worker; open in ui.perfetto.dev)")
    args = ap.parse_args()
    if args.save_history and not args.history_dir:
        ap.error("--save-history requires --history-dir")
    if args.history_service and args.scope == "problem+request":
        ap.error("--history-service needs a tree-only scope: pass "
                 "--scope problem (or global)")

    if args.dry_run:
        # The child owns the process's devices: spawn it before this
        # process touches JAX.
        import subprocess
        import sys

        cmd = [
            sys.executable, "-m", "repro.launch.dryrun",
            "--arch", args.arch, "--shape", args.shape,
        ]
        if args.multi_pod:
            cmd.append("--multi-pod")
        raise SystemExit(subprocess.call(cmd))

    _setup_logging()
    from repro.launch.compile_cache import configure_compile_cache

    log.info("compilation cache: %s", configure_compile_cache())

    cfg, params = load_model(args.arch, smoke=args.smoke)
    if args.history_service:
        _serve_with_service(args, cfg, params)
        return
    tel, metrics_server = _make_telemetry(args)
    eng = make_engine(params, cfg, scope=args.scope, telemetry=tel)
    if args.history_dir:
        import os

        from repro.history import persist

        if os.path.exists(persist.history_path(args.history_dir)):
            persist.load_engine_history(eng, args.history_dir)
            log.info(
                "warm start: %d rollouts / %d problems from %s (epoch "
                "cursor %d, accept %.2f)",
                eng.drafter.store.n_rollouts, eng.drafter.store.n_problems,
                args.history_dir, eng.drafter.store.epoch,
                eng.drafter.store.acceptance(),
            )
        else:
            log.info("cold start: no history at %s", args.history_dir)

    def _persist_history() -> None:
        if args.history_dir and args.save_history:
            from repro.history import persist

            path = persist.save_engine_history(eng, args.history_dir)
            log.info(
                "saved history: %d rollouts -> %s",
                eng.drafter.store.n_rollouts, path,
            )

    journal, recovered = _open_journal(args, tel)
    drain = None
    if args.drain_deadline > 0:
        from repro.fault.drain import DrainController

        drain = DrainController(
            args.drain_deadline, telemetry=tel
        ).install()
    try:
        _serve_rounds(args, eng, tel, journal=journal, drain=drain,
                      recovered=recovered)
    finally:
        # Persist whatever history accumulated, interrupted or not —
        # losing a long session's rollouts defeats the warm start.
        if journal is not None:
            journal.close()
        if drain is not None:
            drain.uninstall()
        _persist_history()
        _export_trace(args, [tel])
        if metrics_server is not None:
            metrics_server.stop()


def _traffic(args, n_requests: int):
    """``grpo_requests`` arguments for a round of ``n_requests`` (the
    same problems every round)."""
    cfg_traffic = SMOKE_TRAFFIC if args.smoke else GRPO_TRAFFIC
    return dict(
        n_problems=max(1, n_requests // GROUP), **cfg_traffic
    )


def _open_journal(args, tel):
    """Open the serve-side write-ahead journal (None when --journal-dir
    is unset). An existing journal is replayed first: unfinished
    sessions come back as salvage for ``_serve_rounds`` to resume."""
    if not args.journal_dir:
        return None, {}
    import os

    from repro.fault.journal import JournalCorruptError, RolloutJournal

    os.makedirs(args.journal_dir, exist_ok=True)
    path = os.path.join(args.journal_dir, "serve.wal")
    recovered = {}
    if os.path.exists(path):
        try:
            sessions = RolloutJournal.recover(path, telemetry=tel)
        except JournalCorruptError as e:
            log.warning("journal quarantined (%s); cold start", e)
            sessions = {}
        recovered = {
            k: s for k, s in sessions.items() if s.resumable and s.tokens
        }
        log.info(
            "journal recovery: %d finished, %d in-flight session(s), "
            "%d salvaged token(s)",
            sum(s.finished for s in sessions.values()), len(recovered),
            sum(len(s.tokens) for s in recovered.values()),
        )
    journal = RolloutJournal(path, telemetry=tel)
    journal.adopt(recovered)
    return journal, recovered


def _log_round(args, tel, rnd: int, msg: str, *fmt_args, **event) -> None:
    """Round-timing line: always recorded in the structured event log,
    printed through ``logging`` every ``--log-every`` rounds."""
    tel.emit("serve_round_done", round=rnd, **event)
    if args.log_every > 0 and rnd % args.log_every == 0:
        log.info(msg, *fmt_args)


def _serve_with_service(args, cfg, params) -> None:
    """Multi-worker serving over the sharded history service: shards as
    subprocesses (or threads with ``--service-mode thread``), one engine
    per worker on its own device, each round's problems partitioned
    across workers (rotated, so every worker ends up drafting from
    peers' history)."""
    import os

    import jax

    from repro.history import persist
    from repro.history.service import HistoryService

    states = None
    if args.history_dir and (
        os.path.exists(os.path.join(args.history_dir,
                                    persist.MANIFEST_FILENAME))
        or os.path.exists(persist.history_path(args.history_dir))
    ):
        loaded = persist.load_service_history(args.history_dir)
        states = loaded["shards"]
        log.info(
            "warm start: %d shard(s) from %s%s",
            loaded["n_shards"], args.history_dir,
            " (legacy single-store payload)" if loaded["legacy"] else "",
        )
        if loaded.get("quarantined"):
            log.warning(
                "quarantined %d corrupt history file(s); affected "
                "shards cold-start", len(loaded["quarantined"]),
            )
    if args.service_mode == "thread":
        svc = HistoryService.spawn_in_process(
            args.shards, window_size=16, states=states
        )
    else:  # subprocess shards load from disk themselves
        svc = HistoryService.spawn_subprocess(
            args.shards, window_size=16,
            load_dir=args.history_dir if states is not None else None,
        )
    # Continue the restored epoch cursor: fresh engines start at 0, and
    # publishing regressed epochs would decay the session's own rollouts
    # into near-invisibility against the warm trees.
    epoch0 = max(
        (int(st["store"]["epoch"]) for st in states or []
         if st is not None),
        default=0,
    )
    # Per-worker telemetry: one registry + /metrics endpoint per worker
    # (PORT+w), each aggregating that worker's engine, drafter, client
    # and fault gauges. The service and supervisor report through the
    # lead worker's registry.
    tels, metric_servers = [], []
    for w in range(args.workers):
        tel, srv = _make_telemetry(args, worker=w)
        tels.append(tel)
        metric_servers.append(srv)
    if tels[0].enabled:
        svc.attach_telemetry(tels[0])
    supervisor = None
    if args.supervise:
        from repro.fault.supervisor import ShardSupervisor

        supervisor = ShardSupervisor(svc, seed=0, telemetry=tels[0])
        supervisor.start(interval_s=1.0)
    # svc.book is live: a supervised restart republishes the new shard
    # address to every client without reconstructing them.
    engines, clients = make_workers(
        params, cfg, svc.book, n_workers=args.workers, scope=args.scope,
        telemetries=tels,
    )
    watchdogs = None
    if args.watchdog_deadline > 0:
        from repro.fault.watchdog import RolloutWatchdog

        watchdogs = [
            RolloutWatchdog(args.watchdog_deadline, flight=tels[w].flight)
            for w in range(args.workers)
        ]
    for eng in engines:
        eng.epoch = eng.drafter.epoch = epoch0
    log.info(
        "history service: %d shard(s) [%s] x %d worker(s) at %s",
        args.shards, args.service_mode, args.workers, svc.addresses,
    )
    n_req = args.requests or 2 * args.batch
    try:
        for rnd in range(args.rounds):
            reqs = grpo_requests(0, vocab=cfg.vocab_size,
                                 **_traffic(args, n_req))
            done, stats, dt = serve_workers(
                engines, clients, reqs, slots=args.slots,
                key=jax.random.key(rnd), rnd=rnd, watchdogs=watchdogs,
            )
            stats = [st for st in stats if st is not None]
            toks = sum(len(r.output) for r in done)
            acc = sum(st.n_accepted for st in stats)
            rds = sum(st.n_rounds for st in stats)
            _log_round(
                args, tels[0], rnd,
                "round %d: %8.1f ms  %d reqs x %d worker(s) tok/s=%7.1f "
                "accept/round=%6.2f",
                rnd, dt * 1e3, len(reqs), len(engines),
                toks / max(dt, 1e-9), acc / max(rds, 1),
                ms=dt * 1e3, reqs=len(reqs), tok_per_s=toks / max(dt, 1e-9),
                accept_per_round=acc / max(rds, 1),
            )
            for eng in engines:
                eng.begin_iteration(epoch0 + rnd + 1)
        if args.history_dir and args.save_history:
            for c in clients:
                c.flush()
            path = svc.save(args.history_dir)
            log.info("saved sharded history manifest -> %s", path)
    finally:
        if supervisor is not None:
            # stop before the service so the restart loop never races
            # an intentional shutdown
            supervisor.stop()
        for c in clients:
            c.close()
        svc.stop()
        _export_trace(args, tels,
                      names=[f"w{w}" for w in range(args.workers)])
        for srv in metric_servers:
            if srv is not None:
                srv.stop()


def _resume_recovered(args, eng, journal, drain, recovered) -> None:
    """Serve the journal's unfinished sessions to completion before any
    new traffic: prompts/budgets come from the journal's begin records,
    salvaged tokens re-enter via prefix re-prefill (token-identical at
    temperature 0)."""
    import jax

    from repro.core.scheduler import Request
    from repro.fault.journal import resume_requests

    reqs = [
        Request(
            rid=i, problem_id=s.problem_id, prompt=list(s.prompt),
            max_new_tokens=s.max_new_tokens or args.batch,
            journal_key=s.key,
        )
        for i, s in enumerate(recovered.values())
    ]
    to_serve, pre_done = resume_requests(reqs, recovered)
    log.info(
        "resuming %d journaled request(s) (%d restored without serving)",
        len(to_serve), len(pre_done),
    )
    if not to_serve:
        return
    for fin in serve_epoch(eng, to_serve, slots=args.slots,
                           key=jax.random.key(0xD5), journal=journal,
                           drain=drain)[0]:
        log.info(
            "  resumed req %3d (%s) done: %3d toks (state %s)",
            fin.rid, fin.problem_id, len(fin.output), fin.state,
        )


def _serve_rounds(args, eng, tel, journal=None, drain=None,
                  recovered=None) -> None:
    import jax

    # Continue the (possibly warm-restored) epoch cursor instead of
    # rewinding to 1 — regressing it would weight stale history equal to
    # fresh rollouts and persist the regressed cursor on exit.
    base_epoch = eng.epoch

    if recovered:
        _resume_recovered(args, eng, journal, drain, recovered)

    n_req = (args.requests or 2 * args.batch) if args.continuous \
        else args.batch
    for rnd in range(args.rounds):
        reqs = grpo_requests(0, vocab=eng.cfg.vocab_size,
                             **_traffic(args, n_req))
        key = jax.random.key(rnd)
        if args.continuous:
            done, st, dt = serve_epoch(
                eng, reqs, slots=args.slots, key=key, journal=journal,
                drain=drain, log_done=True,
            )
            toks = st.n_toks_emitted
            _log_round(
                args, tel, rnd,
                "round %d: %8.1f ms  %d reqs / %d slots  makespan=%d "
                "rounds fwd=%4d tok/s=%7.1f accept/round=%6.2f",
                rnd, dt * 1e3, len(reqs), args.slots, st.n_rounds, st.n_fwd,
                toks / max(dt, 1e-9), st.acceptance_per_round,
                ms=dt * 1e3, reqs=len(reqs), fwd=st.n_fwd,
                tok_per_s=toks / max(dt, 1e-9),
                accept_per_round=st.acceptance_per_round,
            )
        else:
            t0 = time.perf_counter()
            _, st = eng.generate(
                [r.prompt for r in reqs], [r.problem_id for r in reqs],
                max_new_tokens=[r.max_new_tokens for r in reqs], key=key,
                journal=journal,
            )
            dt = time.perf_counter() - t0
            _log_round(
                args, tel, rnd,
                "round %d: %8.1f ms fwd=%4d accept/round=%6.2f",
                rnd, dt * 1e3, st.n_fwd, st.acceptance_per_round,
                ms=dt * 1e3, fwd=st.n_fwd,
                accept_per_round=st.acceptance_per_round,
            )
        if drain is not None and drain.draining:
            log.info(
                "drain (%s): stopping after round %d; unfinished "
                "progress is journaled", drain.reason, rnd,
            )
            break
        eng.begin_iteration(base_epoch + rnd + 1)


if __name__ == "__main__":
    main()
