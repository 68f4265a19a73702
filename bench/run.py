#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python3 bench/run.py --workload qwen2-1.5b.grpo-recur --seed 7 \\
        --seconds 10 --trace 0

``--trace 0`` reports the cell's end-to-end metrics (``step_s``,
``setup_s``); ``--trace 1`` profiles a bounded run of rounds inside the
window's first step (the mix's ``profile``) and reports the per-layer
metrics, with ``device.busy_s``/``window_s`` and a ``breakdown``. The last line of standard output is one JSON object;
the numbers compared with the reference (and their limits) close it,
under ``compared``, and are repeated as the last lines of standard
error. Without a TPU, or with fewer chips than the cell asks for, the
command prints no result and exits 2.

JAX's persistent compilation cache lives in ``<checkout>/.jax_cache``,
so only a checkout's first run of a cell compiles.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def _err(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="also judge the float8 control in the "
                         "program's place on the served tokens (for "
                         "setting limits; off in the benchmark's runs)")
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative integer")

    bench_path = os.path.join(ROOT, "BENCHMARK.json")
    if not os.path.isfile(os.path.join(ROOT, "src", "repro", "__init__.py")):
        _err("the program (src/repro) is not in this checkout")
        return 2
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    from bench import harness

    bench = harness.load_json(bench_path)
    cell = harness.find_cell(bench, args.workload)
    try:
        devs = jax.devices()
    except RuntimeError as e:
        _err(f"JAX found no devices: {e}")
        return 2
    if devs[0].platform != "tpu":
        _err(f"no TPU (JAX found {devs[0].platform}); nothing was run")
        return 2
    if len(devs) < cell["chips"]:
        _err(f"{args.workload} needs {cell['chips']} chips, "
             f"JAX found {len(devs)}")
        return 2
    result = harness.run(
        args.workload, args.seed, args.seconds, bool(args.trace),
        t_start=T_START, bench=bench, log=_err, control=bool(args.control),
    )
    for name, c in result.get("control", {}).get("compared", {}).items():
        _err(f"control {name}: {c['value']!r} (limit {c['limit']!r})")
    if "control" in result:
        _err(f"control correct: {result['control']['correct']}")
    for name, c in result["compared"].items():
        _err(f"compared {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
