"""One run of one benchmark cell: the traffic generator's set-up and
window, the reduction of its profile, the comparison with the
reference, and the result line.

Everything is found by name, so a new cell, configuration, mix or metric
is new files and entries, never an edit here:

- a cell (``BENCHMARK.json`` -> ``workloads``) names a configuration
  (``bench/configs/<config>.json``) and a traffic mix
  (``bench/traffic/<mix>.json``);
- the mix names its generator, ``bench/traffic/<generator>.py``, whose
  ``run(ctx)`` owns the set-up and the window and returns the run
  record (see ``Context``);
- every metric, end-to-end or per-layer, is a reader
  ``bench/metrics/<metric>.py`` whose ``read(run)`` takes the run
  record (plus ``trace``, ``shape`` and ``peaks``) and returns a number,
  or None where it finds nothing to read.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import shutil
import time

import numpy as np

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def find_cell(bench: dict, workload: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == workload:
            return cell
    raise KeyError(f"no workload {workload!r} in BENCHMARK.json")


def cell_metrics(bench: dict, workload: str, kind: str) -> list:
    """The ``end_to_end`` or ``per_layer`` entries this cell reports."""
    return [m for m in bench[kind]
            if workload in m.get("workloads", [workload])]


def program_config(spec):
    """The program's ``ModelConfig`` for a configuration file: the repo
    config named by ``arch`` (its mechanisms: RoPE kind, biases, MLP),
    with every shape from the file's published keys."""
    from bench.reference import shape_of
    from repro.configs import get_config

    s = shape_of(spec)
    cfg = get_config(spec["arch"]).replace(
        num_layers=s["layers"], d_model=s["d"], num_heads=s["heads"],
        num_kv_heads=s["kv_heads"], head_dim=s["head_dim"], d_ff=s["ff"],
        vocab_size=s["vocab"], norm_eps=s["eps"], rope_theta=s["rope_theta"],
        tie_embeddings=s["tied"], attn_bias=s["qkv_bias"],
        dtype=spec["dtype"], **spec.get("program", {}),
    )
    rot = int(cfg.head_dim * (cfg.rope_fraction if cfg.rope == "partial"
                              else 1.0))
    if rot != s["rope_dims"] or cfg.mlp != "swiglu" or cfg.norm != "rms":
        raise ValueError(f"{spec['arch']} does not compute what "
                         f"{spec['name']} states")
    return cfg


def _load_module(kind: str, name: str):
    path = os.path.join(BENCH_DIR, kind, f"{name}.py")
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def load_reader(name: str):
    """``read`` of ``bench/metrics/<name>.py``."""
    return _load_module("metrics", name).read


def load_generator(name: str):
    """``run`` of ``bench/traffic/<name>.py``."""
    return _load_module("traffic", name).run


@dataclasses.dataclass
class Context:
    """What a generator's ``run(ctx)`` gets. It returns the run record:
    ``setup_s``, ``attempted``, ``failed``, ``memory_peak_bytes``,
    ``reference`` (a list of ``(make_params, seqs, starts)``: the served
    sequences to check, each a prompt and its served tokens, and the
    weights they were served with), whatever its metrics read, and, in a
    profiled run, ``trace_dir`` and ``trace_expect`` (the executions of
    each device program the profile must hold)."""

    spec: dict
    mix: dict
    cfg: object
    seed: int
    seconds: float
    devices: list
    t_start: float
    meter: object
    log: object
    trace_dir: str | None


def compare(shape, groups, *, control: bool = False) -> dict:
    """The served tokens against the float32 reference: per position,
    the gap by which a served token's logit lies below the reference's
    best. With ``control``, also the gaps of the tokens the float8
    control puts first on the same positions (the control in the
    program's place)."""
    from bench import reference

    served, ctl = [], []
    for make_params, seqs, starts in groups:
        params = make_params()
        g, c = reference.logit_gaps(shape, params, seqs, starts,
                                    control=control)
        served += g
        if c is not None:
            ctl += c
        del params
    out = {"served": np.concatenate(served) if served else np.zeros(0)}
    if control:
        out["control"] = np.concatenate(ctl) if ctl else np.zeros(0)
    return out


def judge(gaps, failed: int, limits: dict) -> tuple:
    """``(compared, correct)`` for one side's gaps: each number beside
    its limit, and whether every number keeps to it. An empty side (no
    finished request) is not correct."""
    compared = {
        "max_logit_gap": {"value": float(gaps.max()) if gaps.size
                          else float("inf"),
                          "limit": limits["max_logit_gap"]},
        "unfinished": {"value": int(failed), "limit": 0},
    }
    return compared, all(c["value"] <= c["limit"] for c in compared.values())


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, bench: dict | None = None, log=print,
        spec: dict | None = None, mix: dict | None = None,
        control: bool = False) -> dict:
    """One run; returns the result line's object. ``spec`` and ``mix``
    stand in for the cell's configuration and traffic files (tests run
    small ones on the CPU). ``control`` also judges the float8 control
    put in the program's place (``control``); the benchmark's own runs
    leave it off."""
    import jax

    from bench import counts, reference, trace_reduce
    from bench.compile_meter import CompileMeter

    bench = bench or load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = find_cell(bench, workload)
    spec = spec or load_json(os.path.join(BENCH_DIR, "configs",
                                          f"{cell['config']}.json"))
    mix = mix or load_json(os.path.join(BENCH_DIR, "traffic",
                                        f"{cell['traffic']}.json"))
    limits = spec["correct"]
    if limits.get("max_logit_gap") is None:
        raise ValueError(f"{spec['name']} has no correctness limit yet")
    devs = jax.devices()[: cell["chips"]]
    trace_dir = None
    if trace:
        trace_dir = os.path.join(ROOT, ".bench_out",
                                 f"trace-{workload}-{seed}")
    ctx = Context(spec=spec, mix=mix, cfg=program_config(spec), seed=seed,
                  seconds=seconds, devices=devs, t_start=t_start,
                  meter=CompileMeter(), log=log, trace_dir=trace_dir)
    rec = load_generator(mix["generator"])(ctx)

    reduced = None
    if trace:
        files = [os.path.join(dp, f) for dp, _, fs in os.walk(trace_dir)
                 for f in fs if f.endswith(".xplane.pb")]
        if len(files) != 1:
            raise RuntimeError(f"the profile wrote {len(files)} traces")
        reduced = trace_reduce.reduce(trace_reduce.load(files[0]),
                                      expect=rec["trace_expect"])
        shutil.rmtree(trace_dir, ignore_errors=True)

    shape = reference.shape_of(spec)
    t_ref = time.perf_counter()
    cmp = compare(shape, rec.pop("reference"), control=control)
    log(f"reference over {cmp['served'].size} served tokens in "
        f"{time.perf_counter() - t_ref:.2f} s")
    compared, correct = judge(cmp["served"], rec["failed"], limits)

    run_rec = dict(rec, shape=shape, trace=reduced, peaks=(
        counts.load_peaks(devs[0].device_kind) if trace else None))
    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in cell_metrics(bench, workload, kind):
        v = load_reader(m["name"])(run_rec)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs),
              "memory_peak_bytes": rec["memory_peak_bytes"]}
    result = {"correct": bool(correct), "attempted": rec["attempted"],
              "failed": rec["failed"], "metrics": metrics, "device": device}
    if trace:
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        result["breakdown"] = {
            "device_ops": (sorted(([k, v] for k, v in
                                   reduced["programs"].items()),
                                  key=lambda kv: -kv[1])[:5]
                           + reduced["ops"][:5]),
            "idle_gaps": reduced["idle_gaps"],
        }
    served = cmp["served"]
    result["window"] = dict(
        rec["notes"], seconds=rec["window_s"], steps=rec.get("steps"),
        rounds=rec.get("rounds"), setup_s=rec["setup_s"],
        compared_tokens=int(served.size),
        mean_logit_gap=float(served.mean()) if served.size else None,
        mismatch_share=float((served > 0).mean()) if served.size else None)
    if control:
        c_compared, c_correct = judge(cmp["control"], rec["failed"], limits)
        result["control"] = {
            "correct": bool(c_correct), "compared": c_compared,
            "mismatch_share": float((cmp["control"] > 0).mean())
            if cmp["control"].size else None}
    result["compared"] = compared
    return result
