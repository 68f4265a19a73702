"""Each benchmark cell's fused round and admission prefill compile for
one TPU v5e chip at the timed shapes, and fit its HBM beside what stays
resident (the weights and the slot pool).

No chip is attached: the TPU compiler compiles for a described ``v5e``
topology, described inside a module fixture (never at import: only one
process may hold the TPU library, and every test worker imports this
file). These compiles stay in this one file.
"""

import json
import os

import jax
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from bench import harness

BENCH = os.path.dirname(os.path.abspath(__file__))
HBM_BYTES = 16e9
CONFIGS = ("qwen2-1.5b", "chatglm3-6b.pp2")


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with the persistent compilation
    cache off: an entry compiled for a described chip cannot be read
    back without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 -- any failure to describe
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            compilation_cache.reset_cache()


def _on(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _cell(name, sharding):
    """Abstract weights, pool, round state and forest as the cell serves
    them (64 slots, the longest prompt bucket and the length cap)."""
    from repro.core.fused_round import make_state
    from repro.core.spec_engine import _cache_bucket, _prompt_bucket
    from repro.kernels.suffix_match import ops as sm_ops
    from repro.launch import serve
    from repro.models import model as M
    from repro.models.layers import split_tree

    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        spec = json.load(f)
    with open(os.path.join(BENCH, "traffic", "grpo-recur.json")) as f:
        mix = json.load(f)
    cfg = harness.program_config(spec)
    slots = spec["slots"]
    params = _on(jax.eval_shape(
        lambda k: split_tree(M.init_params(cfg, k))[0], jax.random.key(0)),
        sharding)
    eng = serve.make_engine(params, cfg)
    e = eng.engine
    Tp = _prompt_bucket(mix["prompt_len"][1])
    pool_len = _cache_bucket(Tp + mix["scale"]["cap"] + e.max_draft + 2)
    cache = _on(jax.eval_shape(
        lambda: M.init_cache(cfg, slots, pool_len, e.cache_headroom)),
        sharding)
    forest, _ = sm_ops.pack_forest(
        [], min_nodes=1 << 17, min_edges=1 << 17, min_corpus=1 << 17)
    m = eng.drafter.cfg.device_tail
    state = make_state(np.zeros(slots), np.full((slots, m), -1),
                       np.ones(slots, bool), np.zeros(slots),
                       np.full(slots, 64))
    return dict(cfg=cfg, eng=eng, params=params, cache=cache, Tp=Tp,
                pool_len=pool_len, slots=slots, state=_on(state, sharding),
                forest=_on(forest, sharding),
                i32=jax.ShapeDtypeStruct((slots,), np.int32,
                                         sharding=sharding),
                key=_on(jax.eval_shape(lambda: jax.random.key(0)), sharding))


def _nbytes(tree):
    return sum(int(np.prod(x.shape)) * np.dtype(x.dtype).itemsize
               for x in jax.tree.leaves(tree))


@pytest.mark.parametrize("name", CONFIGS)
def test_fused_round_fits_v5e(one_chip, name):
    """The widest fused round (K = 8): weights, pool, forest and state
    as arguments plus the program's temporaries."""
    c = _cell(name, one_chip)
    K = max(c["eng"].engine.block_buckets)
    compiled = c["eng"]._get_fused(K, 1).lower(
        c["params"], c["forest"], c["cache"], c["state"], c["i32"],
        c["i32"], c["key"]).compile()
    ma = compiled.memory_analysis()
    used = ma.argument_size_in_bytes + ma.temp_size_in_bytes
    assert used < HBM_BYTES, (ma.argument_size_in_bytes / 1e9,
                              ma.temp_size_in_bytes / 1e9)
    assert "tpu_custom_call" not in compiled.as_text()


@pytest.mark.parametrize("name", CONFIGS)
def test_admission_prefill_fits_v5e(one_chip, name):
    """A full admission wave (every slot at the longest prompt bucket):
    its arguments, output row cache and temporaries, beside the
    resident slot pool."""
    c = _cell(name, one_chip)
    shp = (c["slots"], c["Tp"])
    tok = jax.ShapeDtypeStruct(shp, np.int32, sharding=one_chip)
    mask = jax.ShapeDtypeStruct(shp, np.bool_, sharding=one_chip)
    compiled = c["eng"]._get_prefill(c["Tp"], c["pool_len"]).lower(
        c["params"], tok, mask).compile()
    ma = compiled.memory_analysis()
    used = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes + _nbytes(c["cache"]))
    assert used < HBM_BYTES, (ma.argument_size_in_bytes / 1e9,
                              ma.output_size_in_bytes / 1e9,
                              ma.temp_size_in_bytes / 1e9)
