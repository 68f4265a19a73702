"""The main path's programs compile for one TPU v5e chip at Qwen2-1.5B
widths, and fit its 16 GB of HBM.

No chip is attached: the TPU compiler compiles for a described ``v5e``
topology. The topology is described inside a module fixture, never at
import (only one process may hold the TPU library, and every test
worker imports this file), and these compiles stay in this one file.
The shapes are the ones ``chip_smoke.py`` serves: 32 slots, GRPO
traffic, K buckets 0/4/8.
"""

import os
import re

import jax
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core.fused_round import make_state
from repro.core.spec_engine import _cache_bucket, _prompt_bucket
from repro.kernels.suffix_match import ops as sm_ops
from repro.launch import serve
from repro.models import model as M
from repro.models.layers import split_tree

HBM_BYTES = 16e9
SLOTS = 32


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with the persistent compilation
    cache off: an entry compiled for a described chip cannot be read
    back without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # no compiler logs in /tmp
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
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
        tree,
    )


@pytest.fixture(scope="module")
def qwen(one_chip):
    """A serving engine over abstract Qwen2-1.5B params on the chip, with
    the pool, round state and a 65k-token forest shaped as served."""
    cfg = get_config("qwen2-1.5b")
    params = _on(jax.eval_shape(
        lambda k: split_tree(M.init_params(cfg, k))[0], jax.random.key(0)
    ), one_chip)
    eng = serve.make_engine(params, cfg)
    e = eng.engine
    prompt_hi = serve.GRPO_TRAFFIC["prompt_len"][1]
    pool_len = _cache_bucket(_prompt_bucket(prompt_hi)
                             + serve.GRPO_TRAFFIC["max_new"][1]
                             + e.max_draft + 2)
    cache = _on(jax.eval_shape(
        lambda: M.init_cache(cfg, SLOTS, pool_len, e.cache_headroom)
    ), one_chip)
    forest, _ = sm_ops.pack_forest(
        [], min_nodes=1 << 17, min_edges=1 << 17, min_corpus=1 << 16
    )
    m = eng.drafter.cfg.device_tail
    state = make_state(np.zeros(SLOTS), np.full((SLOTS, m), -1),
                       np.ones(SLOTS, bool), np.zeros(SLOTS),
                       np.full(SLOTS, 64))
    i32 = jax.ShapeDtypeStruct((SLOTS,), np.int32, sharding=one_chip)
    return {
        "cfg": cfg, "eng": eng, "params": params, "cache": cache,
        "pool_len": pool_len, "forest": _on(forest, one_chip),
        "state": _on(state, one_chip), "i32": i32,
        "key": _on(jax.eval_shape(lambda: jax.random.key(0)), one_chip),
        "tail": m, "sharding": one_chip,
    }


def _fits(compiled) -> None:
    ma = compiled.memory_analysis()
    used = ma.argument_size_in_bytes + ma.temp_size_in_bytes
    assert used < HBM_BYTES, (
        f"arguments {ma.argument_size_in_bytes / 1e9:.2f} GB + temporaries "
        f"{ma.temp_size_in_bytes / 1e9:.2f} GB exceed the chip's HBM"
    )


@pytest.fixture(scope="module")
def fused(qwen):
    """The fused round compiled for the chip, once per K bucket."""
    done = {}

    def get(K):
        if K not in done:
            q = qwen
            done[K] = q["eng"]._get_fused(K, 1).lower(
                q["params"], q["forest"], q["cache"], q["state"], q["i32"],
                q["i32"], q["key"],
            ).compile()
        return done[K]

    return get


@pytest.mark.parametrize("K", (0, 4, 8))
def test_fused_round_compiles_for_v5e(qwen, fused, K):
    """One fused round per K bucket: XLA suffix-match core (no Pallas
    custom call) + full-width verify forward + commit."""
    assert K in qwen["eng"].engine.block_buckets
    compiled = fused(K)
    _fits(compiled)
    assert "tpu_custom_call" not in compiled.as_text()


def _hlo_shape(shape, dtype) -> str:
    """An array type as the HLO text spells it, e.g. ``bf16[2,3]``."""
    name = {"bfloat16": "bf16", "float32": "f32"}[np.dtype(dtype).name]
    return f"{name}[{','.join(map(str, shape))}]"


def test_fused_round_updates_pool_in_place_on_v5e(qwen, fused):
    """The layer scan writes only the verify block's slots into the
    donated K/V pool: no copy of a whole pool, no dynamic-update-slice
    of one (a layer written back into a fresh scan output), no whole
    layer slice computed, and temporaries far below one pool."""
    compiled = fused(8)
    k_pool = qwen["cache"].stages[0][0][0]  # (layers, slots, S+1, Hkv, hd)
    pool = re.escape(_hlo_shape(k_pool.shape, k_pool.dtype))
    layer = re.escape(_hlo_shape(k_pool.shape[1:], k_pool.dtype))
    op = r"%\S+ = {}\{{[^}}]*\}} ([\w-]+)\("
    pool_ops, layer_ops = [], []
    for line in compiled.as_text().splitlines():
        m = re.search(op.format(pool), line)
        if m and (m.group(1) == "copy" or "dynamic-update-slice" in line):
            pool_ops.append(line.strip()[:160])
        m = re.search(op.format(layer), line)
        if m and m.group(1) != "parameter":
            layer_ops.append(line.strip()[:160])
    assert not pool_ops, "whole-pool copy or write-back:\n" + "\n".join(
        pool_ops)
    assert not layer_ops, "whole-layer K/V slice computed:\n" + "\n".join(
        layer_ops)
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < k_pool.size * k_pool.dtype.itemsize / 4, (
        f"temporaries {temp / 1e9:.3f} GB"
    )


def test_prefill_compiles_for_v5e(qwen):
    """The first admission wave: all 32 slots, longest prompt bucket."""
    q = qwen
    Tp = _prompt_bucket(serve.GRPO_TRAFFIC["prompt_len"][1])
    tok = jax.ShapeDtypeStruct((SLOTS, Tp), np.int32, sharding=q["sharding"])
    mask = jax.ShapeDtypeStruct((SLOTS, Tp), np.bool_,
                                sharding=q["sharding"])
    compiled = q["eng"]._get_prefill(Tp, q["pool_len"]).lower(
        q["params"], tok, mask
    ).compile()
    _fits(compiled)


def test_xla_propose_compiles_for_v5e(qwen):
    """The standalone propose (unfused round's draft dispatch)."""
    q = qwen
    query = jax.ShapeDtypeStruct((SLOTS, q["tail"] + 2), np.int32,
                                 sharding=q["sharding"])
    compiled = sm_ops._dispatch.lower(
        query, q["forest"], n_prop_max=8, min_match=2, impl="ref",
        interpret=False,
    ).compile()
    _fits(compiled)
    assert "tpu_custom_call" not in compiled.as_text()
