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


@pytest.mark.parametrize("K", (0, 4, 8))
def test_fused_round_compiles_for_v5e(qwen, K):
    """One fused round per K bucket: XLA suffix-match core (no Pallas
    custom call) + full-width verify forward + commit."""
    assert K in qwen["eng"].engine.block_buckets
    q = qwen
    compiled = q["eng"]._get_fused(K, 1).lower(
        q["params"], q["forest"], q["cache"], q["state"], q["i32"],
        q["i32"], q["key"],
    ).compile()
    _fits(compiled)
    assert "tpu_custom_call" not in compiled.as_text()


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
