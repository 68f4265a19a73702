"""Weights for a benchmark run, made on the device from the run's seed.

The benchmark, not the program, owns the weights: one jitted call turns
the seed into the whole parameter tree, in the dtype the configuration
serves, in the layout of the program's own ``init_params`` (taken from
``jax.eval_shape``, so nothing is initialised twice). The reference
forward (``reference.py``) calls the same generator, so both sides read
bit-identical values.

Scales. Every matrix is N(0, 1/fan_in) over the width it contracts;
the embedding is N(0, 0.02^2); QKV biases are N(0, 0.1^2); RMSNorm
scales are 1 + N(0, 0.05^2). Biases and norm scales are not zero or one
(as a fresh init would leave them), so the comparison with the
reference exercises them.

Policy drift. An RL trainer changes the policy between rollout steps.
``norm_scales(key, step, drift)`` redraws every RMSNorm scale as
``1 + 0.05 * n_0 + drift * n_step``, where ``n_0`` is fixed by the seed
and ``n_step`` is fresh per step: a small, cheap perturbation that moves
every layer's activations, so consecutive steps' greedy rollouts agree
for a while and then part, as they do between RL updates.
"""

from __future__ import annotations

import functools
import math

import numpy as np

BIAS_STD = 0.1
NORM_STD = 0.05
EMBED_STD = 0.02


def seed_key(seed: int, stream: int = 0):
    """A threefry key from any non-negative integer seed (wider than 32
    bits is fine) and a stream number."""
    import jax

    words = np.random.SeedSequence([int(seed), int(stream)]).generate_state(2)
    return jax.random.wrap_key_data(np.asarray(words, np.uint32))


def _path_names(path) -> tuple:
    out = []
    for p in path:
        name = getattr(p, "key", getattr(p, "idx", None))
        out.append(str(name))
    return tuple(out)


def leaf_rule(names: tuple, shape: tuple, stacked: bool):
    """``(kind, std)`` for one leaf: kind is "normal" (N(0, std^2)),
    "bias" or "norm". ``stacked`` leaves carry a leading layer axis."""
    name = names[-1]
    per = shape[1:] if stacked else shape
    if name == "scale":
        return "norm", NORM_STD
    if name in ("bq", "bk", "bv"):
        return "bias", BIAS_STD
    if name == "embed":
        return "normal", EMBED_STD
    if name == "wo" and "attn" in names:  # (heads, head_dim, d)
        return "normal", 1.0 / math.sqrt(per[0] * per[1])
    # (d, heads, head_dim), (d, d_ff), (d_ff, d), (d, vocab)
    return "normal", 1.0 / math.sqrt(per[0])


def abstract_params(cfg):
    """``(shapes, stacked)``: the program's parameter tree as
    ShapeDtypeStructs, and a parallel tree of bools marking leaves that
    carry a leading layer axis."""
    import jax

    from repro.models import model as M
    from repro.models.layers import is_param, split_tree

    tree = jax.eval_shape(lambda k: M.init_params(cfg, k), jax.random.key(0))
    shapes, _ = split_tree(tree)
    stacked = jax.tree.map(lambda p: p.axes[:1] == ("layers",), tree,
                           is_leaf=is_param)
    return shapes, stacked


@functools.lru_cache(maxsize=None)
def _generator(cfg):
    """One jitted program: key -> the whole parameter tree."""
    import jax
    import jax.numpy as jnp

    shapes, stacked = abstract_params(cfg)
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    st_flat = jax.tree.leaves(stacked)

    def gen(key):
        leaves = []
        for i, ((path, sd), st) in enumerate(zip(flat, st_flat)):
            kind, std = leaf_rule(_path_names(path), sd.shape, st)
            k = jax.random.fold_in(key, i)
            x = jax.random.normal(k, sd.shape, jnp.float32) * std
            if kind == "norm":
                x = x + 1.0
            leaves.append(x.astype(sd.dtype))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(gen)


def make_params(cfg, seed: int):
    """The parameter tree for ``seed``, generated on the default device."""
    return _generator(cfg)(seed_key(seed, 0))


def norm_paths(params) -> list:
    """Tree paths of every RMSNorm scale leaf, in flatten order."""
    import jax

    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    return [path for path, _ in flat if _path_names(path)[-1] == "scale"]


@functools.lru_cache(maxsize=None)
def _norm_generator(shapes: tuple, dtypes: tuple, drift: float):
    import jax
    import jax.numpy as jnp

    def gen(base_key, step_key):
        out = []
        for i, (shape, dt) in enumerate(zip(shapes, dtypes)):
            n0 = jax.random.normal(jax.random.fold_in(base_key, i), shape,
                                   jnp.float32)
            ns = jax.random.normal(jax.random.fold_in(step_key, i), shape,
                                   jnp.float32)
            out.append((1.0 + NORM_STD * n0 + drift * ns).astype(dt))
        return out

    return jax.jit(gen)


def with_step_norms(params, seed: int, step: int, drift: float):
    """``params`` with every RMSNorm scale redrawn for RL step ``step``
    (see the module docstring); the other leaves are shared, not
    copied."""
    import jax

    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    idx = [i for i, (path, _) in enumerate(flat)
           if _path_names(path)[-1] == "scale"]
    shapes = tuple(tuple(flat[i][1].shape) for i in idx)
    dtypes = tuple(str(flat[i][1].dtype) for i in idx)
    new = _norm_generator(shapes, dtypes, float(drift))(
        seed_key(seed, 1), seed_key(seed, 1000 + int(step)))
    leaves = [leaf for _, leaf in flat]
    for i, x in zip(idx, new):
        leaves[i] = x
    return jax.tree_util.tree_unflatten(treedef, leaves)
