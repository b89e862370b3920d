"""Random weights from the seed, made on the device in one jitted call.

The program decides the parameter tree (``init_params`` is only traced, by
``jax.eval_shape``, for its shapes and dtypes); the values come from here,
so the reference in ``reference.py`` reads weights the benchmark made and
not weights the program made.  Every leaf is drawn in float32 and cast to
the dtype it is served in:

* embedding and output tables (``table``, ``unembed``): N(0, 0.02²);
* norm scales (``scale``): 1 + N(0, 0.1²), so that a scale the program
  dropped would show against the reference;
* every projection: N(0, 1/d_in), d_in its second-to-last axis.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def jax_seed(seed: int) -> int:
    """A 31-bit key seed from any whole-number run seed."""
    return int(np.random.default_rng([int(seed), 7]).integers(0, 2 ** 31 - 1))


def _leaf_name(path) -> str:
    keys = [getattr(k, "key", getattr(k, "name", str(k))) for k in path]
    return str(keys[-1]) if str(keys[-1]) != "w" else str(keys[-2]) + ".w"


def make_params(model, seed: int):
    """The parameter tree of ``model`` filled from ``seed``, on the default
    device, in one compiled call."""
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def build(key):
        out = []
        for i, (path, sds) in enumerate(leaves):
            k = jax.random.fold_in(key, i)
            name = _leaf_name(path)
            x = jax.random.normal(k, sds.shape, jnp.float32)
            if name in ("table", "unembed"):
                x = x * 0.02
            elif name == "scale":
                x = 1.0 + 0.1 * x
            else:
                x = x * (sds.shape[-2] ** -0.5)
            out.append(x.astype(sds.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.block_until_ready(
        jax.jit(build)(jax.random.PRNGKey(jax_seed(seed))))
