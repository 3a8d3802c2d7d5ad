"""The paper's model tensor (Fig. 7a), made on the device from a seed.

A copy of the program's ``function_tensor`` generator, kept with the
benchmark so that a change to the program cannot move the yardstick:
coordinates uniform and independent per mode, values
``sigmoid(3 * sum_d x_d[i_d])`` with ``x_d ~ U[-1, 1]`` per mode. As in
the program, coordinates are not deduplicated (at 3.1M draws over 16,384^3
cells about one repeat is expected); a repeat is two observations of one
entry, in the program and in the reference alike.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnums=(1, 2))
def _generate(key, shape, nnz):
    ks = jax.random.split(key, len(shape) + 2)
    cols = [jax.random.randint(ks[d], (nnz,), 0, s, jnp.int32)
            for d, s in enumerate(shape)]
    grids = [jax.random.uniform(jax.random.fold_in(ks[-2], d), (s,),
                                minval=-1.0, maxval=1.0)
             for d, s in enumerate(shape)]
    arg = sum(g[i] for g, i in zip(grids, cols))
    return jnp.stack(cols, 1), jax.nn.sigmoid(3.0 * arg)


def generate(key, cfg: dict, nnz: int):
    """``(indices (nnz, N) int32, values (nnz,) float32)`` on the default
    device."""
    return _generate(key, tuple(cfg["shape"]), nnz)
