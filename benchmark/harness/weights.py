"""Weights from the seed: made on the device, in one jitted call, in the
type they are served in. The benchmark makes them; the program and the
plain reference are both handed these and nothing of each other's."""
from __future__ import annotations

import functools


def seed_key(seed: int):
    """A PRNG key from any whole number up to and past 2**31."""
    import jax

    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


@functools.lru_cache(maxsize=4)
def _maker(specs: tuple, dtype_name: str):
    import jax
    import jax.numpy as jnp

    dtype = jnp.dtype(dtype_name)

    def make(key):
        out = []
        for i, (name, shape, init, scale) in enumerate(specs):
            if init == "normal":
                w = scale * jax.random.normal(jax.random.fold_in(key, i),
                                              shape, jnp.float32)
            elif init == "ones":
                w = jnp.ones(shape, jnp.float32)
            else:
                w = jnp.zeros(shape, jnp.float32)
            out.append(w.astype(dtype))
        return out

    return jax.jit(make)


def make_weights(specs, seed: int, dtype_name: str = "bfloat16") -> dict:
    """{leaf name: array} for `specs` = [(name, shape, init, scale)]."""
    specs = tuple((n, tuple(s), i, float(sc)) for n, s, i, sc in specs)
    arrays = _maker(specs, dtype_name)(seed_key(seed))
    return {spec[0]: a for spec, a in zip(specs, arrays)}
