"""Seeded weights, made on the device in one jitted call.

The benchmark owns the weights: the same arrays go into the program's
scope and into the plain reference, so neither takes anything the other
made.  ``spec`` is ``name -> (shape, init)`` as a reference module's
``*_param_spec`` returns it; inits: ``xavier`` (uniform, Glorot),
``embedding`` (normal, std ``d_model ** -0.5``), ``zeros``, ``ones``.
"""

import functools

import jax
import jax.numpy as jnp


def seed_key(seed, stream=0):
    """A PRNG key for any whole-number seed (the driver's exceed 2**31)."""
    seed = int(seed)
    key = jax.random.key(seed & 0x7FFFFFFF)
    return jax.random.fold_in(jax.random.fold_in(key, seed >> 31), stream)


def _leaf(key, shape, init):
    if init == "zeros":
        return jnp.zeros(shape, jnp.float32)
    if init == "ones":
        return jnp.ones(shape, jnp.float32)
    if init == "embedding":
        return jax.random.normal(key, shape, jnp.float32) * shape[-1] ** -0.5
    if init == "xavier":
        bound = (6.0 / (shape[0] + shape[1])) ** 0.5
        return jax.random.uniform(key, shape, jnp.float32, -bound, bound)
    raise ValueError("unknown init %r" % (init,))


@functools.partial(jax.jit, static_argnums=(1,))
def _make(key, items):
    keys = jax.random.split(key, len(items))
    return {name: _leaf(keys[i], shape, init)
            for i, (name, shape, init) in enumerate(items)}


def make_weights(spec, seed):
    """{name: float32 array on the default device}, the same for the same
    seed."""
    items = tuple((n, tuple(s), i) for n, (s, i) in spec.items())
    return _make(seed_key(seed, 1), items)
