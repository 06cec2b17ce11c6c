"""Weights made from ``--seed`` on the device, in one jitted call, in the
layout the program's dense decoder takes (stacked layers under
``periods/slot0``).  The reference calls the same function with the same
seed, so both sides start from the same numbers without the reference
taking anything the program made."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from model_conf import Dims

INIT_STD = 0.02


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size (seeds may exceed 32 bits)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, seed >> 31)


def _normal(key, shape, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * INIT_STD).astype(dtype)


def _norm(dm: Dims, lead, dtype):
    p = {"scale": jnp.ones(lead + (dm.d_model,), dtype)}
    if dm.norm == "layernorm":
        p["bias"] = jnp.zeros(lead + (dm.d_model,), dtype)
    return p


def _make(key, dm: Dims, dtype):
    ks = jax.random.split(key, 10)
    L, d = dm.layers, dm.d_model
    layer = {
        "norm1": _norm(dm, (L,), dtype),
        "norm2": _norm(dm, (L,), dtype),
        "attn": {
            "wq": {"w": _normal(ks[0], (L, d, dm.q_dim), dtype)},
            "wk": {"w": _normal(ks[1], (L, d, dm.kv_dim), dtype)},
            "wv": {"w": _normal(ks[2], (L, d, dm.kv_dim), dtype)},
            "wo": {"w": _normal(ks[3], (L, dm.q_dim, d), dtype)},
        },
        "mlp": {
            "up": {"w": _normal(ks[4], (L, d, dm.d_ff), dtype)},
            "down": {"w": _normal(ks[5], (L, dm.d_ff, d), dtype)},
            "gate": {"w": _normal(ks[6], (L, d, dm.d_ff), dtype)},
        },
    }
    params = {"embed": {"w": _normal(ks[7], (dm.vocab, d), dtype)},
              "final_norm": _norm(dm, (), dtype),
              "periods": {"slot0": layer}}
    if not dm.tied:
        params["head"] = {"w": _normal(ks[8], (d, dm.vocab), dtype)}
    return params


@functools.partial(jax.jit, static_argnums=(1, 2))
def _make_jit(key, dm: Dims, dtype_name: str):
    return _make(key, dm, jnp.dtype(dtype_name))


def make_params(dm: Dims, seed: int, dtype="bfloat16"):
    """The whole parameter tree for ``seed``, on the default device."""
    return _make_jit(seed_key(seed), dm, jnp.dtype(dtype).name)

