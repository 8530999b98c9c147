"""The one input generator: a traffic mix's parameters and a seed in, the
inputs of every step out.

A traffic file (``bench/traffic/<mix>.json``) names a ``phase`` and its
sizes.  ``prefill`` makes ``prompts`` distinct prompts of ``tokens`` hidden
states each.  ``decode`` makes ``prompts`` distinct new-token hidden
states and one KV cache of ``context`` positions per layer, the last
position being the new token's own slot.  Hidden states and cached keys and
values are N(0, 1), made on the device in one jitted call.  Steps visit
the prompts in an order drawn from the seed; every seed gives the same
sizes and the same amount of work.
"""

from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int) -> jax.Array:
    """A JAX key from any non-negative seed, 64 bits or more."""
    words = np.random.SeedSequence(seed).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words), impl="threefry2x32")


def order(seed: int, n: int) -> List[int]:
    """The seeded order in which the steps visit the ``n`` prompts."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, 1]))
    return [int(i) for i in rng.permutation(n)]


def make(cfg: dict, traffic: dict, key: jax.Array) -> Dict[str, jax.Array]:
    d = cfg["hidden_size"]
    n = traffic["prompts"]
    if traffic["phase"] == "prefill":
        return jax.jit(lambda k: {"x": jax.random.normal(
            k, (n, traffic["tokens"], d), jnp.float32)})(key)
    if traffic["phase"] != "decode":
        raise ValueError(f"unknown phase {traffic['phase']!r}")
    if traffic.get("batch", 1) != 1:
        raise ValueError("decode traffic supports batch 1 only")
    cache = (cfg["num_hidden_layers"], cfg["num_key_value_heads"],
             traffic["context"], cfg["head_dim"])

    @jax.jit
    def gen(k):
        kx, kk, kv = jax.random.split(k, 3)
        return {"x": jax.random.normal(kx, (n, d), jnp.float32),
                "k_cache": jax.random.normal(kk, cache, jnp.float32),
                "v_cache": jax.random.normal(kv, cache, jnp.float32)}

    return gen(key)
