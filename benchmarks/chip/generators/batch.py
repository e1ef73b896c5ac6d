"""Offline batches: ``distinct_batches`` full (T, B, ...) blocks made on
the device by one jitted call each: word streams of encoder-exact iid
spikes at the mix's sparsity for a current-encoder network, seeded images
on a k/``levels`` grid (exact in any matmul precision), held for T
frames, for a conv-encoder one."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from harness import traffic
from harness.weights import seed_key


def batches(mix: dict, config: dict, seed: int) -> list:
    """The mix's distinct batches, on the device."""
    b, t = mix["batch"], config["timesteps"]
    keys = jax.random.split(seed_key(seed), mix["distinct_batches"])
    shape = (b, *traffic.frame_shape(config))
    if config["conv_spec"]:
        img = mix["image"]

        @jax.jit
        def make(key):
            k1, k2 = jax.random.split(key)
            lit = jax.random.bernoulli(k1, img["density"], shape)
            level = jax.random.randint(k2, shape, 1, img["levels"])
            x = jnp.where(lit, level, 0).astype(jnp.float32) / img["levels"]
            return jnp.broadcast_to(x[None], (t, *shape))
    else:
        shape = (mix["words"] * t, *shape)
        cur = traffic.event_current(config)

        @jax.jit
        def make(key):
            spikes = jax.random.bernoulli(key, 1.0 - mix["sparsity"], shape)
            return spikes.astype(jnp.float32) * cur
    return [make(k) for k in keys]
