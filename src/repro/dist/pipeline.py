"""GPipe pipeline parallelism over one mesh axis.

`make_pipeline_fn(stage_fn, mesh, axis_name, n_micro)` returns a function
``pipe(Ws, xs)`` where ``Ws`` stacks one stage's parameters per pipeline
rank (leading axis == mesh extent) and ``xs`` stacks the microbatches
(leading axis == n_micro). Execution is the classic schedule: microbatch m
enters stage 0 at tick m and advances one stage per tick via a ring
`ppermute`; the last stage emits microbatch m at tick m + S - 1, so the
whole run takes n_micro + S - 1 ticks with every stage busy in the steady
state. Output equals sequentially composing the stages over each
microbatch (bubble overhead changes time, not values).
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def make_pipeline_fn(stage_fn: Callable, mesh: Mesh, axis_name: str,
                     n_micro: int) -> Callable:
    """Build the GPipe executor (module docs): ``stage_fn(w, x)`` is one
    pipeline stage, staged over ``mesh``'s ``axis_name`` extent; the
    returned ``pipe(Ws, xs)`` runs ``n_micro`` microbatches through the
    classic fill/steady/drain schedule."""
    n_stages = dict(zip(mesh.axis_names, mesh.devices.shape))[axis_name]
    ring = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def body(w_local, xs):
        # w_local: (1, ...) this rank's stage params; xs: (M, B, d) replicated
        idx = jax.lax.axis_index(axis_name)
        w = jax.tree_util.tree_map(lambda t: t[0], w_local)
        m_total = xs.shape[0]

        def tick(t, carry):
            x_cur, buf = carry
            # stage 0 injects microbatch t (clamped reads past the end feed
            # garbage that is never emitted — see schedule note above)
            inp = jnp.where(idx == 0, xs[jnp.clip(t, 0, m_total - 1)], x_cur)
            y = stage_fn(w, inp)
            m = t - (n_stages - 1)                   # micro finishing this tick
            emit = (idx == n_stages - 1) & (m >= 0)
            upd = jax.lax.dynamic_update_index_in_dim(
                buf, y, jnp.clip(m, 0, m_total - 1), axis=0)
            buf = jnp.where(emit, upd, buf)
            x_next = jax.lax.ppermute(y, axis_name, ring)
            return x_next, buf

        x0 = jnp.zeros_like(xs[0])
        buf0 = jnp.zeros_like(xs)
        _, buf = jax.lax.fori_loop(0, m_total + n_stages - 1, tick, (x0, buf0))
        # only the last rank holds real outputs; psum replicates them
        return jax.lax.psum(jnp.where(idx == n_stages - 1, buf, 0.0), axis_name)

    return jax.shard_map(body, mesh=mesh, in_specs=(P(axis_name), P()),
                         out_specs=P(), check_vma=False)
