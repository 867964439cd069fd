"""GPipe-style pipeline parallelism over a mesh axis (opt-in; DESIGN.md §9).

Layers are partitioned into `n_stages` contiguous blocks whose parameters
shard over the pipeline mesh axis; microbatches stream through stages with
``lax.ppermute`` hops. The schedule is the classic GPipe ladder
(n_micro + n_stages - 1 ticks; bubble fraction (S-1)/(M+S-1)).

Scope: forward-pass building block + exactness test
(tests/test_parallel_scaffold.py::test_pipeline_matches_sequential). The production
meshes in this repo favour FSDP+TP (better roofline at 256-512 chips for
the assigned archs); PP becomes the right trade at >2 pods where the DCN
dominates — this module is the substrate for that regime.
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(block_fn: Callable[[Any, jax.Array], jax.Array],
                   stage_params: Any, x: jax.Array, n_micro: int,
                   mesh: Mesh, axis: str = "stage") -> jax.Array:
    """Run ``block_fn`` over `n_stages` parameter slices as a pipeline.

    stage_params: pytree, every leaf has leading dim n_stages (sharded over
    ``axis``). x: (B, ...) with B % n_micro == 0. Returns block_fn applied
    stage-by-stage, exactly equal to the sequential composition.
    """
    stages = mesh.shape[axis]
    b = x.shape[0]
    if b % n_micro:
        raise ValueError("batch must divide n_micro")
    mb = b // n_micro
    xm = x.reshape(n_micro, mb, *x.shape[1:])

    def staged(params_local, xm_local):
        idx = jax.lax.axis_index(axis)
        p = jax.tree.map(lambda a: a[0], params_local)
        ticks = n_micro + stages - 1
        perm = [(i, i + 1) for i in range(stages - 1)]

        def body(t, state):
            carry, outbuf = state
            feed = xm_local[jnp.clip(t, 0, n_micro - 1)]
            inp = jnp.where(idx == 0, feed, carry)
            out = block_fn(p, inp)
            carry_next = jax.lax.ppermute(out, axis, perm)
            widx = t - (stages - 1)
            upd = jax.lax.dynamic_update_index_in_dim(
                outbuf, out, jnp.clip(widx, 0, n_micro - 1), 0)
            write = (idx == stages - 1) & (widx >= 0)
            outbuf = jnp.where(write, upd, outbuf)
            return carry_next, outbuf

        carry0 = jnp.zeros_like(xm_local[0])
        out0 = jnp.zeros_like(xm_local)
        # mark initial carries as device-varying over the stage axis
        # (shard_map vma typing: the loop body outputs are stage-varying)
        if hasattr(jax.lax, "pvary"):
            carry0 = jax.lax.pvary(carry0, (axis,))
            out0 = jax.lax.pvary(out0, (axis,))
        _, outbuf = jax.lax.fori_loop(0, ticks, body, (carry0, out0))
        # only the last stage holds real outputs; broadcast via psum
        outbuf = jnp.where(idx == stages - 1, outbuf,
                           jnp.zeros_like(outbuf))
        return jax.lax.psum(outbuf, axis)

    out = shard_map(staged, mesh=mesh,
                    in_specs=(P(axis), P()), out_specs=P())(stage_params, xm)
    return out.reshape(b, *x.shape[1:])
