"""GPipe-style pipeline parallelism over a ``stage`` mesh axis.

``pipeline(stage_fn)`` runs S stages over M microbatches with the classic
fill/drain schedule (M + S - 1 ticks).  Each device holds one stage's
params (the stage dim of the stacked param tree is sharded on ``stage``);
activations hop stages with a single ``ppermute`` per tick — the
compute/communication overlap XLA gets for free because the permute of
tick t is independent of the local matmul of tick t.

Bubble fraction = (S-1)/(M+S-1); the launcher picks M >= 4S by default.
This module is the optional PP feature (DESIGN.md §6): the 40-cell matrix
uses DP x TP (x EP/SP), which fits every assigned arch; PP is exercised by
tests/test_pipeline.py and examples.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["pipeline"]


def pipeline(stage_fn: Callable, mesh: Mesh, axis: str = "stage"):
    """Build a pipelined apply: (stacked_params, x (M, B, ...)) -> (M, B, ...).

    ``stage_fn(params_slice, x)`` is one stage's computation; all stages
    must share input/output activation shapes (standard for repeated
    transformer blocks).
    """
    S = mesh.shape[axis]

    def _local(params, xs):
        # params: (1, ...) this stage's slice;  xs: (M, B, ...) replicated.
        stage = jax.lax.axis_index(axis)
        M = xs.shape[0]
        T = M + S - 1
        p_local = jax.tree.map(lambda a: a[0], params)
        buf = jnp.zeros_like(xs[0])  # activation entering this stage
        outs = jnp.zeros_like(xs)

        def tick(t, carry):
            buf, outs = carry
            # Stage 0 ingests microbatch t (if any); others use the buffer.
            x_in = jnp.where(
                stage == 0,
                xs[jnp.clip(t, 0, M - 1)],
                buf,
            )
            active = (t - stage >= 0) & (t - stage < M)
            y = stage_fn(p_local, x_in)
            y = jnp.where(active, y, buf)
            # Last stage records its finished microbatch.
            mb = jnp.clip(t - stage, 0, M - 1)
            outs = jnp.where(
                (stage == S - 1) & active,
                outs.at[mb].set(y),
                outs,
            )
            # Hop to the next stage.
            perm = [(i, (i + 1) % S) for i in range(S)]
            buf2 = jax.lax.ppermute(y, axis, perm)
            return buf2, outs

        buf, outs = jax.lax.fori_loop(0, T, tick, (buf, outs))
        # Sum over stages: only the last stage wrote non-zeros.
        outs = jax.lax.psum(outs, axis)
        return outs

    pspec_params = P(axis)

    def apply(stacked_params, x_microbatches):
        in_specs = (jax.tree.map(lambda _: pspec_params, stacked_params),
                    P())
        g = jax.shard_map(_local, mesh=mesh,
                      in_specs=in_specs, out_specs=P(), check_vma=False)
        return g(stacked_params, x_microbatches)

    return apply
