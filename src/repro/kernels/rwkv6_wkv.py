"""RWKV6 wkv recurrence Pallas kernel.

Grid (batch*heads, time_chunks); the [N, N] per-head state is carried in
VMEM scratch across the sequential chunk dimension.  Inside a chunk the
recurrence runs as a ``fori_loop`` over timesteps — the time axis is a
stream, each token's (r, k, v, w) is consumed once, and the only persistent
object is the state token (the FPGA analogue keeps it in a BRAM ping-pong).

A matmul-factored intra-chunk form exists (r~ = r * Wcum, k~ = k / Wcum)
but divides by cumulative decays and underflows in bf16 for long chunks; the
sequential form is numerically exact, and the chunk dimension still provides
the coarse-grained pipelining (documented trade-off, EXPERIMENTS.md §Perf).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import interpret_default, kernel_name

# Autotune candidate lattice (tuning/autotune.py): WKV chunk lengths.
# The N x N state outer products grow quadratically with the chunk,
# so the grid stays small (the planner also caps at 64).
TUNE_SPACE = {"chunk": (16, 32, 64)}


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, y_ref, sout_ref,
                state_ref, *, n_chunks: int, chunk: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    u = u_ref[0].astype(jnp.float32)             # [1, N] (key bonus)

    def step(t, state):
        rt = r_ref[0, t].astype(jnp.float32)[None, :]   # [1, N]
        kt = k_ref[0, t].astype(jnp.float32)[None, :]
        vt = v_ref[0, t].astype(jnp.float32)[None, :]
        wt = w_ref[0, t].astype(jnp.float32)[None, :]
        kv = kt.T @ vt                                  # [N, N]
        y = rt @ (state + u.T * kv)                     # [1, N]
        y_ref[0, t] = y[0].astype(y_ref.dtype)
        return state * wt.T + kv

    state_ref[...] = jax.lax.fori_loop(0, chunk, step, state_ref[...])

    @pl.when(ci == n_chunks - 1)
    def _done():
        sout_ref[0] = state_ref[...]


def wkv6_pallas(r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array,
                u: jax.Array, *, chunk: int = 64,
                interpret: Optional[bool] = None,
                ) -> Tuple[jax.Array, jax.Array]:
    """Shapes as layers.wkv6: r/k/v/w [B,S,H,N], u [H,N]
    -> (y [B,S,H,N], state [B,H,N,N])."""
    bsz, s, h, n = r.shape
    q = min(chunk, s)
    assert s % q == 0
    nc = s // q
    bh = bsz * h

    def flat(x):
        return x.transpose(0, 2, 1, 3).reshape(bh, s, n)

    uk = jnp.tile(u.astype(jnp.float32)[None], (bsz, 1, 1)) \
        .reshape(bh, 1, n)
    interpret = interpret_default() if interpret is None else interpret
    y, state = pl.pallas_call(
        functools.partial(_wkv_kernel, n_chunks=nc, chunk=q),
        grid=(bh, nc),
        in_specs=[
            pl.BlockSpec((1, q, n), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, q, n), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, q, n), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, q, n), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, 1, n), lambda i, j: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, q, n), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, n, n), lambda i, j: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, n), r.dtype),
            jax.ShapeDtypeStruct((bh, n, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n, n), jnp.float32)],
        interpret=interpret,
        **kernel_name("rwkv6_wkv"),
    )(flat(r), flat(k), flat(v), flat(w), uk)
    return (y.reshape(bsz, h, s, n).transpose(0, 2, 1, 3),
            state.reshape(bsz, h, n, n))
