"""Mamba2 chunked SSD Pallas kernel.

Grid (batch*heads, chunks); the chunk dimension is sequential and carries the
[P, N] SSM state in VMEM scratch — the inter-chunk recurrence IS a stream:
each chunk consumes the previous state token, produces the next, and the
state never leaves VMEM (the FPGA version would hold it in BRAM between
pipeline iterations).

Per chunk (intra-chunk work, all MXU-friendly):
    L        = exp(segsum(dA))                  [Q, Q] lower-triangular
    y_diag   = ((C B^T) * L) (x*dt)             [Q, P]
    y_off    = (C h_prev) * exp(cumsum dA)      [Q, P]
    h_next   = h_prev * exp(sum dA) + B^T ((x*dt) * decay_to_end)
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import interpret_default, kernel_name

# Autotune candidate lattice (tuning/autotune.py): SSD chunk lengths
# (the sequential-scan granule; larger chunks amortize the state
# carry, smaller ones shrink the in-VMEM chunk working set).
TUNE_SPACE = {"chunk": (64, 128, 256)}


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, y_ref, hout_ref,
                state_ref, *, n_chunks: int, chunk: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0].astype(jnp.float32)            # [Q, P]
    dt = dt_ref[0].astype(jnp.float32)          # [Q, 1]
    a = a_ref[0].astype(jnp.float32)            # [1, 1] (per head)
    b = b_ref[0].astype(jnp.float32)            # [Q, N]
    c = c_ref[0].astype(jnp.float32)            # [Q, N]
    d_skip = d_ref[0].astype(jnp.float32)       # [1, 1]

    da = dt * a                                  # [Q, 1]
    xdt = x * dt                                 # [Q, P]
    cum = jnp.cumsum(da, axis=0)                 # [Q, 1]
    # Intra-chunk decay matrix L[i, j] = exp(sum_{j<k<=i} da_k), j <= i.
    diff = cum - cum.T                           # [Q, Q]
    tri = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    l_mat = jnp.where(tri, jnp.exp(diff), 0.0)
    cb = jnp.dot(c, b.T, preferred_element_type=jnp.float32)  # [Q, Q]
    y = jnp.dot(cb * l_mat, xdt,
                preferred_element_type=jnp.float32)           # [Q, P]
    # Inter-chunk: contribution of the carried state.
    state = state_ref[...]                                    # [P, N]
    y += jnp.exp(cum) * jnp.dot(c, state.T,
                                preferred_element_type=jnp.float32)
    # State update.
    total = cum[-1:, :]                                       # [1, 1]
    decay_to_end = jnp.exp(total - cum)                       # [Q, 1]
    state_ref[...] = state * jnp.exp(total) + \
        jnp.dot((xdt * decay_to_end).T, b,
                preferred_element_type=jnp.float32)           # [P, N]
    y_ref[0] = (y + x * d_skip).astype(y_ref.dtype)

    @pl.when(ci == n_chunks - 1)
    def _done():
        hout_ref[0] = state_ref[...]


def mamba2_ssd_pallas(x: jax.Array, dt: jax.Array, a_log: jax.Array,
                      b: jax.Array, c: jax.Array, d_skip: jax.Array, *,
                      chunk: int = 128,
                      interpret: Optional[bool] = None,
                      ) -> Tuple[jax.Array, jax.Array]:
    """Shapes as layers.mamba2_ssd: x [B,S,H,P], dt [B,S,H], a_log [H],
    b/c [B,S,N], d_skip [H] -> (y [B,S,H,P], state [B,H,P,N])."""
    bsz, s, h, p = x.shape
    n = b.shape[-1]
    q = min(chunk, s)
    assert s % q == 0, (s, q)
    nc = s // q
    bh = bsz * h

    # Flatten to the (batch*head, chunks, ...) kernel layout.
    xk = x.transpose(0, 2, 1, 3).reshape(bh, s, p)
    dtk = dt.transpose(0, 2, 1).reshape(bh, s, 1)
    ak = -jnp.exp(a_log.astype(jnp.float32))
    ak = jnp.tile(ak.reshape(1, h), (bsz, 1)).reshape(bh, 1, 1)
    dk = jnp.tile(d_skip.reshape(1, h).astype(jnp.float32),
                  (bsz, 1)).reshape(bh, 1, 1)
    bk = jnp.repeat(b, h, axis=0).reshape(bsz, h, s, n) \
        .reshape(bh, s, n)
    ck = jnp.repeat(c, h, axis=0).reshape(bsz, h, s, n) \
        .reshape(bh, s, n)

    interpret = interpret_default() if interpret is None else interpret
    y, hfinal = pl.pallas_call(
        functools.partial(_ssd_kernel, n_chunks=nc, chunk=q),
        grid=(bh, nc),
        in_specs=[
            pl.BlockSpec((1, q, p), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, q, 1), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, 1, 1), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, q, n), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, q, n), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, 1, 1), lambda i, j: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, q, p), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, p, n), lambda i, j: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, p), x.dtype),
            jax.ShapeDtypeStruct((bh, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
        **kernel_name("mamba2_scan"),
    )(xk, dtk.reshape(bh, s, 1), ak, bk, ck, dk)

    y = y.reshape(bsz, h, s, p).transpose(0, 2, 1, 3)
    state = hfinal.reshape(bsz, h, p, n)
    return y, state
