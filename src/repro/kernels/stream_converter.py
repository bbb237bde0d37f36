"""Materialized stream layout converter — Algorithm 1 on real data.

Re-tiles a 2D tensor from a producer itensor layout to a consumer layout
through a window buffer of the Algorithm-1-inferred shape: the direct TPU
twin of the paper's ``itensor_converter`` (Fig. 7(a)).  The shared loop
prefix becomes the Pallas grid (the window is re-used once per shared
iteration — the paper's ping-pong reuse, realized by Pallas' automatic
cross-iteration double buffering); the non-reducible dims become the window
extents.

The wrapper derives grid/BlockSpecs straight from the two ``ITensorType``s,
so core/converter.py decisions are *executable* — tests stream data through
and compare against slicing the tensor in consumer order.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..core.converter import infer_converter
from ..core.itensor import ITensorType
from .common import interpret_default, kernel_name


def _copy_kernel(src_ref, dst_ref):
    dst_ref[0] = src_ref[...]


def convert_layout(data: jax.Array, src: ITensorType, dst: ITensorType, *,
                   interpret: Optional[bool] = None) -> jax.Array:
    """Stream ``data`` (producer layout ``src``) out in consumer layout
    ``dst``; returns the tile stream stacked in consumer order
    [num_tokens, *dst.elem_shape].

    The window BlockSpec is the Algorithm-1 buffer: grid = the shared loop
    prefix; each grid step loads one window (ping) while the previous
    window's tiles drain (pong) — Pallas pipelines this automatically.
    """
    if tuple(data.shape) != src.data_shape:
        raise ValueError(f"{data.shape} != {src.data_shape}")
    spec = infer_converter(src, dst)
    interpret = interpret_default() if interpret is None else interpret

    grid_out = dst.grid_shape
    n_tokens = dst.num_tokens
    eh, ew = dst.elem_shape

    if spec is None:
        # Types match: the 'converter' is a FIFO — emit tiles directly.
        def index_map(t):
            offs = _nth_offset(dst, t)
            return offs

        return pl.pallas_call(
            _copy_kernel,
            grid=(n_tokens,),
            in_specs=[pl.BlockSpec((eh, ew), lambda t: index_map(t))],
            out_specs=pl.BlockSpec((1, eh, ew), lambda t: (t, 0, 0)),
            out_shape=jax.ShapeDtypeStruct((n_tokens, eh, ew), data.dtype),
            interpret=interpret,
            **kernel_name("stream_converter"),
        )(data)

    # Window buffer path: grid over the consumer stream; every tile read
    # comes from the window, whose block index is the shared-prefix part of
    # the tile coordinate.  Window extents from Algorithm 1.
    wh, ww = spec.buf_shape
    gh = src.data_shape[0] // wh
    gw = src.data_shape[1] // ww

    def in_map(t):
        oh, ow = _nth_offset_traced(dst, t)   # element-unit offsets
        return (oh // wh, ow // ww)           # window-block units

    def kernel(win_ref, out_ref, *, spec_shapes):
        t = pl.program_id(0)
        oh, ow = _nth_offset_traced(dst, t)
        local_h = oh % wh
        local_w = ow % ww
        tile = jax.lax.dynamic_slice(win_ref[...], (local_h, local_w),
                                     (eh, ew))
        out_ref[0] = tile

    return pl.pallas_call(
        functools.partial(kernel, spec_shapes=(wh, ww)),
        grid=(n_tokens,),
        in_specs=[pl.BlockSpec((wh, ww), in_map)],
        out_specs=pl.BlockSpec((1, eh, ew), lambda t: (t, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_tokens, eh, ew), data.dtype),
        interpret=interpret,
        **kernel_name("stream_converter"),
    )(data)


def _nth_offset(t_type: ITensorType, n):
    """Data offset of the n-th stream token (trace-time arithmetic)."""
    trips = t_type.tripcounts
    idx = []
    rem = n
    for tc in reversed(trips):
        idx.append(rem % tc)
        rem = rem // tc
    idx = list(reversed(idx))
    offs = tuple(idx[k] * t_type.steps[k] for k in t_type.iter_map.results)
    # BlockSpec index maps are in units of blocks.
    return tuple(o // e for o, e in zip(offs, t_type.elem_shape))


def _nth_offset_traced(t_type: ITensorType, n):
    """Same as _nth_offset but in data elements (for in-window slicing)."""
    trips = t_type.tripcounts
    idx = []
    rem = n
    for tc in reversed(trips):
        idx.append(rem % tc)
        rem = rem // tc
    idx = list(reversed(idx))
    return tuple(idx[k] * t_type.steps[k] for k in t_type.iter_map.results)
