"""Main-path kernels compile for a described TPU v5e at qwen3-0.6b widths.

Interpret mode (every other kernel test) cannot see misaligned blocks,
layouts Mosaic refuses, or VMEM overruns; the TPU compiler can, and it is
installed even where no chip is attached.  Each case lowers one kernel in
bf16 at the serving shapes the engine dispatches (8 slots, 2048-token
slots of 16-token pages, 128-token prefill chunks, the plan's 128 blocks)
against one chip of a described ``v5e:2x2`` topology, and checks that the
compiled program holds the Pallas kernel.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and xdist workers each import this
file.  The worker that runs these tests holds the library until it exits,
so every compile stays in that process.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import (paged_decode_attention, paged_verify_attention,
                           rmsnorm_matmul, streamed_ffn)
from repro.kernels.ops import flash_attention

CFG = get_config("qwen3-0.6b")
SLOTS, MAX_LEN, PAGE, CHUNK, BLOCK, DRAFT_W = 8, 2048, 16, 128, 128, 4
N_PAGES = MAX_LEN // PAGE
POOL_PAGES = 1 + SLOTS * N_PAGES          # +1: the NULL page

BF16, I8, F32, I32 = jnp.bfloat16, jnp.int8, jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:            # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # A compile for a described chip is written to the persistent cache
    # but can never be read back without one; keep the cache out of it.
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _paged(verify: bool, quant: bool):
    hq, hkv, d = CFG.num_heads, CFG.num_kv_heads, CFG.head_dim_
    w = DRAFT_W if verify else 1
    pool_dtype = I8 if quant else BF16
    shapes = [((SLOTS, w, hq, d), BF16),
              ((POOL_PAGES, hkv, PAGE, d), pool_dtype),
              ((POOL_PAGES, hkv, PAGE, d), pool_dtype),
              ((SLOTS, N_PAGES), I32), ((SLOTS,), I32)]
    if quant:
        shapes += [((POOL_PAGES, hkv), F32)] * 2
    kernel = paged_verify_attention if verify else paged_decode_attention

    def fn(q, kp, vp, tbl, off, *scales):
        ks, vs = scales if scales else (None, None)
        return kernel(q, kp, vp, tbl, off, k_scale=ks, v_scale=vs,
                      interpret=False)
    return fn, shapes


def _flash(quant: bool):
    hq, hkv, d = CFG.num_heads, CFG.num_kv_heads, CFG.head_dim_
    kv_dtype = I8 if quant else BF16
    shapes = [((1, CHUNK, hq, d), BF16), ((1, MAX_LEN, hkv, d), kv_dtype),
              ((1, MAX_LEN, hkv, d), kv_dtype), ((), I32), ((), I32)]
    if quant:
        shapes += [((1, MAX_LEN, hkv), F32)] * 2

    def fn(q, k, v, off, kv_len, *scales):
        ks, vs = scales if scales else (None, None)
        return flash_attention(q, k, v, q_offset=off, kv_len=kv_len,
                               block_q=BLOCK, block_kv=BLOCK,
                               k_scale=ks, v_scale=vs, interpret=False)
    return fn, shapes


def _norm_matmul(t: int, w8: bool):
    d, n = CFG.d_model, CFG.q_dim
    shapes = [((t, d), BF16), ((d,), BF16), ((d, n), I8 if w8 else BF16)]
    if w8:
        shapes.append(((n,), F32))

    def fn(x, scale, w, *w_scale):
        return rmsnorm_matmul(x, scale, w, block_t=BLOCK, block_n=BLOCK,
                              w_scale=w_scale[0] if w8 else None,
                              interpret=False)
    return fn, shapes


def _ffn(t: int, w8: bool):
    d, f = CFG.d_model, CFG.d_ff
    wdt = I8 if w8 else BF16
    shapes = [((t, d), BF16), ((d,), BF16), ((d, f), wdt), ((d, f), wdt),
              ((f, d), wdt)]
    if w8:
        shapes += [((f,), F32), ((f,), F32), ((d,), F32)]

    def fn(x, norm, wg, wu, wd, *scales):
        qkw = (dict(zip(("wg_scale", "wu_scale", "wd_scale"), scales))
               if w8 else {})
        return streamed_ffn(x, wg, wu, wd, norm_scale=norm, block_t=BLOCK,
                            block_f=BLOCK, interpret=False, **qkw)
    return fn, shapes


CASES = {
    "paged_decode": lambda: _paged(verify=False, quant=False),
    "paged_decode_int8": lambda: _paged(verify=False, quant=True),
    "paged_verify": lambda: _paged(verify=True, quant=False),
    "paged_verify_int8": lambda: _paged(verify=True, quant=True),
    "flash_offset": lambda: _flash(quant=False),
    "flash_offset_int8": lambda: _flash(quant=True),
}
for _t in (8, 256):
    for _w8 in (False, True):
        _sfx = f"{'_w8' if _w8 else ''}_t{_t}"
        CASES["rmsnorm_matmul" + _sfx] = (
            lambda t=_t, w8=_w8: _norm_matmul(t, w8))
        CASES["streamed_ffn" + _sfx] = lambda t=_t, w8=_w8: _ffn(t, w8)


def test_widths_are_published():
    """The cases run at Qwen3-0.6B's published widths (head_dim 128)."""
    assert (CFG.d_model, CFG.num_heads, CFG.num_kv_heads, CFG.head_dim_,
            CFG.d_ff) == (1024, 16, 8, 128, 3072)


@pytest.mark.parametrize("name", sorted(CASES))
def test_main_path_kernel_compiles_for_v5e(name, one_chip):
    fn, shapes = CASES[name]()
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
