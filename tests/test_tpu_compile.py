"""Main-path kernels compile for a described TPU v5e at qwen3-0.6b widths,
and the paged decode kernel at qwen1.5-0.5b's (head_dim 64) as well.

Interpret mode (every other kernel test) cannot see misaligned blocks,
layouts Mosaic refuses, or VMEM overruns; the TPU compiler can, and it is
installed even where no chip is attached.  Each case lowers one kernel in
bf16 (int8 where named) at the serving shapes the engine dispatches
(2048-token slots of 16-token pages, 128-token prefill chunks, the plan's
128 blocks) against one chip of a described ``v5e:2x2`` topology, and
checks that the compiled program holds the Pallas kernel under its own
name.  A decode step compiled the same way names its layers in its ops'
metadata.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and xdist workers each import this
file.  The worker that runs these tests holds the library until it exits,
so every compile stays in that process.
"""

from __future__ import annotations

import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import (paged_decode_attention, paged_verify_attention,
                           rmsnorm_matmul, streamed_ffn)
from repro.kernels.ops import flash_attention

CFG = get_config("qwen3-0.6b")
MAX_LEN, PAGE, CHUNK, BLOCK, DRAFT_W = 2048, 16, 128, 128, 4
N_PAGES = MAX_LEN // PAGE

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


# The paged kernel at the benchmark's two configurations: qwen3-0.6b's
# chat engine (16 slots, GQA 16/8, head_dim 128) and qwen1.5-0.5b's (8
# slots, MHA 16/16, head_dim 64), each over 128-page tables.
PAGED_SHAPES = {"": (16, 16, 8, 128), "hd64": (8, 16, 16, 64)}


def _paged(verify: bool, quant: bool, shape: str = ""):
    slots, hq, hkv, d = PAGED_SHAPES[shape]
    pool_pages = 1 + slots * N_PAGES          # +1: the NULL page
    w = DRAFT_W if verify else 1
    pool_dtype = I8 if quant else BF16
    shapes = [((slots, w, hq, d), BF16),
              ((pool_pages, hkv, PAGE, d), pool_dtype),
              ((pool_pages, hkv, PAGE, d), pool_dtype),
              ((slots, N_PAGES), I32), ((slots,), I32)]
    if quant:
        shapes += [((pool_pages, hkv), F32)] * 2
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


# The ``kernel_metadata`` name each case's kernel carries, by case prefix.
KERNEL = {"paged": "paged_attention", "flash": "flash_attention",
          "rmsnorm": "rmsnorm_matmul", "streamed": "streamed_ffn"}

CASES = {
    "paged_decode": lambda: _paged(verify=False, quant=False),
    "paged_decode_int8": lambda: _paged(verify=False, quant=True),
    "paged_verify": lambda: _paged(verify=True, quant=False),
    "paged_verify_int8": lambda: _paged(verify=True, quant=True),
    "paged_decode_hd64": lambda: _paged(False, False, "hd64"),
    "paged_decode_hd64_int8": lambda: _paged(False, True, "hd64"),
    "paged_verify_hd64": lambda: _paged(True, False, "hd64"),
    "paged_verify_hd64_int8": lambda: _paged(True, True, "hd64"),
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
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    kernel = KERNEL[name.split("_")[0]]
    assert re.search(r'kernel_metadata=\{\s*"kernel":\s*"%s"\s*\}' % kernel,
                     text), kernel


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
def test_paged_decode_reads_hd64_pools_unpadded_for_v5e(quant, one_chip):
    """At head_dim 64 the paged decode kernel reads the pools as stored:
    neither the lowered nor the compiled program pads them (or q) to 128
    lanes."""
    fn, shapes = _paged(verify=False, quant=quant, shape="hd64")
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    lowered = jax.jit(fn).lower(*args)
    assert "pad" not in lowered.as_text()
    compiled = lowered.compile().as_text()
    assert "tpu_custom_call" in compiled
    assert not re.search(r"\spad\(", compiled)


def test_decode_step_names_its_layers_for_v5e(one_chip):
    """A paged decode step (reduced widths, eager) compiled for the chip
    keeps each layer's ``named_scope`` in its ops' ``op_name``."""
    from repro.models import abstract_params, decode_step
    from repro.serving.kv_cache import paged_cache_defs

    cfg = dataclasses.replace(CFG.reduced(), dtype="bfloat16")
    slots, max_len, page = 2, 64, 16

    def shape(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    params = jax.tree.map(shape, abstract_params(cfg))
    cache = jax.tree.map(shape, paged_cache_defs(cfg, slots, max_len, page),
                         is_leaf=lambda x: hasattr(x, "shape"))
    tok = jax.ShapeDtypeStruct((slots, 1), I32, sharding=one_chip)
    vec = jax.ShapeDtypeStruct((slots,), I32, sharding=one_chip)
    table = jax.ShapeDtypeStruct((slots, max_len // page), I32,
                                 sharding=one_chip)

    def step(p, t, c, pos, tbl):
        return decode_step(p, cfg, t, c, pos, pos, page_table=tbl)

    text = jax.jit(step).lower(params, tok, cache, vec, table).compile(
        ).as_text()
    names = " ".join(re.findall(r'op_name="([^"]*)"', text))
    for scope in ("embed", "layers", "qkv", "kv_append", "attention",
                  "out_proj", "ffn", "final_norm", "lm_head", "argmax"):
        assert f"/{scope}/" in names, scope
