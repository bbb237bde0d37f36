"""Trace reduction, FLOP/byte functions and the peak table (CPU only)."""

import json
from collections import Counter
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import flops, peaks, trace
from bench.trace import Event

DATA = Path(__file__).parent / "data"
QWEN3 = json.loads((Path(__file__).parents[1] / "configs"
                    / "qwen3-0.6b.json").read_text())


def test_recorded_tpu_op_names_classify_by_kernel():
    rec = json.loads((DATA / "v5e_trace_names.json").read_text())
    kinds = Counter(trace.op_kind(o["name"]) for o in rec["xla_ops"])
    assert kinds["paged_attention"] == 1
    assert kinds["flash_attention"] == 1
    assert kinds["streamed_ffn"] == 2
    assert kinds["rmsnorm_matmul"] == 2
    assert kinds["while"] == 3
    assert kinds["copy"] == 10
    assert kinds["pallas_call"] == 0
    assert kinds["bitcast_dynamic-update-slice_fusion"] == 4
    mods = {m["name"].split("(")[0] for m in rec["xla_modules"]}
    assert {"jit__decode_n", "jit__chunk_fwd"} <= mods


# Operand lists of the program's Pallas kernels as compiled for a described
# TPU v5e at qwen3-0.6b and qwen1.5-0.5b widths (the same words at both),
# bf16 and int8 forms.
COMPILED = [
    ("s32:1 bf16:3 bf16:3 bf16:3", "flash_attention"),
    ("s32:1 bf16:3 s8:3 s8:3 f32:3 f32:3", "flash_attention"),
    ("s32:1 s32:2 bf16:4 bf16:4 bf16:4", "paged_attention"),
    ("s32:1 s32:2 bf16:4 s8:4 s8:4 f32:3 f32:3", "paged_attention"),
    ("bf16:2 bf16:1 bf16:2", "rmsnorm_matmul"),
    ("bf16:2 bf16:1 s8:2 f32:2", "rmsnorm_matmul"),
    ("bf16:2 bf16:1 bf16:2 bf16:2 bf16:2", "streamed_ffn"),
    ("bf16:2 bf16:1 s8:2 f32:2 s8:2 f32:2 s8:2 f32:2", "streamed_ffn"),
    ("bf16:2 bf16:2", trace.UNNAMED),
]


@pytest.mark.parametrize("words,kernel", COMPILED,
                         ids=[f"{k}-{i}" for i, (_, k) in enumerate(COMPILED)])
def test_kernel_signatures_name_each_compiled_form_once(words, kernel):
    ops = [(w.split(":")[0], (1,) * int(w.split(":")[1]))
           for w in words.split()]
    assert trace.kernel_of(ops) == kernel


def test_union_gaps_busy_and_clip_by_hand():
    ev = [Event("a", 0, 10), Event("b", 5, 10), Event("c", 30, 5),
          Event("d", 40, 0)]
    assert trace.union(ev) == [(0, 15), (30, 35), (40, 40)]
    assert trace.busy_ns(ev) == 20
    assert trace.gaps(ev, -5, 50) == [(-5, 0), (15, 30), (35, 40),
                                      (40, 50)]
    clipped = trace.clip(ev, 8, 32)
    assert [(e.name, e.start, e.dur) for e in clipped] == [
        ("a", 8, 2), ("b", 8, 7), ("c", 30, 2)]


def test_inside_modules_and_host_label():
    tr = trace.Trace(
        ops=[Event("x", 1, 1), Event("y", 12, 1), Event("z", 25, 1)],
        modules=[Event("jit__decode_n(123)", 10, 5),
                 Event("jit__chunk_fwd(9)", 0, 5)],
        host=[Event("bench batch 0", 0, 40), Event("wait", 20, 10)])
    dec = trace.modules_named(tr, "_decode_n")
    assert [e.name for e in trace.inside(tr.ops, dec)] == ["y"]
    assert trace.host_label(tr.host, 21, 23) == "wait"
    assert trace.host_label(tr.host, 31, 33) == "bench batch 0"
    assert trace.host_label(tr.host, 50, 52) == "(no host event)"


def test_load_reads_a_recorded_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench batch 0"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    pb = next(tmp_path.rglob("*.xplane.pb"))
    tr = trace.load(str(pb))
    assert tr.devices == 0 and tr.ops == []          # no TPU plane on CPU
    ann = [e for e in tr.host if e.name == "bench batch 0"]
    assert len(ann) == 1 and ann[0].dur > 0


def test_flops_and_bytes_by_hand():
    per_layer = 1024 * (2048 + 2 * 1024) + 2048 * 1024 + 3 * 1024 * 3072
    assert flops.layer_matmul_params(QWEN3) == per_layer == 15_728_640
    head = 2 * 1024 * 151936
    assert flops.head_flops(QWEN3) == head
    # positions 0..3 see 1+2+3+4 = 10 keys: 4 * 10 * 16 heads * 128 * 28
    assert flops.attention_flops(QWEN3, 0, 4) == 4 * 10 * 16 * 128 * 28
    assert flops.prompt_flops(QWEN3, 0, 4) == (
        2 * 4 * per_layer * 28 + 4 * 10 * 16 * 128 * 28 + head)
    assert flops.prompt_flops(QWEN3, 4, 4) == 0
    # one token fed at position 9 sees 10 keys
    assert flops.decode_flops(QWEN3, 9, 1) == (
        2 * per_layer * 28 + 4 * 10 * 16 * 128 * 28 + head)
    kv = 2 * 28 * 8 * 128 * 2
    assert flops.kv_bytes_per_token(QWEN3) == kv == 114_688
    # ticks feed positions 100, 101, 102: they read 101 + 102 + 103 tokens
    assert flops.live_kv_bytes(QWEN3, 100, 3) == 306 * kv
    assert flops.decode_weight_bytes(QWEN3) == 2 * (per_layer * 28
                                                   + 1024 * 151936)


def test_peak_table_keyed_by_device_kind():
    p = peaks.peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("TPU v9 imaginary")


def _metric(name):
    import importlib.util
    path = Path(__file__).parents[1] / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(name.replace(".", "_"),
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_roofline_readers_by_hand():
    """Two requests decoded 3 tokens each (2 decode ticks after the first
    token); the decode program ran 1 ms, the paged kernel 0.5 ms."""
    reqs = [SimpleNamespace(prompt=[0] * 100, out_tokens=[1, 2, 3]),
            SimpleNamespace(prompt=[0] * 50, out_tokens=[1, 2, 3])]
    tr = trace.Trace(
        ops=[Event('%c = bf16[2,8,2,128] custom-call(s32[2] %a), '
                   'custom_call_target="tpu_custom_call", '
                   'operand_layout_constraints={s32[2]{0}, s32[2,128]{1,0}, '
                   'bf16[2,8,2,128]{3,2,1,0}, bf16[9,8,16,128]{3,2,1,0}, '
                   'bf16[9,8,16,128]{3,2,1,0}}, frontend_attributes={}',
                   0, 5e5)],
        modules=[Event("jit__decode_n(1)", 0, 1e6)])
    r = SimpleNamespace(cfg=QWEN3, reqs=reqs, trace=tr,
                        counters={"ticks": 2.0},
                        peaks=peaks.peaks("TPU v5 lite"))
    kv = (flops.live_kv_bytes(QWEN3, 100, 2)
          + flops.live_kv_bytes(QWEN3, 50, 2))
    got = _metric("paged_attention_roofline").read(r)
    assert got == pytest.approx(100 * kv / 819e9 / 5e-4)
    need = 2 * flops.decode_weight_bytes(QWEN3) + kv
    got = _metric("decode_step_roofline").read(r)
    assert got == pytest.approx(100 * need / 819e9 / 1e-3)
