"""``chip_smoke.py`` at a tiny size on CPU: its phases, its margin rule,
and its refusal to run (or print a result) without a TPU."""

from __future__ import annotations

import dataclasses
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import init_params

_spec = importlib.util.spec_from_file_location(
    "chip_smoke", Path(__file__).resolve().parents[1] / "chip_smoke.py")
cs = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(cs)


@pytest.fixture(scope="module")
def small():
    cfg = dataclasses.replace(get_config(cs.ARCH).reduced(),
                              use_fused_kernels=True)
    return cfg, init_params(jax.random.PRNGKey(0), cfg)


def test_refuses_without_tpu(capsys):
    assert cs.main([]) != 0
    assert capsys.readouterr().out == ""        # no result line


def test_margin_rule():
    prompt = np.arange(2)
    margin = np.zeros(8)
    assert cs._first_divergence(prompt, [5, 6, 7], [5, 6, 7], margin,
                                tol=0.1) is None
    margin[len(prompt) - 1 + 2] = 0.15          # within 2*tol: tolerated
    assert cs._first_divergence(prompt, [5, 6, 7], [5, 6, 9], margin,
                                tol=0.1) == (2, pytest.approx(0.15))
    margin[len(prompt) - 1 + 2] = 0.5           # decisive step: failure
    with pytest.raises(cs.SmokeFailure):
        cs._first_divergence(prompt, [5, 6, 7], [5, 6, 9], margin, tol=0.1)


def test_correctness_phase_fused_matches_eager(small):
    cfg, params = small
    rng = np.random.default_rng(0)
    r = cs.correctness_phase(cfg, params, cs._prompts(cfg, (32, 80), rng),
                             new_tokens=8, max_len=128, block=16)
    assert r["max_abs_logit_err"] <= cs.TOL_F32
    assert len(r["token_divergence"]) == 2


def test_serving_phase_serves_every_request(small):
    cfg, params = small
    rng = np.random.default_rng(1)
    bf16 = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
    prompts = cs._prompts(cfg, rng.integers(16, 64, 4), rng)
    r = cs.serving_phase(cfg, bf16, prompts, cs._prompts(cfg, (16,), rng)[0],
                         new_tokens=8, slots=2, max_len=128)
    assert r["requests"] == 4
    assert all(len(t) == 8 for t in r["tokens"])
    assert set(cs.FUSED_STAGES) <= set(r["stages"])


def test_compile_cache_dir_is_placed_from_outside(monkeypatch, tmp_path):
    from repro.launch import compile_cache

    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
        monkeypatch.delenv(compile_cache.ENV_VAR)
        path = compile_cache.enable_compile_cache()
        # A fixed in-checkout path: the cache key includes it.
        assert path == str(Path(cs.__file__).resolve().parent / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
