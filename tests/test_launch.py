"""Launch-layer machinery testable without 512 devices: input specs,
HLO collective parsing, roofline arithmetic, accum/param accounting."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, SHAPES, cells_for, get_config
from repro.launch.dryrun import (
    TRAIN_ACCUM, parse_collectives, roofline_terms, _shape_bytes,
)
from repro.launch.specs import count_params
from repro.launch import hw


def test_shape_bytes_parser():
    assert _shape_bytes("bf16[128,1024]{1,0}") == 128 * 1024 * 2
    assert _shape_bytes("f32[16]{0}") == 64
    assert _shape_bytes("(bf16[8,8]{1,0}, f32[4]{0})") == 128 + 16
    assert _shape_bytes("pred[]") == 1


def test_parse_collectives_counts_ops():
    hlo = """
  %ag = bf16[64,512]{1,0} all-gather(bf16[4,512]{1,0} %x), replica_groups={}
  %ar.1 = f32[1024]{0} all-reduce(f32[1024]{0} %y), to_apply=%sum
  %cp = f32[32]{0} collective-permute(f32[32]{0} %z), source_target_pairs={{0,1}}
  %a2a = (bf16[8,8]{1,0}, bf16[8,8]{1,0}) all-to-all(bf16[8,8]{1,0} %a, bf16[8,8]{1,0} %b)
"""
    out = parse_collectives(hlo)
    assert out["all-gather"]["count"] == 1
    assert out["all-gather"]["bytes"] == 64 * 512 * 2
    assert out["all-reduce"]["bytes"] == 4096
    assert out["collective-permute"]["count"] == 1
    assert out["all-to-all"]["bytes"] == 2 * 64 * 2


def test_roofline_terms_math():
    coll = {"all-reduce": {"count": 1, "bytes": hw.ICI_BW}}  # 1s at 2x mult
    t = roofline_terms(hw.PEAK_FLOPS_BF16, hw.HBM_BW, coll)
    assert t["compute_s"] == pytest.approx(1.0)
    assert t["memory_s"] == pytest.approx(1.0)
    assert t["collective_s"] == pytest.approx(2.0)
    assert t["bottleneck"] == "collective_s"


def test_count_params_llama405():
    total, active = count_params(get_config("llama3-405b"))
    assert 3.9e11 < total < 4.2e11       # ~405B
    assert total == active               # dense


def test_count_params_moe_active_fraction():
    total, active = count_params(get_config("granite-moe-1b-a400m"))
    assert 1.2e9 < total < 1.5e9         # ~1.3B total
    assert 3.5e8 < active < 5.5e8        # ~400M active
    t2, a2 = count_params(get_config("deepseek-v2-236b"))
    assert 2.0e11 < t2 < 2.6e11          # ~236B total
    assert 1.5e10 < a2 < 3.0e10          # ~21B active


def test_cell_coverage_is_32():
    cells = sum(len(cells_for(get_config(a))) for a in ARCH_IDS)
    assert cells == 32                   # 10x3 + 2 long_500k (ssm/hybrid)


def test_accum_configured_for_big_models():
    assert TRAIN_ACCUM["llama3-405b"] >= 16
    assert TRAIN_ACCUM["nemotron-4-340b"] >= 16


def test_mesh_factories():
    from repro.launch.mesh import make_elastic_mesh, make_test_mesh
    m = make_test_mesh()
    assert set(m.axis_names) == {"data", "model"}
    e = make_elastic_mesh(1, model_parallel=4)
    assert e.size == 1


@pytest.mark.parametrize("env_dir", [None, "elsewhere"],
                         ids=["repo-cache", "env-cache"])
def test_use_compile_cache(monkeypatch, tmp_path, env_dir):
    """Without JAX_COMPILATION_CACHE_DIR the cache goes to the fixed
    <repo>/.jax_cache; with it, nothing is set in code and the directory
    named there is reported."""
    import os
    from repro.launch import cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: seen.update({name: value}))
    seen = {}
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert cache.use_compile_cache() == cache.REPO_CACHE_DIR
        assert seen == {"jax_compilation_cache_dir": cache.REPO_CACHE_DIR}
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert cache.REPO_CACHE_DIR == os.path.join(repo, ".jax_cache")
    else:
        want = str(tmp_path / env_dir)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
        assert cache.use_compile_cache() == want
        assert seen == {}
    assert jax.config.jax_compilation_cache_dir == before
