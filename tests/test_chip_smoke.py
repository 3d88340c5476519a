"""CPU rehearsal of chip_smoke.py: its phase functions at a tiny size (the
kernels interpreted), and its refusals — ``main()`` itself never runs
without a TPU, so these tests are what guard the script between chip
runs."""
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def _exact(r: dict, *keys):
    for key in keys:
        assert r[key] == r["checked"], (key, r)


def test_store_phase_tiny_matches_reference():
    res = chip_smoke.store_phase(0, n=4096, q=64, chunk=512)
    assert "select:composite" in res["composite"]["plan"]
    assert "select:fused" in res["fused"]["plan"]
    assert "prebuilt" in res["fused"]["plan"]
    for r in res.values():
        assert r["checked"] == 64 // chip_smoke.REF_EVERY
        _exact(r, "match_dists", "match_ids")


def test_served_phase_tiny_completes_without_failover():
    from repro.configs import get_config, scaled_down

    r = chip_smoke.served_phase(0, cfg=scaled_down(get_config("rwkv6-1.6b")),
                                n_requests=2, prompt_len=3, new_tokens=3)
    assert "select:fused" in r["plan"]
    assert r["done"] == r["complete"] == 2 and r["tokens_in_vocab"]
    assert r["shed"] == r["timed_out"] == r["lost"] == 0
    assert r["failover_ticks"] == r["search_failures"] == 0
    assert r["match_dists"] == r["match_ids"] == r["checked"] == 2


def test_sharded_phase_tiny_four_devices(multidevice):
    out = multidevice(f"""
import sys
sys.path.insert(0, {REPO!r})
import jax, chip_smoke
r = chip_smoke.sharded_phase(0, jax.devices()[:4], n=4 * 2048, q=32,
                             chunk=512)
assert r["rows_per_device"] == 2048 and "hist_merge" in r["plan"], r
assert r["match_dists"] == r["match_ids"] == r["checked"] == 2, r
print("SHARDED_OK")
""", n_devices=4)
    assert "SHARDED_OK" in out


def test_reference_ties_break_by_index():
    import jax.numpy as jnp

    codes = jnp.zeros((1024, 8), jnp.uint32)         # every row ties
    q = jnp.zeros((2, 8), jnp.uint32)
    dd, ii = chip_smoke.reference_topk(codes, q, 5, shards=2, chunk=256)
    assert (dd == 0).all() and (ii == jnp.arange(5)).all()


def test_main_refuses_cpu(capsys):
    with pytest.raises(SystemExit) as e:
        chip_smoke.main([])
    assert e.value.code != 0
    assert '"ok"' not in capsys.readouterr().out


def test_refuses_outside_checkout(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


@pytest.mark.parametrize("env_dir", [None, "/nonexistent/jax-cache"])
def test_compile_cache_location(monkeypatch, env_dir):
    """JAX_COMPILATION_CACHE_DIR wins (JAX reads it; nothing is set in
    code); unset, the cache goes to the fixed <checkout>/.jax_cache."""
    import jax

    from repro.launch import cache

    before = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    try:
        got = cache.enable_compile_cache()
        if env_dir is None:
            assert got == os.path.join(REPO, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
        else:
            assert got == env_dir
            assert jax.config.jax_compilation_cache_dir == before
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
