"""The main path's kernels compile for a TPU v5e chip that is described, not
attached: the fused top-k passes at the TPU tiling the heuristic picks for
the kNN-TagSpace shape (d=256, Q=4096) at N = 2^20 and N = 2^26, both
levels of the two-level pass 1 at the benchmark cells' geometry, and the
materializing distance kernel. Each compiled program must hold the Mosaic
kernel (``tpu_custom_call``) — what the TPU compiler refuses fails here,
without a chip. So must the benchmark's two search programs at small N (one
chip over a prebuilt layout, four chips through hist_merge), under the
kernels' own instruction names, with no host callback. All compiles stay in
this one file (one worker, one TPU compiler library)."""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import engine, layout as layout_mod
from repro.kernels import tuning
from repro.kernels.hamming import hamming_distance_pallas
from repro.kernels.topk_select import hamming_emit_pallas, hamming_hist_pallas

D, Q, K = 256, 4096, 16
W, BINS = D // 32, D + 1
SEARCH_Q, SEARCH_ROWS = 128, 1 << 20


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """Compiles for a described chip cannot be read back from the
    persistent cache; keep them out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "not here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    from jax.sharding import SingleDeviceSharding

    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype=jnp.int32: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)


def _geometry(n: int):
    bq, bn, sub = tuning._topk_blocks_default(Q, n, W, max(BINS, K), "tpu")
    n_pad = -(-n // bn) * bn
    return bq, bn, sub, n_pad, (Q // bq, n_pad // bn)


@pytest.mark.parametrize("n", [1 << 20, 1 << 26])
def test_hist_compiles_for_v5e(spec, n):
    bq, bn, sub, n_pad, tiles = _geometry(n)
    fn = jax.jit(lambda q, x, nv, m: hamming_hist_pallas(
        q, x, BINS, nv, block_mask=m, bq=bq, bn=bn, sub=sub))
    compiled = fn.lower(spec((Q, W), jnp.uint32), spec((n_pad, W), jnp.uint32),
                        spec(()), spec(tiles)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("level", ["coarse", "fine"])
def test_two_level_hist_compiles_for_v5e(spec, level):
    """Both levels of pass 1 at the benchmark cells' geometry: Q = 128,
    N = 2^26 per chip, d = 256 (bq 128, bn 65536, sub 128)."""
    n, q = 1 << 26, SEARCH_Q
    bq, bn, sub = tuning._topk_blocks_default(q, n, W, max(BINS, K), "tpu")
    assert (bq, bn, sub) == (128, 65536, 128)
    shift = tuning.race_shift(BINS)
    tiles = (q // bq, n // bn)
    if level == "coarse":
        fn = jax.jit(lambda q, x, nv, m: hamming_hist_pallas(
            q, x, BINS, nv, block_mask=m, shift=shift, bq=bq, bn=bn,
            sub=sub))
        args = (spec(()), spec(tiles))
    else:
        fn = jax.jit(lambda q, x, nv, m, b, bm: hamming_hist_pallas(
            q, x, BINS, nv, block_mask=m, base=b, block_min=bm,
            window=1 << shift, bq=bq, bn=bn, sub=sub))
        args = (spec(()), spec(tiles), spec((q,)), spec(tiles))
    compiled = fn.lower(spec((q, W), jnp.uint32), spec((n, W), jnp.uint32),
                        *args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("n", [1 << 20, 1 << 26])
def test_emit_compiles_for_v5e(spec, n):
    bq, bn, sub, n_pad, tiles = _geometry(n)
    fn = jax.jit(lambda q, x, r, nlt, nv, bm, m, sb, ib: hamming_emit_pallas(
        q, x, r, nlt, BINS, K, nv, block_min=bm, block_mask=m, slot_base=sb,
        id_base=ib, bq=bq, bn=bn, sub=sub))
    compiled = fn.lower(
        spec((Q, W), jnp.uint32), spec((n_pad, W), jnp.uint32), spec((Q,)),
        spec((Q,)), spec(()), spec(tiles), spec(tiles), spec((Q,)),
        spec(())).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_distance_kernel_compiles_for_v5e(spec):
    n = 1 << 20
    bq, bn = tuning.distance_blocks(Q, n, W, backend="tpu")
    fn = jax.jit(lambda q, x: hamming_distance_pallas(q, x, bq=bq, bn=bn))
    compiled = fn.lower(spec((Q, W), jnp.uint32),
                        spec((n, W), jnp.uint32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


_INSTR = re.compile(r"^\s*(?:ROOT )?%([\w.-]+) = ", re.M)


@pytest.fixture
def as_v5e(monkeypatch):
    """The program asks ``jax.default_backend()`` for its kernel geometry and
    whether to interpret the kernels: answer for the described chip."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


_KERNEL = re.compile(r'^\s*(?:ROOT )?%([\w.-]+) = .*'
                     r'custom_call_target="tpu_custom_call"', re.M)


def _check_search_program(hlo: str, collectives=()):
    """The benchmark's device-trace readers key on these instruction names;
    a host callback would keep the program out of the compile cache. Pass 1
    at d = 256 races in two levels, both calls named
    ``hamming_hist_pallas``; pass 2 is one ``hamming_emit_pallas``, and no
    other kernel runs."""
    names = _INSTR.findall(hlo)
    for prefix in ("hamming_hist_pallas", "hamming_emit_pallas") + collectives:
        assert any(n.startswith(prefix) for n in names), prefix
    kernels = sorted(n.split(".")[0] for n in _KERNEL.findall(hlo))
    assert tuning.race_shift(BINS) > 0
    assert kernels == ["hamming_emit_pallas"] + ["hamming_hist_pallas"] * 2, (
        kernels)
    assert not re.search(r'custom_call_target="[^"]*callback', hlo)
    assert "is_host_transfer=true" not in hlo


def test_one_chip_layout_search_compiles_for_v5e(spec, as_v5e):
    n_buckets = 1 << layout_mod.default_bits(SEARCH_ROWS)
    lay = layout_mod.BucketLayout(
        codes=spec((SEARCH_ROWS, W), jnp.uint32), perm=spec((SEARCH_ROWS,)),
        inv=spec((SEARCH_ROWS,)), starts=spec((n_buckets + 1,)))
    fn = jax.jit(lambda cc, lo, q: engine.KNNEngine(
        codes=cc, d=D, layout=lo).search(q, K))
    compiled = fn.lower(spec((SEARCH_ROWS, W), jnp.uint32), lay,
                        spec((SEARCH_Q, W), jnp.uint32)).compile()
    _check_search_program(compiled.as_text())


def test_four_chip_hist_merge_search_compiles_for_v5e(topo, as_v5e):
    mesh = Mesh(np.asarray(topo.devices), ("data",))
    fn = jax.jit(lambda cc, q: engine.search_sharded(
        cc, q, K, D, mesh, ("data",)))
    compiled = fn.lower(
        jax.ShapeDtypeStruct((4 * SEARCH_ROWS, W), jnp.uint32,
                             sharding=NamedSharding(mesh, P("data", None))),
        jax.ShapeDtypeStruct((SEARCH_Q, W), jnp.uint32,
                             sharding=NamedSharding(mesh, P()))).compile()
    _check_search_program(compiled.as_text(), collectives=("all-reduce",))
