"""A CPU rehearsal of the harness at tiny sizes: each store cell's set-up,
closed loop, check against the plain reference and metric readers, driven
through the runner's functions (``run.py``'s ``main`` refuses the CPU);
the check comes out false under each fault the cells can have; cells,
configurations, traffic mixes and metrics added as files are found by name.
"""
import json
import os
import shutil
import subprocess
import sys
import time

import pytest

import _paths
from harness import peaks, runner, spec, trace
from harness.trace import Event

V5E = peaks.PEAKS["TPU v5 lite"]
TINY_STORE = {"rows": 1 << 14, "check_queries_per_batch": 4}
TINY_BATCHES = {"batch": 8, "pool_batches": 3}


def _tiny(cell: spec.Cell) -> spec.Cell:
    cell.config = dict(cell.config, **TINY_STORE)
    cell.traffic = dict(cell.traffic, **TINY_BATCHES)
    return cell


def _ctx(cell, seed=2 ** 31 + 11):
    import jax
    return runner.Ctx(cell=cell, seed=seed, devices=jax.devices()[:cell.chips],
                      peaks=V5E)


def _synthetic_summary(batches: int, chips: int) -> dict:
    devices = {}
    for c in range(chips):
        ops = []
        for b in range(batches):
            t = 1.0 + 3.0 * b
            ops += [Event("hamming_hist_pallas.1", t, t + 2.0),
                    Event("hamming_emit_pallas.1", t + 2.0, t + 2.5),
                    Event("all-reduce.1", t + 2.5, t + 2.6)]
        devices[f"/device:TPU:{c}"] = ops
    return trace.reduce({"devices": devices, "spans": [
        Event("bench.window", 0.5, 1.0 + 3.0 * batches)]})


def test_store_cell_runs_and_checks():
    cell = _tiny(spec.load_cell("tagspace.batch128"))
    res = runner.run_cell(_ctx(cell), 0.5, False, time.perf_counter())
    assert res["correct"] is True
    assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert res["metrics"]["search_qps"]["value"] > 0
    assert list(res)[-1] == "checks"
    assert res["checks"]["wrong_rows"] == {"value": 0.0, "limit": 0.0}
    assert res["attempted"] % 8 == 0 and res["failed"] == 0


def _popcount(x):
    import numpy as np
    return np.unpackbits(np.asarray(x).view(np.uint8), axis=-1).sum(-1)


@pytest.mark.parametrize("dist", [{"kind": "uniform"},
                                  {"kind": "clustered", "centres": 4,
                                   "flip_log2": 4}])
def test_store_and_queries_share_the_configured_distribution(dist):
    """Codes and queries come from the distribution the configuration
    states: the same seed gives the same codes, and the queries are
    held-out draws (not copies of stored rows) from the same distribution."""
    import numpy as np
    from systems import store
    cell = _tiny(spec.load_cell("tagspace.batch128"))
    cell.config = dict(cell.config, rows=1 << 10, codes=dist)
    ctx = _ctx(cell, seed=2 ** 40 + 7)
    codes = np.asarray(store.make_codes(ctx))
    assert codes.shape == (1 << 10, 8) and codes.dtype == np.uint32
    assert (codes == np.asarray(store.make_codes(ctx))).all()
    pool = store.make_queries(ctx)
    assert len(pool) == 3 and pool[0].shape == (8, 8)
    q = np.concatenate([np.asarray(b) for b in pool])
    dist_to_store = _popcount(q[:, None, :] ^ codes[None, :, :])
    nearest = dist_to_store.min(axis=1)
    assert (nearest > 0).all()                      # held out, not copied
    if dist["kind"] == "uniform":
        # 256 fair bits: each row's popcount lies near 128
        assert abs(_popcount(codes).mean() - 128) < 2
        assert nearest.min() > 64
    else:
        # bits flipped at p = 1/16 from one of 4 shared centres: a query's
        # nearest stored row shares its centre, about 2 * 16 bits away
        assert nearest.max() < 64
        assert len({tuple(r) for r in codes}) == len(codes)


def test_store_readers_on_a_synthetic_trace():
    """Every per-layer metric of the store cells reads a trace of two
    batches: the shares stay within (0, 100]."""
    for name in ("tagspace.batch128", "tagspace-x4.batch128"):
        cell = spec.load_cell(name)
        run = {"trace": _synthetic_summary(2, cell.chips), "batches": 2,
               "least_time_per_batch_s": 0.0112}
        got = spec.read_per_layer(cell, run)
        assert set(got) == {m["name"] for m in cell.per_layer}
        for m in cell.per_layer:
            v = got[m["name"]]["value"]
            assert v > 0, (m["name"], v)
            if m["unit"] == "%":
                assert v <= 100, (m["name"], v)
        assert got["hist_roofline.store"]["value"] == pytest.approx(0.56)
        assert got["emit_ms.store"]["value"] == pytest.approx(500.0)


def _broken(monkeypatch, fault):
    """Make the store's timed search produce wrong answers."""
    from systems import store

    setup = store.setup

    def broken_setup(ctx):
        st = setup(ctx)
        search, n = st.search, ctx.config["rows"]

        def half(*a):                   # half of the batch left out
            d, i = search(*a)
            q = d.shape[0] // 2
            return d.at[q:].set(0), i.at[q:].set(0)

        def altered(*a):                # an answer altered where produced
            d, i = search(*a)
            return d, i.at[:, 0].set((i[:, 0] + n // 2) % n)

        st.search = {"half": half, "altered": altered}[fault]
        return st

    monkeypatch.setattr(store, "setup", broken_setup)


@pytest.mark.parametrize("fault", ["half", "altered"])
def test_store_check_fails_under_fault(monkeypatch, fault):
    _broken(monkeypatch, fault)
    cell = _tiny(spec.load_cell("tagspace.batch128"))
    res = runner.run_cell(_ctx(cell), 0.3, False, time.perf_counter())
    assert res["correct"] is False
    assert res["checks"]["wrong_rows"]["value"] > 0


def test_store_control_fails():
    from systems import store
    cell = _tiny(spec.load_cell("tagspace.batch128"))
    for seed in (1, 2, 3):
        checks = store.control(_ctx(cell, seed), 4)
        assert checks["wrong_rows"][0] > checks["wrong_rows"][1]


FOUR_CHIPS = r"""
import json, sys, time
sys.path.insert(0, {bench!r}); sys.path.insert(0, {src!r})
import jax
from harness import peaks, runner, spec
from systems import store
cell = spec.load_cell("tagspace-x4.batch128")
cell.config = dict(cell.config, rows=1 << 14, check_queries_per_batch=4)
cell.traffic = dict(cell.traffic, batch=8, pool_batches=3)
ctx = runner.Ctx(cell=cell, seed=5, devices=jax.devices()[:4],
                 peaks=peaks.PEAKS["TPU v5 lite"])
out = {{"sound": runner.run_cell(ctx, 0.3, False, time.perf_counter()),
        "control": store.control(ctx, 3)}}
setup = store.setup
def no_exchange(ctx):
    # the exchange between chips left out: each answer is the first
    # shard's own, never merged with the others'
    st = setup(ctx)
    from repro.core import engine
    n, d, k = ctx.config["rows"], ctx.config["code_bits"], ctx.config["k"]
    st.search = jax.jit(lambda c, q: engine.KNNEngine(
        codes=c[: n // 4], d=d).search(q, k))
    return st
store.setup = no_exchange
out["no_exchange"] = runner.run_cell(ctx, 0.3, False, time.perf_counter())
print("RESULT " + json.dumps(out))
"""


def test_four_chip_store_cell_on_virtual_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = FOUR_CHIPS.format(bench=_paths.BENCH,
                             src=os.path.join(_paths.ROOT, "src"))
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.split("RESULT ", 1)[1])
    assert out["sound"]["correct"] is True
    assert out["sound"]["device"]["count"] == 4
    assert out["control"]["wrong_rows"][0] > 0
    assert out["no_exchange"]["correct"] is False


def test_main_refuses_the_cpu(capsys):
    sys.path.insert(0, _paths.BENCH)
    import run
    rc = run.main(["--workload", "tagspace.batch128", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    assert rc == 2
    out, err = capsys.readouterr()
    assert out == "" and "needs a TPU" in err


def _checkout(tmp_path, with_src: bool):
    root = tmp_path / "checkout"
    shutil.copytree(_paths.BENCH, root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(_paths.ROOT, "BENCHMARK.json"), root)
    if with_src:
        shutil.copytree(os.path.join(_paths.ROOT, "src"), root / "src",
                        ignore=shutil.ignore_patterns("__pycache__"))
    return root


def test_command_without_the_program_prints_no_result(tmp_path):
    root = _checkout(tmp_path, with_src=False)
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "tagspace.batch128",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=root, capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_importing_the_benchmark_touches_no_device():
    code = ("import sys; sys.path[:0] = [{b!r}, {s!r}]\n"
            "import run, control\n"
            "from harness import peaks, runner, spec, trace, traffic, work\n"
            "from systems import store\n"
            "from refs import hamming_topk\n"
            "from jax._src import xla_bridge\n"
            "print('BACKENDS', len(xla_bridge._backends))\n").format(
                b=_paths.BENCH, s=os.path.join(_paths.ROOT, "src"))
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=300,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 0, p.stderr[-2000:]
    assert "BACKENDS 0" in p.stdout


TEST_METRIC = '''"""Test-only: batches the window completed."""


def read(run):
    return float(run["batches"])
'''


@pytest.mark.parametrize("codes", [{"kind": "uniform"},
                                   {"kind": "clustered", "centres": 16,
                                    "flip_log2": 3}])
def test_new_cell_config_traffic_and_metric_are_found_by_name(tmp_path, codes):
    """A cell added as files plus BENCHMARK.json entries, its configuration
    naming its own code distribution: no existing file changes, and the
    runner drives it."""
    root = _checkout(tmp_path, with_src=False)
    bench_dir = root / "chipbench"
    cfg = json.loads((bench_dir / "configs" / "tagspace-d256.json").read_text())
    cfg.update(rows=1 << 13, check_queries_per_batch=2, codes=codes)
    (bench_dir / "configs" / "tiny-d256.json").write_text(json.dumps(cfg))
    tr = json.loads((bench_dir / "traffic" / "batch128.json").read_text())
    tr.update(batch=4, pool_batches=2)
    (bench_dir / "traffic" / "tiny4.json").write_text(json.dumps(tr))
    (bench_dir / "metrics" / "batches_seen.test.py").write_text(TEST_METRIC)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-d256", "source": "test",
                             "file": "chipbench/configs/tiny-d256.json",
                             "reduced": ["rows"], "why": "test"})
    bench["workloads"].append({"name": "tiny.batch4", "config": "tiny-d256",
                               "traffic": "tiny4", "chips": 1, "why": "test"})
    bench["end_to_end"][0]["workloads"].append("tiny.batch4")
    bench["per_layer"].append({"name": "batches_seen.test", "unit": "count",
                               "better": "higher", "source": "host_clock",
                               "layer": "test", "moves": "search_qps",
                               "workloads": ["tiny.batch4"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = spec.load_cell("tiny.batch4", root=str(root),
                          bench_dir=str(bench_dir))
    assert cell.config["rows"] == 1 << 13 and cell.traffic["batch"] == 4
    assert [m["name"] for m in cell.per_layer] == ["batches_seen.test"]
    assert {m["name"] for m in cell.end_to_end} == {"search_qps", "setup_s"}
    res = runner.run_cell(_ctx(cell), 0.3, True, time.perf_counter())
    assert res["correct"] is True
    # on the CPU the trace holds no device: only the test metric reads
    assert list(res["metrics"]) == ["batches_seen.test"]
    assert res["metrics"]["batches_seen.test"]["value"] >= 1
    assert res["device"]["busy_s"] == 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
