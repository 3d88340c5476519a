"""A CPU rehearsal of the knnlm.decode16 cell at a tiny Finch size with
float32 weights: set-up, closed loop, the staged check against the plain
reference and the metric readers, through the runner's functions; the check
comes out false under each control (no state reset, a dropped first token,
the WKV state carried in bf16, activations rounded to fp8, a skipped
datastore row)."""
import dataclasses
import os
import subprocess
import sys
import time

import pytest

import _paths
from harness import peaks, runner, spec

V5E = peaks.PEAKS["TPU v5 lite"]


def _tiny_model():
    from repro.configs import get_config, scaled_down
    # d_model 256: ITQ projects onto 256 code bits
    return dataclasses.replace(scaled_down(get_config("rwkv6-1.6b"),
                                           d_model=256), dtype="float32")


def _tiny_cell():
    m = _tiny_model()
    cell = spec.load_cell("knnlm.decode16")
    cell.config = dict(
        cell.config, num_hidden_layers=m.num_layers, hidden_size=m.d_model,
        head_size=m.rwkv.head_dim, intermediate_size=m.d_ff,
        vocab_size=m.vocab_size, time_mix_extra_dim=m.rwkv.mix_lora,
        time_decay_extra_dim=m.rwkv.decay_lora, dtype="float32",
        rows=1 << 12, itq_sample_tokens=64, itq_sample_len=16, itq_iters=5,
        slots=2, check_requests=2,
        # float32 weights: the LM limits are float32-level here
        limits=dict(cell.config["limits"], lm_logp_err=1e-3,
                    key_rel_err=1e-4, block_err=1e-4))
    cell.traffic = dict(cell.traffic, clients=2, prompt_len=[3, 9],
                        new_tokens=3)
    return cell


@pytest.fixture
def tiny(monkeypatch):
    import repro.configs
    from repro.dist import steps
    model, cell = _tiny_model(), _tiny_cell()
    monkeypatch.setattr(repro.configs, "get_config", lambda name: model)
    monkeypatch.setattr(steps, "_SERVE_CACHE", {})
    return runner.Ctx(cell=cell, seed=2 ** 33 + 5, devices=[None], peaks=V5E)


def _run(ctx, trace=False):
    import jax
    ctx.devices = jax.devices()[:1]
    return runner.run_cell(ctx, 0.0, trace, time.perf_counter())


def test_knnlm_cell_runs_and_checks(tiny):
    res = _run(tiny, trace=True)
    assert res["correct"] is True, res["checks"]
    checks = res["checks"]
    assert checks["wrong_rows"]["value"] == 0
    assert checks["code_bits_off"]["value"] == 0
    assert checks["reused_short"]["value"] == 0
    assert checks["first_token_missing"]["value"] == 0
    assert checks["mix_err"]["value"] <= 1e-5
    assert 0 < checks["wkv_err"]["value"] <= 1e-5
    assert 0 < checks["block_err"]["value"] <= 1e-5
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    # on the CPU the trace holds no device: the readers find nothing
    assert res["metrics"] == {}


def test_knnlm_metrics_and_readers(tiny):
    res = _run(tiny)
    assert set(res["metrics"]) == {"search_qps", "setup_s"}
    assert res["metrics"]["search_qps"]["value"] > 0
    cell = tiny.cell
    run = {"trace": {"busy_s": 8.0, "window_s": 10.0, "chips": 1,
                     "op_s": {"hamming_hist_pallas.1": 3.0,
                              "hamming_emit_pallas.2": 2.0, "fusion.3": 3.0},
                     "op_s_per_chip": [], "idle_by_span": {}, "gaps": []},
           "batches": 20, "least_time_per_batch_s": 0.015,
           "lm_least_time_s": 0.6}
    got = spec.read_per_layer(cell, run)
    assert set(got) == {"search_roofline.store", "hist_roofline.store",
                        "emit_ms.store", "device_idle.store", "lm_ms.knnlm",
                        "lm_roofline.knnlm"}
    assert got["hist_roofline.store"]["value"] == pytest.approx(10.0)
    assert got["emit_ms.store"]["value"] == pytest.approx(100.0)
    assert got["lm_ms.knnlm"]["value"] == pytest.approx(150.0)
    assert got["lm_roofline.knnlm"]["value"] == pytest.approx(20.0)
    assert got["device_idle.store"]["value"] == pytest.approx(20.0)


def test_knnlm_layer_inputs_are_the_store_metrics_at_q16():
    from harness import work
    from systems import knnlm
    cell = _tiny_cell()
    ctx = runner.Ctx(cell=cell, seed=1, devices=[None], peaks=V5E)
    rec = {"counts": {"decode_steps": 40, "prefills": 1,
                      "prefill_tokens": 8192, "first_tokens_served": 16}}
    got = knnlm.layer_inputs(ctx, rec)
    assert got["batches"] == 41
    assert got["least_time_per_batch_s"] == work.search_least_time(
        V5E, cell.config["slots"], cell.config["rows"],
        cell.config["code_bits"])
    assert got["lm_least_time_s"] > 0


def _no_reset(monkeypatch):
    from repro.runtime import server
    monkeypatch.setattr(server.Server, "_reset_slots", lambda self, s: None)


def _drop_first_token(monkeypatch):
    from repro.runtime import server
    admit = server.Server._admit

    def dropped(self, pairs):
        n = admit(self, pairs)
        self.step_log[-1]["served"][:] = False
        for _, req in pairs:
            req.out_tokens.pop(0)
        return n

    monkeypatch.setattr(server.Server, "_admit", dropped)


def _bf16_state(monkeypatch):
    import jax
    from repro.models import lm, rwkv6
    block = rwkv6.rwkv6_block

    def carried_bf16(*a, **kw):
        # reduce_precision, not a round trip through astype: a compiler
        # that allows excess precision may drop the round trip
        x, st, *seen = block(*a, **kw)
        return (x, st._replace(wkv=jax.lax.reduce_precision(
            st.wkv, exponent_bits=8, mantissa_bits=7)), *seen)

    monkeypatch.setattr(lm, "rwkv6_block", carried_bf16)


def _fp8_activations(monkeypatch):
    import jax.numpy as jnp
    from repro.models import rwkv6
    channel_mix = rwkv6.rwkv6_channel_mix

    def rounded(p, cfg, x, *a, **kw):
        out, last = channel_mix(p, cfg, x, *a, **kw)
        return out.astype(jnp.float8_e4m3fn).astype(out.dtype), last

    monkeypatch.setattr(rwkv6, "rwkv6_channel_mix", rounded)


def _skip_row(monkeypatch):
    from repro.core import retrieval
    search = retrieval.knn_search

    def skip_nearest(store, hidden, rcfg, **kw):
        # each query's nearest row is left out of its answer
        q, d, i = search(store, hidden, dataclasses.replace(rcfg, k=rcfg.k + 1),
                         **kw)
        return q, d[:, 1:], i[:, 1:]

    monkeypatch.setattr(retrieval, "knn_search", skip_nearest)


@pytest.mark.parametrize("control,fails", [
    (_no_reset, ("lm_logp_err", "key_rel_err")),
    (_drop_first_token, ("first_token_missing",)),
    (_bf16_state, ("wkv_err",)),
    (_fp8_activations, ("block_err",)),
    (_skip_row, ("wrong_rows",)),
])
def test_knnlm_check_fails_under_control(tiny, monkeypatch, control, fails):
    control(monkeypatch)
    res = _run(tiny)
    assert res["correct"] is False
    bad = {n for n, c in res["checks"].items() if c["value"] > c["limit"]}
    assert bad & set(fails), res["checks"]


def test_importing_the_knnlm_system_touches_no_device():
    code = ("import sys; sys.path[:0] = [{b!r}, {s!r}]\n"
            "from harness import lm_work\n"
            "from systems import knnlm\n"
            "from refs import rwkv6_knnlm\n"
            "from jax._src import xla_bridge\n"
            "print('BACKENDS', len(xla_bridge._backends))\n").format(
                b=_paths.BENCH, s=os.path.join(_paths.ROOT, "src"))
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=300,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 0, p.stderr[-2000:]
    assert "BACKENDS 0" in p.stdout


@pytest.mark.parametrize("off", [None, "time_decay_extra_dim", "dtype"])
def test_benchmark_weights_load_into_the_program_tree(off):
    import jax
    from systems import knnlm
    cell, model = _tiny_cell(), _tiny_model()
    config = dict(cell.config)
    if off == "time_decay_extra_dim":
        config[off] += 1
    elif off == "dtype":
        config[off] = "bfloat16"
    key = jax.random.PRNGKey(3)
    if off is not None:
        with pytest.raises(ValueError, match="decay_w1" if off.startswith(
                "time") else "embed.table"):
            knnlm.load_params(config, model, key)
        return
    params = knnlm.load_params(config, model, key)
    from repro.models import lm
    want = jax.eval_shape(lambda k: lm.init_params(k, model), key)
    assert (jax.tree_util.tree_structure(params)
            == jax.tree_util.tree_structure(want))
    for a, b in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(want)):
        assert a.shape == b.shape and a.dtype == b.dtype


def test_recurrence_err_float32_level_and_a_bf16_state():
    import jax
    import jax.numpy as jnp
    from refs import rwkv6_knnlm as ref
    n, H, hd = 6, 2, 8
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    r, k, v = (jax.random.normal(ks[i], (n, H * hd)) for i in range(3))
    logw = -jnp.exp(jax.random.uniform(ks[3], (n, H * hd), minval=-6.0,
                                       maxval=-1.0))
    bonus = jax.random.uniform(ks[4], (H, hd), minval=-0.5, maxval=0.5)
    s_in = jax.random.normal(ks[5], (n, H, hd, hd))
    sh = lambda a: a.reshape(n, H, hd)
    kv = sh(k)[..., :, None] * sh(v)[..., None, :]
    y = jnp.einsum("nhi,nhij->nhj", sh(r), s_in + bonus[None, :, :, None] * kv,
                   precision="highest").reshape(n, -1)
    s_next = jnp.exp(sh(logw))[..., None] * s_in + kv
    has = jnp.asarray([True] * (n - 1) + [False])
    y_err, s_err = ref.recurrence_err(r, k, v, logw, y, bonus, s_in, s_next,
                                      has)
    assert float(jnp.max(y_err)) < 2 ** -20
    assert float(jnp.max(s_err)) < 2 ** -20 and float(s_err[-1]) == 0.0
    rounded = s_next.astype(jnp.bfloat16).astype(jnp.float32)
    _, s_err = ref.recurrence_err(r, k, v, logw, y, bonus, s_in, rounded, has)
    assert float(jnp.max(s_err)) > 2 ** -11
