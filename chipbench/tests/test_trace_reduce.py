"""The reduction from a profiler trace to per-layer numbers: busy/idle
union, device time per operation, idle gaps labelled by the host span open
during each gap."""
import glob
import os

import pytest

import _paths  # noqa: F401
from harness import trace
from harness.trace import Event

HLO = ('%hamming_hist_pallas.1 = (s32[1,257,8,128]{3,2,1,0:T(8,128)S(1)}, '
       's32[1,1,1024]) custom-call(s32[1]{0:T(128)} %constant.10), '
       'custom_call_target="tpu_custom_call"')


def test_op_names():
    assert trace.op_name(HLO) == "hamming_hist_pallas.1"
    assert trace.base_name("hamming_hist_pallas.1") == "hamming_hist_pallas"
    assert trace.base_name("fusion.12.3") == "fusion"
    assert trace.base_name("copy") == "copy"


def test_union_merges_and_clips():
    assert trace.union([(3, 5), (1, 2), (1.5, 2.5), (4, 9)], 0, 8) == [
        (1, 2.5), (3, 8)]
    assert trace.union([(0, 1)], 2, 3) == []


def _one_chip():
    ops = [Event("hamming_hist_pallas.1", 1.0, 2.0),
           Event("fusion.3", 1.9, 2.5),
           Event("hamming_emit_pallas.1", 3.0, 3.5),
           Event("copy.1", 4.5, 4.6)]                 # after the window
    spans = [Event("bench.window", 0.5, 4.0),
             Event("bench.batch", 0.9, 2.6),
             Event("bench.submit", 2.5, 2.95),
             Event("bench.batch", 2.9, 3.6),
             Event("other", 0.0, 9.0)]
    return {"devices": {"/device:TPU:0": ops}, "spans": spans}


def test_reduce_one_chip():
    s = trace.reduce(_one_chip())
    assert s["window_s"] == pytest.approx(3.5)
    assert s["chips"] == 1
    # busy: [1.0, 2.5] and [3.0, 3.5]
    assert s["busy_s"] == pytest.approx(2.0)
    assert s["op_s"] == pytest.approx({"hamming_hist_pallas.1": 1.0,
                                       "fusion.3": 0.6,
                                       "hamming_emit_pallas.1": 0.5})
    # gaps [0.5, 1.0] (mid 0.75: window only), [2.5, 3.0] (mid 2.75: the
    # submit span), [3.5, 4.0] (mid 3.75: window only)
    assert s["idle_by_span"] == pytest.approx({"bench.window": 1.0,
                                               "bench.submit": 0.5})
    assert [g[0] for g in s["gaps"]] == ["bench.window", "bench.submit",
                                         "bench.window"]
    assert trace.op_seconds(s, ["hamming_"]) == pytest.approx(1.5)
    b = trace.breakdown(s)
    assert b["device_ops"][0] == ["hamming_hist_pallas", pytest.approx(1.0)]
    assert b["idle_gaps"][0] == ["bench.window", pytest.approx(1.0)]


def test_reduce_two_chips_means_and_per_chip():
    tr = _one_chip()
    tr["devices"]["/device:TPU:1"] = [Event("all-reduce.2", 1.0, 3.0)]
    s = trace.reduce(tr)
    assert s["chips"] == 2
    assert s["busy_s_per_chip"] == pytest.approx([2.0, 2.0])
    assert s["busy_s"] == pytest.approx(2.0)
    assert trace.op_seconds(s, ["all-reduce"], chip=1) == pytest.approx(2.0)
    assert trace.op_seconds(s, ["all-reduce"], chip=0) == 0
    assert trace.op_seconds(s, ["all-reduce"]) == pytest.approx(1.0)


def test_reduce_without_window_span_uses_the_events():
    tr = _one_chip()
    tr["spans"] = []
    s = trace.reduce(tr)
    assert s["window_s"] == pytest.approx(3.6)
    assert s["idle_by_span"] == pytest.approx({"outside_spans": 1.5})


def test_recorded_trace_keeps_benchmark_spans():
    """A trace recorded here (CPU: no device plane) yields the benchmark's
    host spans and nothing of the device."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    with trace.capture() as cap:
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(2):
                with jax.profiler.TraceAnnotation("bench.batch"):
                    f(x).block_until_ready()
    try:
        assert cap["path"] and os.path.exists(cap["path"])
        tr = trace.load_xplane(cap["path"])
    finally:
        trace.discard(cap)
    assert not glob.glob(os.path.join(cap["dir"], "*"))
    names = sorted(e.name for e in tr["spans"])
    assert names == ["bench.batch", "bench.batch", "bench.window"]
    assert all(e.end >= e.start for e in tr["spans"])
    s = trace.reduce(tr)
    assert s["chips"] == 0 and s["busy_s"] == 0
