"""Serving hardening: admission control, deadlines, degradation ladder,
fault-injected soak. The pinned acceptance run is
``test_soak_with_faults_no_lost_requests`` — 500 ticks, search + checkpoint
save failures at p=0.05, every request terminates, recovery to the exact
plan after load drops."""
import dataclasses
import tempfile

import numpy as np
import pytest

import jax

from repro import compat
from repro.configs import get_config, scaled_down
from repro.core import retrieval
from repro.models import lm
from repro.runtime import faults as faults_mod, server as server_mod


@pytest.fixture(scope="module")
def env():
    cfg = scaled_down(get_config("gemma-2b"), d_model=64, d_ff=128,
                      vocab_size=256)
    cfg = dataclasses.replace(cfg, retrieval=dataclasses.replace(
        cfg.retrieval, datastore_size=512, code_bits=64, k=8, chunk_size=512))
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    store = retrieval.synthetic_datastore(cfg)
    return cfg, mesh, params, store


def _req(uid, rng, vocab, n_new=6, deadline=None, plen=None):
    plen = int(rng.integers(1, 4)) if plen is None else plen
    return server_mod.Request(
        uid=uid, prompt=rng.integers(0, vocab, plen).astype(np.int32),
        max_new_tokens=n_new, deadline_ticks=deadline)


def _drain(srv, guard):
    while srv.has_work and srv.ticks < guard:
        srv.tick()


# ---------------------------------------------------------------------------
# the pinned soak (acceptance criterion)
# ---------------------------------------------------------------------------

def test_soak_with_faults_no_lost_requests(env):
    cfg, mesh, params, store = env
    inj = faults_mod.FaultInjector(
        seed=7, p={"store_search": 0.05, "ckpt_save": 0.05,
                   "ckpt_restore": 0.05})
    with tempfile.TemporaryDirectory() as tmp:
        srv = server_mod.Server(
            cfg, mesh, params, max_batch=4, max_len=24, store=store,
            max_queue=6, default_deadline_ticks=50,
            degradation=server_mod.DegradationPolicy(
                queue_high=3, queue_low=1, cooldown_ticks=4),
            fault_injector=inj, snapshot_dir=tmp, snapshot_every=10)
        rng = np.random.default_rng(11)
        uid = 0
        saw_degraded_under_load = False
        for t in range(500):
            # overload for the first 300 ticks, then a light trickle so the
            # policy has live ticks to recover through
            rate = 2.0 if t < 300 else 0.1
            for _ in range(rng.poisson(rate)):
                srv.submit(_req(uid, rng, cfg.vocab_size))
                uid += 1
            srv.tick()
            if t < 300 and srv.rung > 0:
                saw_degraded_under_load = True
        _drain(srv, guard=800)          # bounded: deadlines forbid deadlock

        s = srv.stats()
        # no lost requests: done + shed + timed_out == submitted
        assert s["lost"] == 0, s
        assert s["in_flight"] == 0, s
        assert s["submitted"] == s["done"] + s["shed"] + s["timed_out"]
        assert s["submitted"] > 100
        # overload actually degraded the plan, and pressure-clear recovered
        # it back to the full exact rung
        assert saw_degraded_under_load
        assert s["degraded_ticks"] > 0
        assert s["transitions"] >= 2
        assert s["rung"] == "exact"
        # the injector really exercised the search + checkpoint-save paths
        assert inj.fired.get("store_search", 0) > 0
        assert inj.calls.get("ckpt_save", 0) > 0
        assert s["search_retries"] > 0


# ---------------------------------------------------------------------------
# degradation ladder
# ---------------------------------------------------------------------------

def test_degradation_policy_walks_one_rung_per_tick():
    pol = server_mod.DegradationPolicy(queue_high=4, queue_low=1,
                                       cooldown_ticks=3)
    r = 0
    r = pol.update(r, 4, queue_depth=10, tick_s=0.01)
    assert r == 1                       # pressure: one rung down
    r = pol.update(r, 4, queue_depth=10, tick_s=0.01)
    assert r == 2                       # still pressured
    r = pol.update(r, 4, queue_depth=2, tick_s=0.01)
    assert r == 2                       # neither pressured nor calm: hold
    for _ in range(2):
        r = pol.update(r, 4, queue_depth=0, tick_s=0.01)
        assert r == 2                   # calm but inside cooldown
    r = pol.update(r, 4, queue_depth=0, tick_s=0.01)
    assert r == 1                       # cooldown satisfied: one rung up
    r = pol.update(r, 4, queue_depth=10, tick_s=0.01)
    assert r == 2                       # relapse resets the climb


def test_latency_ewma_pressure_triggers_downshift():
    pol = server_mod.DegradationPolicy(queue_high=100, tick_high_s=0.01,
                                       alpha=1.0)
    assert pol.update(0, 3, queue_depth=0, tick_s=0.5) == 1
    assert pol.ewma_s == 0.5


def test_ladder_has_probe_rungs_and_serves_through_them(env):
    cfg, mesh, params, _ = env
    cfg2 = dataclasses.replace(cfg, retrieval=dataclasses.replace(
        cfg.retrieval, layout="hamming_prefix", layout_buckets=16))
    store2 = retrieval.synthetic_datastore(cfg2)
    srv = server_mod.Server(
        cfg2, mesh, params, max_batch=2, max_len=16, store=store2,
        degradation=server_mod.DegradationPolicy(queue_high=2, queue_low=0,
                                                 cooldown_ticks=2))
    names = [r.name for r in srv.rungs]
    assert names[0] == "exact" and names[-1] == "retrieval_off"
    assert any(n.startswith("probe") for n in names), names

    rng = np.random.default_rng(3)
    for uid in range(8):                # burst >> capacity: forces descent
        srv.submit(_req(uid, rng, cfg2.vocab_size, n_new=3))
    _drain(srv, guard=120)
    s = srv.stats()
    assert s["lost"] == 0 and s["in_flight"] == 0
    visited = {t[2] for t in srv.transitions}
    assert any(n.startswith("probe") for n in visited), srv.transitions
    # every transition re-logged an active plan (recorded via transitions
    # list); recovery: feed calm ticks until the ladder climbs back
    uid = 100
    while srv.rung != 0 and srv.ticks < 400:
        if not srv.has_work:
            srv.submit(_req(uid, rng, cfg2.vocab_size, n_new=2))
            uid += 1
        srv.tick()
    assert srv.rung == 0, srv.transitions


def test_top_rung_bit_identical_to_unhardened_server(env):
    cfg, mesh, params, store = env
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab_size, 3).astype(np.int32)
               for _ in range(3)]

    def serve(hardened):
        kw = {}
        if hardened:
            kw = dict(max_queue=16, default_deadline_ticks=500,
                      degradation=server_mod.DegradationPolicy(
                          queue_high=10**6),   # never pressured
                      fault_injector=faults_mod.FaultInjector(seed=0, p={}))
        srv = server_mod.Server(cfg, mesh, params, max_batch=2, max_len=16,
                                store=store, **kw)
        for uid, pr in enumerate(prompts):
            srv.submit(server_mod.Request(uid=uid, prompt=pr.copy(),
                                          max_new_tokens=5))
        srv.run(max_ticks=100)
        return {r.uid: r.out_tokens for r in srv.done}

    assert serve(False) == serve(True)


# ---------------------------------------------------------------------------
# admission control: shed, deadline, capacity, empty prompt
# ---------------------------------------------------------------------------

def test_bounded_queue_sheds_beyond_capacity(env):
    cfg, mesh, params, store = env
    srv = server_mod.Server(cfg, mesh, params, max_batch=1, max_len=16,
                            store=store, max_queue=2)
    rng = np.random.default_rng(0)
    accepted = [srv.submit(_req(u, rng, cfg.vocab_size, n_new=2))
                for u in range(5)]
    assert accepted == [True, True, False, False, False]
    assert all(r.status == "shed" and r.finish_reason == "queue_full"
               for r in srv.shed)
    _drain(srv, guard=60)
    s = srv.stats()
    assert s["shed"] == 3 and s["done"] == 2 and s["lost"] == 0


def test_deadline_evicts_queued_and_active_requests(env):
    cfg, mesh, params, store = env
    srv = server_mod.Server(cfg, mesh, params, max_batch=1, max_len=30,
                            store=store)
    rng = np.random.default_rng(1)
    hog = _req(0, rng, cfg.vocab_size, n_new=25)       # occupies the slot
    starved = _req(1, rng, cfg.vocab_size, n_new=2, deadline=4)
    slow = _req(2, rng, cfg.vocab_size, n_new=25, deadline=8)
    srv.submit(hog), srv.submit(starved), srv.submit(slow)
    _drain(srv, guard=100)
    assert starved.status == "timed_out"      # died waiting in the queue
    assert slow.status == "timed_out"         # evicted from its slot
    assert slow.finish_reason == "deadline"
    assert hog.status == "done"
    assert srv.stats()["lost"] == 0


def test_capacity_eviction_retires_and_reuses_slot(env):
    cfg, mesh, params, store = env
    max_len, plen = 12, 4
    srv = server_mod.Server(cfg, mesh, params, max_batch=1, max_len=max_len,
                            store=store)
    rng = np.random.default_rng(2)
    capped = _req(0, rng, cfg.vocab_size, n_new=100, plen=plen)
    follower = _req(1, rng, cfg.vocab_size, n_new=2, plen=1)
    srv.submit(capped), srv.submit(follower)
    _drain(srv, guard=60)
    # the pos < max_len - 1 guard retires the runaway request with exactly
    # the tokens served before the cache filled: the first from the slot
    # prefill, then one a decode step at positions plen .. max_len - 2
    assert capped.status == "done" and capped.finish_reason == "capacity"
    assert len(capped.out_tokens) == max_len - plen
    # and its slot was reused: the follower completed in the same slot pool
    assert follower.status == "done" and follower.finish_reason == "complete"
    assert len(follower.out_tokens) == 2


def test_empty_prompt_admitted_via_bos_fallback(env):
    cfg, mesh, params, store = env
    srv = server_mod.Server(cfg, mesh, params, max_batch=1, max_len=16,
                            store=store)
    req = server_mod.Request(uid=0, prompt=np.zeros((0,), np.int32),
                             max_new_tokens=3)
    srv.submit(req)
    _drain(srv, guard=30)
    assert req.status == "done"
    assert len(req.out_tokens) == 3


# ---------------------------------------------------------------------------
# faults: injector, retry, snapshot fallback
# ---------------------------------------------------------------------------

def test_retry_call_retries_then_succeeds_and_reraises():
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise faults_mod.InjectedFault("x")
        return "ok"

    slept = []
    assert faults_mod.retry_call(flaky, retries=3, backoff_s=0.01,
                                 sleep=slept.append, rng=0) == "ok"
    assert calls["n"] == 3 and len(slept) == 2
    # full-jitter default: each delay draws inside the doubling envelope
    # (the exact envelope/cap contract is pinned in tests/test_faults.py)
    assert 0.0 <= slept[0] <= 0.01 and 0.0 <= slept[1] <= 0.02

    with pytest.raises(faults_mod.InjectedFault):
        faults_mod.retry_call(lambda: (_ for _ in ()).throw(
            faults_mod.InjectedFault("y")), retries=1, sleep=lambda _: None)


def test_injector_is_seeded_and_counts():
    a = faults_mod.FaultInjector(seed=4, p={"s": 0.5})
    b = faults_mod.FaultInjector(seed=4, p={"s": 0.5})

    def trace(inj):
        out = []
        for _ in range(50):
            try:
                inj.check("s")
                out.append(0)
            except faults_mod.InjectedFault:
                out.append(1)
        return out

    ta = trace(a)
    assert ta == trace(b)               # same seed, same fault sequence
    assert a.fired["s"] == sum(ta) and a.calls["s"] == 50


def test_search_fault_falls_over_and_recovers(env):
    cfg, mesh, params, store = env
    # p=1 on the search site: every retrieval attempt fails, so each tick
    # must fail over to retrieval-off decode; requests still finish
    inj = faults_mod.FaultInjector(seed=0, p={"store_search": 1.0})
    srv = server_mod.Server(
        cfg, mesh, params, max_batch=1, max_len=16, store=store,
        degradation=server_mod.DegradationPolicy(queue_high=10**6,
                                                 cooldown_ticks=1),
        fault_injector=inj, search_retries=1)
    rng = np.random.default_rng(6)
    req = _req(0, rng, cfg.vocab_size, n_new=3)
    srv.submit(req)
    _drain(srv, guard=40)
    assert req.status == "done"
    s = srv.stats()
    assert s["failover_ticks"] > 0 and s["search_failures"] > 0
    assert s["lost"] == 0
    # the failover transition was logged
    assert any(t[2] == "retrieval_off" for t in srv.transitions)
    # once the fault clears, calm ticks walk back to the exact plan
    inj.p["store_search"] = 0.0
    uid = 1
    while srv.rung != 0 and srv.ticks < 200:
        if not srv.has_work:
            srv.submit(_req(uid, rng, cfg.vocab_size, n_new=2))
            uid += 1
        srv.tick()
    assert srv.rung == 0


class _OneShotFault(faults_mod.FaultInjector):
    """Raises exactly once, on the first check of ``site`` — deterministic
    trigger for the snapshot-restore path."""

    def __init__(self, site):
        super().__init__(seed=0, p={})
        self._site = site

    def check(self, s):
        super().check(s)            # keeps the call counters honest
        if s == self._site and self.calls[s] == 1:
            self.fired[s] = self.fired.get(s, 0) + 1
            raise faults_mod.InjectedFault(s)


def test_snapshot_restore_fallback(env):
    cfg, mesh, params, store = env
    with tempfile.TemporaryDirectory() as tmp:
        inj = _OneShotFault("store_search")
        srv = server_mod.Server(cfg, mesh, params, max_batch=1, max_len=16,
                                store=store, fault_injector=inj,
                                search_retries=0, snapshot_dir=tmp)
        # last-good snapshot was written at startup
        assert srv.counters["snapshot_saves"] == 1
        rng = np.random.default_rng(8)
        req = _req(0, rng, cfg.vocab_size, n_new=2)
        srv.submit(req)
        _drain(srv, guard=30)
        # the single fault exhausted retries (retries=0), restored the
        # store from the snapshot, and completed the step at the SAME rung
        # — no retrieval-off failover transition
        assert srv.counters["snapshot_restores"] == 1
        assert srv.counters["failover_ticks"] == 0
        assert srv.transitions == []
        assert req.status == "done"
        assert srv.stats()["lost"] == 0


def test_stats_percentiles_present(env):
    cfg, mesh, params, store = env
    srv = server_mod.Server(cfg, mesh, params, max_batch=2, max_len=16,
                            store=store)
    rng = np.random.default_rng(9)
    for uid in range(3):
        srv.submit(_req(uid, rng, cfg.vocab_size, n_new=2))
    _drain(srv, guard=50)
    s = srv.stats()
    assert s["p50_token_s"] > 0 and s["p99_token_s"] >= s["p50_token_s"]
    assert s["p99_queue_ticks"] >= s["p50_queue_ticks"] >= 0
    assert s["done"] == 3 and s["lost"] == 0
