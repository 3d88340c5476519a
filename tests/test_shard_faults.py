"""Shard-fault tolerance: degraded-but-exact answers, health states,
replica placement, and the host-orchestrated fault-tolerant search.

The load-bearing pin is BIT-IDENTITY: excluding a dead shard via the
participation mask (SPMD path) or serving a range from a replica after a
mid-stream kill (host path) must produce exactly the answer a from-scratch
search over only the surviving rows would — dists AND ids, including the
k > survivors and zero-coverage edges — while the CoverageReport says
precisely what was searched.
"""
import numpy as np
import pytest

from repro.dist.health import (CoverageReport, HealthRegistry, DEAD,
                               HEALTHY, RECOVERING, SUSPECT)
from repro.dist.sharding import ReplicaMap


# ---------------------------------------------------------------------------
# SPMD participation mask: every single-dead pattern over uneven shards
# ---------------------------------------------------------------------------

def test_participation_mask_single_dead_patterns(multidevice):
    """Uneven 4-device shards; for EVERY single-dead-shard pattern the
    masked sharded answer equals a rebuilt store of only surviving rows
    (dists and ids, ids renumbered over the masked scan), with k larger
    than one shard and k larger than all survivors; the all-dead mask
    yields pure sentinels. hist_tree agrees bit-for-bit throughout."""
    multidevice("""
import warnings
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh
from repro.core import binary, engine
from repro.kernels import ops

rng = np.random.default_rng(7)
d, Q, n_loc = 64, 6, 512
nv = np.array([300, 512, 11, 201], np.int32)
xb = rng.integers(0, 2, (4 * n_loc, d)).astype(np.uint8)
qp = binary.pack_bits(jnp.asarray(rng.integers(0, 2, (Q, d)), jnp.uint8))
xp_full = np.asarray(binary.pack_bits(jnp.asarray(xb)))
parts, valid = [], []
for s in range(4):
    blk = xp_full[s * n_loc:(s + 1) * n_loc].copy()
    valid.append(blk[:nv[s]].copy())
    blk[nv[s]:] = 0xFFFFFFFF
    parts.append(blk)
xpad = jnp.asarray(np.concatenate(parts))
mesh = Mesh(np.array(jax.devices()).reshape(4), ("data",))

for dead in range(4):
    part = np.ones(4, np.int32); part[dead] = 0
    surv = jnp.asarray(np.concatenate(
        [valid[s] for s in range(4) if s != dead]))
    for k in (64, 1200):       # 64 > nv[2]=11; 1200 > any survivor total
        rd, ri = ops.hamming_topk(qp, surv, k, d + 1)
        with mesh, warnings.catch_warnings():
            warnings.simplefilter("ignore")
            hd, hi = engine.search_sharded(
                xpad, qp, k, d, mesh, ("data",),
                shard_n_valid=jnp.asarray(nv),
                shard_participate=jnp.asarray(part))
            td, ti = engine.search_sharded(
                xpad, qp, k, d, mesh, ("data",), merge="hist_tree",
                fanout=2, shard_n_valid=jnp.asarray(nv),
                shard_participate=jnp.asarray(part))
        assert (hd == rd).all() and (hi == ri).all(), ("mask", dead, k)
        assert (td == hd).all() and (ti == hi).all(), ("tree", dead, k)

# all shards dead: nothing to search -> pure (bins, 0) sentinels
with mesh, warnings.catch_warnings():
    warnings.simplefilter("ignore")
    zd, zi = engine.search_sharded(
        xpad, qp, 16, d, mesh, ("data",), shard_n_valid=jnp.asarray(nv),
        shard_participate=jnp.zeros(4, jnp.int32))
assert (zd == d + 1).all() and (zi == 0).all(), "all-dead sentinels"
print("OK")
""", n_devices=4)


def test_hist_tree_identity_and_even_masks(multidevice):
    """Even shards, no n_valid: hist_tree (every fanout, including a
    non-dividing one) is bit-identical to flat hist_merge, healthy and
    with a participation mask (derived id bases over the masked scan)."""
    multidevice("""
import warnings
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh
from repro.core import binary, engine
from repro.kernels import ops

rng = np.random.default_rng(8)
d, N, Q, k = 64, 2048, 8, 16
xp = binary.pack_bits(jnp.asarray(rng.integers(0, 2, (N, d)), jnp.uint8))
qp = binary.pack_bits(jnp.asarray(rng.integers(0, 2, (Q, d)), jnp.uint8))
mesh = Mesh(np.array(jax.devices()).reshape(4), ("data",))

rd, ri = ops.hamming_topk(qp, xp, k, d + 1)
with mesh:
    hd, hi = engine.search_sharded(xp, qp, k, d, mesh, ("data",))
assert (hd == rd).all() and (hi == ri).all()
for fanout in (2, 3, 4):       # 3 does not divide 4: remainder round
    with mesh:
        td, ti = engine.search_sharded(xp, qp, k, d, mesh, ("data",),
                                       merge="hist_tree", fanout=fanout)
    assert (td == hd).all() and (ti == hi).all(), fanout

# masked + even shards (no shard_n_valid): id bases derive from the
# masked scan, so ids renumber exactly as the surviving-rows rebuild
part = np.array([1, 0, 1, 1], np.int32)
surv = jnp.asarray(np.concatenate([np.asarray(xp)[:512],
                                   np.asarray(xp)[1024:]]))
rd2, ri2 = ops.hamming_topk(qp, surv, k, d + 1)
with mesh, warnings.catch_warnings():
    warnings.simplefilter("ignore")
    md, mi = engine.search_sharded(xp, qp, k, d, mesh, ("data",),
                                   shard_participate=jnp.asarray(part))
    ud, ui = engine.search_sharded(xp, qp, k, d, mesh, ("data",),
                                   merge="hist_tree", fanout=2,
                                   shard_participate=jnp.asarray(part))
assert (md == rd2).all() and (mi == ri2).all(), "masked even"
assert (ud == md).all() and (ui == mi).all(), "masked tree"
print("OK")
""", n_devices=4)


# ---------------------------------------------------------------------------
# planner: hist_tree strategy selection + participation plumbing guards
# ---------------------------------------------------------------------------

def test_planner_hist_tree_selection():
    from repro.core import plan

    # auto: many shards -> hist_tree with a tuned fanout; few -> hist_merge
    big = plan.plan_sharded(plan.stats_for(1 << 20, 64, 2, 8, n_shards=64),
                            16, axes=("data",))
    assert big.merge.strategy == "hist_tree" and big.merge.fanout >= 2
    assert "hist_tree" in big.compact() and f"@f{big.merge.fanout}" in \
        big.compact()
    small = plan.plan_sharded(plan.stats_for(1 << 14, 64, 2, 8, n_shards=4),
                              16, axes=("data",))
    assert small.merge.strategy == "hist_merge" and small.merge.fanout == 0

    # forced hist_tree at few shards gets a defaulted fanout; forced
    # fanout must be >= 2 and only applies to hist_tree
    forced = plan.plan_sharded(plan.stats_for(1 << 14, 64, 2, 8, n_shards=4),
                               16, axes=("data",), merge="hist_tree")
    assert forced.merge.strategy == "hist_tree" and forced.merge.fanout >= 2
    with pytest.raises(ValueError):
        plan.plan_sharded(plan.stats_for(1 << 14, 64, 2, 8, n_shards=4),
                          16, axes=("data",), force="merge=hist_tree,fanout=1")
    f4 = plan.plan_sharded(plan.stats_for(1 << 20, 64, 2, 8, n_shards=8),
                           16, axes=("data",),
                           force="merge=hist_tree,fanout=4")
    assert f4.merge.fanout == 4

    # geometry() predicts both tree levels' traffic
    g = big.geometry()["merge"]
    assert g["strategy"] == "hist_tree"
    assert g["tree_levels"] >= 2
    assert g["hist_tree_bytes"] <= g["merge_bytes"] * 1.001
    assert "merge-levels" in big.explain_str() or \
        "levels" in big.explain_str()


def test_participation_requires_hist_family():
    """shard_participate through a concat_sort merge would silently search
    dead rows — the executor must refuse, not guess."""
    import jax, jax.numpy as jnp
    from jax.sharding import Mesh
    from repro.core import plan

    stats = plan.stats_for(1024, 64, 2, 4, n_shards=1)
    p = plan.plan_sharded(stats, 8, axes=("data",), merge="concat_sort")
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1), ("data",))
    q = jnp.zeros((4, 2), jnp.uint32)
    x = jnp.zeros((1024, 2), jnp.uint32)
    with pytest.raises(ValueError, match="hist"):
        with mesh:
            plan.execute(p, q, codes=x, mesh=mesh,
                         shard_participate=jnp.ones(1, jnp.int32))


# ---------------------------------------------------------------------------
# health registry state machine
# ---------------------------------------------------------------------------

def test_health_state_machine_walk():
    reg = HealthRegistry(["a", "b"], deadline_s=0.05, suspect_after=1,
                        dead_after=3, recover_probes=2)
    assert reg.state("a") == HEALTHY
    assert reg.observe("a", False) == SUSPECT        # 1 failure -> suspect
    assert reg.observe("a", True, 0.01) == HEALTHY   # success recovers
    for _ in range(3):
        st = reg.observe("a", False)
    assert st == DEAD and reg.state("a") == DEAD
    assert sorted(reg.serving()) == ["b"]
    assert reg.not_serving() == ["a"]

    reg.revive("a")
    assert reg.state("a") == RECOVERING
    assert "a" not in reg.serving()                  # recovering ≠ serving
    assert reg.observe("a", True, 0.0) == RECOVERING # 1 of 2 probes
    assert reg.observe("a", True, 0.0) == HEALTHY    # 2nd probe promotes
    # recovering + a failure drops straight back to dead
    reg.kill("a"); reg.revive("a")
    assert reg.observe("a", False) == DEAD


def test_health_deadline_miss_is_failure():
    """ok=True over the deadline counts as a failure — a stalled shard is
    as gone as a crashed one."""
    reg = HealthRegistry(["a"], deadline_s=0.01, suspect_after=1,
                        dead_after=2)
    assert reg.observe("a", True, latency_s=0.5) == SUSPECT
    assert reg.observe("a", True, latency_s=0.5) == DEAD
    snap = reg.snapshot()
    assert snap["counters"]["a"]["deadline_misses"] == 2
    assert snap["n_dead"] == 1
    assert ("a", SUSPECT, DEAD) in snap["transitions"]


def test_health_unknown_unit_and_bad_thresholds():
    reg = HealthRegistry(["a"])
    with pytest.raises(KeyError):
        reg.observe("nope", True)
    with pytest.raises(ValueError):
        HealthRegistry(["a"], suspect_after=2, dead_after=1)


def test_coverage_report_accounting():
    r = CoverageReport(covered_rows=750, total_rows=1000,
                       dead_shards=("unit2",))
    assert r.coverage_frac == 0.75 and not r.complete
    assert r.as_dict()["dead_shards"] == ["unit2"]
    assert CoverageReport(5, 5).complete
    assert CoverageReport(0, 0).coverage_frac == 1.0      # empty store
    assert CoverageReport(0, 0, ("u",)).coverage_frac == 0.0


# ---------------------------------------------------------------------------
# replica placement arithmetic
# ---------------------------------------------------------------------------

def test_replica_map_placement_properties():
    m = ReplicaMap((10, 20, 30, 40), ("u0", "u1", "u2", "u3"), factor=2)
    assert m.total_rows == 100
    assert m.holders(0) == ("u0", "u1")                  # ring, primary 1st
    assert m.holders(3) == ("u3", "u0")                  # wraps
    assert m.held_by("u0") == (0, 3)
    assert m.range_bounds(2) == (30, 60)
    # healthy fleet: every range served by its primary
    alive = ("u0", "u1", "u2", "u3")
    assert m.assignment(alive) == {0: "u0", 1: "u1", 2: "u2", 3: "u3"}
    # one death: replica serves, nothing uncovered
    assert m.owner(1, ("u0", "u2", "u3")) == "u2"
    assert m.uncovered(("u0", "u2", "u3")) == []
    assert m.covered_rows(("u0", "u2", "u3")) == 100
    # both holders of range 1 dead: the range is lost, others survive
    assert m.uncovered(("u0", "u3")) == [1]
    assert m.covered_rows(("u0", "u3")) == 80
    # held overrides nominal possession (revived-empty unit)
    held = {"u0": {0, 3}, "u1": set(), "u2": {1, 2}, "u3": {2, 3}}
    assert m.owner(1, alive, held=held) == "u2"          # u1 empty
    assert m.owner(0, alive, held=held) == "u0"


def test_replica_map_rebuild_targets():
    m = ReplicaMap((1, 1, 1, 1), ("u0", "u1", "u2", "u3"), factor=2)
    # u1 died and came back empty: both its ranges refill, nominal first
    held = {"u0": {0, 3}, "u1": set(), "u2": {1, 2}, "u3": {2, 3}}
    work = m.rebuild_targets(("u0", "u1", "u2", "u3"), held=held)
    assert (0, "u0", "u1") in work and (1, "u2", "u1") in work
    # applying the work restores factor everywhere
    for i, _src, tgt in work:
        held[tgt].add(i)
    assert m.rebuild_targets(("u0", "u1", "u2", "u3"), held=held) == []
    # a fully lost range yields no work (nothing to copy from): range 0's
    # holders are u0+u1, both dead here
    lost = m.rebuild_targets(("u2", "u3"))
    assert all(i != 0 for i, _s, _t in lost)
    with pytest.raises(ValueError):
        ReplicaMap((1, 1), ("a", "b"), factor=3)
    with pytest.raises(ValueError):
        ReplicaMap((1,), ("a", "b"))


# ---------------------------------------------------------------------------
# host-orchestrated fault-tolerant search
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def corpus():
    rng = np.random.default_rng(0)
    counts = [300, 512, 11, 201]
    N = sum(counts)
    codes = rng.integers(0, 2 ** 32, (N, 2), dtype=np.uint32)
    q = rng.integers(0, 2 ** 32, (5, 2), dtype=np.uint32)
    return codes, q, counts, N


def _fts(codes, counts, **kw):
    from repro.dist.search import FaultTolerantSearch
    return FaultTolerantSearch(codes, 64, counts=counts, **kw)


def test_fts_healthy_equals_reference(corpus):
    from repro.dist.search import reference_over_covered
    codes, q, counts, N = corpus
    fts = _fts(codes, counts)
    dd, ii, rep = fts.search(q, 16)
    rd, ri = reference_over_covered(codes, q, 16, 64, np.arange(N))
    assert np.array_equal(dd, rd) and np.array_equal(ii, ri)
    assert rep.complete and rep.coverage_frac == 1.0


@pytest.mark.parametrize("dead", [0, 1, 2, 3])
def test_fts_single_dead_is_degraded_but_exact(corpus, dead):
    from repro.dist.search import reference_over_covered
    codes, q, counts, N = corpus
    bounds = np.cumsum([0] + counts)
    for k in (16, 1200):           # 1200 > every survivor total
        fts = _fts(codes, counts)
        fts.kill(f"unit{dead}")
        dd, ii, rep = fts.search(q, k)
        m = np.concatenate([np.arange(bounds[i], bounds[i + 1])
                            for i in range(4) if i != dead])
        rd, ri = reference_over_covered(codes, q, k, 64, m)
        assert np.array_equal(dd, rd), (dead, k)
        assert np.array_equal(ii, ri), (dead, k)
        assert rep.covered_rows == N - counts[dead]
        assert rep.dead_shards == (f"unit{dead}",)
        assert np.isclose(rep.coverage_frac, (N - counts[dead]) / N)


def test_fts_replica_keeps_full_coverage(corpus):
    from repro.dist.search import reference_over_covered
    codes, q, counts, N = corpus
    fts = _fts(codes, counts, factor=2)
    fts.kill("unit1")
    dd, ii, rep = fts.search(q, 16)
    rd, ri = reference_over_covered(codes, q, 16, 64, np.arange(N))
    assert np.array_equal(dd, rd) and np.array_equal(ii, ri)
    assert rep.coverage_frac == 1.0 and rep.dead_shards == ("unit1",)


def test_fts_rereplication_restores_coverage(corpus):
    """R=2, both holders of range 1 die -> degraded-but-exact; a warm
    revive + maintain() returns coverage to exactly 1.0."""
    from repro.dist.search import reference_over_covered
    codes, q, counts, N = corpus
    bounds = np.cumsum([0] + counts)
    fts = _fts(codes, counts, factor=2)
    fts.kill("unit1"); fts.kill("unit2")
    dd, ii, rep = fts.search(q, 16)
    m = np.concatenate([np.arange(bounds[i], bounds[i + 1])
                        for i in (0, 2, 3)])   # range 2 survives via unit3
    rd, ri = reference_over_covered(codes, q, 16, 64, m)
    assert np.array_equal(dd, rd) and np.array_equal(ii, ri)
    assert rep.covered_rows == N - counts[1]
    fts.revive("unit1", with_data=True)
    out = fts.maintain()
    assert fts.registry.state("unit1") == HEALTHY
    assert out["recovered"] == ["unit1"]
    assert fts.coverage().coverage_frac == 1.0
    dd, ii, rep = fts.search(q, 16)
    rd, ri = reference_over_covered(codes, q, 16, 64, np.arange(N))
    assert np.array_equal(dd, rd) and np.array_equal(ii, ri)
    assert rep.coverage_frac == 1.0


def test_fts_cold_revive_refills_from_replicas(corpus):
    codes, q, counts, N = corpus
    fts = _fts(codes, counts, factor=2)
    fts.kill("unit1")
    assert fts.coverage().coverage_frac == 1.0    # replica holds range 1
    fts.revive("unit1", with_data=False)          # disk gone
    out = fts.maintain()
    assert out["copied"] >= 2 and fts.registry.state("unit1") == HEALTHY
    assert fts.coverage().coverage_frac == 1.0
    assert set(fts.covered_ranges()) == {0, 1, 2, 3}


def test_fts_injected_faults_drive_failover(corpus):
    from repro.dist.search import reference_over_covered
    from repro.runtime import faults
    codes, q, counts, N = corpus
    inj = faults.FaultInjector(seed=1, p={"shard_hist@unit0": 1.0,
                                          "shard_emit@unit0": 1.0})
    fts = _fts(codes, counts, factor=2, injector=inj)
    dd, ii, rep = fts.search(q, 16)
    rd, ri = reference_over_covered(codes, q, 16, 64, np.arange(N))
    assert np.array_equal(dd, rd) and np.array_equal(ii, ri)
    assert rep.coverage_frac == 1.0               # replica covered it
    assert fts.registry.state("unit0") == DEAD    # driven by observations
    assert fts.counters["failovers"] >= 1
    assert inj.fired.get("shard_hist@unit0", 0) >= 1


def test_fts_transient_first_call_fails_over(corpus):
    """A unit step that raises a transient error on its first calls at a
    new shape walks that unit to dead, and the range fails over to its
    replica: no first call runs outside the fault handling."""
    from repro.dist.search import reference_over_covered
    codes, q, counts, N = corpus
    fts = _fts(codes, counts, factor=2)
    compiled, failures = fts._compiled, [fts.registry.dead_after]

    def flaky(fn, qq, range_idx):
        exe = compiled(fn, qq, range_idx)

        def call(*args):
            if failures[0] > 0:
                failures[0] -= 1
                raise TimeoutError("unit step timed out")
            return exe(*args)
        return call

    fts._compiled = flaky
    dd, ii, rep = fts.search(q[:3], 16)
    rd, ri = reference_over_covered(codes, q[:3], 16, 64, np.arange(N))
    assert np.array_equal(dd, rd) and np.array_equal(ii, ri)
    assert rep.coverage_frac == 1.0 and rep.dead_shards == ("unit0",)
    assert fts.registry.state("unit0") == DEAD
    assert fts.counters["failovers"] == 1


def test_fts_compile_is_not_shard_latency(corpus, monkeypatch):
    """Compiling a unit step at a new shape is set-up: a compile that takes
    far longer than the per-call deadline marks no unit suspect."""
    import jax
    from repro.dist.search import reference_over_covered
    codes, q, counts, N = corpus
    now = [0.0]
    compile_ = jax.stages.Lowered.compile

    def slow_compile(self, *args, **kwargs):
        now[0] += 10.0
        return compile_(self, *args, **kwargs)

    monkeypatch.setattr(jax.stages.Lowered, "compile", slow_compile)
    fts = _fts(codes, counts, deadline_s=0.25, clock=lambda: now[0])
    dd, ii, rep = fts.search(q, 16)
    rd, ri = reference_over_covered(codes, q, 16, 64, np.arange(N))
    assert np.array_equal(dd, rd) and np.array_equal(ii, ri)
    assert now[0] >= 10.0 and rep.complete
    assert all(fts.registry.state(u) == HEALTHY for u in fts.map.units)
    assert fts.counters["failovers"] == 0


def test_fts_merge_faults_retry_exactly(corpus):
    from repro.dist.search import reference_over_covered
    from repro.runtime import faults
    codes, q, counts, N = corpus
    inj = faults.FaultInjector(seed=2, p={"merge_psum": 0.5})
    fts = _fts(codes, counts, injector=inj)
    dd, ii, _ = fts.search(q, 16)
    rd, ri = reference_over_covered(codes, q, 16, 64, np.arange(N))
    assert np.array_equal(dd, rd) and np.array_equal(ii, ri)
    assert sum(v for s, v in inj.calls.items()
               if s.startswith("merge_psum")) >= 2


def test_fts_all_dead_and_zero_k_edges(corpus):
    codes, q, counts, N = corpus
    fts = _fts(codes, counts)
    for u in fts.map.units:
        fts.kill(u)
    dd, ii, rep = fts.search(q, 7)
    assert (dd == 65).all() and (ii == N).all()
    assert rep.covered_rows == 0 and rep.coverage_frac == 0.0
    assert len(rep.dead_shards) == 4
