"""Incremental admission fast path: SolutionCache + warm-started DP.

Safety story under test, in order of importance:

- **off == pre-cache path, bit for bit** — seeded fuzz over admission /
  release / fail / restore interleavings with all-unique request
  signatures drives the cache machinery (classification, plan merge,
  negative recording) without ever producing a hit, so ``cache_enabled``
  on vs off must agree on every ticket, residual array, and counter at
  every step — at the centralized placer, through the depth>1 pipeline,
  and across an R=4 regional plane.
- **a hit can never over-commit** — positive entries are advisory: every
  hit is revalidated against the float64 residual truth before any
  reserve, so churn (fail/restore/defrag) between fill and hit must
  re-route or reject, never serve a stale mapping onto dead capacity.
- **tier 2 is bounded** — warm-started correction solves report at most
  ``max_correction_supersteps`` relaxation rounds; failures fall back to
  a cold solve, so admission quality never drops below the cold path.
"""
import dataclasses

import numpy as np
import pytest

from repro.core import (
    AdmissionPipeline,
    OnlinePlacer,
    SolutionCache,
    random_dataflow,
    request_signature,
    validate_mapping,
    waxman,
)
from repro.core.leastcost import warm_seed_from_mapping
from repro.service import ControlPlane, RegionalControlPlane

PYM = dict(method="leastcost_python")


def _light(rg, k, *, p=5, seed0=500):
    return [
        random_dataflow(rg, p, seed=seed0 + i,
                        creq_range=(0.02, 0.1), breq_range=(0.5, 3.0))
        for i in range(k)
    ]


def _cache_free(stats):
    """Stats minus wall clock and the cache/warm traffic counters (the
    only legitimate on-vs-off divergence when no signature ever repeats:
    the on side counts its misses)."""
    d = dataclasses.asdict(stats)
    for k in ("solve_ms", "overhead_ms", "conflict_resolve_ms",
              "dp_wait_ms", "reconstruct_ms", "remap_ms", "cache_hits",
              "cache_misses", "cache_stale", "cache_neg_hits", "warm_solves",
              "warm_fallbacks"):
        d.pop(k)
    return d


# ---------------------------------------------------------------------------
# SolutionCache unit behavior
# ---------------------------------------------------------------------------


def test_solution_cache_lru_eviction_and_negative_clearing():
    c = SolutionCache(capacity=2)
    c.put("a", 1)
    c.put("b", 2)
    assert c.get("a") == 1  # touches "a": now "b" is the LRU entry
    c.put("c", 3)
    assert c.get("b") is None and c.get("a") == 1 and c.get("c") == 3
    assert len(c) == 2
    # a negative entry is exact-stamp: a different stamp is NOT a hit
    c.put_negative("x", (7, 0))
    assert c.negative_hit("x", (7, 0))
    assert not c.negative_hit("x", (8, 0))
    assert not c.negative_hit("y", (7, 0))
    # a positive fill clears the negative for the same signature
    c.put("x", 9)
    assert not c.negative_hit("x", (7, 0))
    c.drop("x")
    assert c.get("x") is None
    c.clear()
    assert len(c) == 0 and c.negatives == 0


def test_request_signature_discriminates_and_repeats():
    rg = waxman(10, seed=0)
    df1 = random_dataflow(rg, 4, seed=1)
    df2 = random_dataflow(rg, 4, seed=1)
    df3 = random_dataflow(rg, 4, seed=2)
    assert request_signature(df1) == request_signature(df2)
    assert request_signature(df1) != request_signature(df3)


# ---------------------------------------------------------------------------
# cache off <-> on bit-identity under unique signatures (all plane levels)
# ---------------------------------------------------------------------------


def _fuzz_identity(seed, make_admit, a, b, rg, steps=30):
    """Shared op fuzz: admit (signatures never repeat), release.  Hit-free
    by construction, so the cache-on side's classification / plan-merge /
    negative-recording machinery must be perfectly transparent — identical
    decisions, tickets, and residual arrays at every step.  (Structural
    churn re-admits *cached* signatures via ``fail_node`` remaps, where
    the fast path legitimately serves a different-but-valid mapping; the
    churn contracts are covered by the stale/warm tests below.)"""
    rng = np.random.default_rng(seed)
    uniq = [0]
    for step in range(steps):
        op = rng.choice(["admit", "release"], p=[0.6, 0.4])
        if op == "admit":
            k = int(rng.integers(1, 5))
            dfs = _light(rg, k, p=4, seed0=10_000 * seed + uniq[0])
            uniq[0] += k  # signatures never repeat across the whole run
            make_admit(dfs)
        elif op == "release" and a.tickets:
            tid = int(rng.choice(sorted(a.tickets)))
            if tid in b.tickets:
                a.release(tid)
                b.release(tid)
        np.testing.assert_array_equal(a.cap, b.cap)
        np.testing.assert_array_equal(a.bw, b.bw)
        assert sorted(a.tickets) == sorted(b.tickets)
        for tid, t in a.tickets.items():
            assert t.mapping == b.tickets[tid].mapping
        a.check_invariants()
        b.check_invariants()
    assert b.stats.cache_hits == 0 and b.stats.warm_solves == 0


@pytest.mark.parametrize("seed", [0, 1])
def test_cache_off_identity_centralized(seed):
    rg = waxman(12, seed=5)
    a = OnlinePlacer(rg, cache_enabled=False)
    b = OnlinePlacer(rg)  # cache on (the default)

    def admit(dfs):
        for x, y in zip(a.admit_many(dfs), b.admit_many(dfs)):
            assert (x is None) == (y is None)
            if x is not None:
                assert x.tid == y.tid
                assert x.mapping.assign == y.mapping.assign

    _fuzz_identity(seed, admit, a, b, rg)
    assert _cache_free(a.stats) == _cache_free(b.stats)


@pytest.mark.parametrize("seed", [0, 1])
def test_cache_off_identity_pipelined_depth3(seed):
    """Both sides drive a depth-3 pipeline (dispatch overlaps up to three
    uncommitted batches), so the cache-on plan path is exercised under
    epoch fencing — releases between dispatch and commit force the
    stale-batch re-solve on both sides identically."""
    rg = waxman(12, seed=5)
    a = OnlinePlacer(rg, cache_enabled=False)
    b = OnlinePlacer(rg)
    pa = AdmissionPipeline(a, depth=3)
    pb = AdmissionPipeline(b, depth=3)

    def admit(dfs):
        oa, ob = pa.push(dfs), pb.push(dfs)
        assert len(oa) == len(ob)  # same batches retire at the same pushes
        for (_, ta), (_, tb) in zip(oa, ob):
            for x, y in zip(ta, tb):
                assert (x is None) == (y is None)
                if x is not None:
                    assert x.tid == y.tid
                    assert x.mapping.assign == y.mapping.assign

    _fuzz_identity(seed, admit, a, b, rg, steps=25)
    for (_, ta), (_, tb) in zip(pa.flush(), pb.flush()):
        assert [t and t.tid for t in ta] == [t and t.tid for t in tb]
    np.testing.assert_array_equal(a.cap, b.cap)
    np.testing.assert_array_equal(a.bw, b.bw)
    assert _cache_free(a.stats) == _cache_free(b.stats)
    a.check_invariants()
    b.check_invariants()


@pytest.mark.parametrize("seed", [0])
def test_cache_off_identity_regional_r4(seed):
    """cache_enabled rides **solve_cfg down to every per-region placer;
    with unique signatures the R=4 plane must behave identically on/off:
    same rids, same tickets, same conservation ledger, every step."""
    rg = waxman(20, seed=7)
    kw = dict(micro_batch=4, max_attempts=3, **PYM)
    a = RegionalControlPlane(rg, regions=4, seed=seed, cache_enabled=False,
                             **kw)
    b = RegionalControlPlane(rg, regions=4, seed=seed, **kw)
    for cp in (a, b):
        cp.register_tenant("t", weight=1.0)
    rng = np.random.default_rng(seed)
    uniq = 0
    for step in range(25):
        op = rng.choice(["submit", "pump", "release"], p=[0.45, 0.35, 0.20])
        if op == "submit":
            df = _light(rg, 1, p=4, seed0=50_000 + uniq)[0]
            uniq += 1
            assert a.submit("t", df) == b.submit("t", df)
        elif op == "pump":
            r = int(rng.integers(1, 3))
            # intra-region placements carry .tid, cross-region spans .rid
            key = lambda t: getattr(t, "rid", None) or getattr(t, "tid", None)
            assert ([key(t) for t in a.pump(rounds=r)]
                    == [key(t) for t in b.pump(rounds=r)])
        elif op == "release":
            ids = a.active_ids()
            assert ids == b.active_ids()
            if ids:
                rid = int(rng.choice(ids))
                a.release(rid)
                b.release(rid)
        assert a.conservation() == b.conservation()
        a.check_invariants()
        b.check_invariants()
    for pa, pb in zip(a.regions, b.regions):
        np.testing.assert_array_equal(pa.placer.cap, pb.placer.cap)
        np.testing.assert_array_equal(pa.placer.bw, pb.placer.bw)
        # the knob rode **solve_cfg down to every per-region placer.  (The
        # broker's chain-retry loop re-admits identical segment signatures
        # on the bit-exact residual its own abort restored, so the cached
        # side may legitimately count hits — each one serving exactly the
        # mapping the deterministic cold solve just produced, which is why
        # the step-by-step state identity above still holds.)
        assert pb.placer.cache is not None
        assert pa.placer.cache is None


# ---------------------------------------------------------------------------
# tier 1: hits skip the DP and are excluded from solve accounting
# ---------------------------------------------------------------------------


def test_repeat_batch_is_pure_hits_and_skips_solve_accounting():
    rg = waxman(16, seed=2)
    placer = OnlinePlacer(rg)
    dfs = _light(rg, 8)
    first = placer.admit_many(dfs)
    assert all(t is not None for t in first)
    base = placer.stats.solves
    base_n = placer.stats.solve_n_sum
    for t in first:
        placer.release(t)
    second = placer.admit_many(dfs)
    assert all(t is not None for t in second)
    assert placer.stats.cache_hits == 8
    # satellite: hit admissions never touch solves / solve_n_sum / solve_ms
    assert placer.stats.solves == base
    assert placer.stats.solve_n_sum == base_n
    # the reused mappings are exactly the previously committed ones
    for x, y in zip(first, second):
        assert y.mapping.assign == x.mapping.assign
        validate_mapping(placer.base, y.df, y.mapping)
    placer.check_invariants()


def test_negative_cache_short_circuits_repeat_rejections():
    rg = waxman(10, seed=4)
    placer = OnlinePlacer(rg)
    impossible = random_dataflow(rg, 4, seed=9,
                                 creq_range=(50.0, 60.0),  # >> any cap
                                 breq_range=(0.1, 0.2))
    assert placer.admit(impossible) is None
    solves = placer.stats.solves
    assert placer.admit(impossible) is None  # same residual stamp
    assert placer.stats.cache_neg_hits == 1
    assert placer.stats.solves == solves  # no re-solve
    # any residual mutation invalidates the stamp: a fresh solve runs
    ok = placer.admit(_light(rg, 1, seed0=77)[0])
    assert ok is not None
    assert placer.admit(impossible) is None
    assert placer.stats.solves > solves
    placer.check_invariants()


# ---------------------------------------------------------------------------
# stale entries under churn: revalidate, never over-commit
# ---------------------------------------------------------------------------


def test_stale_hit_after_node_failure_rerouted_never_overcommitted():
    rg = waxman(16, seed=2)
    placer = OnlinePlacer(rg)
    dfs = _light(rg, 8)
    first = placer.admit_many(dfs)
    assert all(t is not None for t in first)
    victim = first[0].mapping.route[len(first[0].mapping.route) // 2]
    for t in first:
        placer.release(t)
    placer.fail_node(victim)  # cached routes through victim are now stale
    second = placer.admit_many(dfs)
    for t in second:
        if t is not None:
            assert victim not in t.mapping.route
            validate_mapping(placer.base, t.df, t.mapping)
    assert placer.stats.cache_stale >= 1
    placer.check_invariants()
    placer.restore_node(victim)
    placer.check_invariants()


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_stale_cache_churn_property_fuzz(seed):
    """fail / restore / defrag / release between cache fill and hit:
    every committed mapping must validate against the residual it was
    reserved on (check_invariants recomputes the ledger each step)."""
    from repro.service.defrag import defrag as run_defrag

    rg = waxman(14, seed=6)
    placer = OnlinePlacer(rg)
    pool = _light(rg, 6, p=4, seed0=900 * (seed + 1))  # repeats by design
    rng = np.random.default_rng(seed)
    failed: list[int] = []
    for step in range(35):
        op = rng.choice(
            ["admit", "release", "fail", "restore", "defrag"],
            p=[0.40, 0.25, 0.12, 0.13, 0.10],
        )
        if op == "admit":
            df = pool[int(rng.integers(0, len(pool)))]
            t = placer.admit(df)
            if t is not None:
                validate_mapping(placer.base, t.df, t.mapping)
        elif op == "release" and placer.tickets:
            placer.release(int(rng.choice(sorted(placer.tickets))))
        elif op == "fail" and len(failed) < 2:
            v = int(rng.integers(0, rg.n))
            if v not in failed:
                placer.fail_node(v)
                failed.append(v)
        elif op == "restore" and failed:
            placer.restore_node(failed.pop(int(rng.integers(0, len(failed)))))
        elif op == "defrag":
            run_defrag(placer)
        placer.check_invariants()
    # the run must actually have exercised the cache paths
    assert placer.stats.cache_hits + placer.stats.cache_stale > 0


# ---------------------------------------------------------------------------
# tier 2: warm-started bounded correction supersteps
# ---------------------------------------------------------------------------


def test_warm_seed_walks_mapping_and_stops_at_violations():
    rg = waxman(16, seed=2)
    placer = OnlinePlacer(rg)
    t = placer.admit(_light(rg, 1)[0])
    assert t is not None
    # on the *pre-commit* residual the walk spans the whole route: one
    # arrival state per hop, in route order, costs non-decreasing
    placer.release(t)
    seed = warm_seed_from_mapping(placer.residual_graph(), t.df, t.mapping)
    assert seed is not None
    assert len(seed["v"]) == len(t.mapping.route) - 1
    assert list(seed["v"]) == list(t.mapping.route[1:])
    assert np.all(np.diff(seed["cost"]) >= 0)
    assert np.all(seed["j"] >= 1) and np.all(seed["j"] <= t.df.p)
    # a dead node on the route truncates the walk instead of seeding junk
    victim = t.mapping.route[-1]
    placer.fail_node(victim)
    seed2 = warm_seed_from_mapping(placer.residual_graph(), t.df, t.mapping)
    if seed2 is not None:
        assert victim not in seed2["v"]
    placer.restore_node(victim)


def test_warm_solves_respect_the_superstep_fuse():
    rg = waxman(16, seed=2)
    placer = OnlinePlacer(rg)
    fuse = placer.max_correction_supersteps
    dfs = _light(rg, 8)
    ts = placer.admit_many(dfs)
    assert all(t is not None for t in ts)
    routes = [t.mapping.route for t in ts]
    victim = routes[0][1] if len(routes[0]) > 1 else routes[0][0]
    placer.fail_node(victim)  # remaps displaced tickets through stale entries
    for t in list(placer.tickets.values()):
        placer.release(t)
    placer.admit_many(dfs)  # stale entries -> warm-started correction solves
    st = placer.stats
    assert st.warm_solves >= 1, st
    warm = st.supersteps.get("warm", {})
    cold = st.supersteps.get("cold", {})
    assert warm and cold
    # the fuse bounds every warm solve; the cold fixpoint runs past it
    assert max(warm) <= fuse < max(cold), (warm, cold)
    placer.check_invariants()


def test_cache_disabled_means_no_cache_object_no_plan():
    rg = waxman(12, seed=1)
    placer = OnlinePlacer(rg, cache_enabled=False)
    assert placer.cache is None
    pend = placer.dispatch_admit(_light(rg, 3, p=4))
    assert pend.plan is None
    placer.commit_admit(pend)
    assert placer.stats.cache_hits == placer.stats.cache_misses == 0
    placer.check_invariants()
