"""Batched fused superstep kernel (kernels/minplus/batched) vs the vmapped
pure-jnp DP: bit-for-bit parity on mixed-p padded batches, in both the
fused-jnp mirror and Pallas interpret mode (the CPU-CI kernel cross-check),
plus tie-breaking / BIG-clamp / padded-column edge cases and the engine /
online-service integration of ``use_kernel=True``."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import OnlinePlacer, random_dataflow, solve_batch, waxman
from repro.core.leastcost import (
    _leastcost_dp,
    _leastcost_dp_batched,
    _move_step_ref,
    _place_step,
    leastcost_jax,
    leastcost_jax_batched,
)
from repro.core.problem import BATCH_IN_AXES, BIG, stack_requests
from repro.kernels.minplus import batched as bk


def _stream(rg, ps, seed0=500):
    """Light requests (several fit the network at once) of mixed length."""
    return [
        random_dataflow(rg, p, seed=seed0 + i,
                        creq_range=(0.02, 0.2), breq_range=(0.5, 5.0))
        for i, p in enumerate(ps)
    ]


def _vmapped_dp(tensors, n, p_max, max_rounds):
    fn = jax.vmap(
        lambda t: _leastcost_dp(t, n=n, p=p_max, max_rounds=max_rounds),
        in_axes=(BATCH_IN_AXES,),
    )
    return fn(tensors)


def _assert_dp_equal(a, b):
    for x, y, name in zip(a[:5], b[:5], ("C", "par_v", "par_j", "cost", "j")):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y), err_msg=name)


# ---------------------------------------------------------------------------
# Full-DP parity: fused batched path vs vmapped jnp oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n,ps,seed", [
    (12, [4, 6, 5, 3], 0),
    (16, [5, 5, 5], 7),
    (20, [3, 7, 4, 6, 2, 5], 21),
])
def test_fused_ref_matches_vmapped_bitforbit(n, ps, seed):
    rg = waxman(n, seed=seed)
    dfs = _stream(rg, ps, seed0=1000 * seed)
    tensors, p_max = stack_requests(rg, dfs)
    out_v = _vmapped_dp(tensors, n, p_max, n - 1)
    out_b = _leastcost_dp_batched(tensors, B=len(dfs), n=n, p=p_max,
                                  max_rounds=n - 1, impl="ref")
    _assert_dp_equal(out_v, out_b)


@pytest.mark.parametrize("tiles,ps", [
    ((1, 128, 128), [4, 6, 3]),
    ((2, 128, 128), [4, 6, 3]),
    ((4, 128, 256), [4, 6, 3]),
    ((2, 256, 128), [12, 5, 9]),
])
def test_pallas_interpret_matches_ref_bitforbit(tiles, ps):
    """Interpret-mode Pallas kernel vs the fused jnp mirror, including
    b_tile > 1 (padded batch rows), several v or w blocks, and K_pad = 16
    (two sublane rows of k columns)."""
    n = 13
    rg = waxman(n, seed=5)
    dfs = _stream(rg, ps, seed0=40)
    tensors, p_max = stack_requests(rg, dfs)
    out_ref = _leastcost_dp_batched(tensors, B=len(dfs), n=n, p=p_max,
                                    max_rounds=n - 1, impl="ref")
    out_pal = _leastcost_dp_batched(tensors, B=len(dfs), n=n, p=p_max,
                                    max_rounds=n - 1, impl="interpret",
                                    tiles=tiles)
    _assert_dp_equal(out_ref, out_pal)


def test_mappings_match_and_respect_mixed_p():
    """End-to-end: kernel-path mappings equal the vmapped path's exactly and
    keep each request's true length (padded columns never leak)."""
    rg = waxman(18, seed=2)
    dfs = _stream(rg, [3, 6, 4, 5, 6, 2], seed0=70)
    ms_v = leastcost_jax_batched(rg, dfs)
    ms_k = leastcost_jax_batched(rg, dfs, use_kernel=True)
    assert any(m is not None for m in ms_v)
    for df, a, b in zip(dfs, ms_v, ms_k):
        assert (a is None) == (b is None)
        if a is not None:
            assert a.assign == b.assign and a.route == b.route
            assert a.cost == b.cost
            assert len(b.assign) == df.p


# ---------------------------------------------------------------------------
# Single-superstep edge cases (ties, BIG clamping, padded masking)
# ---------------------------------------------------------------------------


def _superstep_pair(C, pv, pj, lat, bw, cap, prefix, breq_k, tiles):
    ref = bk.batched_superstep_ref(C, pv, pj, lat, bw, cap, prefix, breq_k)
    pads = bk.pad_batched_problem(lat, bw, cap, prefix, breq_k, tiles=tiles)
    Bp, K_pad = pads["prefix"].shape
    n_pad = pads["lat"].shape[0]
    B, n, K = C.shape

    def fill(x, v):
        return jnp.full((Bp, n_pad, K_pad), v, x.dtype).at[:B, :n, :K].set(x)

    pal = bk.batched_superstep_pallas(
        fill(C, BIG), fill(pv, -1), fill(pj, -1),
        pads["lat"], pads["bw"], pads["cap"], pads["prefix"], pads["breq_k"],
        tiles=tiles, interpret=True,
    )
    pal = tuple(x[:B, :n, :K] for x in pal)
    return ref, pal


def _random_state(B, n, K, seed, big_frac=0.4):
    rng = np.random.default_rng(seed)
    C = np.where(rng.random((B, n, K)) < big_frac, BIG,
                 rng.random((B, n, K)) * 10).astype(np.float32)
    pv = rng.integers(-1, n, size=(B, n, K)).astype(np.int32)
    pj = rng.integers(-1, K, size=(B, n, K)).astype(np.int32)
    lat = np.where(rng.random((n, n)) < 0.5, BIG,
                   rng.random((n, n)) * 5 + 0.1).astype(np.float32)
    np.fill_diagonal(lat, BIG)
    bw = (rng.random((n, n)) * 100).astype(np.float32)
    cap = (rng.random(n) * 6).astype(np.float32)
    creq = rng.random((B, K - 1)).astype(np.float32) * 2
    prefix = np.concatenate(
        [np.zeros((B, 1), np.float32), np.cumsum(creq, axis=1)], axis=1)
    breq_k = np.concatenate(
        [np.full((B, 1), BIG, np.float32),
         (rng.random((B, K - 2)) * 60).astype(np.float32),
         np.full((B, 1), BIG, np.float32)], axis=1)
    j = jnp.asarray
    return (j(C), j(pv), j(pj), j(lat), j(bw), j(cap), j(prefix), j(breq_k))


@pytest.mark.parametrize("seed,tiles,K", [(0, (1, 128, 128), 6),
                                          (1, (2, 128, 256), 6),
                                          (2, (4, 256, 128), 11)])
def test_superstep_random_states(seed, tiles, K):
    args = _random_state(B=3, n=12, K=K, seed=seed)
    ref, pal = _superstep_pair(*args, tiles=tiles)
    for r, p, name in zip(ref, pal, ("C", "par_v", "par_j")):
        np.testing.assert_array_equal(np.asarray(r), np.asarray(p),
                                      err_msg=name)


def test_superstep_ties_break_like_jnp_path():
    """Move ties must break to the FIRST v (kernel: strict `<` across
    v-tiles + first-min within a tile), place ties to the LARGEST j —
    exactly the jnp `_place_step` / `_move_step_ref` rules."""
    B, n, K = 2, 16, 4
    # zero-cost states only at v in {0, 1}, j in {1, 2}: every other row
    # reaches cost 1 through a tie between v=0 and v=1, and the place step
    # at the winning v ties between j=1 and j=2 for k=2
    C = jnp.full((B, n, K), BIG, jnp.float32)
    C = C.at[:, :2, 1:3].set(0.0)
    pv = jnp.full((B, n, K), -1, jnp.int32)
    pj = jnp.full((B, n, K), -1, jnp.int32)
    lat = jnp.full((n, n), 1.0, jnp.float32)  # every move costs 1
    lat = lat.at[jnp.arange(n), jnp.arange(n)].set(BIG)  # no self moves
    bw = jnp.full((n, n), 100.0, jnp.float32)
    cap = jnp.full((n,), 50.0, jnp.float32)
    prefix = jnp.tile(jnp.arange(K, dtype=jnp.float32)[None, :], (B, 1)) * 0.1
    breq_k = jnp.concatenate(
        [jnp.full((B, 1), BIG), jnp.full((B, K - 2), 1.0),
         jnp.full((B, 1), BIG)], axis=1)
    ref, pal = _superstep_pair(C, pv, pj, lat, bw, cap, prefix, breq_k,
                               tiles=(1, 128, 128))
    for r, p in zip(ref, pal):
        np.testing.assert_array_equal(np.asarray(r), np.asarray(p))
    Cn, pvn, pjn = (np.asarray(x) for x in pal)
    assert (Cn[:, 2:, 1:3] == 1.0).all()  # updated via the tied move
    assert (pvn[:, 2:, 1:3] == 0).all()  # v=0 wins the v-tie
    assert (pjn[:, 2:, 1] == 1).all()  # only j=1 reaches k=1
    assert (pjn[:, 2:, 2] == 2).all()  # j in {1,2} tie at k=2 -> largest j


def test_superstep_big_overflow_clamped():
    """Where every feasible move adds lat to a BIG state, the kernel clamps
    BIG + lat while the jnp path does not — the difference must not leak
    through the monotone state update."""
    args = list(_random_state(B=2, n=10, K=5, seed=9, big_frac=1.0))
    # C all BIG -> every move candidate is BIG + lat (incl. lat = BIG rows)
    ref, pal = _superstep_pair(*args, tiles=(1, 128, 128))
    for r, p in zip(ref, pal):
        np.testing.assert_array_equal(np.asarray(r), np.asarray(p))
    # state must be unchanged: nothing can improve on BIG
    np.testing.assert_array_equal(np.asarray(ref[0]), np.asarray(args[0]))


def test_padded_columns_stay_masked():
    """k columns beyond a request's p_eff carry BIG breq (ghost dataflow
    edges): the kernel's padded k/batch blocks must never produce a finite
    cost there."""
    rg = waxman(12, seed=11)
    dfs = _stream(rg, [3, 6], seed0=90)  # p_eff 3 vs 6: columns 4..6 ghost
    tensors, p_max = stack_requests(rg, dfs)
    C, *_ = _leastcost_dp_batched(tensors, B=2, n=12, p=p_max,
                                  max_rounds=11, impl="interpret",
                                  tiles=(2, 128, 128))
    C = np.asarray(C)
    # request 0 has p_eff=3: state columns beyond its true sink (k > 3) are
    # unreachable -> must still hold BIG
    assert (C[0, :, 4:] >= BIG / 2).all()


def test_place_move_refs_still_agree_with_batched_mirrors():
    """The fused mirrors in kernels/minplus/batched must track the canonical
    single-request steps in core.leastcost (guards against drift)."""
    rng = np.random.default_rng(3)
    n, K = 11, 6
    C = jnp.asarray(np.where(rng.random((n, K)) < 0.3, BIG,
                             rng.random((n, K)) * 8).astype(np.float32))
    cap = jnp.asarray((rng.random(n) * 5).astype(np.float32))
    prefix = jnp.asarray(np.concatenate(
        [[0.0], np.cumsum(rng.random(K - 1) * 2)]).astype(np.float32))
    P1, pj1 = _place_step(C, cap, prefix)
    P2, pj2 = bk._place_batched_ref(C[None], cap, prefix[None])
    np.testing.assert_array_equal(np.asarray(P1), np.asarray(P2[0]))
    np.testing.assert_array_equal(np.asarray(pj1), np.asarray(pj2[0]))

    lat = jnp.asarray(np.where(rng.random((n, n)) < 0.5, BIG,
                               rng.random((n, n)) * 4 + 0.1).astype(np.float32))
    bw = jnp.asarray((rng.random((n, n)) * 100).astype(np.float32))
    breq = jnp.asarray((rng.random(K - 2) * 50).astype(np.float32))
    Cm1, pv1 = _move_step_ref(P1, lat, bw, breq)
    breq_k = jnp.concatenate([jnp.full((1,), BIG), breq, jnp.full((1,), BIG)])
    Cm2, pv2 = bk._move_batched_ref(P1[None], lat, bw, breq_k[None])
    np.testing.assert_array_equal(np.asarray(Cm1), np.asarray(Cm2[0]))
    np.testing.assert_array_equal(np.asarray(pv1), np.asarray(pv2[0]))


# ---------------------------------------------------------------------------
# Engine / online-service integration
# ---------------------------------------------------------------------------


def test_bucket_batch_results_unchanged():
    """Power-of-two tensor-level bucketing (the online placer's recompile
    bound) must not change any real request's result, on either DP path."""
    rg = waxman(14, seed=8)
    dfs = _stream(rg, [5, 4, 6], seed0=55)  # 3 requests -> bucket of 4
    for kw in ({}, dict(use_kernel=True)):
        plain = leastcost_jax_batched(rg, dfs, **kw)
        bucketed = leastcost_jax_batched(rg, dfs, bucket_batch=True, **kw)
        assert len(bucketed) == len(dfs)
        for a, b in zip(plain, bucketed):
            assert (a is None) == (b is None)
            if a is not None:
                assert a.assign == b.assign and a.cost == b.cost


def test_engine_solve_batch_kernel_parity():
    rg = waxman(16, seed=13)
    dfs = _stream(rg, [5, 4, 6, 5], seed0=60)
    ms_v, st_v = solve_batch(rg, dfs, method="leastcost_jax")
    ms_k, st_k = solve_batch(rg, dfs, method="leastcost_jax", use_kernel=True)
    assert st_v.kernel_impl == "" and st_k.kernel_impl == "ref"
    assert st_k.batch_size == len(dfs) and st_k.rounds > 0
    for a, b in zip(ms_v, ms_k):
        assert (a is None) == (b is None)
        if a is not None:
            assert a.assign == b.assign and a.cost == b.cost


def test_engine_solve_kernel_single_request():
    rg = waxman(14, seed=17)
    df = _stream(rg, [5], seed0=30)[0]
    m_v, _ = leastcost_jax(rg, df)
    m_k, st = leastcost_jax(rg, df, use_kernel=True)
    assert st.kernel_impl == "ref"
    assert (m_v is None) == (m_k is None)
    if m_v is not None:
        assert m_v.assign == m_k.assign and m_v.cost == m_k.cost


def test_online_placer_kernel_path():
    rg = waxman(16, seed=4)
    dfs = _stream(rg, [4, 5, 3, 5, 4, 6], seed0=20)
    plain = OnlinePlacer(rg)
    fused = OnlinePlacer(rg, use_kernel=True)
    t_p = plain.admit_many(dfs)
    t_f = fused.admit_many(dfs)
    fused.check_invariants()
    assert fused.solve_cfg.get("use_kernel") is True
    assert [t is None for t in t_p] == [t is None for t in t_f]
    for a, b in zip(t_p, t_f):
        if a is not None:
            assert a.mapping.cost == b.mapping.cost
    # churn re-mapping also runs through the kernel path
    used = [v for t in t_f if t for v in t.mapping.route]
    if used:
        fused.fail_node(max(set(used), key=used.count))
        fused.check_invariants()
