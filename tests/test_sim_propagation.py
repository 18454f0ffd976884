"""Tests for the vectorized SAGIN propagation engine: geometry equivalence
with the seed implementation, multi-region batching, interval extraction."""
import numpy as np
import pytest

from repro.core.constellation import (WalkerStar, access_intervals,
                                      elevation_angles, target_eci)
from repro.sim.propagation import (Region, access_intervals_loop,
                                   access_intervals_multi,
                                   access_intervals_vec,
                                   coverage_dot_threshold,
                                   intervals_from_visibility,
                                   positions_eci_batch, resolve_backend,
                                   sin_elevations, targets_eci_batch,
                                   visibility)

REGIONS = [Region("indiana", 40.0, -86.0), Region("nairobi", -1.3, 36.8),
           Region("sydney", -33.9, 151.2)]


def assert_same_intervals(a, b):
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert x.sat == y.sat
        assert x.start == y.start
        assert x.end == y.end


def test_positions_match_seed_walker_star():
    ws = WalkerStar()
    t = np.linspace(0.0, 2 * 3600.0, 93)
    np.testing.assert_allclose(positions_eci_batch(ws, t),
                               ws.positions_eci(t), rtol=1e-12, atol=1e-5)


def test_targets_match_seed_target_eci():
    t = np.linspace(0.0, 6 * 3600.0, 201)
    batch = targets_eci_batch(REGIONS, t)
    for i, r in enumerate(REGIONS):
        np.testing.assert_allclose(batch[i],
                                   target_eci(r.lat_deg, r.lon_deg, t),
                                   rtol=1e-12, atol=1e-6)


def test_sin_elevations_match_seed_elevation_angles():
    ws = WalkerStar(n_sats=20, n_planes=4)
    t = np.linspace(0.0, 3600.0, 121)
    got = sin_elevations(ws, REGIONS, t)
    for i, r in enumerate(REGIONS):
        ref = np.sin(elevation_angles(ws, r.lat_deg, r.lon_deg, t))
        np.testing.assert_allclose(got[i], ref, rtol=1e-9, atol=1e-12)


def test_dot_threshold_equals_elevation_mask():
    """The central-angle threshold must reproduce sine-space thresholding."""
    ws = WalkerStar()
    t = np.arange(0.0, 2 * 3600.0, 10.0)
    sin_el = sin_elevations(ws, REGIONS, t)
    ref = sin_el >= np.sin(np.deg2rad(15.0))
    got = visibility(ws, REGIONS, t, backend="numpy")
    np.testing.assert_array_equal(got, ref)


def test_vectorized_intervals_equal_seed_loop():
    ws = WalkerStar()
    ref = access_intervals_loop(ws, 40.0, -86.0, t_end=4 * 3600.0)
    got = access_intervals_vec(ws, 40.0, -86.0, t_end=4 * 3600.0)
    assert len(ref) > 0
    assert_same_intervals(ref, got)


def test_core_access_intervals_delegates_to_vectorized():
    ws = WalkerStar()
    a = access_intervals(ws, t_end=2 * 3600.0)
    b = access_intervals_vec(ws, t_end=2 * 3600.0)
    assert_same_intervals(a, b)


def test_multi_region_shares_one_propagation():
    """Batched multi-region output equals independent per-region passes."""
    ws = WalkerStar(n_sats=40, n_planes=5)
    multi = access_intervals_multi(ws, REGIONS, t_end=2 * 3600.0)
    assert set(multi) == {r.name for r in REGIONS}
    for r in REGIONS:
        ref = access_intervals_loop(ws, r.lat_deg, r.lon_deg,
                                    t_end=2 * 3600.0)
        assert_same_intervals(ref, multi[r.name])


def test_mega_constellation_shape():
    ws = WalkerStar(n_sats=1080, n_planes=27, altitude=550e3,
                    inclination_deg=53.0)
    t = np.arange(0.0, 1800.0, 30.0)
    vis = visibility(ws, REGIONS, t)
    assert vis.shape == (len(REGIONS), len(t), 1080)
    # a 1080-sat shell must cover mid-latitude regions essentially always
    assert vis[0].any(axis=1).mean() > 0.95


def test_per_region_min_elevation():
    ws = WalkerStar()
    strict = Region("strict", 40.0, -86.0, min_elevation_deg=40.0)
    loose = Region("loose", 40.0, -86.0, min_elevation_deg=5.0)
    t = np.arange(0.0, 6 * 3600.0, 10.0)
    vis = visibility(ws, [strict, loose], t)
    assert vis[0].sum() < vis[1].sum()
    assert coverage_dot_threshold(ws, 40.0) > coverage_dot_threshold(ws, 5.0)


def test_intervals_from_visibility_edge_windows():
    """Windows open at t=0 and still open at the horizon match seed
    conventions (end clamped to the last sample)."""
    t = np.arange(0.0, 50.0, 10.0)
    v = np.zeros((5, 2), dtype=bool)
    v[:2, 0] = True      # open at t=0, closes at sample 2
    v[3:, 1] = True      # opens at sample 3, still open at horizon
    ivs = intervals_from_visibility(v, t)
    assert [(iv.sat, iv.start, iv.end) for iv in ivs] == [
        (0, 0.0, 20.0), (1, 30.0, 40.0)]


def test_backend_resolution():
    assert resolve_backend("numpy") is np
    import jax.numpy as jnp
    assert resolve_backend("jax") is jnp
    with pytest.raises(ValueError):
        resolve_backend("tensorflow")


def test_jax_backend_without_x64_raises():
    """Interval boundaries are precision-critical: the jax backend must
    refuse to run in float32 instead of silently shifting windows."""
    import jax
    if jax.config.jax_enable_x64:
        pytest.skip("x64 globally enabled; the guard cannot trip")
    ws = WalkerStar(n_sats=20, n_planes=4)
    with pytest.raises(ValueError, match="x64"):
        access_intervals_multi(ws, REGIONS, t_end=3600.0, backend="jax")


def test_jax_backend_with_x64_matches_numpy_exactly():
    import jax
    ws = WalkerStar(n_sats=20, n_planes=4)
    a = access_intervals_multi(ws, REGIONS, t_end=3600.0, backend="numpy")
    with jax.enable_x64(True):
        b = access_intervals_multi(ws, REGIONS, t_end=3600.0, backend="jax")
    for r in REGIONS:
        assert_same_intervals(a[r.name], b[r.name])


def test_intervals_from_visibility_empty_mask_short_circuits():
    t = np.arange(0.0, 100.0, 10.0)
    assert intervals_from_visibility(np.zeros((len(t), 7), bool), t) == []


def test_basis_caches_are_shared_and_read_only():
    """constellation/region bases (and the contracted gram) are memoized
    per frozen constellation/region tuple and marked immutable."""
    from repro.sim.propagation import constellation_basis, region_basis
    ws = WalkerStar(n_sats=20, n_planes=4)
    b1 = constellation_basis(ws)
    b2 = constellation_basis(WalkerStar(n_sats=20, n_planes=4))
    assert b1 is b2                       # equal frozen configs, one entry
    assert not b1.flags.writeable
    with pytest.raises(ValueError):
        b1[0, 0, 0] = 1.0
    r1 = region_basis(REGIONS)
    assert r1 is region_basis(tuple(REGIONS))
    assert not r1.flags.writeable
    # cached basis still reproduces the seed geometry
    t = np.linspace(0.0, 3600.0, 37)
    np.testing.assert_allclose(positions_eci_batch(ws, t),
                               ws.positions_eci(t), rtol=1e-12, atol=1e-5)
