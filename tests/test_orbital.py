"""Orbital dynamics tests: integrator accuracy, HCW, cluster (paper §2.2)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.orbital import (ClusterDesign, hcw_propagate, hcw_state,
                                integrate, integrate_dense, make_rhs,
                                mean_motion, neighbor_distances,
                                simulate_cluster, specific_energy,
                                sun_sync_inclination)
from repro.core.orbital import constants as C
from repro.core.orbital.frames import eci_to_hill, hill_to_eci

pytestmark = pytest.mark.usefixtures("x64")


def _circular_state(a):
    v = (C.MU_EARTH / a) ** 0.5
    return jnp.array([a, 0.0, 0.0, 0.0, v, 0.0])


class TestIntegrators:
    def test_energy_conservation_one_orbit(self):
        a = C.R_EARTH + C.CLUSTER_ALTITUDE
        y0 = _circular_state(a)
        T = 2 * np.pi / mean_motion(a)
        yf = integrate(make_rhs(j2=False), y0, 0.0, 5.0, int(T / 5.0))
        e0, ef = specific_energy(y0), specific_energy(yf)
        assert abs(float((ef - e0) / e0)) < 1e-12

    def test_circular_orbit_cm_accuracy(self):
        """Paper §4.1: cm accuracy vs 1e7 m orbit scale in binary64."""
        a = C.R_EARTH + C.CLUSTER_ALTITUDE
        y0 = _circular_state(a)
        T = 2 * np.pi / mean_motion(a)
        n_steps = 2048
        yf = integrate(make_rhs(j2=False), y0, 0.0, T / n_steps, n_steps)
        # after exactly one period the orbit must close to << 1 cm
        assert float(jnp.linalg.norm(yf[:3] - y0[:3])) < 1e-2
        # radius stays constant along the whole circular orbit
        _, traj = integrate_dense(make_rhs(j2=False), y0, 0.0, T / n_steps,
                                  n_steps, stride=64)
        r = jnp.linalg.norm(traj[:, :3], axis=-1)
        assert float(jnp.max(jnp.abs(r - a))) < 1e-2

    @pytest.mark.parametrize("method,order", [("rk4", 4), ("dopri5", 5)])
    def test_convergence_order(self, method, order):
        """Step-halving error ratio ~ 2^order validates the RK tableaux."""
        a = C.R_EARTH + 400e3
        # eccentric orbit exercises the tableau harder than a circular one
        y0 = jnp.array([a, 0.0, 0.0, 0.0, 1.05 * (C.MU_EARTH / a) ** 0.5, 0.0])
        T = 2000.0
        f = make_rhs(j2=False)
        ref = integrate(f, y0, 0.0, T / 4096, 4096, method="dopri5")
        errs = []
        for n in (64, 128):
            yf = integrate(f, y0, 0.0, T / n, n, method=method)
            errs.append(float(jnp.linalg.norm(yf[:3] - ref[:3])))
        rate = np.log2(errs[0] / errs[1])
        assert rate > order - 0.7, f"{method}: observed order {rate:.2f}"

    def test_j2_nodal_precession_rate(self):
        """J2 must precess the sun-sync orbit node by ~0.9856 deg/day."""
        a = C.R_EARTH + C.CLUSTER_ALTITUDE
        inc = sun_sync_inclination(a)
        v = (C.MU_EARTH / a) ** 0.5
        y0 = jnp.array([a, 0.0, 0.0,
                        0.0, v * np.cos(inc), v * np.sin(inc)])
        T = 2 * np.pi / mean_motion(a)
        n_orbits = 20
        yf = integrate(make_rhs(j2=True), y0, 0.0, 5.0,
                       int(n_orbits * T / 5.0))
        # node direction = z x h
        def node(y):
            h = jnp.cross(y[:3], y[3:])
            nvec = jnp.cross(jnp.array([0.0, 0.0, 1.0]), h)
            return jnp.arctan2(nvec[1], nvec[0])
        dnode = float(node(yf) - node(y0))
        elapsed = int(n_orbits * T / 5.0) * 5.0
        rate = dnode / elapsed
        assert rate == pytest.approx(C.OMEGA_SUN_SYNC, rel=0.05)


class TestHCW:
    def test_hcw_propagate_matches_family(self):
        n = mean_motion(C.R_EARTH + C.CLUSTER_ALTITUDE)
        ab = jnp.array([[120.0, -80.0]])
        s0 = hcw_state(ab, n, 0.0)
        for t in (300.0, 1500.0, 4000.0):
            pred = hcw_propagate(s0, n, t)
            exact = hcw_state(ab, n, t)
            np.testing.assert_allclose(np.asarray(pred), np.asarray(exact),
                                       atol=1e-6)

    def test_nonlinear_matches_hcw_small_offsets(self):
        """Full two-body propagation ~ HCW for small separations."""
        d = ClusterDesign(sun_synchronous=False, kappa=1.0)
        ref = d.reference_state()
        ab = jnp.array([[50.0, 30.0]])
        rel0 = hcw_state(ab, d.n, 0.0)
        y0 = hill_to_eci(ref, rel0)[0]
        t = 0.3 * d.period
        yref = integrate(make_rhs(j2=False), ref, 0.0, 2.0,
                         int(t / 2.0))
        y = integrate(make_rhs(j2=False), y0, 0.0, 2.0, int(t / 2.0))
        hill = eci_to_hill(yref, y)
        exact_t = int(t / 2.0) * 2.0
        pred = hcw_state(ab, d.n, exact_t)[0]
        # linearization error ~ (sep/a)*sep ~ mm-cm scale
        assert float(jnp.linalg.norm(hill[:3] - pred[:3])) < 0.05

    def test_frame_roundtrip(self):
        d = ClusterDesign()
        ref = d.reference_state()
        rel = hcw_state(d.alpha_beta(), d.n, 0.0)
        back = eci_to_hill(ref, hill_to_eci(ref, rel))
        np.testing.assert_allclose(np.asarray(back), np.asarray(rel),
                                   atol=1e-8)


class TestCluster:
    """Reproduces the quantitative claims of §2.2 / Figs. 2-3."""

    @pytest.fixture(scope="class")
    def sim(self):
        d = ClusterDesign()
        ts, hill, reli = simulate_cluster(d, n_orbits=1.0, dt=5.0)
        return d, ts, hill, reli

    def test_81_satellites(self, sim):
        d, ts, hill, _ = sim
        assert d.n_sats == 81 and hill.shape[1] == 81

    def test_neighbor_distance_oscillation_100_200m(self, sim):
        """Fig. 3: direct-neighbor distances oscillate ~100-200 m."""
        _, _, hill, _ = sim
        direct, diag = neighbor_distances(hill)
        assert 90.0 < float(direct.min()) < 110.0
        assert 190.0 < float(direct.max()) < 215.0
        # diagonal neighbors: s*sqrt(2) .. s*sqrt(8)
        assert 130.0 < float(diag.min()) < 150.0
        assert 270.0 < float(diag.max()) < 295.0

    def test_bounding_ellipse_2_to_1(self, sim):
        """§2.2: cluster fits a rotating +-R prograde, +-R/2 altitude ellipse."""
        _, _, hill, _ = sim
        ymax = float(jnp.abs(hill[..., 1]).max())
        xmax = float(jnp.abs(hill[..., 0]).max())
        assert ymax / xmax == pytest.approx(2.0, rel=0.05)
        # satellites stay bounded within ~R of the center
        r = float(jnp.linalg.norm(hill[..., :3], axis=-1).max())
        assert r < 1.25 * ymax

    def test_two_shape_cycles_per_orbit(self, sim):
        """§2.2: cluster shape reproduces itself twice per orbit."""
        d, ts, hill, _ = sim
        pos = hill[..., :3]
        # pairwise-distance signature of the shape at t=0, T/2, T
        idx = jnp.array([0, 1, 9, 10, 40, 44, 80])
        def sig(p):
            sub = p[idx]
            return jnp.linalg.norm(sub[:, None] - sub[None], axis=-1)
        s0 = sig(pos[0])
        half = len(ts) // 2
        mid = sig(pos[half])
        quarter = sig(pos[len(ts) // 4])
        # shape at T/2 matches t=0 to within J2/nonlinear perturbation scale
        assert float(jnp.max(jnp.abs(mid - s0))) < 0.05 * float(jnp.max(s0))
        # ... while at T/4 it is substantially different
        assert float(jnp.max(jnp.abs(quarter - s0))) > 0.2 * float(jnp.max(s0))

    def test_planar_cluster_stays_planar(self, sim):
        _, _, hill, _ = sim
        assert float(jnp.abs(hill[..., 2]).max()) < 2.0  # meters of cross-track

    def test_keplerian_cluster_closes_after_one_orbit(self):
        """§2.2: in pure Keplerian free fall the constellation reproduces
        itself perfectly after a full orbit, at zero delta-v."""
        d = ClusterDesign(sun_synchronous=False)
        ts, hill, _ = simulate_cluster(d, n_orbits=1.0, dt=2.0, j2=False)
        drift = jnp.linalg.norm(hill[-1, :, :3] - hill[0, :, :3], axis=-1)
        # linearized HCW init leaves an O(A^2/a) period mismatch ~ 1 m/orbit
        assert float(drift.max()) < 2.0

    def test_energy_matched_init_closes_to_mm(self):
        """Beyond-paper: semi-major-axis-matched init closes ~1000x tighter."""
        d = ClusterDesign(sun_synchronous=False, energy_matched=True)
        ts, hill, _ = simulate_cluster(d, n_orbits=1.0, dt=2.0, j2=False)
        drift = jnp.linalg.norm(hill[-1, :, :3] - hill[0, :, :3], axis=-1)
        assert float(drift.max()) < 5e-3


class TestJ2Drift:
    def test_axis_ratio_tuning_reduces_drift(self):
        """§2.2: a per-mille axis-ratio adjustment suppresses J2 drift."""
        from repro.core.orbital import j2_drift_rate
        base = j2_drift_rate(ClusterDesign(kappa=1.0), n_orbits=6.0)
        tuned = j2_drift_rate(ClusterDesign(kappa=0.999), n_orbits=6.0)
        assert tuned < 0.5 * base
        assert tuned < 5.0  # m/s/year per km — paper reports < 3 for its conv.
