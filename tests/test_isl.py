"""ISL link-budget tests: every quantitative claim of §2.1/§4.2 (Fig. 1)."""
import numpy as np
import pytest

from repro.core.isl import (DWDM_CHANNELS_75GHZ, DWDM_CHANNELS_100GHZ,
                            DWDM_RATE_PER_CHANNEL, PPB_OOK, PPB_PM16QAM,
                            PPB_SHANNON, ISLNetwork, OpticalTerminal,
                            required_pointing_accuracy_rad)


@pytest.fixture(scope="module")
def term():
    return OpticalTerminal()


class TestLinkBudget:
    def test_antenna_gain_105_db(self, term):
        assert term.antenna_gain_db == pytest.approx(105.1, abs=0.2)

    def test_beam_divergence_18_9_urad(self, term):
        assert term.beam_divergence_rad * 1e6 == pytest.approx(18.9, abs=0.1)

    def test_received_power_5000km_1_6uW(self, term):
        assert term.received_power_w(5e6) * 1e6 == pytest.approx(1.6, abs=0.1)

    def test_beam_spot_radius_95m_at_5000km(self, term):
        assert term.beam_spot_radius_m(5e6) >= 94.0

    def test_confocal_distances(self, term):
        """L = pi a^2/lambda: ~5 km (10 cm), 1.25 km (5 cm), 0.32 km (2.5 cm)."""
        assert term.confocal_distance_m(0.10) / 1e3 == pytest.approx(5.0, abs=0.1)
        assert term.confocal_distance_m(0.05) / 1e3 == pytest.approx(1.25, abs=0.05)
        assert term.confocal_distance_m(0.025) / 1e3 == pytest.approx(0.32, abs=0.01)

    def test_ppb_constants(self):
        assert PPB_OOK == 71.0 and PPB_PM16QAM == 196.0
        assert PPB_SHANNON == pytest.approx(1.386, abs=0.01)

    def test_dwdm_9_6_tbps(self, term):
        """24 x 400G on 100 GHz grid = 9.6 Tbps; 75 GHz grid -> 12.8 Tbps."""
        assert DWDM_CHANNELS_100GHZ * DWDM_RATE_PER_CHANNEL == 9.6e12
        assert DWDM_CHANNELS_75GHZ * DWDM_RATE_PER_CHANNEL == 12.8e12
        assert term.dwdm_rate_bps(1e3) == 9.6e12

    def test_dwdm_range_about_300km(self, term):
        assert 250e3 < term.max_dwdm_distance_m() < 350e3

    def test_dwdm_power_budget_0_24mW(self):
        from repro.core.isl.link_budget import DWDM_POWER_PER_CHANNEL
        assert 24 * DWDM_POWER_PER_CHANNEL == pytest.approx(0.24e-3)

    def test_pointing_accuracy_1urad(self):
        assert required_pointing_accuracy_rad() * 1e6 == pytest.approx(1.0, abs=0.05)

    def test_inverse_square_scaling(self, term):
        """Fig. 1 lines: far-field bandwidth ~ 1/d^2."""
        r1 = term.photon_limited_rate_bps(100e3, PPB_OOK)
        r2 = term.photon_limited_rate_bps(200e3, PPB_OOK)
        assert r1 / r2 == pytest.approx(4.0, rel=1e-6)

    def test_modulation_ordering(self, term):
        """Shannon > OOK > 16QAM in rate at equal power (PPB ordering)."""
        d = 50e3
        assert (term.photon_limited_rate_bps(d, PPB_SHANNON)
                > term.photon_limited_rate_bps(d, PPB_OOK)
                > term.photon_limited_rate_bps(d, PPB_PM16QAM))

    def test_spatial_mux_breakpoints(self, term):
        """2x2 at <=1.25 km, 4x4 at <=0.32 km (Fig. 1 left)."""
        assert term.spatial_mux_count(1.25e3) == 2
        assert term.spatial_mux_count(0.316e3) == 4
        assert term.spatial_mux_count(4e3) == 1

    def test_aggregate_bandwidth_scales_inverse_distance(self, term):
        """Total spatially-multiplexed bandwidth ~ 1/d (paper §4.2)."""
        bw_results = [term.aggregate_bandwidth_bps(d)
                      for d in (1.25e3, 316.0, 79.0)]
        assert bw_results[0] == pytest.approx(4 * 9.6e12)
        assert bw_results[1] == pytest.approx(16 * 9.6e12)
        assert bw_results[2] == pytest.approx(64 * 9.6e12)

    def test_aggregate_bandwidth_vectorized_matches_scalar(self, term):
        """The vectorized path (used for whole (N, N) matrices) must agree
        with per-distance evaluation, including the far-field tail."""
        ds = np.array([79.0, 316.0, 1.25e3, 4e3, 2e5, 1e6])
        vec = term.aggregate_bandwidth_bps(ds)
        assert vec.shape == ds.shape
        for d, v in zip(ds, vec):
            assert v == term.aggregate_bandwidth_bps(float(d))


class TestTopology:
    def test_formation_distances_support_full_stack(self, x64):
        """At the 100-200 m §2.2 formation distances every neighbor link
        carries >= the full 24-channel DWDM stack (>= 9.6 Tbps)."""
        from repro.core.orbital import ClusterDesign, hcw_state
        d = ClusterDesign()
        pos = np.asarray(hcw_state(d.alpha_beta(), d.n, 0.0)[..., :3])
        net = ISLNetwork()
        edges, caps = net.neighbor_graph(pos, k=8)
        assert caps.min() >= 9.6e12

    def test_bandwidth_matrix_symmetry(self):
        rng = np.random.default_rng(0)
        pos = rng.normal(scale=300.0, size=(12, 3))
        bw = ISLNetwork().bandwidth_matrix(pos)
        np.testing.assert_allclose(bw, bw.T)
        assert (np.diag(bw) == 0).all()

    def test_neighbor_graph_symmetrizes_asymmetric_knn(self):
        """Regression: kNN is asymmetric, and the old per-row `i < j`
        filter dropped link (i, j) whenever j was in i's k-nearest but not
        vice versa. On a sheared 3x3 lattice (100 m x, 200 m y — the HCW
        2:1 shape) with k=3 that silently loses three real terminals."""
        xs, ys = np.meshgrid(np.arange(3) * 100.0, np.arange(3) * 200.0,
                             indexing="ij")
        pos = np.stack([xs.ravel(), ys.ravel(), np.zeros(9)], axis=-1)
        net = ISLNetwork()
        d = net.distance_matrix(pos)
        k = 3
        edges, caps = net.neighbor_graph(pos, k=k)
        assert len(caps) == len(edges)
        assert (edges[:, 0] < edges[:, 1]).all()        # normalized
        eset = {tuple(e) for e in edges}
        # union property: every row's own k-nearest must be present
        for i in range(9):
            for j in np.argsort(d[i])[:k]:
                assert (min(i, int(j)), max(i, int(j))) in eset
        old = {(i, int(j)) for i in range(9)
               for j in np.argsort(d[i], kind="stable")[:k] if i < int(j)}
        assert old < eset                                # strictly more

    def test_neighbor_graph_9x9_retains_physical_neighbors(self, x64):
        """Acceptance: on the paper's 9x9 lattice every satellite keeps
        its direct formation links (the edges the pod fabric routes over)
        in the symmetrized k=8 graph."""
        from repro.core.orbital import ClusterDesign, hcw_state
        d = ClusterDesign()
        pos = np.asarray(hcw_state(d.alpha_beta(), d.n, 0.0)[..., :3])
        edges, _ = ISLNetwork().neighbor_graph(pos, k=8)
        eset = {tuple(e) for e in edges}
        for r in range(9):
            for c in range(9):
                i = r * 9 + c
                for rr, cc in ((r + 1, c), (r, c + 1)):
                    if rr < 9 and cc < 9:
                        j = rr * 9 + cc
                        assert (min(i, j), max(i, j)) in eset, (i, j)

    def test_pod_axis_conservative_is_worst_neighbor_link(self, x64):
        """Regression: the conservative pod-axis figure must be the worst
        routed (neighbor-graph) link, not the ~2.2 km corner-to-corner
        pair of the all-pairs matrix that nothing routes over."""
        from repro.core.isl import pod_axis_bandwidth_bytes
        from repro.core.orbital import ClusterDesign, hcw_state
        d = ClusterDesign()
        pos = np.asarray(hcw_state(d.alpha_beta(), d.n, 0.0)[..., :3])
        net = ISLNetwork()
        _, caps = net.neighbor_graph(pos, k=8)
        got = pod_axis_bandwidth_bytes(pos)
        assert got == caps.min() / 8.0
        bw = net.bandwidth_matrix(pos)
        all_pairs_worst = bw[np.isfinite(bw) & (bw > 0)].min() / 8.0
        assert got > all_pairs_worst
