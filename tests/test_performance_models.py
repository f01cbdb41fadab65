"""Performance-plane tests: demand tables, bottleneck identification, MVA /
fluid / DES agreement, and reproduction of the paper's headline claims."""
import numpy as np
import pytest

from repro.core import (
    ablation_steps,
    calibrate_alpha,
    compartmentalized_model,
    craq_model,
    des_throughput,
    fluid_throughput,
    mixed_workload_speedup,
    multipaxos_model,
    mva_curve,
    mva_curves_batch,
    read_scalability_law,
    unreplicated_model,
)
from repro.core.analytical import (
    PAPER_COMPARTMENTALIZED_UNBATCHED,
    PAPER_MULTIPAXOS_UNBATCHED,
)


def test_multipaxos_leader_is_bottleneck():
    name, _ = multipaxos_model(f=1).bottleneck()
    assert name == "leader"


def test_compartmentalized_write_bottleneck_is_leader():
    """Paper section 8.1: even fully compartmentalized, the (sequencing)
    leader remains the write-path bottleneck."""
    m = compartmentalized_model(f=1, n_proxy_leaders=10, grid_rows=2,
                                grid_cols=2, n_replicas=4)
    name, _ = m.bottleneck(f_write=1.0)
    assert name == "leader"


def test_decoupling_alone_shifts_bottleneck_to_proxies():
    """Paper Fig. 29a: right after decoupling (2 proxies over the 2f+1
    majority-quorum acceptors), proxies bottleneck."""
    m = compartmentalized_model(f=1, n_proxy_leaders=2, grid_rows=3,
                                grid_cols=1, n_replicas=2, quorums="majority")
    name, d = m.bottleneck()
    assert name == "proxy"
    assert d == (1 + 2 + 2 + 2) / 2


def test_write_only_speedup_matches_paper_band():
    """Headline claim: ~6x on write-only workloads.  The structural model
    (message counts only, one calibration anchor) must land in [3.5x, 8x]."""
    alpha = calibrate_alpha(PAPER_MULTIPAXOS_UNBATCHED)
    mp = multipaxos_model(f=1).peak_throughput(alpha)
    cm = compartmentalized_model(f=1, n_proxy_leaders=10, grid_rows=2,
                                 grid_cols=2, n_replicas=4).peak_throughput(alpha)
    assert mp == pytest.approx(PAPER_MULTIPAXOS_UNBATCHED, rel=1e-6)
    speedup = cm / mp
    assert 3.5 <= speedup <= 8.0, f"speedup {speedup:.2f} out of band"


def test_mixed_workload_speedup_exceeds_write_only():
    """Headline claim: 16x on a 90% read workload - reads bypass both the
    leader and all-replica execution, so the mixed speedup must dominate the
    write-only speedup."""
    alpha = calibrate_alpha(PAPER_MULTIPAXOS_UNBATCHED)
    _, _, s_write = mixed_workload_speedup(f_write=1.0, alpha=alpha)
    _, _, s_mixed = mixed_workload_speedup(f_write=0.1, alpha=alpha)
    assert s_mixed > 2.0 * s_write
    assert s_mixed >= 10.0


def test_ablation_staircase_is_monotone():
    """Fig. 29a: each compartmentalization step must not reduce throughput."""
    alpha = calibrate_alpha(PAPER_MULTIPAXOS_UNBATCHED)
    peaks = [m.peak_throughput(alpha) for _, m in ablation_steps()]
    assert all(b >= a * 0.999 for a, b in zip(peaks, peaks[1:])), peaks
    assert peaks[-1] / peaks[0] >= 3.5


def test_batching_multiplies_throughput():
    alpha = calibrate_alpha(PAPER_MULTIPAXOS_UNBATCHED)
    unbatched = compartmentalized_model(f=1, n_proxy_leaders=10, grid_rows=2,
                                        grid_cols=2, n_replicas=4)
    batched = compartmentalized_model(f=1, n_proxy_leaders=3, grid_rows=2,
                                      grid_cols=2, n_replicas=2, batch_size=100,
                                      n_batchers=2, n_unbatchers=3)
    assert (batched.peak_throughput(alpha)
            > 3.0 * unbatched.peak_throughput(alpha))


def test_read_scalability_law_limits():
    """Paper section 8.3: T -> alpha/f_w as n -> inf; linear for 100% reads."""
    alpha = 100_000.0
    assert read_scalability_law(6, 0.0, alpha) == pytest.approx(6 * alpha)
    t_inf = read_scalability_law(10_000, 0.5, alpha)
    assert t_inf == pytest.approx(alpha / 0.5, rel=0.01)
    # 1% -> 2% writes halves peak throughput (the paper's counterintuitive
    # observation), in the large-n limit
    t1 = read_scalability_law(100_000, 0.01, alpha)
    t2 = read_scalability_law(100_000, 0.02, alpha)
    assert t1 / t2 == pytest.approx(2.0, rel=0.05)


def test_mva_saturates_at_bottleneck():
    model = compartmentalized_model(f=1, n_proxy_leaders=10, grid_rows=2,
                                    grid_cols=2, n_replicas=4)
    alpha = calibrate_alpha(PAPER_MULTIPAXOS_UNBATCHED)
    clients, x, r = mva_curve(model, alpha, n_clients_max=400)
    peak_bound = model.peak_throughput(alpha)
    assert x[-1] <= peak_bound * 1.001
    assert x[-1] >= peak_bound * 0.95       # within 5% of the bound
    assert np.all(np.diff(x) >= -1e-4 * x[:-1])  # monotone (f32 tolerance)
    # latency flat at low load, rising near saturation
    assert r[-1] > r[0] * 2


def test_mva_batch_matches_single():
    models = [multipaxos_model(), compartmentalized_model()]
    alpha = calibrate_alpha(PAPER_MULTIPAXOS_UNBATCHED)
    _, xs, _ = mva_curves_batch(models, alpha, n_clients_max=64)
    for i, m in enumerate(models):
        _, x_single, _ = mva_curve(m, alpha, n_clients_max=64)
        np.testing.assert_allclose(xs[i], x_single, rtol=1e-6)


def test_fluid_agrees_with_mva():
    model = compartmentalized_model(f=1, n_proxy_leaders=10, grid_rows=2,
                                    grid_cols=2, n_replicas=4)
    alpha = calibrate_alpha(PAPER_MULTIPAXOS_UNBATCHED)
    _, x_mva, _ = mva_curve(model, alpha, n_clients_max=256)
    x_fluid = fluid_throughput(model, alpha, n_clients=256, sim_time=0.05)
    assert x_fluid == pytest.approx(float(x_mva[-1]), rel=0.15)


def test_des_agrees_with_mva_at_saturation():
    model = multipaxos_model(f=1)
    alpha = calibrate_alpha(PAPER_MULTIPAXOS_UNBATCHED)
    x_des, lat = des_throughput(model, alpha, n_clients=64, n_commands=5_000)
    _, x_mva, _ = mva_curve(model, alpha, n_clients_max=64)
    assert x_des == pytest.approx(float(x_mva[-1]), rel=0.1)
    assert lat > 0


def test_craq_skew_degrades_throughput():
    """Fig. 33: CRAQ throughput falls as skew rises; ~3x drop at p=1."""
    alpha = calibrate_alpha(PAPER_MULTIPAXOS_UNBATCHED)
    t_uniform = craq_model(n_nodes=6, skew_p=0.0, f_write=0.05, alpha=alpha)
    t_skewed = craq_model(n_nodes=6, skew_p=1.0, f_write=0.05, alpha=alpha)
    assert t_skewed < t_uniform
    assert t_uniform / t_skewed >= 1.5


def test_compartmentalized_is_skew_insensitive():
    """Compartmentalized MultiPaxos ignores keys entirely: same model for
    any skew, so throughput is flat by construction - assert the model has
    no key-dependent inputs by comparing two mixes."""
    m = compartmentalized_model(f=1, n_proxy_leaders=10, grid_rows=2,
                                grid_cols=2, n_replicas=6)
    alpha = calibrate_alpha(PAPER_MULTIPAXOS_UNBATCHED)
    assert (m.peak_throughput(alpha, f_write=0.05)
            == m.peak_throughput(alpha, f_write=0.05))
