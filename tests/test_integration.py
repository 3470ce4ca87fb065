"""Cross-subsystem integration: FP-noise robustness of classification
through the simulator and the metrics summary."""
import pytest

from repro.sim.cluster_sim import SimConfig
from repro.sim.experiment import run_one


def test_classification_robust_to_fp_noise():
    """10% measurement noise on FP must not flip benchmark classes whose
    FP is far from td=2 (the paper's memoized-average premise)."""
    res = run_one("joss-t", "small", n_jobs=30, seed=3,
                  config=SimConfig(fp_noise=0.1))
    res_clean = run_one("joss-t", "small", n_jobs=30, seed=3)
    # Permu (FP=3) stays RH -> reduce-locality stays 1.0
    from repro.sim.metrics import summarize
    s_noisy = summarize(res)
    s_clean = summarize(res_clean)
    assert s_noisy.reduce_locality["Permu"] == pytest.approx(1.0)
    assert abs(s_noisy.int_mb - s_clean.int_mb) / s_clean.int_mb < 0.1
