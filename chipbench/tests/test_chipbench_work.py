"""The required-work counts and the peaks table."""
import pytest

from chipbench import peaks, work


def test_admm_iteration_hand_count():
    # N=2 agents, T=3 samples, D=4 features, ring (2 neighbours)
    flops, nbytes = work.admm_iteration(2, 3, 4)
    assert flops == 4 * 2 * 3 * 4                    # phi theta + phi^T r
    # phi once (24 floats), labels (6), 6 rows of 4 per agent (48)
    assert nbytes == 4 * (24 + 6 + 48)


def test_megastep_call_is_one_iteration():
    assert work.megastep_call(16, 2048, 16384) == work.admm_iteration(
        16, 2048, 16384)


def test_big_d_iteration_needs_2_6_ms_of_hbm():
    flops, nbytes = work.admm_iteration(16, 2048, 16384)
    p = peaks.peaks_for("TPU v5 lite")
    assert peaks.roofline_s(flops, nbytes, p) == pytest.approx(
        nbytes / 819e9)
    assert 2.6e-3 < nbytes / 819e9 < 2.7e-3


def test_v5e_peaks():
    p = peaks.peaks_for("TPU v5 lite")
    assert (p.flops_bf16, p.hbm_bytes_s, p.hbm_bytes) == (197e12, 819e9,
                                                          16e9)
    assert "TPU v5e" in p.source


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("TPU v99")


@pytest.mark.parametrize("chips", [1, 4])
def test_fit_step_mfu_counts_every_chip(chips):
    """The whole step's share is of the peaks of all the cell's chips: the
    same work in the same time on four chips reads a quarter."""
    import types

    from chipbench import spec
    read = spec.load_reader("fit_step_mfu")
    work_ = work.admm_iteration(32, 2048, 65536)
    p = peaks.peaks_for("TPU v5 lite")
    run = types.SimpleNamespace(
        fit={"iterations": 100, "work": work_}, trace={}, window_s=2.0,
        peaks=p, cell=types.SimpleNamespace(chips=chips))
    need = work_[1] / 819e9                      # bandwidth-bound
    assert read(run) == pytest.approx(100.0 * need / 0.02 / chips)
