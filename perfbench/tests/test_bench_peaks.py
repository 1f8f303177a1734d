"""The peaks table and the refusal to run without the chip."""
import pytest

from perfbench import peaks

pytestmark = pytest.mark.tier1


def test_v5e_peaks_and_their_source():
    p = peaks.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in p["source"]


def test_an_unknown_device_is_an_error():
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks("TPU v9 imaginary")
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks("cpu")


def test_a_run_without_a_tpu_prints_no_result(capsys):
    from perfbench import run
    rc = run.main(["--workload", "resnet50.b256.1chip", "--seed", "1",
                   "--seconds", "1"])
    assert rc == run.NO_CHIP
    assert capsys.readouterr().out == ""
