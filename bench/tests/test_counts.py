"""The algorithm's operations and bytes, against values worked by hand."""
import pytest

from bench import counts, harness

MNIST = harness.load_json(harness.BENCH / "configs" / "mnist-stored.json")
WEBSPAM = harness.load_json(
    harness.BENCH / "configs" / "webspam-regen-packed.json")
PEAK = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_mnist_step_counts():
    # one 512-row step at 149 nonzeros a row: 512 * 149 * (10 * 1024 + 1)
    assert counts.cws_ops(512, 149, 1024) == 512 * 149 * 10241 == 781265408
    # rows 512*784*4 = 1,605,632; params 3*784*1024*4 = 9,633,792;
    # int32 indices 512*1024*4 = 2,097,152
    assert counts.param_bytes(MNIST) == 9633792
    assert counts.out_bytes_per_row(MNIST) == 4096
    assert counts.cws_bytes(MNIST, 512, 1) == 1605632 + 9633792 + 2097152
    # head forward and backward: 2 * 1024 * 10 per row
    assert counts.head_ops(512, 1024, 10, backward=True) == 512 * 20480


def test_webspam_pass_counts():
    # regenerated parameters read nothing; b = 8 packs 4 codes a word:
    # 256 words = 1,024 bytes a row; rows 254 * 4 = 1,016 bytes
    assert counts.param_bytes(WEBSPAM) == 0
    assert counts.out_bytes_per_row(WEBSPAM) == 1024
    assert counts.cws_bytes(WEBSPAM, 350000, 43) == 350000 * (1016 + 1024)
    assert counts.cws_ops(350000, 84, 1024) == 350000 * 84 * 10241


@pytest.mark.parametrize("ops,nbytes,seconds,share,bound", [
    (197e12, 1.0, 2.0, 50.0, "ops"),          # 1 s of ops in 2 s
    (1.0, 819e9, 4.0, 25.0, "bytes"),         # 1 s of bytes in 4 s
    (781265408, 13336576, 1e-3, 1.6283975579975578, "bytes"),
])
def test_roofline(ops, nbytes, seconds, share, bound):
    got, which = counts.roofline(ops, nbytes, seconds, PEAK)
    assert which == bound
    assert got == pytest.approx(share, rel=1e-12)


def test_peaks_table_names_its_source_and_refuses_unknown_devices():
    table = harness.load_json(harness.BENCH / "peaks.json")
    assert "TPU v5e" in table["source"]
    assert harness.peak_for("TPU v5 lite")["flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        harness.peak_for("TPU v9 imaginary")
