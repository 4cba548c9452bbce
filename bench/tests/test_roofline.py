"""The roofline work functions on hand-worked shapes, and the peaks."""
import pytest

from bench import roofline

HIST = ('%histogram.9 = f32[1,4096]{1,0:T(1,128)} custom-call('
        's32[65536,1]{0,1:T(2,128)} %copy.22), '
        'custom_call_target="tpu_custom_call"')
RANK = ('%gf2_rank.3 = s32[1,8192]{1,0} custom-call(s32[32,8192]{1,0} '
        '%transpose.1), custom_call_target="tpu_custom_call"')


def test_histogram_bytes_read_indices_and_write_counts():
    assert roofline.histogram_bytes(n=65536, k=4096) == 4 * 65536 + 4 * 4096
    assert roofline.kernel_bytes("histogram", HIST) == 278528


def test_gf2_rank_bytes_read_matrices_and_write_ranks():
    assert roofline.gf2_rank_bytes(8192) == 8192 * 132
    assert roofline.kernel_bytes("gf2_rank", RANK) == 8192 * 132


def test_share_is_least_time_over_kernel_time():
    # 278528 bytes at 819 GB/s take 340.08 ns; the call took 1000 ns
    ev = [("histogram.9", 0, 1000, HIST), ("fusion.1", 0, 5000, "")]
    share = roofline.roofline_share("histogram", ev, 819e9)
    assert share == pytest.approx(100 * 278528 / 819e9 / 1e-6)
    two = ev + [("histogram.12", 2000, 4000, HIST)]
    assert roofline.roofline_share("histogram", two, 819e9) == \
        pytest.approx(100 * 2 * 278528 / 819e9 / 3e-6)


def test_no_call_reads_nothing():
    assert roofline.roofline_share("gf2_rank", [("histogram.9", 0, 10, HIST)],
                                   819e9) is None


def test_unknown_device_is_an_error():
    assert roofline.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peaks_for("cpu")
