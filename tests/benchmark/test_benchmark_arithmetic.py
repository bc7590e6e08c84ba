"""The benchmark's arithmetic against hand-worked cases: percentiles,
rates, the quartile spread, the kernel's least bytes and roofline share,
the table of peaks, and the plain reference against the program's own
host code."""

import numpy as np
import pytest

from benchmark import peaks, reference, roofline, stats


@pytest.mark.parametrize("values,q,want", [
    ([10.0], 95, 10.0),
    (list(range(1, 101)), 95, 95),
    (list(range(1, 101)), 50, 50),
    ([5, 1, 4, 2, 3], 95, 5),
    ([5, 1, 4, 2, 3], 40, 2),
    (list(range(1, 21)), 95, 19),
])
def test_percentile_is_nearest_rank_over_all_values(values, q, want):
    assert stats.percentile(values, q) == want


def test_percentile_of_nothing_is_nothing():
    assert stats.percentile([], 95) is None


def test_rate_is_all_the_work_over_all_the_time():
    assert stats.rate(4200.0, 30.0) == 140.0
    with pytest.raises(ValueError):
        stats.rate(1.0, 0.0)


def test_quartile_spread_matches_the_contracts_recipe():
    # statistics.quantiles([1..6], n=4) = [1.75, 3.5, 5.25]
    assert stats.quartile_spread([1, 2, 3, 4, 5, 6]) == pytest.approx(1.0)
    assert stats.quartile_spread([100, 100, 100, 100, 100, 100]) == 0.0


def test_series_summary():
    s = stats.series_summary([10.0, 10.0, 10.0])
    assert s == {"min": 10.0, "max": 10.0, "cv": 0.0}
    s = stats.series_summary([5.0, 15.0])
    assert s["cv"] == pytest.approx(0.5)


def test_ec_apply_least_bytes_and_roofline_share():
    # k=4 data rows in, r=2 parity rows out, 1 MiB lanes
    assert roofline.ec_apply_bytes(4, 2, 1 << 20) == 6 << 20
    # 819e9 B/s: 6 MiB take 7.68 us at the peak; measured 76.8 us = 10%
    least = (6 << 20) / 819e9
    assert roofline.roofline_share(6 << 20, least * 10, 819e9) == \
        pytest.approx(10.0)
    with pytest.raises(ValueError):
        roofline.roofline_share(1, 0.0, 819e9)


def test_peaks_table_knows_the_v5e_and_refuses_the_unknown():
    assert peaks.peaks_of("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks_of("TPU v9 imaginary")
    with pytest.raises(KeyError):
        peaks.peaks_of("cpu")


@pytest.mark.parametrize("k,m", [(4, 2), (2, 1), (8, 4)])
def test_reference_generator_and_apply_equal_the_programs_host_code(k, m):
    from ceph_tpu.ec import gf256
    gen = reference.generator(k, m)
    assert np.array_equal(gen, gf256.rs_vandermonde_matrix(k, m))
    rng = np.random.default_rng(k * 10 + m)
    chunks = rng.integers(0, 256, (k, 4096), dtype=np.uint8)
    assert np.array_equal(reference.apply(gen[k:], chunks),
                          gf256.host_apply(gen[k:], chunks))
    lost = [1]
    present = [i for i in range(k + m) if i not in lost][:k]
    assert np.array_equal(
        reference.decode_matrix(k, m, present, lost),
        gf256.decode_matrix(gen, present, lost))


def test_reference_field_is_0x11d():
    # 2 * 128 = 256 -> reduced by 0x11d = 0x1d
    assert reference.gf_mul(2, 128) == 0x1D
    assert reference.gf_mul(7, reference.gf_inv(7)) == 1
    t = reference.mul_table()
    assert np.array_equal(t, t.T) and not t[0].any()


def test_reference_shards_rebuild_a_lost_chunk():
    data = reference.payloads(5, 1, 4096)[0]
    sh = reference.shards(data, 4, 2)
    assert b"".join(s.tobytes() for s in sh[:4]) == data
    present = [0, 2, 3, 4]
    dec = reference.decode_matrix(4, 2, present, [1])
    got = reference.apply(dec, np.stack([sh[i] for i in present]))
    assert np.array_equal(got[0], sh[1])


def test_payloads_follow_the_seed_and_large_seeds_are_fine():
    a = reference.payloads(2_500_000_123, 4, 1024)
    b = reference.payloads(2_500_000_123, 4, 1024)
    c = reference.payloads(2_500_000_124, 4, 1024)
    assert a == b and a != c and len(set(a)) == 4
