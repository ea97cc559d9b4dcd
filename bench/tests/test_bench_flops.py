"""Algorithmic operation and byte counts against numbers worked by hand."""
import pytest

import _tiny  # noqa: F401
from bench.harness import flops, layers


def test_select_pass_flops_by_hand():
    # 32 real rows, d_in 512 -> 256 -> 256, N = 100,352, K = 96, k = 16:
    # projection 2*32*(131072 + 65536) = 12,582,912
    # similarity 2*32*100352*256       = 1,644,167,168
    # prototypes 2*32*96*256           = 1,572,864
    # vote       2*32*16               = 1,024
    got = flops.select_pass_flops(32, 512, 256, 2, 100_352, 96, 16)
    assert got == 12_582_912 + 1_644_167_168 + 1_572_864 + 1_024


def test_retrieve_counts_by_hand():
    # queries 32*256*4 = 32,768 B; corpus 100352*256*4 = 102,760,448 B;
    # out 32*16*(4+4) = 4,096 B
    assert flops.retrieve_bytes(32, 100_352, 256, 16) == 102_797_312
    assert flops.retrieve_flops(32, 100_352, 256) == 1_644_167_168


def test_roofline_names_its_bound():
    t, bound = flops.roofline_s(1_644_167_168, 102_797_312, 197e12, 819e9)
    assert bound == "memory"
    assert t == pytest.approx(102_797_312 / 819e9)
    t, bound = flops.roofline_s(1e15, 1.0, 197e12, 819e9)
    assert bound == "compute" and t == pytest.approx(1e15 / 197e12)


@pytest.mark.parametrize("rows,bucket", [(1, 8), (8, 8), (9, 16),
                                         (16, 16), (17, 32), (32, 32)])
def test_bucket_of_matches_admission_buckets(rows, bucket):
    from repro.core.rps import bucket_batch

    assert layers.bucket_of(rows) == bucket == bucket_batch(rows)
