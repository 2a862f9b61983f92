"""The table of peaks.  The counts from shapes are a family's, and are
tested against counts made by hand beside it (test_perfbench_family_*)."""

import pytest

from perfbench import opsbytes


def test_peaks_are_the_published_ones_and_an_unknown_kind_is_an_error():
    p = opsbytes.peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    for kind in ("cpu", "TPU v9", "source"):
        with pytest.raises(KeyError):
            opsbytes.peaks(kind)
