"""The particle kernels' required work, against hand counts."""
import numpy as np
import pytest

import work


def test_per_particle_counts_by_hand():
    # read z, x, ux, uy, uz, w and write z, x, ux, uy, uz: 11 float32
    assert work.BYTES_PER_PARTICLE == 44
    # splines 4 sets x 4 points x 8 ops, twice; gather 16 + 6*16*2;
    # Boris 60; move 15; deposit 16 + 5 + 3*16*2
    assert work.OPS_PER_PARTICLE == 256 + 208 + 60 + 15 + 117 == 656


def test_work_of_two_species_by_hand():
    box = 64
    electrons = np.array([10, 0, 5])
    ions = np.array([0, 0, 7])
    ops, bytes_ = work.particle_phase_work([electrons, ions], box)
    assert ops == 656 * 22
    tile = 4 * (6 * 68**2 + 3 * 70**2)  # fields over box+4, currents over box+6
    # electrons live in 2 boxes, ions in 1
    assert bytes_ == 44 * 22 + 3 * tile


def test_no_particles_no_work():
    assert work.particle_phase_work([np.zeros(9)], 64) == (0.0, 0.0)


def test_peaks_by_device_kind():
    p = work.peaks("TPU v5 lite")
    assert p["peak_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        work.peaks("TPU v9 imaginary")


def test_roofline_bound_is_memory_at_this_intensity():
    ops, bytes_ = work.particle_phase_work([np.full(9, 1000)], 64)
    t, bound = work.roofline_s(ops, bytes_, "TPU v5 lite")
    assert bound == "memory" and t == pytest.approx(bytes_ / 819e9)
    t, bound = work.roofline_s(1e15, 1.0, "TPU v5 lite")
    assert bound == "compute" and t == pytest.approx(1e15 / 197e12)
