"""Pallas kernel tests, in interpret mode. The interpreter multiplies in f32
where the MXU rounds to bf16 at default precision, so it cannot vouch for the
compiled kernels: dev/probe_pallas.py runs them compiled on the chip against
numpy at 6M rows."""

import numpy as np
import pytest

from ballista_tpu.ops.pallas_kernels import grouped_aggregate, pallas_available


pytestmark = pytest.mark.skipif(
    not pallas_available(), reason="pallas not importable"
)


def _ref(codes, vals, mask, G):
    ref = np.zeros((G, vals.shape[1]), dtype=np.float64)
    np.add.at(ref, codes[mask], vals[mask].astype(np.float64))
    return ref


def test_grouped_aggregate_matches_reference():
    rng = np.random.default_rng(1)
    N, G, A = 4096, 6, 4
    codes = rng.integers(0, G, N).astype(np.int32)
    vals = rng.uniform(-5, 5, (N, A)).astype(np.float32)
    mask = rng.random(N) > 0.4
    out = grouped_aggregate(codes, vals, mask, G, interpret=True)
    assert out is not None
    np.testing.assert_allclose(out, _ref(codes, vals, mask, G), rtol=1e-4, atol=1e-3)


def test_grouped_aggregate_unaligned_length():
    rng = np.random.default_rng(2)
    N, G, A = 3001, 5, 2  # not a multiple of the block size
    codes = rng.integers(0, G, N).astype(np.int32)
    vals = rng.uniform(0, 1, (N, A)).astype(np.float32)
    mask = np.ones(N, dtype=bool)
    out = grouped_aggregate(codes, vals, mask, G, interpret=True)
    np.testing.assert_allclose(out, _ref(codes, vals, mask, G), rtol=1e-4, atol=1e-3)


def test_declines_large_group_count():
    codes = np.zeros(10, dtype=np.int32)
    vals = np.zeros((10, 1), dtype=np.float32)
    mask = np.ones(10, dtype=bool)
    assert grouped_aggregate(codes, vals, mask, 1000, interpret=True) is None


def test_empty_input_returns_zeros():
    out = grouped_aggregate(
        np.zeros(0, dtype=np.int32),
        np.zeros((0, 3), dtype=np.float32),
        np.zeros(0, dtype=bool),
        4,
        interpret=True,
    )
    assert out.shape == (4, 3) and (out == 0).all()
