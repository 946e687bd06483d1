"""Native host codec (native/codec.cpp via ctypes) vs numpy oracles.

Every binding is exercised against its pure-numpy fallback on the same
inputs; the library builds itself on first use (``native.load``), and if
the toolchain is unavailable the fallback is what runs and the oracle
comparison is still meaningful (self-consistency).
"""

import numpy as np
import pytest

from fedtpu import native


def test_library_name_is_tied_to_source_and_flags(monkeypatch):
    """The loader only ever opens the file named by a hash of the tracked
    source + flags, so a binary from another build is never picked up."""
    base = native._lib_path()
    assert "libfedtpu_native-" in base
    if native.available():
        assert native.codec_name() == "native:" + base.rsplit("/", 1)[1]
    monkeypatch.setattr(native, "_CXX", native._CXX + ("-march=native",))
    assert native._lib_path() != base


def test_kth_magnitude_matches_partition(rng):
    x = rng.normal(size=5001).astype(np.float32)
    for k in (1, 7, 500, 5001):
        got = native.kth_magnitude(x, k)
        want = float(np.sort(np.abs(x))[::-1][k - 1])
        assert got == pytest.approx(want, rel=1e-6)


def test_kth_magnitude_edge_cases():
    assert native.kth_magnitude(np.zeros(0, np.float32), 3) == 0.0
    x = np.array([1.0, -2.0], np.float32)
    assert native.kth_magnitude(x, 0) == 2.0  # clamped to k=1
    assert native.kth_magnitude(x, 99) == 1.0  # clamped to k=n


def test_pack_unpack_sparse_roundtrip(rng):
    x = rng.normal(size=4096).astype(np.float32)
    t = native.kth_magnitude(x, 41)
    idx, vals = native.pack_sparse(x, t)
    assert len(idx) >= 41  # ties may keep extras
    dense = native.unpack_sparse(idx, vals, x.size)
    keep = np.abs(x) >= t
    np.testing.assert_array_equal(dense, np.where(keep, x, 0.0))


def test_pack_sparse_with_residual_conserves_mass(rng):
    x = rng.normal(size=2048).astype(np.float32)
    t = native.kth_magnitude(x, 20)
    idx, vals, residual = native.pack_sparse_with_residual(x, t)
    dense = native.unpack_sparse(idx, vals, x.size)
    np.testing.assert_allclose(dense + residual, x, atol=1e-7)
    # Kept entries have zero residual; dropped have zero dense.
    assert np.all(residual[idx] == 0.0)
    assert np.all(dense[np.abs(x) < t] == 0.0)


def test_quant_int8_error_bound(rng):
    x = rng.normal(size=3000).astype(np.float32)
    codes, scale = native.quant_int8(x)
    back = native.dequant_int8(codes, scale, x.size)
    assert np.abs(back - x).max() <= scale / 2 + 1e-7
    assert codes.dtype == np.int8


def test_quant_int8_zero_input():
    codes, scale = native.quant_int8(np.zeros(64, np.float32))
    assert scale == 0.0
    assert not codes.any()
    np.testing.assert_array_equal(
        native.dequant_int8(codes, scale, 64), np.zeros(64, np.float32)
    )


def test_native_and_fallback_agree(rng):
    """When the shared library is built, its outputs must match the numpy
    fallback path bit-for-bit (modulo float rounding in quant)."""
    if not native.available():
        pytest.skip("native library not built")
    x = rng.normal(size=1111).astype(np.float32)
    t = native.kth_magnitude(x, 30)

    # Force the fallback by temporarily hiding the lib.
    lib = native._lib
    try:
        native._lib = None
        f_idx, f_vals = native.pack_sparse(x, t)
        f_codes, f_scale = native.quant_int8(x)
    finally:
        native._lib = lib

    n_idx, n_vals = native.pack_sparse(x, t)
    np.testing.assert_array_equal(f_idx, n_idx)
    np.testing.assert_array_equal(f_vals, n_vals)
    n_codes, n_scale = native.quant_int8(x)
    assert f_scale == pytest.approx(n_scale, rel=1e-7)
    # round-half cases may differ by 1 code between rint and nearbyint only
    # if the tie-breaking modes differed; both are banker's rounding.
    np.testing.assert_array_equal(f_codes, n_codes)
