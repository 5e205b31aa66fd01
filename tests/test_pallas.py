"""Pallas timestamp-hash kernel: bit-exact vs oracle and XLA path.

Runs the kernel in interpreter mode (CPU test env); the compiled form
is compiled for the chip in tests/test_tpu_compile.py and run on it by
chip_smoke.py.
"""

import numpy as np

from evolu_tpu.core.timestamp import Timestamp, timestamp_to_hash
from evolu_tpu.ops.encode import timestamp_hashes
from evolu_tpu.ops.pallas_hash import timestamp_hashes_pallas


def _batch(n=300, seed=3):
    rng = np.random.default_rng(seed)
    millis = 1_700_000_000_000 + rng.integers(0, 365 * 86_400_000, n).astype(np.int64)
    counter = rng.integers(0, 65536, n).astype(np.int32)
    node = rng.integers(0, 2**64, n, dtype=np.uint64)
    return millis, counter, node


def test_pallas_matches_xla_path():
    millis, counter, node = _batch()
    got = np.asarray(timestamp_hashes_pallas(millis, counter, node, interpret=True))
    want = np.asarray(timestamp_hashes(millis, counter, node))
    np.testing.assert_array_equal(got, want)


def test_pallas_matches_host_oracle():
    millis, counter, node = _batch(64, seed=9)
    got = np.asarray(timestamp_hashes_pallas(millis, counter, node, interpret=True))
    for i in range(len(millis)):
        t = Timestamp(int(millis[i]), int(counter[i]), f"{int(node[i]):016x}")
        assert int(got[i]) == timestamp_to_hash(t) & 0xFFFFFFFF, i


def test_pallas_edge_dates_and_padding():
    # Epoch boundary, leap day, century/leap-year rules, year 9999; and a
    # deliberately non-tile-aligned batch length.
    cases = [
        0,
        951_782_400_000,        # 2000-02-29
        4_107_542_399_000,      # 2100-02-28 end of day (2100 not a leap year)
        253_402_300_799_999,    # 9999-12-31T23:59:59.999
    ]
    millis = np.array(cases * 13, np.int64)[:50]
    counter = np.arange(50, dtype=np.int32) % 65536
    node = (np.arange(50, dtype=np.uint64) * np.uint64(0x9E3779B97F4A7C15))
    got = np.asarray(timestamp_hashes_pallas(millis, counter, node, interpret=True))
    want = np.asarray(timestamp_hashes(millis, counter, node))
    np.testing.assert_array_equal(got, want)


def test_pallas_segmented_scan_matches_reference():
    """The single-pass Pallas segmented lex-max scan must be
    bit-identical to merge._segmented_max_scan_reference across random
    segment shapes, forward and reverse, including cross-block
    segments (N spans several grid steps) and all-zero/sentinel keys."""
    import jax
    from evolu_tpu.ops.merge import _segmented_max_scan_reference
    from evolu_tpu.ops.pallas_scan import segmented_max_scan_pallas

    rng = np.random.default_rng(5)
    with jax.enable_x64(True):
        for n in (1, 127, 128, 4096, 70000):
            flags = rng.random(n) < 0.03
            flags[0] = True
            k1 = rng.integers(0, 2**64, n, dtype=np.uint64)
            k2 = rng.integers(0, 2**64, n, dtype=np.uint64)
            # Ties in k1 (forces the k2 limb compare) and zero keys.
            k1[rng.random(n) < 0.3] = np.uint64(42) << np.uint64(32)
            k1[rng.random(n) < 0.1] = 0
            k2[rng.random(n) < 0.1] = 0
            for reverse in (False, True):
                f = flags if not reverse else np.roll(flags, -1)  # ends
                exp1, exp2 = _segmented_max_scan_reference(
                    jax.numpy.asarray(f), jax.numpy.asarray(k1),
                    jax.numpy.asarray(k2), reverse=reverse,
                )
                got1, got2 = segmented_max_scan_pallas(
                    jax.numpy.asarray(f), jax.numpy.asarray(k1),
                    jax.numpy.asarray(k2), reverse=reverse, interpret=True,
                )
                assert (np.asarray(exp1) == np.asarray(got1)).all(), (n, reverse)
                assert (np.asarray(exp2) == np.asarray(got2)).all(), (n, reverse)


def test_pallas_segmented_xor_scan_matches_reference():
    """The single-pass Pallas segmented XOR scan must be bit-identical
    to the associative_scan reference, including cross-block segments."""
    import jax
    from evolu_tpu.ops.merkle_ops import segmented_xor_scan_reference
    from evolu_tpu.ops.pallas_scan import segmented_xor_scan_pallas

    rng = np.random.default_rng(10)
    for n in (1, 4096, 70000):
        flags = rng.random(n) < 0.02
        flags[0] = True
        v = rng.integers(0, 2**32, n, dtype=np.uint32)
        exp = segmented_xor_scan_reference(jax.numpy.asarray(flags), jax.numpy.asarray(v))
        got = segmented_xor_scan_pallas(jax.numpy.asarray(flags), jax.numpy.asarray(v), interpret=True)
        assert (np.asarray(exp) == np.asarray(got)).all(), n
