"""Pallas TPU kernel for batched timestamp hashing.

Same computation as `encode.timestamp_hashes` (murmur3-32 of the
canonical 46-char timestamp string, timestamp.ts:87-88) but expressed
as an explicit VMEM-blocked Pallas kernel: the XLA path materializes
~46 fused byte columns between HBM round-trips at the fusion
boundaries; here one grid step streams a (8, 128)-tiled block of the
five 32-bit input components into VMEM and emits the 32-bit hash, with
every intermediate staying in registers/VMEM.

Split of work: the two int64 divmods that reduce raw `millis` to
(days, seconds-of-day, millis-of-second) run in plain XLA before the
kernel (Pallas TPU kernels are 32-bit; everything after the split fits
u32/i32 exactly — SURVEY.md §7 bit-exactness notes). The kernel is
bit-exact vs the host oracle and the XLA path (tests/test_pallas.py).

Falls back transparently: `timestamp_hashes_pallas(..., interpret=True)`
runs the same kernel in interpreter mode on CPU (the test env).

Status: `encode.timestamp_hashes` (XLA autofusion) is the production
path; this hand-blocked kernel is the bit-exact alternative (it would
win only if a future pipeline needs the hash fused with ops XLA refuses
to fuse). Their relative speed on the attached chip: not measured.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from evolu_tpu.ops import bucket_size, with_x64

_LANES = 128
_SUBLANES = 8
_BLOCK_ROWS = 64  # rows (of 128 lanes) per grid step: 64*128 = 8192 ts/step

_C1 = 0xCC9E2D51
_C2 = 0x1B873593


def _u32(x):
    return jnp.uint32(x)


def _rotl(x, r: int):
    return (x << _u32(r)) | (x >> _u32(32 - r))


def _mix_k(k):
    return _rotl(k * _u32(_C1), 15) * _u32(_C2)


def _civil_from_days_i32(days):
    """Howard Hinnant's civil_from_days in int32 (days < 2^23 for any
    representable date, so every intermediate fits). All constants are
    pinned to int32 — under enable_x64 a bare Python int would promote
    the arithmetic to int64, which Pallas TPU kernels reject."""
    c = jnp.int32
    z = days + c(719468)
    era = z // c(146097)
    doe = z - era * c(146097)
    yoe = (doe - doe // c(1460) + doe // c(36524) - doe // c(146096)) // c(365)
    y = yoe + era * c(400)
    doy = doe - (c(365) * yoe + yoe // c(4) - yoe // c(100))
    mp = (c(5) * doy + c(2)) // c(153)
    d = doy - (c(153) * mp + c(2)) // c(5) + c(1)
    m = mp + jnp.where(mp < c(10), c(3), c(-9))
    y = y + (m <= c(2)).astype(jnp.int32)
    return y, m, d


def _digits(x, n):
    out = []
    for i in range(n - 1, -1, -1):
        out.append((x // _u32(10**i)) % _u32(10) + _u32(ord("0")))
    return out


def _hex_nibble(x, upper):
    return jnp.where(x < 10, x + _u32(ord("0")), x + _u32((ord("A") if upper else ord("a")) - 10))


def _hash_kernel(days_ref, sod_ref, ms_ref, counter_ref, node_hi_ref, node_lo_ref, out_ref):
    """One VMEM block: 5 u32/i32 component planes → u32 murmur3 hashes."""
    days = days_ref[:]
    sod = sod_ref[:].astype(jnp.uint32)
    ms = ms_ref[:]
    counter = counter_ref[:]
    node_hi = node_hi_ref[:]
    node_lo = node_lo_ref[:]

    hh, mm, ss = sod // _u32(3600), (sod // _u32(60)) % _u32(60), sod % _u32(60)
    y, mo, d = _civil_from_days_i32(days)
    y, mo, d = y.astype(jnp.uint32), mo.astype(jnp.uint32), d.astype(jnp.uint32)

    dash, colon = _u32(ord("-")), _u32(ord(":"))
    cols = []
    cols += _digits(y, 4)
    cols.append(jnp.broadcast_to(dash, y.shape))
    cols += _digits(mo, 2)
    cols.append(jnp.broadcast_to(dash, y.shape))
    cols += _digits(d, 2)
    cols.append(jnp.broadcast_to(_u32(ord("T")), y.shape))
    cols += _digits(hh, 2)
    cols.append(jnp.broadcast_to(colon, y.shape))
    cols += _digits(mm, 2)
    cols.append(jnp.broadcast_to(colon, y.shape))
    cols += _digits(ss, 2)
    cols.append(jnp.broadcast_to(_u32(ord(".")), y.shape))
    cols += _digits(ms, 3)
    cols.append(jnp.broadcast_to(_u32(ord("Z")), y.shape))
    cols.append(jnp.broadcast_to(dash, y.shape))
    for shift in (12, 8, 4, 0):
        cols.append(_hex_nibble((counter >> _u32(shift)) & _u32(0xF), upper=True))
    cols.append(jnp.broadcast_to(dash, y.shape))
    for half in (node_hi, node_lo):
        for shift in (28, 24, 20, 16, 12, 8, 4, 0):
            cols.append(_hex_nibble((half >> _u32(shift)) & _u32(0xF), upper=False))

    # murmur3-32 over the 46 bytes (11 words + 2-byte tail).
    h = jnp.zeros_like(cols[0])
    for i in range(11):
        b = i * 4
        k = cols[b] | (cols[b + 1] << _u32(8)) | (cols[b + 2] << _u32(16)) | (cols[b + 3] << _u32(24))
        h = h ^ _mix_k(k)
        h = _rotl(h, 13)
        h = h * _u32(5) + _u32(0xE6546B64)
    k = cols[44] ^ (cols[45] << _u32(8))
    h = h ^ _mix_k(k)
    h = h ^ _u32(46)
    h = h ^ (h >> _u32(16))
    h = h * _u32(0x85EBCA6B)
    h = h ^ (h >> _u32(13))
    h = h * _u32(0xC2B2AE35)
    h = h ^ (h >> _u32(16))
    out_ref[:] = h


@functools.partial(jax.jit, static_argnames=("interpret",))
def _hash_blocks(days, sod, ms, counter, node_hi, node_lo, interpret: bool = False):
    rows = days.shape[0]  # always a multiple of _BLOCK_ROWS (caller pads)
    spec = pl.BlockSpec((_BLOCK_ROWS, _LANES), lambda i: (i, 0), memory_space=pltpu.VMEM)
    return pl.pallas_call(
        _hash_kernel,
        out_shape=jax.ShapeDtypeStruct((rows, _LANES), jnp.uint32),
        grid=(rows // _BLOCK_ROWS,),
        in_specs=[spec] * 6,
        out_specs=spec,
        interpret=interpret,
    )(days, sod, ms, counter, node_hi, node_lo)


@with_x64
def timestamp_hashes_pallas(millis, counter, node, interpret: bool = False):
    """(N,) int64 millis, int32 counter, uint64 node → (N,) uint32
    murmur3 hashes, via the Pallas kernel. Pads N up to a full tile
    grid internally."""
    millis = jnp.asarray(millis, jnp.int64)
    counter = jnp.asarray(counter, jnp.int32)
    node = jnp.asarray(node, jnp.uint64)
    n = millis.shape[0]

    # 64-bit reduction in XLA; everything into the kernel is 32-bit.
    ms = (millis % 1000).astype(jnp.uint32)
    secs = millis // 1000
    days = (secs // 86400).astype(jnp.int32)
    sod = (secs % 86400).astype(jnp.int32)
    c32 = counter.astype(jnp.uint32)
    node_hi = (node >> jnp.uint64(32)).astype(jnp.uint32)
    node_lo = node.astype(jnp.uint32)

    tile = _BLOCK_ROWS * _LANES  # one grid step's worth of elements
    # Power-of-two buckets (>= one grid step): jit compiles once per
    # bucket, not once per distinct batch size (ops.bucket_size policy).
    padded = bucket_size(n, multiple=tile)
    comps = []
    for a in (days, sod, ms, c32, node_hi, node_lo):
        a = jnp.pad(a, (0, padded - n))
        comps.append(a.reshape(padded // _LANES, _LANES))
    # The kernel is pure 32-bit; trace it OUTSIDE the x64 scope so the
    # grid index map emits i32 (an i64 index map fails TPU compilation).
    with jax.enable_x64(False):
        out = _hash_blocks(*comps, interpret=interpret)
    return out.reshape(-1)[:n]
