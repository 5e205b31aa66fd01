"""Pallas TPU kernel: single-pass segmented lexicographic max scan.

The LWW planner's wall after the sort is its two segmented scans
(`merge._segmented_max_scan`). The XLA blocked formulation does
log2(256) = 8 shifted elementwise passes over the full arrays — every
pass a round-trip of ~17 bytes/row through HBM. This kernel runs the
scan in ONE pass over HBM: a sequential grid walks the array in
blocks; inside a block everything stays in VMEM (7 lane-shift combines
+ a small cross-row scan), and the running carry crosses grid steps in
SMEM scratch (the TPU grid executes sequentially on a core, so scratch
persists between steps — the canonical Pallas carry pattern).

TPU Pallas has no 64-bit vectors, so the (k1, k2) uint64 HLC keys ride
as four uint32 limb planes with a 4-limb lexicographic compare — the
split/recombine happens in XLA outside the kernel (bit-exact, fused
into the neighbors).

Same monoid and semantics as `merge._segmented_max_scan_reference`:
inclusive segmented lex-max; `flags[i]` marks a segment start (segment
END when reverse=True — the wrapper flips, scans forward, flips back,
exactly like the XLA paths). Bit-identity is property-pinned in
tests/test_pallas.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_BLOCK_ROWS = 256  # rows per grid step: 256*128 = 32768 elements


def _lex_ge(a1h, a1l, a2h, a2l, b1h, b1l, b2h, b2l):
    """(a1, a2) >= (b1, b2) lexicographically, on u32 limbs."""
    return (a1h > b1h) | (
        (a1h == b1h)
        & (
            (a1l > b1l)
            | (
                (a1l == b1l)
                & ((a2h > b2h) | ((a2h == b2h) & (a2l >= b2l)))
            )
        )
    )


def _comb(left, right):
    """The segmented lex-max monoid on (flag, 4 key limbs): the operand
    nearest the scan head (right) wins outright when flagged."""
    lf, l1h, l1l, l2h, l2l = left
    rf, r1h, r1l, r2h, r2l = right
    a_wins = _lex_ge(l1h, l1l, l2h, l2l, r1h, r1l, r2h, r2l)

    def pick(lv, rv):
        return jnp.where(rf != 0, rv, jnp.where(a_wins, lv, rv))

    return (lf | rf, pick(l1h, r1h), pick(l1l, r1l), pick(l2h, r2h), pick(l2l, r2l))


def _seg_xor(left, right):
    """Segmented XOR monoid on (flag, value)."""
    lf, lv = left
    rf, rv = right
    return (lf | rf, jnp.where(rf != 0, rv, lv ^ rv))


def _seg_sum(left, right):
    """Segmented u64 SUM monoid on (flag, hi limb, lo limb) — the
    add-monoid twin of `_comb` for the PN-counter fold
    (ops/crdt_merge.py). No 64-bit vectors on TPU Pallas, so the sum
    carries across two u32 limbs: unsigned u32 add wraps, and
    `lo < either operand` detects the wrap (values are non-negative
    pos/neg partial sums, so plain modular limb addition is exact)."""
    lf, lh, ll = left
    rf, rh, rl = right
    lo = ll + rl
    carry = (lo < rl).astype(jnp.uint32)
    hi = lh + rh + carry
    return (lf | rf, jnp.where(rf != 0, rh, hi), jnp.where(rf != 0, rl, lo))


def _make_scan_kernel(n_planes: int, combine):
    """Kernel factory: inclusive segmented scan over `n_planes` u32
    planes (plane 0 is the segment flag) under `combine`, one grid
    step per (R, 128) block in row-major element order, carry across
    the sequential grid in SMEM."""

    def kernel(*refs):
        in_refs = refs[:n_planes]
        out_refs = refs[n_planes : 2 * n_planes]
        carry = refs[2 * n_planes]
        step = pl.program_id(0)

        @pl.when(step == 0)
        def _init():
            for i in range(n_planes):
                carry[i] = jnp.uint32(0)

        vals = tuple(r[:] for r in in_refs)

        # 1) In-row inclusive scan along the 128 lanes: log2(128) = 7
        #    shifted combines; lanes shifted in from the left are
        #    masked to the monoid identity (flag 0, values 0).
        lane = jax.lax.broadcasted_iota(jnp.int32, vals[0].shape, 1)
        shift = 1
        while shift < _LANES:
            shifted = tuple(pltpu.roll(v, shift, 1) for v in vals)
            edge = lane < shift
            shifted = tuple(jnp.where(edge, jnp.uint32(0), v) for v in shifted)
            vals = combine(shifted, vals)
            shift *= 2

        # 2) Cross-row scan over the row totals (lane 127 column).
        totals = tuple(v[:, _LANES - 1 :] for v in vals)
        row = jax.lax.broadcasted_iota(jnp.int32, totals[0].shape, 0)
        shift = 1
        while shift < _BLOCK_ROWS:
            shifted = tuple(pltpu.roll(t, shift, 0) for t in totals)
            edge = row < shift
            shifted = tuple(jnp.where(edge, jnp.uint32(0), t) for t in shifted)
            totals = combine(shifted, totals)
            shift *= 2

        # 3) Exclusive row carry: rows shift down by one; row 0 takes
        #    the block carry from scratch, every other row combines it
        #    in as the left-most operand.
        prev = tuple(pltpu.roll(t, 1, 0) for t in totals)
        prev = tuple(jnp.where(row < 1, jnp.uint32(0), t) for t in prev)
        carry_in = tuple(jnp.full_like(prev[0], carry[i]) for i in range(n_planes))
        row_carry = combine(carry_in, prev)

        # 4) out[r, l] = combine(row_carry[r], in_row_scan[r, l]).
        out = combine(row_carry, vals)
        for o_ref, o in zip(out_refs, out):
            o_ref[:] = o

        # 5) Save the block's inclusive total as the next step's carry.
        for i in range(n_planes):
            carry[i] = out[i][_BLOCK_ROWS - 1, _LANES - 1]

    return kernel


_LEX_KERNEL = _make_scan_kernel(5, _comb)
_XOR_KERNEL = _make_scan_kernel(2, _seg_xor)
_SUM_KERNEL = _make_scan_kernel(3, _seg_sum)


def _scan_call(kernel, n_planes, planes, interpret):
    rows = planes[0].shape[0]  # multiple of _BLOCK_ROWS (caller pads)
    spec = pl.BlockSpec((_BLOCK_ROWS, _LANES), lambda i: (i, 0),
                        memory_space=pltpu.VMEM)
    shape = jax.ShapeDtypeStruct((rows, _LANES), jnp.uint32)
    return pl.pallas_call(
        kernel,
        out_shape=(shape,) * n_planes,
        grid=(rows // _BLOCK_ROWS,),
        in_specs=[spec] * n_planes,
        out_specs=(spec,) * n_planes,
        scratch_shapes=[pltpu.SMEM((n_planes,), jnp.uint32)],
        interpret=interpret,
    )(*planes)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _scan_blocks(f, k1h, k1l, k2h, k2l, interpret: bool = False):
    return _scan_call(_LEX_KERNEL, 5, (f, k1h, k1l, k2h, k2l), interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _xor_scan_blocks(f, v, interpret: bool = False):
    return _scan_call(_XOR_KERNEL, 2, (f, v), interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _sum_scan_blocks(f, hi, lo, interpret: bool = False):
    return _scan_call(_SUM_KERNEL, 3, (f, hi, lo), interpret)


def segmented_xor_scan_pallas(flags, values_u32, interpret: bool = False):
    """(N,) bool flags (segment starts) + (N,) uint32 → inclusive
    segmented XOR scan. At each segment's last row the value is the
    segment's total XOR — the only positions the Merkle decode reads."""
    n = flags.shape[0]
    tile = _BLOCK_ROWS * _LANES
    padded = -(-max(n, 1) // tile) * tile
    pad = padded - n
    f = jnp.pad(flags.astype(jnp.uint32), (0, pad))
    v = jnp.pad(jnp.asarray(values_u32, jnp.uint32), (0, pad))
    planes = [a.reshape(padded // _LANES, _LANES) for a in (f, v)]
    with jax.enable_x64(False):
        _, out = _xor_scan_blocks(*planes, interpret=interpret)
    return out.reshape(-1)[:n]


def segmented_sum_scan_pallas(flags, values_u64, interpret: bool = False):
    """Drop-in for `crdt_merge.segmented_sum_scan`: (N,) bool flags
    (segment starts) + uint64 values → inclusive segmented sum, one HBM
    pass. The u64⇄u32 limb split runs in XLA around the kernel; exact
    for the PN-counter fold's non-negative partial sums (< 2^55)."""
    n = flags.shape[0]
    tile = _BLOCK_ROWS * _LANES
    padded = -(-max(n, 1) // tile) * tile
    pad = padded - n
    f = jnp.pad(flags.astype(jnp.uint32), (0, pad))
    v = jnp.asarray(values_u64, jnp.uint64)
    vh = jnp.pad((v >> jnp.uint64(32)).astype(jnp.uint32), (0, pad))
    vl = jnp.pad(v.astype(jnp.uint32), (0, pad))
    planes = [a.reshape(padded // _LANES, _LANES) for a in (f, vh, vl)]
    with jax.enable_x64(False):
        _, oh, ol = _sum_scan_blocks(*planes, interpret=interpret)
    return (
        oh.reshape(-1)[:n].astype(jnp.uint64) << jnp.uint64(32)
    ) | ol.reshape(-1)[:n].astype(jnp.uint64)


def segmented_max_scan_pallas(flags, k1, k2, reverse: bool = False,
                              interpret: bool = False):
    """Drop-in for `merge._segmented_max_scan`: (N,) bool flags + uint64
    keys → inclusive segmented lex-max (m1, m2) uint64. Traceable; the
    u64⇄u32 limb split and padding run in XLA around the kernel."""
    if reverse:
        o1, o2 = segmented_max_scan_pallas(
            flags[::-1], k1[::-1], k2[::-1], interpret=interpret
        )
        return o1[::-1], o2[::-1]
    n = flags.shape[0]
    tile = _BLOCK_ROWS * _LANES
    padded = -(-max(n, 1) // tile) * tile
    pad = padded - n

    f = jnp.pad(flags.astype(jnp.uint32), (0, pad))
    k1 = jnp.asarray(k1, jnp.uint64)
    k2 = jnp.asarray(k2, jnp.uint64)
    k1h = jnp.pad((k1 >> jnp.uint64(32)).astype(jnp.uint32), (0, pad))
    k1l = jnp.pad(k1.astype(jnp.uint32), (0, pad))
    k2h = jnp.pad((k2 >> jnp.uint64(32)).astype(jnp.uint32), (0, pad))
    k2l = jnp.pad(k2.astype(jnp.uint32), (0, pad))

    planes = [a.reshape(padded // _LANES, _LANES) for a in (f, k1h, k1l, k2h, k2l)]
    # The kernel is pure 32-bit; trace it outside the x64 scope so the
    # grid index map emits i32 (an i64 index map fails TPU compilation).
    with jax.enable_x64(False):
        _, m1h, m1l, m2h, m2l = _scan_blocks(*planes, interpret=interpret)

    def join(hi, lo):
        return (hi.reshape(-1)[:n].astype(jnp.uint64) << jnp.uint64(32)) | lo.reshape(
            -1
        )[:n].astype(jnp.uint64)

    return join(m1h, m1l), join(m2h, m2l)
