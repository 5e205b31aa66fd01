"""Sort-free scatter-argmax LWW merge plan (ISSUE 4 tentpole).

The round-5 ablation put 65% of the merge pipeline in one `lax.sort`
(measured before PR 1; not re-measured on the attached chip), yet LWW
resolution needs
a per-cell MAX, not a total order (reference applyMessages.ts:34-40) —
the commutative per-key reduction Merkle-CRDTs exploit to make merge
order-free (arxiv 2004.00107). This module is the dense formulation:
scatter each message's HLC key into a cell-indexed winner table in
HBM, take the per-cell lexicographic max (two chained u64 scatter-max
passes — the (k1, k2) compare is 128-bit, which no single packed key
can carry), then gather the winners back to label each row.

The reference's xor quirk (applyMessages.ts:104-122) is the part a
per-cell max alone cannot reproduce: the Merkle XOR is gated on
"running winner != message timestamp" where the running winner folds
the stored winner and all EARLIER BATCH rows of the cell — an
inherently order-dependent prefix quantity. The exact algebra (same
derivation as `merge.plan_merge_sorted_flags`, with p = the in-batch
prefix max and e the stored winner, a = e>s, b = e==s):

    xor[i]   = False  ⟺  ¬a ∧ ¬gt_before[i] ∧ (b ∨ eq_before[i])
    upsert[i] =            s_i == t(c) ∧ first-achiever ∧ ¬a ∧ ¬b

`gt_before`/`eq_before` (an earlier batch row of the cell strictly
greater / exactly equal) collapse to scatter-computable quantities
WHEN the batch holds no duplicate (cell, k1, k2) row below the cell
max:

  - eq_before ≡ False for every row (no in-batch duplicates at all is
    the precondition actually enforced — see `batch_has_duplicate_keys`
    — so `(b ∨ eq_before)` reduces to `b`);
  - for b-rows, gt_before ⟺ FB[c] < i where FB[c] is the FIRST batch
    index beating the stored winner (one scatter-min of idx over the
    ¬a∧¬b rows — every row of a cell shares the same e, so "beats e"
    is the row's own flag);
  - dup-free cells have a unique max achiever, so upsert needs no
    first-achiever tie-break.

Duplicate (cell, k1, k2) keys are identical 46-char timestamps in the
same cell — upstream paths (relay PK, in-batch dedup in
engine.start_batch) never produce them, but the planner contract must
hold for arbitrary input, so the ROUTER (`use_scatter_plan`) detects
them host-side with a sorted-hash screen (false positives over-route
to the sort path — safe; false negatives are impossible: equal keys
hash equal) and routes such batches to the sort path. Same pattern as
the wide-id fallback: static host-side routing, two separately
compiled kernels, bit-identical plans wherever both can run
(property-pinned in tests/test_scatter_merge.py).

Cost model notes (why this is config-selectable, not the default):
three scatters + three gathers against table rows vs ONE sort. The
pre-PR-1 v5e pricing (1M-row u64 gathers ~4× a sort; XLA lowers
scatters to serialized updates on TPU) predicts a heavy loss on TPU
silicon — on the attached chip: not measured; on the CPU backend the
same formulation measures ~13× FASTER than the 1M single-device
sort+scan plan. `merge_plan_path()` therefore routes "auto" by
backend.
"""

from __future__ import annotations

import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from evolu_tpu.ops.merge import _PAD_CELL, winner_flags

# Hard table bound: cell ids ride 25 bits in the r5 packed sort key,
# and 2^25 winner slots = 512 MB of u64 pairs — the largest table the
# tentpole brief prices. Batches beyond it keep the sort path.
MAX_TABLE_BITS = 25

# Multiplicative hash constants for the duplicate screen (odd, from
# splitmix64's finalizer family — quality only affects the false
# positive rate, never correctness).
_H1 = np.uint64(0xBF58476D1CE4E5B9)
_H2 = np.uint64(0x94D049BB133111EB)
_H3 = np.uint64(0x9E3779B97F4A7C15)


def table_size_for(cell_max: int) -> int:
    """Power-of-two winner-table size covering cell ids 0..cell_max
    (bucket-stable: the kernel recompiles per table bucket, never per
    batch)."""
    size = 64
    while size <= cell_max:
        size *= 2
    return size


def batch_has_duplicate_keys(cell_id, k1, k2) -> bool:
    """Host-side duplicate screen for the scatter router: True if any
    two REAL rows MAY share (cell, k1, k2) — padding rows (the layout
    sentinels, all identical (PAD, 0, 0)) are excluded, or every
    padded shard layout would self-report as duplicate. Sorted-hash
    check: equal triples hash equal (no false negatives — a missed
    duplicate would silently corrupt the xor mask), unequal triples
    collide with ~N²/2⁶⁴ probability and only over-route to the sort
    path. A dup is the same 46-char timestamp hitting the same cell
    twice in one batch, which every upstream dedup already screens —
    this is the planner-contract backstop, not a hot-path
    expectation."""
    real = cell_id != int(_PAD_CELL)
    if not real.all():
        cell_id, k1, k2 = cell_id[real], k1[real], k2[real]
    n = len(k1)
    if n < 2:
        return False
    with np.errstate(over="ignore"):
        h = (
            k1.astype(np.uint64) * _H1
            ^ k2.astype(np.uint64) * _H2
            ^ cell_id.astype(np.uint64) * _H3
        )
    h.sort()
    return bool((h[1:] == h[:-1]).any())


# -- plan-path selection -------------------------------------------------

_VALID_PATHS = ("auto", "sort", "scatter")
_plan_path = "auto"


def set_plan_path(path: str) -> None:
    """Select the LWW plan formulation: "sort" (the r5 sort+scan
    pipeline), "scatter" (this module), or "auto" (by backend: scatter
    on CPU where it measures ~13× faster, sort on TPU where the
    recorded cost model prices scatters/gathers far above one sort —
    docs/BENCHMARKS.md r6). Wired from `Config.merge_plan` at runtime
    init; the EVOLU_MERGE_PLAN env var overrides either (bench/test
    pinning)."""
    if path not in _VALID_PATHS:
        raise ValueError(f"merge_plan must be one of {_VALID_PATHS}, got {path!r}")
    global _plan_path
    _plan_path = path


def merge_plan_path() -> str:
    """The effective plan path ("sort" | "scatter") after env override
    and "auto" resolution. Reads the default backend lazily — calling
    this must not initialize XLA earlier than the caller's own kernel
    dispatch would."""
    path = os.environ.get("EVOLU_MERGE_PLAN", "") or _plan_path
    if path not in _VALID_PATHS:
        # Loud, like set_plan_path: the env var exists to PIN a kernel
        # for benches/tests — a typo silently resolving to "auto"
        # would record numbers for the wrong kernel.
        raise ValueError(
            f"EVOLU_MERGE_PLAN must be one of {_VALID_PATHS}, got {path!r}"
        )
    if path == "auto":
        return "scatter" if jax.default_backend() == "cpu" else "sort"
    return path


def use_scatter_plan(cell_id, k1, k2, cell_max: Optional[int] = None) -> bool:
    """Full host-side routing decision for one batch: the configured
    path, the table bound, and the duplicate screen. `cell_max` saves
    a pass when the caller already holds the max (shard routing)."""
    if merge_plan_path() != "scatter":
        return False
    if cell_max is None:
        real = cell_id != int(_PAD_CELL)
        cell_max = int(cell_id.max(initial=0, where=real))
    if cell_max >= 1 << MAX_TABLE_BITS:
        return False
    return not batch_has_duplicate_keys(cell_id, k1, k2)


def scatter_table_for(cell_id, k1, k2) -> Optional[int]:
    """Admission AND sizing in one call for the plan entry points: the
    winner-table size when the scatter plan should serve this batch,
    else None. The pad-free cell max is computed ONCE and feeds both
    decisions, so admission and table sizing can never disagree."""
    if merge_plan_path() != "scatter":
        return None
    real = cell_id != int(_PAD_CELL)
    cell_max = int(cell_id.max(initial=0, where=real))
    if cell_max >= 1 << MAX_TABLE_BITS or batch_has_duplicate_keys(cell_id, k1, k2):
        return None
    return table_size_for(cell_max)


# -- the kernel ----------------------------------------------------------


def scatter_plan_masks(cell_id, k1, k2, ex_k1, ex_k2, table_size: int):
    """The dense LWW plan (traceable core): → (xor_mask, upsert_mask)
    bools in ORIGINAL batch order — no sort, no permutation to undo.

    Preconditions (enforced by `use_scatter_plan`, NOT re-checked on
    device): real cell ids < table_size, and no duplicate
    (cell, k1, k2) row. Padding rows carry cell_id=_PAD_CELL and
    scatter to the dump slot `table_size` (mode="drop" on writes; the
    dump-slot gather is masked by `real`).

    TPU notes honored even though the default routing keeps this off
    TPU: comparisons are compare+select only (no maxui), and the
    scatters are plain u64/int32 tables — no 64-bit VECTORS are
    produced by the gathers' consumers beyond what the sort path
    already materializes. Must be traced under enable_x64(True) like
    every planner core (u64 keys)."""
    n = cell_id.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    a, b = winner_flags(k1, k2, ex_k1, ex_k2)
    real = cell_id != _PAD_CELL
    cell = jnp.where(real, cell_id, jnp.int32(table_size))
    # Per-cell lex max over (k1, k2): chained scatter-max passes. The
    # second pass maxes k2 only over rows achieving t1 (losers
    # contribute the u64 zero — the monoid identity, and a legitimate
    # value: max(0, real zeros) is still exact).
    t1 = jnp.zeros(table_size + 1, jnp.uint64).at[cell].max(k1, mode="drop")
    is_t1 = (k1 == t1[cell]) & real
    t2 = (
        jnp.zeros(table_size + 1, jnp.uint64)
        .at[cell]
        .max(jnp.where(is_t1, k2, jnp.uint64(0)), mode="drop")
    )
    is_t = is_t1 & (k2 == t2[cell])
    # FB[c]: first batch index that beats the stored winner — the only
    # prefix quantity the dup-free xor algebra needs (b-rows re-XOR
    # exactly when a beater precedes them).
    beats_e = (~a) & (~b) & real
    fb = (
        jnp.full(table_size + 1, n, jnp.int32)
        .at[cell]
        .min(jnp.where(beats_e, idx, jnp.int32(n)), mode="drop")
    )
    # Dup-free: eq_before ≡ False, so xor=False ⟺ b ∧ ¬gt_before; and
    # the cell max has a unique achiever, so upsert needs no
    # first-achiever tie-break.
    xor_mask = real & (~b | (fb[cell] < idx))
    upsert_mask = is_t & (~a) & (~b)
    return xor_mask, upsert_mask


# Mask-only dispatch for `plan_batch_device` (the plan-masks contract,
# original order — the sort path pays a device RESTORING sort to get
# back to batch order; this path never leaves it).
plan_masks_scatter = jax.jit(scatter_plan_masks, static_argnames=("table_size",))
