"""Pallas-vs-XLA timestamp-hash benchmark on REAL TPU silicon.

Runs `ops.pallas_hash._hash_blocks` NON-interpreted on the chip,
asserts bit-exactness against the XLA path (`encode.timestamp_hashes`)
at 1M hashes, and times both with K iterations fused into one jit so
the fixed per-dispatch cost amortizes out (same protocol as bench.py).

Requires a TPU backend: on any other it prints no number and exits
with code 2. The XLA path is production; the two have not been timed
on the attached chip.

Prints one JSON line.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from evolu_tpu.ops import pallas_hash as ph
from evolu_tpu.ops.encode import timestamp_hashes

N = 1 << 20
K = 16


def main():
    if jax.default_backend() != "tpu":
        print(f"pallas_hash_tpu measures the TPU and found backend "
              f"{jax.default_backend()!r}", file=sys.stderr)
        return 2
    rng = np.random.default_rng(0)
    with jax.enable_x64(True):
        millis = jax.device_put(jnp.asarray(
            (1_700_000_000_000 + rng.integers(0, 3_600_000, N)).astype(np.int64)))
        counter = jax.device_put(jnp.asarray(rng.integers(0, 65536, N).astype(np.int32)))
        node = jax.device_put(jnp.asarray(rng.integers(1, 2**63, N).astype(np.uint64)))

        @jax.jit
        def xla_k(millis, counter, node):
            acc = jnp.uint32(0)
            for i in range(K):
                h = timestamp_hashes(millis, counter ^ jnp.int32(i), node)
                acc = acc ^ jax.lax.reduce(h, jnp.uint32(0), jnp.bitwise_xor, (0,))
            return acc

        @jax.jit
        def split(millis, counter, node):
            ms = (millis % 1000).astype(jnp.uint32)
            secs = millis // 1000
            return ((secs // 86400).astype(jnp.int32).reshape(N // 128, 128),
                    (secs % 86400).astype(jnp.int32).reshape(N // 128, 128),
                    ms.reshape(N // 128, 128),
                    counter.reshape(N // 128, 128),
                    (node >> jnp.uint64(32)).astype(jnp.uint32).reshape(N // 128, 128),
                    node.astype(jnp.uint32).reshape(N // 128, 128))

        comps = jax.block_until_ready(split(millis, counter, node))
        expect = int(jax.block_until_ready(xla_k(millis, counter, node)))

    # The Pallas kernel is pure 32-bit: trace OUTSIDE the x64 scope or
    # Mosaic rejects the i64 grid index map (verified on the chip).
    with jax.enable_x64(False):
        days, sod, msr, c32, nh, nl = comps

        @jax.jit
        def pl_k(days, sod, msr, c32, nh, nl):
            acc = jnp.uint32(0)
            for i in range(K):
                c = (c32 ^ jnp.int32(i)).astype(jnp.uint32)
                h = ph._hash_blocks(days, sod, msr, c, nh, nl, interpret=False)
                acc = acc ^ jax.lax.reduce(h, jnp.uint32(0), jnp.bitwise_xor, (0, 1))
            return acc

        got = int(jax.block_until_ready(pl_k(days, sod, msr, c32, nh, nl)))
        assert got == expect, (hex(got), hex(expect))

        def median_iter_ms(fn, *args):
            ts = []
            for _ in range(10):
                t0 = time.perf_counter()
                jax.block_until_ready(fn(*args))
                ts.append(time.perf_counter() - t0)
            ts.sort()
            return ts[5] / K * 1000

        with jax.enable_x64(True):
            xla_ms = median_iter_ms(xla_k, millis, counter, node)
        pl_ms = median_iter_ms(pl_k, days, sod, msr, c32, nh, nl)

    print(json.dumps({
        "metric": "timestamp_hash_ms_per_1M_on_tpu",
        "value": round(min(xla_ms, pl_ms), 3),
        "unit": "ms",
        "detail": {
            "bit_exact": True, "n": N, "fused_iters": K,
            "xla_ms": round(xla_ms, 3), "pallas_ms": round(pl_ms, 3),
            "xla_mhashes_per_sec": round(N / xla_ms / 1000),
            "pallas_mhashes_per_sec": round(N / pl_ms / 1000),
            "winner": "xla" if xla_ms <= pl_ms else "pallas",
            "platform": jax.devices()[0].platform,
            "device_kind": jax.devices()[0].device_kind,
            "devices": len(jax.devices()),
        },
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
