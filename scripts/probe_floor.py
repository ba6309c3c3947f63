"""Probe the device->host pull floor and candidate workarounds.

Round-3 probes measured ~66 ms per device_get regardless of size
(PERF.md §1) — the floor IS the p50 of small queries. This probe checks
whether any supported output path beats it on the runtime at hand:

1. plain jax.device_get of jit outputs, several sizes (the baseline);
2. np.asarray on the output (same path, sanity);
3. copy_to_host_async + block, overlap-friendly variant;
4. jit with out_shardings memory_kind="pinned_host" (XLA writes the
   output into host-visible memory; the pull may skip a round trip);
5. dispatch/pull overlap: issue query B's device call before pulling
   query A's result (pipelining two in-flight queries).

Run: python scripts/probe_floor.py  (needs the TPU; ~1 min)
"""

import time

import jax
import jax.numpy as jnp
import numpy as np


def timeit(fn, n=10):
    fn()  # warm
    ts = []
    for _ in range(n):
        t = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t)
    return float(np.median(ts)) * 1e3


def main():
    dev = jax.devices()[0]
    print("device:", dev)
    x = jax.device_put(np.arange(1 << 20, dtype=np.float32), dev)

    @jax.jit
    def f(x, n):
        return (x[:n] * 2).sum(), x[:n] * 2

    for size in (128, 1 << 12, 1 << 16, 1 << 20):
        @jax.jit
        def g(x):
            return x[:size] * 2

        out = g(x)
        out.block_until_ready()
        ms = timeit(lambda: jax.device_get(g(x)))
        print(f"device_get jit out {size * 4 / 1024:.0f} KB: {ms:.1f} ms")

    # pinned_host output
    try:
        sh = jax.sharding.SingleDeviceSharding(dev, memory_kind="pinned_host")

        @jax.jit
        def h(x):
            return x[: 1 << 12] * 2

        hp = jax.jit(h, out_shardings=sh)
        out = hp(x)
        out.block_until_ready()
        ms = timeit(lambda: np.asarray(hp(x)))
        print(f"pinned_host out 16 KB: {ms:.1f} ms")
    except Exception as e:  # noqa: BLE001
        print("pinned_host unsupported:", type(e).__name__, str(e)[:120])

    # async copy overlap
    @jax.jit
    def g2(x):
        return x[: 1 << 12] * 2

    def overlap():
        a = g2(x)
        try:
            a.copy_to_host_async()
        except Exception:  # noqa: BLE001
            pass
        b = g2(x)  # second dispatch in flight
        ra = jax.device_get(a)
        rb = jax.device_get(b)
        return ra, rb

    ms = timeit(overlap)
    print(f"two overlapped queries: {ms:.1f} ms ({ms / 2:.1f} ms each)")

    # dispatch-only cost (no pull)
    def dispatch_only():
        g2(x).block_until_ready()

    print(f"dispatch+block, no pull: {timeit(dispatch_only):.1f} ms")


if __name__ == "__main__":
    main()
