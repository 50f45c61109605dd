#!/usr/bin/env python
"""Communication-bandwidth measurement (reference ``tools/bandwidth/`` —
measure.py benchmarks kvstore push/pull throughput across devices).

Measures, on whatever devices are visible:
  * host->device and device->host transfer bandwidth (the PCIe test analog);
  * all-reduce (psum) bus bandwidth over the device mesh — the ICI path on
    a real TPU slice, ring-simulated on a forced-host CPU mesh
    (XLA_FLAGS=--xla_force_host_platform_device_count=8 for a dry run);
  * kvstore push/pull round-trip throughput, matching the reference tool's
    workload shape.

Usage::

    python tools/bandwidth.py [--size-mb 64] [--iters 10]
"""
from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _bw(nbytes, seconds):
    return nbytes / seconds / 1e9


def measure_transfer(size_mb: float, iters: int):
    import jax
    import numpy as np

    n = int(size_mb * 1e6 / 4)
    host = np.random.RandomState(0).rand(n).astype(np.float32)
    dev = jax.device_put(host)
    dev.block_until_ready()

    t = time.time()
    for _ in range(iters):
        dev = jax.device_put(host)
        dev.block_until_ready()
    h2d = _bw(host.nbytes * iters, time.time() - t)

    t = time.time()
    for _ in range(iters):
        _ = np.asarray(dev)
    d2h = _bw(host.nbytes * iters, time.time() - t)
    return h2d, d2h


def measure_allreduce(size_mb: float, iters: int):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devs = jax.devices()
    if len(devs) < 2:
        return None, len(devs)
    mesh = Mesh(np.array(devs), ("dp",))
    n = int(size_mb * 1e6 / 4)
    x = jnp.zeros((len(devs), n), jnp.float32)
    x = jax.device_put(x, NamedSharding(mesh, P("dp", None)))

    @jax.jit
    def allreduce(x):
        # cross-shard sum: GSPMD lowers this to one all-reduce over the mesh
        return x.sum(0)

    y = allreduce(x)
    y.block_until_ready()
    t = time.time()
    for _ in range(iters):
        y = allreduce(x)
    y.block_until_ready()
    dt = (time.time() - t) / iters
    # ring all-reduce moves 2*(p-1)/p of the data per device
    p = len(devs)
    algo_bytes = 2 * (p - 1) / p * n * 4
    return _bw(algo_bytes * iters, dt * iters), p


def measure_kvstore(size_mb: float, iters: int):
    import numpy as np

    import incubator_mxnet_tpu as mx

    kv = mx.kv.create("device")
    n = int(size_mb * 1e6 / 4)
    val = mx.nd.array(np.random.RandomState(0).rand(n).astype(np.float32))
    kv.init("w", val)
    out = mx.nd.zeros((n,))
    kv.push("w", val)
    kv.pull("w", out=out)
    out.wait_to_read()
    t = time.time()
    for _ in range(iters):
        kv.push("w", val)
        kv.pull("w", out=out)
    out.wait_to_read()
    return _bw(val._data.nbytes * 2 * iters, time.time() - t)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--size-mb", type=float, default=64)
    ap.add_argument("--iters", type=int, default=10)
    args = ap.parse_args()

    h2d, d2h = measure_transfer(args.size_mb, args.iters)
    print("host->device : %7.2f GB/s" % h2d)
    print("device->host : %7.2f GB/s" % d2h)
    ar, ndev = measure_allreduce(args.size_mb, args.iters)
    if ar is None:
        print("all-reduce   : skipped (1 device; set "
              "XLA_FLAGS=--xla_force_host_platform_device_count=8 "
              "JAX_PLATFORMS=cpu for a mesh dry run)")
    else:
        print("all-reduce   : %7.2f GB/s bus bandwidth over %d devices"
              % (ar, ndev))
    kv_bw = measure_kvstore(args.size_mb, args.iters)
    print("kvstore push+pull: %7.2f GB/s" % kv_bw)


if __name__ == "__main__":
    main()
