#!/usr/bin/env python
"""Allreduce bandwidth benchmark — the tools/bandwidth/measure.py analog.

Reference: tools/bandwidth/measure.py:139 (pushes model-sized gradients
through a kvstore for several rounds, reports per-device GB/s and a
correctness error).

TPU-native: measures BOTH comm paths —
  kvstore : per-key push/pull through the KVStore veneer (host round trip)
  fused   : one jitted psum over a dp mesh of the local devices (the path
            compiled training steps actually use; ICI/host-memory bound)

Run under a virtual mesh for CI boxes:
  XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \\
      python tools/bandwidth.py --num-devices 8
"""
import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--sizes", default="25e6,5e6,1e6",
                   help="comma list of gradient element counts "
                        "(default roughly resnet-scale buckets)")
    p.add_argument("--rounds", type=int, default=5)
    p.add_argument("--warmup", type=int, default=1)
    p.add_argument("--num-devices", type=int, default=0,
                   help="devices in the fused mesh (0 = all local)")
    p.add_argument("--test", action="store_true",
                   help="tiny sizes for CI")
    return p.parse_args(argv)


def main(argv=None):
    args = parse_args(argv)
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    import mxnet_tpu as mx

    sizes = [int(float(s)) for s in args.sizes.split(",")]
    if args.test:
        sizes = [4096, 1024]
    devs = jax.devices()
    n = args.num_devices or len(devs)
    devs = devs[:n]
    rng = np.random.default_rng(0)
    results = []

    # --- kvstore per-key path -------------------------------------------
    kv = mx.kv.create("local")
    vals = []
    for i, s in enumerate(sizes):
        v = mx.nd.array(rng.standard_normal(s).astype(np.float32))
        kv.init(i, mx.nd.zeros((s,)))
        vals.append(v)
    outs = [mx.nd.zeros((s,)) for s in sizes]
    for r in range(args.warmup + args.rounds):
        if r == args.warmup:
            t0 = time.perf_counter()
        for i, v in enumerate(vals):
            kv.push(i, v)
            kv.pull(i, out=outs[i])
        for o in outs:
            o.wait_to_read()
    dt = (time.perf_counter() - t0) / args.rounds
    nbytes = sum(s * 4 for s in sizes)
    # correctness: pull returns the last pushed value on the local store
    err = max(float(np.abs(o.asnumpy()[:64] - v.asnumpy()[:64]).max())
              for o, v in zip(outs, vals))
    results.append(("kvstore", 2 * nbytes / dt / 1e9, err))

    # --- fused psum over the device mesh --------------------------------
    if n > 1:
        mesh = Mesh(np.array(devs), ("dp",))
        sharded = NamedSharding(mesh, P("dp"))
        repl = NamedSharding(mesh, P())

        @jax.jit
        def allreduce(x):
            return jax.lax.with_sharding_constraint(
                x.sum(axis=0), repl)

        xs = []
        for s in sizes:
            per = rng.standard_normal((n, s)).astype(np.float32)
            xs.append(jax.device_put(per, sharded))
        expect = [x.sum(0) for x in [np.asarray(x) for x in xs]]
        outs = [allreduce(x) for x in xs]  # compile + warm
        jax.block_until_ready(outs)
        t0 = time.perf_counter()
        for _ in range(args.rounds):
            outs = [allreduce(x) for x in xs]
        jax.block_until_ready(outs)
        dt = (time.perf_counter() - t0) / args.rounds
        err = max(float(np.abs(np.asarray(o)[:64] - e[:64]).max())
                  for o, e in zip(outs, expect))
        # ring allreduce moves 2(n-1)/n of the data per device
        gbps = sum(s * 4 for s in sizes) * 2 * (n - 1) / n / dt / 1e9
        results.append(("fused-psum(x%d)" % n, gbps, err))

    for name, gbps, err in results:
        print("%-16s %8.2f GB/s/device   max_err %.2e" % (name, gbps, err))
    return results


if __name__ == "__main__":
    main()
