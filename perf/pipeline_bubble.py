"""Pipeline bubble-overhead measurement.

GPipe's schedule runs m + n - 1 ticks for m microbatches over n stages;
the (n-1)/(m+n-1) idle fraction is the bubble.  This measures it as the
step-time ratio between microbatch counts at FIXED total batch on the
virtual CPU mesh (relative tick costs are what matter; absolute CPU
times are not TPU times).

Run: JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
     python perf/pipeline_bubble.py
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8").strip()

import numpy as np  # noqa: E402


def main():
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu.parallel import PipelineModule
    from mxnet_tpu.io import DataBatch

    def conv_bn(nf, name, stride=(1, 1)):
        x = mx.sym.Variable("data")
        c = mx.sym.Convolution(x, num_filter=nf, kernel=(3, 3),
                               stride=stride, pad=(1, 1), no_bias=True,
                               name=name + "_conv")
        b = mx.sym.BatchNorm(c, fix_gamma=False, name=name + "_bn")
        return mx.sym.Activation(b, act_type="relu")

    pooled = mx.sym.Pooling(mx.sym.Variable("data"), global_pool=True,
                            kernel=(2, 2), pool_type="avg")
    head = mx.sym.FullyConnected(mx.sym.Flatten(pooled), num_hidden=10,
                                 name="head_fc")
    stages = [conv_bn(16, "embed"), conv_bn(16, "body", (2, 2)),
              conv_bn(16, "body2", (2, 2)), head]
    n = len(stages)
    B = 32
    rng = np.random.RandomState(0)
    X = rng.uniform(-1, 1, (B, 3, 32, 32)).astype(np.float32)
    Y = (np.arange(B) % 10).astype(np.float32)

    results = {}
    for m in (2, 4, 8, 16):
        pm = PipelineModule(stages, n_microbatch=m)
        pm.bind(data_shapes=[("data", (B, 3, 32, 32))])
        pm.init_params()
        pm.init_optimizer(learning_rate=0.01)
        batch = DataBatch(data=[mx.nd.array(X)], label=[mx.nd.array(Y)])
        pm.forward_backward(batch)
        pm.update()                                    # compile
        t0 = time.perf_counter()
        reps = 8
        for _ in range(reps):
            pm.forward_backward(batch)
            pm.update()
        _ = pm.loss
        dt = (time.perf_counter() - t0) / reps
        theo = (n - 1) / (m + n - 1)
        results[m] = dt
        print("m=%2d  step %7.1f ms   ticks %2d   theoretical bubble %4.1f%%"
              % (m, dt * 1e3, m + n - 1, 100 * theo))
    # measured bubble at m: extrapolate the per-tick cost from the two
    # largest m (each tick processes B/m samples, so normalize per sample)
    m_hi = 16
    per_tick_hi = results[m_hi] / (m_hi + n - 1)
    for m in (2, 4, 8):
        # bubble-free time is m-independent at fixed total batch: fewer,
        # proportionally bigger microbatches do the same work
        ideal = per_tick_hi * m_hi
        meas = results[m]
        print("m=%2d  measured bubble+overhead vs m=16-tick baseline: %4.1f%%"
              % (m, 100 * (meas - ideal) / meas))


if __name__ == "__main__":
    main()
