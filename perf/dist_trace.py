"""Structural trace of the n=8 fused data-parallel train step.

The north-star dist configuration (BASELINE.json v5e-16 dist_sync)
cannot run on this 1-chip harness, so the scaling argument rests on
program STRUCTURE: inside ONE compiled step over an 8-device mesh,
  * gradient all-reduces must appear a small, batch-size-independent
    number of times (XLA fuses the per-parameter psums), and
  * they must be interleaved with backward computation in the
    compiled schedule (not serialized after it), which is what lets
    real hardware overlap collectives with compute over ICI.

This inspects the optimized HLO of the Module's fused fwd+bwd+grad
step for a ResNet over a dp=8 virtual CPU mesh and reports:
  - all-reduce instruction count
  - schedule positions of the all-reduces (fraction through the entry
    computation's instruction sequence)
  - the fraction of convolution/fusion ops that appear AFTER the first
    all-reduce (nonzero => interleaved with backward, not appended)

Run: JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
     python perf/dist_trace.py
"""
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8").strip()

import numpy as np  # noqa: E402


def main():
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu.models import get_resnet_symbol
    from mxnet_tpu.parallel import data_parallel_plan
    from mxnet_tpu import io as mio

    B = 16
    net = get_resnet_symbol(num_classes=10, num_layers=18,
                            image_shape=(3, 32, 32), layout="NHWC")
    X = np.random.RandomState(0).uniform(0, 1, (B, 32, 32, 3)) \
        .astype(np.float32)
    y = (np.arange(B) % 10).astype(np.float32)
    it = mio.NDArrayIter(X, y, batch_size=B, label_name="softmax_label")
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.set_sharding_plan(data_parallel_plan())
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params()
    ex = mod._executor
    batch = next(iter(it))
    mod.forward(batch, is_train=True)
    mod.backward()                       # builds + runs the fused fwd_bwd

    fn = ex._fwd_bwd_jit[False]
    old = tuple(ex.grad_dict[n]._data for n in ex._dense_grad_names)
    lowered = fn.lower(ex._arg_vals(), ex._aux_vals(),
                       jax.random.PRNGKey(0), old)
    hlo = lowered.compile().as_text()

    lines = hlo.splitlines()
    # entry computation = the largest computation block
    blocks, cur = [], []
    for ln in lines:
        if ln.startswith("%") or ln.startswith("ENTRY"):
            if cur:
                blocks.append(cur)
            cur = [ln]
        elif cur:
            cur.append(ln)
    if cur:
        blocks.append(cur)
    entry = max(blocks, key=len)
    instr = [ln for ln in entry if "=" in ln]
    n_instr = len(instr)
    ar_pos = [i for i, ln in enumerate(instr) if
              re.search(r"= .*(all-reduce|all_reduce)", ln)]
    conv_pos = [i for i, ln in enumerate(instr)
                if "convolution" in ln or "fusion" in ln]
    after_first_ar = [p for p in conv_pos if ar_pos and p > ar_pos[0]]
    report = {
        "devices": len(jax.devices()),
        "entry_instructions": n_instr,
        "all_reduce_count": len(ar_pos),
        "all_reduce_positions_frac": [round(p / max(n_instr, 1), 3)
                                      for p in ar_pos],
        "compute_ops_total": len(conv_pos),
        "compute_ops_after_first_all_reduce": len(after_first_ar),
        "interleaved": bool(after_first_ar),
    }
    import json
    print(json.dumps(report))


if __name__ == "__main__":
    main()
