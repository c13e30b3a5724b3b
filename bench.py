"""Benchmark driver: ResNet-50 training throughput + MFU on one TPU chip.
Fails without a TPU: a CPU timing is never printed under these names.

Baseline: the reference's published 109 images/sec training ResNet-50,
1x K80, batch 32 (example/image-classification/README.md:147-155;
BASELINE.md).  Prints ONE JSON line.

The benched step is symbolic ResNet-50 (NHWC internal layout — the
TPU-preferred channels-last form the Convolution op supports via its
reference `layout` parameter) traced to ONE fused fwd+bwd+SGD XLA program,
batch 256 bf16.  Input normalization (uint8 -> bf16, scale) runs in-graph:
batches cross host->device as uint8 NHWC (4x less transfer than f32) and
the chip does the cast.

Measurements:
  1. compute: block-average step time on a resident device batch (see the
     protocol comment at the measurement).  This is `mfu`.
  2. pipeline: the streaming rate of ImageRecordIter itself — RecordIO
     read, rand-crop 224 from stored 256, mirror, batch assembly on this
     host (`pipeline_images_per_sec` for raw records,
     `pipeline_jpeg_images_per_sec` for JPEG decode), measured by
     perf/pipeline_probe.py after the device phase has exited.
     `piped_images_per_sec` is min(compute, pipeline);
     `input_bound_raw_records` / `input_bound_jpeg` say which side binds.
  3. `train_jpeg_images_per_sec`: JPEG decode overlapped with device
     steps inside the device phase.
  4. tools/bandwidth.py, twice: the local kvstore on the chip, and the
     compiled psum over 8 virtual CPU devices.

One process per chip: main() never imports JAX and runs each of these as
a child, one after another, so no child that needs the chip starts while
another process holds it.  Any child that fails fails the run.

MFU uses XLA's own per-step FLOP count (cost_analysis, multiply-add = 2
FLOPs) against the chip's bf16 peak (telemetry/step.py PEAKS_TFLOPS); a
device kind that is not in that table is an error.
"""
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

def _peak_for(device):
    """bf16 peak FLOP/s of ``device``; an unknown kind has no honest MFU
    denominator and ends the run.  The table lives in telemetry/step.py
    so this bench and the live ``mxnet_train_mfu`` gauge share it."""
    from mxnet_tpu.telemetry.step import peak_flops_for
    peak = peak_flops_for(device)
    if peak is None:
        raise SystemExit(
            "bench: no peak FLOP/s known for device kind %r; add it to "
            "PEAKS_TFLOPS in mxnet_tpu/telemetry/step.py with its source"
            % getattr(device, "device_kind", None))
    return peak


def _make_raw_rec(path, n, stored, seed=0):
    """Pack n random raw-uint8 records at stored x stored (the
    `im2rec --encoding raw` format)."""
    from mxnet_tpu import recordio
    rng = np.random.default_rng(seed)
    w = recordio.MXIndexedRecordIO(path + ".idx", path + ".rec", "w")
    for i in range(n):
        img = rng.integers(0, 256, (stored, stored, 3), dtype=np.uint8)
        header = recordio.IRHeader(0, float(i % 1000), i, 0)
        w.write_idx(i, recordio.pack(header, img.tobytes()))
    w.close()
    return path + ".rec"


def _device_main():
    import jax
    import jax.numpy as jnp
    import mxnet_tpu  # noqa: F401
    from mxnet_tpu import config
    from mxnet_tpu.models import get_resnet_symbol
    from mxnet_tpu.executor import build_graph_fn
    from mxnet_tpu.image import ImageRecordIterImpl

    config.compile_cache_dir()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit("bench: needs a TPU; JAX found %s" % jax.devices())
    peak = _peak_for(dev)
    batch = 256
    image = 224
    stored = image + 32  # rand-crop window source size
    # bf16 params+activations: the TPU-idiomatic training dtype (MXU-native);
    # labels/loss/batch-norm stats stay f32
    dtype = jnp.bfloat16

    # stem="fused": input-BN + stem conv with the rectangle-sum dbeta
    # backward — identical math to the reference graph (equivalence-tested,
    # tests/test_bn_stem.py).  An earlier chip record, since deleted, had it
    # at 91.9 ms against 94.7 ms for the plain stem and stem="s2d" slower
    # than both; not measured on today's code.
    net = get_resnet_symbol(num_classes=1000, num_layers=50,
                            image_shape=(3, image, image), layout="NHWC",
                            stem="fused")
    arg_names = net.list_arguments()
    aux_names = net.list_auxiliary_states()
    graph_fn = build_graph_fn(net, arg_names, aux_names)
    shapes = {"data": (batch, image, image, 3), "softmax_label": (batch,)}
    arg_shapes, _, aux_shapes = net.infer_shape(**shapes)

    rng = np.random.RandomState(0)
    data_names = {"data", "softmax_label"}
    params = []
    grad_idx = [i for i, n in enumerate(arg_names) if n not in data_names]
    for i in grad_idx:
        params.append(jnp.asarray(
            rng.uniform(-0.05, 0.05, arg_shapes[i]).astype(np.float32),
            dtype))
    params = tuple(params)
    auxs = tuple(jnp.zeros(s, jnp.float32) if "mean" in n
                 else jnp.ones(s, jnp.float32)
                 for n, s in zip(aux_names, aux_shapes))
    data_pos = arg_names.index("data")
    label_pos = arg_names.index("softmax_label")
    lr = 0.05
    inv255 = 1.0 / 255.0

    def train_step(data_u8, labels, params, auxs, key):
        # in-graph input normalization: uint8 HWC batch → scaled bf16.
        # XLA fuses this into the first conv's input; host ships 1 byte/px.
        data = data_u8.astype(dtype) * jnp.asarray(inv255, dtype)

        def loss_fn(*wrt):
            av = [None] * len(arg_names)
            av[data_pos] = data
            av[label_pos] = labels
            for i, w in zip(grad_idx, wrt):
                av[i] = w
            outs, new_aux = graph_fn(tuple(av), auxs, key, True)
            probs = outs[0].astype(jnp.float32)
            lab = labels.astype(jnp.int32)
            ll = -jnp.mean(jnp.log(probs[jnp.arange(probs.shape[0]),
                                         lab] + 1e-8))
            return ll, new_aux

        (loss, new_aux), grads = jax.value_and_grad(
            loss_fn, argnums=tuple(range(len(params))), has_aux=True)(*params)
        new_params = tuple(p - jnp.asarray(lr, p.dtype) * g
                           for p, g in zip(params, grads))
        return loss, new_params, new_aux

    step = jax.jit(train_step, donate_argnums=(2,))
    key = jax.random.PRNGKey(0)
    data_u8 = jnp.asarray(rng.randint(0, 255, shapes["data"], dtype=np.uint8))
    labels = jnp.asarray(rng.randint(0, 1000, (batch,)).astype(np.float32))
    compiled = step.lower(data_u8, labels, params, auxs, key).compile()
    step_flops = compiled.cost_analysis()["flops"]
    # cross-check: the static analytic count (analysis/flops.py — the
    # live mxnet_train_mfu gauge's numerator) against XLA's own number
    # for the same program; reported side by side so drift is visible
    from mxnet_tpu.analysis.flops import count_flops
    analytic_flops = count_flops(net, shapes, training=True)["total"]

    # ---- compute-only measurement ----
    # Protocol ("r4_block_min"): the first ~10 calls after a compile run
    # 2-2.5x slow, so warm up past that transient, then time independent
    # K-step blocks end-to-end (params are donated and chain call-to-call,
    # so every step really executes) and take the MINIMUM block average —
    # lower-bounded by true device time, stalls can only add.
    loss, params, auxs = compiled(data_u8, labels, params, auxs, key)
    _ = float(np.asarray(loss))

    # ---- overlapped end-to-end (before the long compute blocks) ----
    # JPEG decode OVERLAPPED with device train steps: each iteration pulls
    # the next decoded batch while the device runs a step on the resident
    # batch (decoded pixels are not shipped to the device).  Threaded
    # pool: cv2 releases the GIL.  The host pipeline's own capability keys
    # are measured by main() in a clean process after this one exits.
    tmpdir = tempfile.mkdtemp(prefix="benchrec")
    try:
        n_rec = 2 * batch
        rec = _make_raw_rec(os.path.join(tmpdir, "train"), n_rec, stored)
        from mxnet_tpu import recordio as _rio
        jrec = os.path.join(tmpdir, "train_jpg")
        w = _rio.MXIndexedRecordIO(jrec + ".idx", jrec + ".rec", "w")
        rd = _rio.MXIndexedRecordIO(None, rec, "r")
        for k in rd.keys[:n_rec // 2]:
            hdr, buf = _rio.unpack(rd.read_idx(k))
            img = np.frombuffer(buf, np.uint8).reshape(stored, stored, 3)
            w.write_idx(k, _rio.pack_img(hdr, img, quality=90))
        w.close()
        rd.close()
        it_e2e = ImageRecordIterImpl(
            path_imgrec=jrec + ".rec", data_shape=(3, image, image),
            batch_size=batch, rand_crop=True, rand_mirror=True,
            shuffle=True, layout="NHWC",
            preprocess_threads=max(4, (os.cpu_count() or 1)),
            prefetch_buffer=2, use_processes=False, dtype="uint8")
        it_e2e.next()  # warm the pool

        def _next_batch():
            try:
                return it_e2e.next()
            except StopIteration:
                it_e2e.reset()
                return it_e2e.next()
        n_e2e = 12
        # warm PAST the post-compile transient (see the protocol comment
        # above), then two overlapped warm iterations for the decode pool
        for i in range(18):
            loss, params, auxs = compiled(
                data_u8, labels, params, auxs,
                jax.random.fold_in(key, 19_000 + i))
        for i in range(2):
            _next_batch()
            loss, params, auxs = compiled(
                data_u8, labels, params, auxs,
                jax.random.fold_in(key, 20_000 + i))
        _ = float(np.asarray(loss))
        t0 = time.perf_counter()
        for i in range(n_e2e):
            _next_batch()
            loss, params, auxs = compiled(
                data_u8, labels, params, auxs,
                jax.random.fold_in(key, 30_000 + i))
        _ = float(np.asarray(loss))  # sync
        e2e_jpeg = n_e2e * batch / (time.perf_counter() - t0)
        it_e2e.close()
    finally:
        shutil.rmtree(tmpdir, ignore_errors=True)

    k2, warm, reps = 100, 20, 3
    for i in range(warm):
        loss, params, auxs = compiled(data_u8, labels, params, auxs,
                                      jax.random.fold_in(key, 10_000 + i))
    _ = float(np.asarray(loss))
    averages = []
    for _rep in range(reps):
        t0 = time.perf_counter()
        for i in range(k2):
            loss, params, auxs = compiled(data_u8, labels, params, auxs,
                                          jax.random.fold_in(key, i))
        _ = float(np.asarray(loss))  # true host sync
        averages.append((time.perf_counter() - t0) / k2)
    dt = min(averages)

    imgs_per_sec = batch / dt
    mfu = step_flops / dt / peak
    baseline = 109.0  # K80 batch-32 training img/s (BASELINE.md)
    result = {
        "metric": "resnet50_train_images_per_sec",
        "value": round(imgs_per_sec, 2),
        "unit": "images/sec",
        "vs_baseline": round(imgs_per_sec / baseline, 3),
        "mfu": round(mfu, 4),
        "step_ms": round(dt * 1e3, 2),
        "batch": batch,
        "xla_gflops_per_step": round(step_flops / 1e9, 1),
        "analytic_gflops_per_step": round(analytic_flops / 1e9, 1),
        "peak_tflops": round(peak / 1e12, 1),
        "device": dev.device_kind,
        "platform": dev.platform,
        "device_count": len(jax.devices()),
        "host_cores": os.cpu_count(),
        "protocol": "r4_block_min",
    }
    # decode pool overlapped with device training steps
    result["train_jpeg_images_per_sec"] = round(e2e_jpeg, 2)
    print(json.dumps(result))


def _child(argv, timeout, env=None):
    """Run one child to its end and return its stdout; a child that
    fails fails the run."""
    import subprocess
    proc = subprocess.run([sys.executable] + argv, capture_output=True,
                          text=True, timeout=timeout, env=env)
    if proc.returncode != 0:
        sys.stderr.write(proc.stdout[-2000:] + proc.stderr[-4000:])
        raise SystemExit("bench: %s exited with %d"
                         % (os.path.basename(argv[0]), proc.returncode))
    return proc.stdout


def _bandwidth(here, result):
    """tools/bandwidth.py, twice, each in a process of its own after the
    device phase has released the chip (SURVEY acceptance number,
    tools/bandwidth/README.md 11.1 GB/s/GPU baseline)."""
    import re
    tool = os.path.join(here, "tools", "bandwidth.py")
    rx = re.compile(r"^(\S+)\s+([0-9.]+) GB/s/device\s+max_err\s+(\S+)",
                    re.M)
    rows = rx.findall(_child(
        [tool, "--rounds", "3", "--sizes", "25e6,5e6"], 300))
    # per-key push/pull through kv.create("local"): the device-LOCAL store
    # path, never cross-device communication, however many chips the host
    # has — so the key says local HBM and cannot be read against the
    # reference's cross-device number
    (gbps, err), = [(g, e) for name, g, e in rows if name == "kvstore"]
    result["kvstore_push_pull_local_hbm_gbps"] = round(float(gbps), 2)
    result["kvstore_bandwidth_max_err"] = float(err)
    env8 = dict(os.environ,
                XLA_FLAGS="--xla_force_host_platform_device_count=8",
                JAX_PLATFORMS="cpu")
    rows = rx.findall(_child(
        [tool, "--rounds", "3", "--sizes", "5e6,1e6", "--num-devices", "8"],
        300, env=env8))
    # compiled psum over 8 VIRTUAL cpu devices: host-memory bound, says
    # nothing about the chip's interconnect
    gbps, = [g for name, g, _e in rows if name.startswith("fused-psum")]
    result["allreduce_gbps_virtual8"] = round(float(gbps), 3)


def main():
    """Orchestration from a process that never imports JAX: the device
    phase, the two bandwidth runs and the host-pipeline probe each run as
    a child, one after another, so the chip has one owner at a time and
    the pipeline probe has the host's cores to itself."""
    here = os.path.dirname(os.path.abspath(__file__))
    out = _child([os.path.abspath(__file__), "--device-phase"], 1800)
    result = json.loads(out.strip().splitlines()[-1])
    _bandwidth(here, result)
    probe = json.loads(_child(
        [os.path.join(here, "perf", "pipeline_probe.py"),
         "--batch", str(result["batch"]), "--image", "224",
         "--batches", "4"], 900).strip().splitlines()[-1])
    pipe_raw = max(probe["raw_u8_procs2"], probe["raw_u8_threads2"])
    pipe_jpeg = max(probe["jpeg_u8_procs1"], probe["jpeg_u8_procs2"],
                    probe["jpeg_u8_procs4"], probe["jpeg_u8_threads2"])
    chip = result["value"]
    piped = min(chip, pipe_raw)
    result["pipeline_images_per_sec"] = round(pipe_raw, 2)
    result["pipeline_images_per_sec_threads"] = round(
        probe["raw_u8_threads2"], 2)
    result["piped_images_per_sec"] = round(piped, 2)
    result["piped_mfu"] = round(result["mfu"] * piped / chip, 4)
    result["input_bound_raw_records"] = bool(pipe_raw < chip)
    result["pipeline_jpeg_images_per_sec"] = round(pipe_jpeg, 2)
    result["input_bound_jpeg"] = bool(pipe_jpeg < chip)
    result["pipeline_jpeg_f32_images_per_sec"] = round(
        probe["jpeg_f32_threads2"], 2)
    print(json.dumps(result))


if __name__ == "__main__":
    if "--device-phase" in sys.argv:
        _device_main()
    else:
        main()
