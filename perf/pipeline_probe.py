"""Input-pipeline probe: threaded vs multiprocess, uint8 vs float32, and a
worker-scaling curve for the host-ceiling argument.

Writes JPEG + raw record files like bench.py's pipeline measurement and
times ImageRecordIterImpl streaming under each configuration.

Usage: python perf/pipeline_probe.py [--batch 256] [--image 224]
"""
import argparse
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def make_recs(tmpdir, n, stored):
    from mxnet_tpu import recordio
    rng = np.random.default_rng(0)
    raw = os.path.join(tmpdir, "raw")
    jpg = os.path.join(tmpdir, "jpg")
    wr = recordio.MXIndexedRecordIO(raw + ".idx", raw + ".rec", "w")
    wj = recordio.MXIndexedRecordIO(jpg + ".idx", jpg + ".rec", "w")
    for i in range(n):
        img = rng.integers(0, 256, (stored, stored, 3), dtype=np.uint8)
        header = recordio.IRHeader(0, float(i % 1000), i, 0)
        wr.write_idx(i, recordio.pack(header, img.tobytes()))
        wj.write_idx(i, recordio.pack_img(header, img, quality=90))
    wr.close()
    wj.close()
    return raw + ".rec", jpg + ".rec"


def rate(rec, batch, image, n_batches, workers, use_processes, **kw):
    from mxnet_tpu.image import ImageRecordIterImpl
    it = ImageRecordIterImpl(
        path_imgrec=rec, data_shape=(3, image, image), batch_size=batch,
        rand_crop=True, rand_mirror=True, shuffle=True, layout="NHWC",
        preprocess_threads=workers, prefetch_buffer=2,
        use_processes=use_processes, **kw)
    it.next()  # warm: page cache, pool spin-up (incl. spawn imports)
    t0 = time.perf_counter()
    done = 0
    while done < n_batches:
        try:
            it.next()
        except StopIteration:
            it.reset()
            continue
        done += 1
    r = n_batches * batch / (time.perf_counter() - t0)
    it.close()
    return round(r, 1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--image", type=int, default=224)
    ap.add_argument("--batches", type=int, default=4)
    args = ap.parse_args()
    stored = args.image + 32
    tmpdir = tempfile.mkdtemp(prefix="piperec")
    out = {"host_cores": os.cpu_count()}
    try:
        raw, jpg = make_recs(tmpdir, 2 * args.batch, stored)
        rkw = dict(raw_shape=(stored, stored, 3), dtype="uint8")
        out["raw_u8_threads2"] = rate(raw, args.batch, args.image,
                                      args.batches, 2, False, **rkw)
        # jpeg: float32+scale (the r3 measurement) vs uint8 end-to-end
        # (the shape the fused train step actually ingests - it normalizes
        # in-graph, so host float conversion is pure waste)
        out["jpeg_f32_threads2"] = rate(jpg, args.batch, args.image,
                                        args.batches, 2, False,
                                        dtype="float32", scale=1 / 255.0)
        out["jpeg_u8_threads2"] = rate(jpg, args.batch, args.image,
                                       args.batches, 2, False, dtype="uint8")
        for w in (1, 2, 4):
            out[f"jpeg_u8_procs{w}"] = rate(jpg, args.batch, args.image,
                                            args.batches, w, True,
                                            dtype="uint8")
        out[f"raw_u8_procs2"] = rate(raw, args.batch, args.image,
                                     args.batches, 2, True, **rkw)
    finally:
        shutil.rmtree(tmpdir, ignore_errors=True)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
