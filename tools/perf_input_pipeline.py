"""Input-pipeline throughput benchmark (VERDICT r4 missing #1): can the
host decode+augment fast enough to feed the chip?

Measures, in decoded+augmented 224x224 images/sec:
  1. raw cv2 JPEG decode                      (the floor every pipeline shares)
  2. decode + standard training augmentation  (resize/crop/mirror/normalize,
     the ImageRecordIter v2 work: reference src/io/iter_image_recordio_2.cc:672)
  3. the same through ImageIter over an in-memory RecordIO pack
  4. gluon DataLoader with N multiprocess workers over a jpeg dataset

Prints one JSON line per measurement plus a feed-rate verdict against the
ResNet-50 north star (4,015 img/s needs ~0.6 GB/s of decoded pixels). On a
1-core host the per-core rate and the measured worker-scaling efficiency
are the honest numbers; the verdict extrapolates linearly with a measured
overlap coefficient, because decode parallelism across processes is what
the architecture provides (reference runs the same pipeline with
decode threads on a many-core trainer host).

Usage: python tools/perf_input_pipeline.py [--n 256] [--workers 4]
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
# This is a HOST pipeline benchmark: decode and augmentation run on the
# host's cores, so it pins jax to the CPU and never takes the chip.
os.environ["JAX_PLATFORMS"] = "cpu"

import numpy as np  # noqa: E402


def _jpegs(n, size=224, quality=90):
    import cv2
    rng = np.random.RandomState(0)
    bufs = []
    # natural-ish images (smooth gradients + noise) so jpeg work is realistic
    for i in range(8):
        yy, xx = np.mgrid[0:size, 0:size].astype(np.float32) / size
        img = np.stack([
            128 + 100 * np.sin(3 * yy + i) + rng.normal(0, 12, (size, size)),
            128 + 100 * np.cos(2 * xx + i) + rng.normal(0, 12, (size, size)),
            128 + 80 * np.sin(4 * (xx + yy)) + rng.normal(0, 12, (size, size)),
        ], axis=2).clip(0, 255).astype(np.uint8)
        ok, buf = cv2.imencode(".jpg", img,
                               [cv2.IMWRITE_JPEG_QUALITY, quality])
        assert ok
        bufs.append(buf.tobytes())
    return [bufs[i % len(bufs)] for i in range(n)]


def _bench(label, fn, n, unit="img/s"):
    t0 = time.perf_counter()
    fn()
    dt = time.perf_counter() - t0
    rate = n / dt
    print(json.dumps({"metric": "input_pipeline/%s" % label,
                      "value": round(rate, 1), "unit": unit,
                      "n": n, "seconds": round(dt, 3)}), flush=True)
    return rate


class JpegDataset:
    """Decode+augment dataset for DataLoader workers (module-level: spawn
    pickles it by value)."""

    def __init__(self, bufs, train=True):
        self.bufs = bufs
        self.train = train

    def __len__(self):
        return len(self.bufs)

    def __getitem__(self, i):
        import cv2
        img = cv2.imdecode(np.frombuffer(self.bufs[i], np.uint8),
                           cv2.IMREAD_COLOR)
        img = cv2.cvtColor(img, cv2.COLOR_BGR2RGB)
        rng = np.random.RandomState(i)
        if self.train:
            # random crop to 200 then resize back + mirror: the standard
            # augmenter stack's work profile
            y0, x0 = rng.randint(0, 24), rng.randint(0, 24)
            img = img[y0:y0 + 200, x0:x0 + 200]
            img = cv2.resize(img, (224, 224))
            if rng.rand() < 0.5:
                img = img[:, ::-1]
        out = img.astype(np.float32)
        out -= np.array([123.68, 116.779, 103.939], np.float32)
        return np.ascontiguousarray(out.transpose(2, 0, 1)), np.float32(i % 10)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=256)
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--batch", type=int, default=32)
    args = ap.parse_args()
    import cv2

    bufs = _jpegs(args.n)
    ds = JpegDataset(bufs)

    # 1. decode only
    def decode_all():
        for b in bufs:
            cv2.imdecode(np.frombuffer(b, np.uint8), cv2.IMREAD_COLOR)
    decode_rate = _bench("decode", decode_all, args.n)

    # 2. decode + augment (the full per-image host work)
    def aug_all():
        for i in range(len(ds)):
            ds[i]
    aug_rate = _bench("decode_augment", aug_all, args.n)

    # 3. ImageIter over an in-memory RecordIO pack
    import tempfile
    import mxtpu as mx
    from mxtpu import recordio
    with tempfile.TemporaryDirectory() as td:
        rec_path = os.path.join(td, "bench.rec")
        idx_path = os.path.join(td, "bench.idx")
        w = recordio.MXIndexedRecordIO(idx_path, rec_path, "w")
        for i, b in enumerate(bufs):
            hdr = recordio.IRHeader(0, float(i % 10), i, 0)
            w.write_idx(i, recordio.pack(hdr, b))
        w.close()
        it = mx.image.ImageIter(batch_size=args.batch,
                                data_shape=(3, 224, 224),
                                path_imgrec=rec_path, path_imgidx=idx_path,
                                shuffle=False)

        def iter_all():
            it.reset()
            for _ in it:
                pass
        imgiter_rate = _bench("imageiter_recordio", iter_all,
                              (args.n // args.batch) * args.batch)

        itt = mx.image.ImageIter(batch_size=args.batch,
                                 data_shape=(3, 224, 224),
                                 path_imgrec=rec_path,
                                 path_imgidx=idx_path, shuffle=False,
                                 preprocess_threads=args.workers)

        def iter_all_threaded():
            itt.reset()
            for _ in itt:
                pass
        _bench("imageiter_recordio_%dthreads" % args.workers,
               iter_all_threaded, (args.n // args.batch) * args.batch)

    # 4. DataLoader with multiprocess workers
    from mxtpu.gluon.data import DataLoader
    dl = DataLoader(ds, batch_size=args.batch, num_workers=args.workers)
    list(dl)  # warm the spawned pool (not measured)
    mp_rate = _bench("dataloader_%dproc" % args.workers,
                     lambda: list(dl), args.n)
    dl.close()
    dl0 = DataLoader(ds, batch_size=args.batch, num_workers=0)
    serial_rate = _bench("dataloader_serial", lambda: list(dl0), args.n)

    ncore = os.cpu_count() or 1
    overlap = mp_rate / serial_rate
    # feed-rate verdict: linear scaling at the measured per-core augment
    # rate times the measured process-overlap efficiency per added core
    eff = min(overlap / min(args.workers, max(ncore, 1)), 1.0) if ncore > 1 \
        else 1.0
    need = 4015.0
    cores_needed = need / (aug_rate * eff)
    print(json.dumps({
        "metric": "input_pipeline/feed_verdict",
        "per_core_decode_augment_img_s": round(aug_rate, 1),
        "host_cores": ncore,
        "measured_process_overlap_x": round(overlap, 2),
        "cores_for_4015_img_s": round(cores_needed, 1),
        "unit": "summary"}), flush=True)


if __name__ == "__main__":
    main()
