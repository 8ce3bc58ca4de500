"""ImageNet AlexNet — the flagship/benchmark model (BASELINE.json
north-star: samples/sec/chip on AlexNet, scaling efficiency 1→8 chips).

Reference: the Znicz ImagenetWorkflow (absent submodule; architecture per
the AlexNet caffe config the reference's docs reference). TPU-first
choices: NHWC layout, bf16 compute with f32 master weights/accumulation,
227×227 inputs so conv1 (k11 s4) tiles cleanly, LRN after conv1/conv2 as in
the original.

ImageNet itself cannot live in HBM or be downloaded here; the loader is a
deterministic synthetic ImageNet-shaped stream (the throughput benchmark's
subject is the compute pipeline, not the JPEG decode — the reference's
fullbatch loader likewise pre-staged decoded tensors on device,
veles/loader/fullbatch.py:79)."""

from __future__ import annotations

import os

import numpy as np

from ..loader.base import TRAIN, VALID, Loader
from .standard import StandardWorkflow

ALEXNET_CONFIG = {
    "name": "AlexNet",
    "compute_dtype": "bfloat16",
    "layers": [
        {"type": "conv_relu", "n_kernels": 96, "kx": 11, "stride": 4,
         "padding": "VALID", "name": "conv1"},
        {"type": "lrn", "name": "lrn1", "method": "auto"},
        {"type": "max_pooling", "window": 3, "stride": 2, "name": "pool1"},
        {"type": "conv_relu", "n_kernels": 256, "kx": 5, "padding": 2,
         "name": "conv2"},
        {"type": "lrn", "name": "lrn2", "method": "auto"},
        {"type": "max_pooling", "window": 3, "stride": 2, "name": "pool2"},
        {"type": "conv_relu", "n_kernels": 384, "kx": 3, "padding": 1,
         "name": "conv3"},
        {"type": "conv_relu", "n_kernels": 384, "kx": 3, "padding": 1,
         "name": "conv4"},
        {"type": "conv_relu", "n_kernels": 256, "kx": 3, "padding": 1,
         "name": "conv5"},
        {"type": "max_pooling", "window": 3, "stride": 2, "name": "pool5"},
        {"type": "all2all_relu", "output_size": 4096, "name": "fc6"},
        {"type": "dropout", "dropout_ratio": 0.5, "name": "drop6"},
        {"type": "all2all_relu", "output_size": 4096, "name": "fc7"},
        {"type": "dropout", "dropout_ratio": 0.5, "name": "drop7"},
        {"type": "softmax", "output_size": 1000, "name": "fc8"},
    ],
    "loss": "softmax",
    "optimizer": "momentum",
    "optimizer_args": {"lr": 0.01, "momentum": 0.9, "l2": 5e-4},
    "max_epochs": 90,
}

INPUT_HW = 227


class ImagenetSyntheticLoader(Loader):
    """Deterministic ImageNet-shaped stream: 227x227x3 f32, 1000 classes.
    Batches are generated on the fly (no dataset residency), modeling the
    reference's streaming fallback for datasets beyond device memory
    (veles/loader/fullbatch.py:164-242)."""

    def __init__(self, minibatch_size=128, n_train=4096, n_valid=512,
                 n_classes=1000, seed=13, **kw):
        super().__init__(minibatch_size=minibatch_size, **kw)
        self.n_train = n_train
        self.n_valid = n_valid
        self.n_classes = n_classes
        self.seed = seed

    def load_data(self):
        self.class_lengths = [0, self.n_valid, self.n_train]

    def fill_minibatch(self, indices, klass):
        rng = np.random.default_rng(
            [self.seed, klass, int(indices[0]) if len(indices) else 0])
        n = len(indices)
        labels = (indices % self.n_classes).astype(np.int32)
        x = rng.standard_normal(
            (n, INPUT_HW, INPUT_HW, 3)).astype(np.float32)
        return {"@input": x, "@labels": labels}


class ImagenetHostLoader(Loader):
    """End-to-end input-pipeline variant: a host-resident uint8 image store
    with per-sample random crop + mirror augmentation on the host (the
    ImageLoader path, reference: veles/loader/image.py:106) and the
    uint8→float mean/disp normalization left ON DEVICE (the first workflow
    unit, backed by the Pallas mean_disp kernel) — so the host does only
    slicing + one memcpy per batch and the VPU does the arithmetic.

    Measures what the round-1 bench skipped: host augmentation + the
    Trainer's prefetch overlap (BASELINE.md staged vs end-to-end rows).
    """

    STORE_HW = 256  # stored image side; random-cropped to INPUT_HW

    def __init__(self, minibatch_size=128, n_train=4096, n_valid=512,
                 n_classes=1000, seed=13, **kw):
        super().__init__(minibatch_size=minibatch_size, **kw)
        self.n_train = n_train
        self.n_valid = n_valid
        self.n_classes = n_classes
        self.seed = seed
        self._store = None
        self._pool = None

    def _executor(self, workers: int):
        # one long-lived pool: per-batch executor create/join would recur
        # every minibatch of the throughput benchmark
        if self._pool is None:
            from concurrent.futures import ThreadPoolExecutor
            self._pool = ThreadPoolExecutor(workers)
        return self._pool

    def load_data(self):
        self._store, _ = _synth_store(self.n_train + self.n_valid,
                                      self.seed)
        self.class_lengths = [0, self.n_valid, self.n_train]

    def fill_minibatch(self, indices, klass):
        hw, out = self.STORE_HW, INPUT_HW
        base = self.n_valid if klass == TRAIN else 0
        rng = np.random.default_rng(
            [self.seed, klass, int(indices[0]) if len(indices) else 0])
        n = len(indices)
        if klass == TRAIN:
            offs = rng.integers(0, hw - out + 1, (n, 2))
            flip = rng.random(n) < 0.5
        else:
            c = (hw - out) // 2
            offs = np.full((n, 2), c)
            flip = np.zeros(n, bool)
        # contiguous-row slicing beats a sliding_window_view fancy gather
        # ~2x (the gather degenerates to element-wise copies); chunk over
        # a thread pool only when the host actually has cores — the
        # slice copies release the GIL (the reference ran loader work on
        # its thread pool likewise)
        idx = np.asarray(indices) + base
        xs = np.empty((n, out, out, 3), np.uint8)

        def fill(lo, hi):
            for i in range(lo, hi):
                oy, ox = offs[i]
                img = self._store[idx[i], oy:oy + out, ox:ox + out]
                xs[i] = img[:, ::-1] if flip[i] else img

        workers = min(8, os.cpu_count() or 1)
        if n >= 128 and workers > 1:
            chunk = -(-n // workers)
            list(self._executor(workers).map(
                lambda lo: fill(lo, min(lo + chunk, n)),
                range(0, n, chunk)))
        else:
            fill(0, n)
        labels = (indices % self.n_classes).astype(np.int32)
        return {"@input": xs, "@labels": labels}


def alexnet_workflow(minibatch_size=128, loader=None,
                     **overrides) -> StandardWorkflow:
    cfg = dict(ALEXNET_CONFIG)
    cfg.update(overrides)
    sw = StandardWorkflow(cfg)
    sw.loader = loader if loader is not None else \
        ImagenetSyntheticLoader(minibatch_size=minibatch_size)
    return sw


def _e2e_config(**overrides) -> dict:
    """AlexNet config with the device-side mean/disp normalize unit
    prepended — shared by BOTH e2e variants so they measure the same
    compute pipeline and differ only in where augmentation runs."""
    cfg = dict(ALEXNET_CONFIG)
    cfg["layers"] = [
        {"type": "norm", "name": "norm0",
         "mean": np.full((INPUT_HW, INPUT_HW, 3), 127.5, np.float32),
         "rdisp": np.full((INPUT_HW, INPUT_HW, 3), 1 / 64.0, np.float32)},
    ] + [dict(l) for l in ALEXNET_CONFIG["layers"]]
    cfg.update(overrides)
    return cfg


def _synth_store(n: int, seed: int = 13, n_classes: int = 1000):
    """Deterministic synthetic decoded-JPEG store (uint8 256x256x3) +
    labels — the single recipe behind every e2e input-pipeline variant.
    The pixels carry no signal; with ``n_classes`` well under the head's
    1000 the label prior alone is learnable, which is what lets a run of
    a few steps show a falling loss (``chip_smoke.py``)."""
    hw = ImagenetHostLoader.STORE_HW
    rng = np.random.default_rng(seed)
    store = rng.integers(0, 256, (n, hw, hw, 3), np.uint8)
    labels = np.arange(n, dtype=np.int32) % n_classes
    return store, labels


def alexnet_e2e_workflow(minibatch_size=128, n_train=4096,
                         **overrides) -> StandardWorkflow:
    """AlexNet fed through the host image path: uint8 batches from
    ImagenetHostLoader, normalized on device by a prepended MeanDisp unit
    (Pallas kernel) — the end-to-end throughput configuration."""
    sw = StandardWorkflow(_e2e_config(**overrides))
    sw.loader = ImagenetHostLoader(minibatch_size=minibatch_size,
                                   n_train=n_train)
    return sw


def alexnet_e2e_device_workflow(minibatch_size=128, n_train=4096,
                                n_valid=512, seed=13, n_classes=1000,
                                **overrides) -> StandardWorkflow:
    """End-to-end AlexNet on the TPU-native input pipeline: the uint8
    256x256 store lives in HBM (FullBatchAugmentedLoader) and the random
    crop + mirror + mean/disp normalize all run on device — per step the
    host ships indices and a few KB of augmentation descriptors, nothing
    else.  This is the formulation the host-streaming variant
    (alexnet_e2e_workflow) converges to when host->device bandwidth, not
    compute, is the binding constraint."""
    from ..loader.base import TRAIN, VALID
    from ..loader.fullbatch import FullBatchAugmentedLoader

    sw = StandardWorkflow(_e2e_config(**overrides))
    store, labels = _synth_store(n_train + n_valid, seed, n_classes)
    sw.loader = FullBatchAugmentedLoader(
        {TRAIN: store[n_valid:], VALID: store[:n_valid]},
        {TRAIN: labels[n_valid:], VALID: labels[:n_valid]},
        minibatch_size=minibatch_size, crop_hw=(INPUT_HW, INPUT_HW),
        mirror=True)
    return sw
