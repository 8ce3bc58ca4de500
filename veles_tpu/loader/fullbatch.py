"""Fullbatch loader: whole dataset resident in device HBM, minibatch gather
on device.

Reference parity: veles/loader/fullbatch.py:79 — dataset uploaded to device
memory once, minibatches gathered by a fill_minibatch_data_labels kernel
(ocl/fullbatch_loader.cl) from shuffled indices; graceful host fallback on
OOM (:164-242).

TPU redesign: the dataset lives as jax Arrays in HBM; the gather runs in a
tiny jitted function — the Pallas per-index DMA kernel on TPU (barrier'd
on-chip winner, 1.42x vs jnp.take) and ``jnp.take(data, idx, axis=0)``
elsewhere — so only the *indices* cross the host→device boundary each step
(the exact analog of the reference's ship-indices-only distributed
protocol, veles/loader/base.py:631-639). On HBM-overflow the loader
transparently degrades to host-side gather (ArrayLoader behavior),
mirroring the reference's OOM fallback.
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import use_pallas_default
from ..runtime import program_scopes
from .base import ArrayLoader, CLASS_NAMES, TEST, TRAIN, VALID

# Packed-DMA-gather eligibility, calibrated to an on-chip measurement of
# the loader's pack→gather→unpack path (3,136-byte rows at 30% pad
# overhead won 1.42x vs jnp.take on v5e) — don't pack below the
# measured-winning envelope.
_PACK_MIN_ROW_BYTES = 3072
_PACK_MAX_PAD = 1.35


class FullBatchLoader(ArrayLoader):
    """ArrayLoader whose gather happens on device."""

    def __init__(self, *args, device=None, force_host: bool = False,
                 use_pallas_gather: Optional[bool] = None, **kw):
        super().__init__(*args, **kw)
        self._device = device
        self._force_host = force_host
        self._use_pallas_gather = use_pallas_gather
        self._dev_data: Dict[int, dict] = {}
        self._gather = None
        self._compiled: Dict[tuple, object] = {}
        self.on_device = False

    def initialize(self):
        super().initialize()
        if self._force_host:
            return
        try:
            self._upload()
            self.on_device = True
            return
        except (RuntimeError, jax.errors.JaxRuntimeError) as e:
            self._dev_data.clear()
            if not self._want_pallas():
                # gather is plain jnp.take (no packed layout) — a retry
                # without packing would re-run a byte-identical upload.
                err = e
            else:
                # The packed-gather layout pads rows; if that padding is
                # what overflowed HBM, retry unpacked before giving up
                # device residency entirely.
                self.warning("device upload failed (%s); retrying without "
                             "packed gather", e)
                try:
                    self._upload(allow_pallas=False)
                    self.on_device = True
                    return
                except (RuntimeError, jax.errors.JaxRuntimeError) as e2:
                    err = e2
        # OOM fallback (reference: veles/loader/fullbatch.py:164-242).
        self.warning("device upload failed (%s); host-side gather", err)
        self._dev_data.clear()
        self.on_device = False

    def _want_pallas(self) -> bool:
        """Effective gather policy: explicit flag wins; None follows the
        shared platform default (Pallas on TPU — see comment in _upload)."""
        if self._use_pallas_gather is not None:
            return bool(self._use_pallas_gather)
        platform = (self._device.platform if self._device is not None
                    else None)
        return use_pallas_default(platform)

    def _upload(self, allow_pallas: bool = True):
        self._compiled = {}
        put = (lambda x: jax.device_put(x, self._device)) \
            if self._device is not None else jax.device_put
        for klass in (TEST, VALID, TRAIN):
            if self.class_lengths[klass] == 0:
                continue
            entry = {"@input": put(self._data[klass])}
            if self._labels.get(klass) is not None:
                entry["@labels"] = put(self._labels[klass])
            if self._targets.get(klass) is not None:
                entry["@targets"] = put(self._targets[klass])
            self._dev_data[klass] = entry

        # The Pallas DMA-gather kernel is the TPU default: measured on-chip
        # with an optimization_barrier'd harness (v5e, 512 rows of a
        # 60k x 784 set) the per-index DMA kernel wins — 0.63 ms vs
        # 0.89 ms for jnp.take (1.42x).  So the default follows the
        # platform policy, and ``use_pallas_gather=False`` forces
        # jnp.take.
        use_pallas = allow_pallas and self._want_pallas()
        if use_pallas:
            # Per-index HBM→HBM DMA kernel (parity:
            # ocl/fullbatch_loader.cl fill_minibatch_data_labels).  Big
            # arrays are packed into the kernel's tiled row layout ONCE
            # here.  Eligibility is the measured winning envelope alone:
            # the 784-feature f32 case (3.1 KB rows, padded to 1024
            # features = 30% HBM overhead) still won 1.42x, so rows of
            # >= _PACK_MIN_ROW_BYTES with padding overhead
            # <= _PACK_MAX_PAD are packed; labels, small and awkward
            # rows stay on jnp.take.
            from ..ops.pallas_kernels import (pack_rows, gather_rows_packed,
                                              unpack_rows)
            # packed_meta is PER (class, key): eligibility (via dtype)
            # can differ between classes of one dataset, and the gather
            # jit below must exactly match what its own class's arrays
            # look like.
            packed_meta = {}
            for klass, entry in self._dev_data.items():
                for key, arr in entry.items():
                    f = int(np.prod(arr.shape[1:]))
                    f_pad = -(-f // 1024) * 1024
                    # 4-byte dtypes only: the kernel's (8, 128) block
                    # tiling and the measurements are f32/i32; narrower
                    # dtypes tile differently and were never benched.
                    if (arr.dtype.itemsize == 4
                            and f * 4 >= _PACK_MIN_ROW_BYTES
                            and f_pad <= f * _PACK_MAX_PAD):
                        packed, f, sshape = pack_rows(arr)
                        entry[key] = packed
                        packed_meta[(klass, key)] = (f, tuple(sshape))

            def make_gather(klass):
                @jax.jit
                def gather(tree, idx):
                    out = {}
                    with jax.named_scope("loader_gather"):
                        for key, a in tree.items():
                            meta = packed_meta.get((klass, key))
                            if meta is not None:
                                f, sshape = meta
                                out[key] = unpack_rows(
                                    gather_rows_packed(a, idx), f, sshape)
                            else:
                                out[key] = jnp.take(a, idx, axis=0)
                    return out
                return gather

            self._gather = {klass: make_gather(klass)
                            for klass in self._dev_data}
        else:
            @jax.jit
            def take_gather(tree, idx):
                with jax.named_scope("loader_gather"):
                    return jax.tree.map(
                        lambda a: jnp.take(a, idx, axis=0), tree)

            self._gather = {klass: take_gather
                            for klass in self._dev_data}

    def _program(self, scope: str, klass: int, fn, *args):
        """``fn``, one of the loader's jitted programs, compiled for one
        class's arrays: once, ahead of its first call, so that what was
        compiled can be noted like a step program (the scope table of
        ``runtime/program_scopes.py``; a trace's events of the loader's
        own programs then go to ``loader_gather`` / ``loader_aug``).  The
        same program the jitted function would have compiled at that
        call, and the only compile of it."""
        compiled = self._compiled.get((scope, klass))
        if compiled is None:
            compiled = fn.lower(*args).compile()
            program_scopes.note_compiled(
                f"{scope}.{CLASS_NAMES[klass]}", compiled)
            self._compiled[(scope, klass)] = compiled
        return compiled

    def make_batch(self, chunk: np.ndarray, klass: int):
        if not self.on_device:
            return super().make_batch(chunk, klass)
        bs = self.minibatch_size
        valid_n = len(chunk)
        if valid_n < bs:
            chunk = np.concatenate(
                [chunk, np.zeros(bs - valid_n, chunk.dtype)])
        args = (self._dev_data[klass], jnp.asarray(chunk, jnp.int32))
        batch = dict(self._program("loader_gather", klass,
                                   self._gather[klass], *args)(*args))
        mask = np.zeros(bs, np.float32)
        mask[:valid_n] = 1.0
        batch["@mask"] = jnp.asarray(mask)
        return batch

class FullBatchAugmentedLoader(FullBatchLoader):
    """Device-side random-crop + mirror augmentation over a device-resident
    uint8 image store — the TPU-native input pipeline.

    Reference analog: the host image pipeline's random crop/mirror
    (veles/loader/image.py:106) feeding the fullbatch on-device gather
    (veles/loader/fullbatch.py:79).  The reference did augmentation on the
    host because its devices were remote OpenCL contexts; on TPU the HBM
    holds the decoded uint8 store and the crop/mirror is pure slicing, so
    the whole pipeline — gather by shuffled index, per-sample dynamic-slice
    crop, conditional mirror — runs inside ONE jitted function on device.
    Per step the host ships only the index vector plus a (B, 2) crop-offset
    array and a (B,) flip mask (a few KB), not the pixels: the gather
    half of the reference's ship-indices-only discipline, extended to
    augmentation descriptors.

    Train batches get random offsets/flips drawn deterministically from the
    loader PRNG stream (reproducible across resume/shards, like
    epoch_permutation); valid/test batches get the center crop, no flip.
    The host OOM fallback reproduces identical pixels with numpy slicing.
    """

    def __init__(self, *args, crop_hw, mirror: bool = True, **kw):
        # The packed Pallas gather stores rows flattened — useless here,
        # since the crop must slice the (H, W, C) geometry before any
        # reshape; the fused take+crop below IS the device path.
        if kw.pop("use_pallas_gather", None):
            raise ValueError(
                "FullBatchAugmentedLoader fuses its own take+crop device "
                "gather; use_pallas_gather does not apply")
        super().__init__(*args, use_pallas_gather=False, **kw)
        self.crop_hw = tuple(int(c) for c in crop_hw)
        self.mirror = bool(mirror)
        self._aug = None
        self._aug_epoch = 0

    def initialize(self):
        # Validate BEFORE the (possibly GB-scale) upload: otherwise the
        # same mistake fails three different ways later (np rng low>=high
        # on train, negative center offsets on the host path, XLA
        # dynamic_slice error on device).
        ch, cw = self.crop_hw
        for klass in (TEST, VALID, TRAIN):
            if self._data.get(klass) is None:
                continue
            if self._data[klass].ndim < 3:
                raise ValueError(
                    f"class-{klass} store must be (N, H, W[, C]) images, "
                    f"got shape {self._data[klass].shape}")
            hs, ws = self._store_hw(klass)
            if ch > hs or cw > ws:
                raise ValueError(
                    f"crop_hw {self.crop_hw} exceeds class-{klass} store "
                    f"geometry {(hs, ws)}")
        super().initialize()

    def _store_hw(self, klass: int):
        return self._data[klass].shape[1:3]

    def iter_epoch(self, klass, epoch=None):
        # Stash the epoch for _draw_aug (make_batch's signature has no
        # epoch): crops must differ per epoch even after shuffle_limit
        # freezes the permutation — epoch_permutation mixes epoch into
        # its seed for the same reason (base.py). Only TRAIN draws
        # consult it, so only a TRAIN iterator may write it — an eval
        # iterator started mid-train-epoch (spec probe, mid-epoch
        # validation) must not retroactively change the train crops.
        if klass == TRAIN:
            self._aug_epoch = (self.epoch_number if epoch is None
                               else int(epoch))
        yield from super().iter_epoch(klass, epoch)

    def _draw_aug(self, n: int, klass: int, anchor: int):
        """(offsets (n,2) int32, flips (n,) bool) for one minibatch —
        deterministic in (loader seed, epoch, klass, first index),
        matching the epoch_permutation determinism contract."""
        hs, ws = self._store_hw(klass)
        ch, cw = self.crop_hw
        if klass == TRAIN:
            from .. import prng
            rng = np.random.Generator(np.random.PCG64(
                [prng.get(self.prng_name).seed, self._aug_epoch, klass,
                 anchor, 0xC407]))
            offs = np.stack([rng.integers(0, hs - ch + 1, n),
                             rng.integers(0, ws - cw + 1, n)],
                            1).astype(np.int32)
            flips = (rng.random(n) < 0.5) if self.mirror \
                else np.zeros(n, bool)
        else:
            offs = np.broadcast_to(
                np.array([(hs - ch) // 2, (ws - cw) // 2], np.int32),
                (n, 2)).copy()
            flips = np.zeros(n, bool)
        return offs, flips

    def _upload(self, allow_pallas: bool = True):
        super()._upload(allow_pallas=False)
        ch, cw = self.crop_hw

        @jax.jit
        def aug(tree, idx, offs, flips):
            out = {}
            with jax.named_scope("loader_aug"):
                for key, a in tree.items():
                    if key == "@input":
                        imgs = jnp.take(a, idx, axis=0)

                        def crop1(img, off, flip):
                            c = jax.lax.dynamic_slice(
                                img,
                                (off[0], off[1]) + (0,) * (img.ndim - 2),
                                (ch, cw) + img.shape[2:])
                            return jnp.where(flip, c[:, ::-1], c)

                        out[key] = jax.vmap(crop1)(imgs, offs, flips)
                    else:
                        out[key] = jnp.take(a, idx, axis=0)
            return out

        self._aug = aug

    def make_batch(self, chunk: np.ndarray, klass: int):
        if not self.on_device:
            return super(FullBatchLoader, self).make_batch(chunk, klass)
        bs = self.minibatch_size
        valid_n = len(chunk)
        if valid_n < bs:
            chunk = np.concatenate(
                [chunk, np.zeros(bs - valid_n, chunk.dtype)])
        anchor = int(chunk[0]) if valid_n else 0
        offs, flips = self._draw_aug(bs, klass, anchor)
        args = (self._dev_data[klass], jnp.asarray(chunk, jnp.int32),
                jnp.asarray(offs), jnp.asarray(flips))
        batch = dict(self._program("loader_aug", klass, self._aug,
                                   *args)(*args))
        mask = np.zeros(bs, np.float32)
        mask[:valid_n] = 1.0
        batch["@mask"] = jnp.asarray(mask)
        return batch

    def fill_minibatch(self, indices, klass):
        """Host fallback: numpy slicing, pixel-identical to the device
        path (same _draw_aug descriptors)."""
        batch = super().fill_minibatch(indices, klass)
        ch, cw = self.crop_hw
        offs, flips = self._draw_aug(
            len(indices), klass, int(indices[0]) if len(indices) else 0)
        imgs = batch["@input"]
        out = np.empty(imgs.shape[:1] + (ch, cw) + imgs.shape[3:],
                       imgs.dtype)
        for i in range(len(imgs)):
            oy, ox = offs[i]
            c = imgs[i, oy:oy + ch, ox:ox + cw]
            out[i] = c[:, ::-1] if flips[i] else c
        batch["@input"] = out
        return batch
