"""Training-data stores: raw vs ZFP-compressed, online decompression.

Implements the paper's two workflows (Fig. 2):
  workflow 1: RawArrayStore        -- one raw array file per sample
  workflow 2: CompressedArrayStore -- per-sample ZFP streams; each batch
              access reads the compressed bytes and decodes on device via
              the Codec layer (kernel path; compiled oracle on CPU).

This is the data layer's home for the ``ArrayStore`` protocol, IO accounting
and the bandwidth throttle (they historically lived in ``core.pipeline``;
that shim is gone -- stores must not import *upward* from core).
All stores count bytes moved and read time so the Fig. 11/12 benchmarks can
report data-loading throughput and per-epoch time.  The optional bandwidth
throttle emulates the paper's three file systems (workspace / VAST / GPFS)
on the container's single disk -- DESIGN.md §8 records this adaptation.
"""
from __future__ import annotations

import os
import time
from typing import Optional, Protocol, Sequence, Tuple, runtime_checkable

import jax.numpy as jnp
import numpy as np

from repro.compression import decode_stacked_payloads, get_codec
from repro.obs import trace as obs_trace
# THE IoStats implementation (single definition, registry-backed, with
# merge/reset/snapshot) lives in the observability layer; this re-export is
# the stores' historical import location.
from repro.obs.metrics import IoStats


@runtime_checkable
class ArrayStore(Protocol):
    """Protocol every training-data store implements.

    Shared by RawArrayStore, CompressedArrayStore,
    repro.data.shards.ShardedCompressedStore and
    repro.data.device_store.DeviceResidentCompressedStore, so loaders,
    benchmarks and the train loop are store-agnostic: anything with indexed
    batch access, IO accounting, and a logical footprint.
    """
    stats: IoStats
    shape: Tuple[int, ...]
    num_samples: int
    sample_nbytes: int

    def get_batch(self, idx: np.ndarray) -> jnp.ndarray: ...

    @property
    def stored_bytes(self) -> int: ...


def throttle(nbytes: int, started: float, bandwidth_mbs: Optional[float]):
    """Sleep until ``nbytes`` would have moved at ``bandwidth_mbs`` MB/s."""
    if bandwidth_mbs is None:
        return
    needed = nbytes / (bandwidth_mbs * 1e6)
    elapsed = time.perf_counter() - started
    if needed > elapsed:
        time.sleep(needed - elapsed)


_throttle = throttle          # historical (underscored) name, still imported


def channels_last(batch: jnp.ndarray) -> jnp.ndarray:
    """(B, C, H, W) store batch -> (B, H, W, C) model layout.

    The stores compress over the trailing two dims, so they hold samples
    channels-first; the surrogate consumes channels-last.  Pass this as
    ``train_surrogate(..., target_transform=channels_last)``.  Pure jnp, so
    it traces into the fused device-resident train step unchanged.
    """
    return jnp.transpose(batch, (0, 2, 3, 1))


class RawArrayStore:
    """One raw .npy per sample (paper: one HDF5 per sample), or in-memory."""

    def __init__(self, samples: Sequence[np.ndarray] | np.ndarray,
                 root: Optional[str] = None,
                 bandwidth_mbs: Optional[float] = None):
        self.bandwidth_mbs = bandwidth_mbs
        self.stats = IoStats()
        self._mem = None
        self.root = root
        n = len(samples)
        self.shape = tuple(np.asarray(samples[0]).shape)
        if root is None:
            # same float32 cast as the on-disk path: float64 inputs must not
            # change sample_nbytes / throughput accounting between modes
            self._mem = np.stack([np.asarray(s, np.float32) for s in samples])
        else:
            os.makedirs(root, exist_ok=True)
            for i in range(n):
                np.save(os.path.join(root, f"sample_{i:06d}.npy"),
                        np.asarray(samples[i], np.float32))
        self.num_samples = n
        self.sample_nbytes = int(np.prod(self.shape)) * 4

    @property
    def stored_bytes(self) -> int:
        return self.sample_nbytes * self.num_samples

    def get_batch(self, idx: np.ndarray) -> jnp.ndarray:
        with obs_trace.span("data.get_batch", cat="data", store="raw",
                            batch=len(idx)):
            t0 = time.perf_counter()
            if self._mem is not None:
                batch = self._mem[np.asarray(idx)]
            else:
                batch = np.stack([np.load(os.path.join(self.root,
                                                       f"sample_{i:06d}.npy"))
                                  for i in np.asarray(idx)])
            nbytes = batch.nbytes
            throttle(nbytes, t0, self.bandwidth_mbs)
            self.stats.account(nbytes,
                               read_seconds=time.perf_counter() - t0)
            return jnp.asarray(batch)


class CompressedArrayStore:
    """Per-sample ZFP streams with per-sample (Algorithm 1) tolerances.

    Samples are (C, H, W) or (H, W) float arrays; compression runs over the
    trailing two dims.  Per-sample payload widths vary with the adaptive
    rate; batches pad to the in-batch max width (padded words decode as zero
    planes, so decoding stays exact) and run one kernel decode per batch.
    """

    def __init__(self, samples: Sequence[np.ndarray],
                 tolerances: Optional[Sequence[float]] = None,
                 bits_per_value: Optional[int] = None,
                 root: Optional[str] = None,
                 bandwidth_mbs: Optional[float] = None):
        assert (tolerances is None) != (bits_per_value is None)
        self.bandwidth_mbs = bandwidth_mbs
        self.stats = IoStats()
        self.root = root
        self.shape = tuple(np.asarray(samples[0]).shape)
        self.num_samples = len(samples)
        self.sample_nbytes = int(np.prod(self.shape)) * 4
        self._payload, self._emax, self._widths = [], [], []
        self.logical_bytes = 0
        if root is not None:
            os.makedirs(root, exist_ok=True)
        if tolerances is not None:
            codec = get_codec("fixed_accuracy")
        else:
            codec = get_codec("fixed_rate", bits_per_value=bits_per_value)
        for i, s in enumerate(samples):
            x = jnp.asarray(np.asarray(s, np.float32))
            tols = (None if tolerances is None
                    else jnp.asarray([float(tolerances[i])], jnp.float32))
            cf = codec.encode_batch(x[None], tols)
            if tolerances is not None:
                w = int(np.ceil(int(jnp.max(cf.nplanes)) / 2)) or 1
                payload = np.asarray(cf.payload)[0, :, :w]
                self.logical_bytes += int(np.asarray(codec.nbytes(cf))[0])
            else:
                payload = np.asarray(cf.payload)[0]
                w = payload.shape[1]
                self.logical_bytes += payload.nbytes + cf.emax.shape[1]
            emax = np.asarray(cf.emax, np.int32)[0]
            # batched fields record the PER-SAMPLE shape (leading N only on
            # the arrays), so padded_shape carries over unchanged
            self._padded_shape = cf.padded_shape
            if root is None:
                self._payload.append(payload)
                self._emax.append(emax)
            else:
                np.savez(os.path.join(root, f"sample_{i:06d}.npz"),
                         payload=payload, emax=emax)
            self._widths.append(w)

    @property
    def stored_bytes(self) -> int:
        return self.logical_bytes

    @property
    def ratio(self) -> float:
        return self.sample_nbytes * self.num_samples / max(self.logical_bytes, 1)

    def get_batch(self, idx: np.ndarray) -> jnp.ndarray:
        with obs_trace.span("data.get_batch", cat="data", store="zfp",
                            batch=len(idx)):
            idx = np.asarray(idx)
            t0 = time.perf_counter()
            payloads, emaxs, nbytes = [], [], 0
            for i in idx:
                if self.root is None:
                    p, e = self._payload[i], self._emax[i]
                else:
                    z = np.load(os.path.join(self.root, f"sample_{i:06d}.npz"))
                    p, e = z["payload"], z["emax"]
                nbytes += p.nbytes + e.nbytes
                payloads.append(p)
                emaxs.append(e)
            wmax = max(p.shape[1] for p in payloads)
            payloads = [np.pad(p, ((0, 0), (0, wmax - p.shape[1])))
                        for p in payloads]
            throttle(nbytes, t0, self.bandwidth_mbs)
            t1 = time.perf_counter()
            batch = decode_stacked_payloads(np.stack(payloads),
                                            np.stack(emaxs),
                                            self._padded_shape, self.shape)
            batch.block_until_ready()
            self.stats.account(nbytes, read_seconds=t1 - t0,
                               decode_seconds=time.perf_counter() - t1)
            return batch
