"""Sharded compressed dataset container: many samples per file, one decode.

Per-sample files (``CompressedArrayStore`` with ``root=``) pay a file open +
zip parse per sample per batch — the classic small-file problem that chunked
container formats solve for lossy-compressed scientific data.  This module
packs ``shard_size`` samples into each shard file and decodes a whole batch
with a single ``decode_stacked_payloads`` call.

On-disk layout (``root/``):
  manifest.json          -- format tag, sample/padded shapes, block count,
                            shard size, per-sample tolerances / payload
                            widths / logical byte counts, shard table
  shard_00000.bin, ...   -- flat little-endian int32 words; each sample
                            record is ``nb * width`` payload words (packed
                            bit planes, see compression/transform.py)
                            followed by ``nb`` emax words

Shard files are memory-mapped on open, so a batch fetch is a handful of
contiguous record reads instead of per-sample file opens; the assembled
batch pads payloads to the in-batch max width (padded words decode as zero
planes) and runs ONE kernel decode.  Byte-for-byte, every sample record
holds exactly the stream ``encode_fixed_accuracy`` would produce, so
``get_batch`` is bit-exact with ``CompressedArrayStore.get_batch``.
"""
from __future__ import annotations

import json
import os
import time
from typing import Dict, Optional, Sequence

import jax.numpy as jnp
import numpy as np

from repro.compression import (
    compressed_nbytes_batch, decode_stacked_payloads, get_codec,
)
from repro.data.store import IoStats, throttle
from repro.obs import trace as obs_trace

MANIFEST_NAME = "manifest.json"
FORMAT_TAG = "repro-shards-v1"


def _shard_filename(k: int) -> str:
    return f"shard_{k:05d}.bin"


def atomic_write_json(path: str, obj: dict) -> None:
    """Write JSON via unique temp file + ``os.replace`` so a kill mid-write
    can never leave a torn file at ``path`` (the reader sees either the old
    content or the new, never a partial stream).  The temp name is unique
    per writer, so concurrent writers (two hosts finalizing the same store
    on a shared FS) cannot rename each other's half-written bytes -- last
    complete write wins."""
    import tempfile
    d = os.path.dirname(os.path.abspath(path)) or "."
    fd, tmp = tempfile.mkstemp(dir=d, prefix=os.path.basename(path) + ".",
                               suffix=".tmp")
    try:
        with os.fdopen(fd, "w") as f:
            json.dump(obj, f)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


def pack_sample_records(cf) -> tuple:
    """Per-sample shard records from a batched ``CompressedField``.

    Returns ``(records, widths, logical_bytes)``: ``records[j]`` is the flat
    little-endian int32 word array (``nb * w`` payload words followed by
    ``nb`` emax words) that shard files store for sample ``j``; ``widths[j]``
    is the per-sample payload width ``w``.  The single implementation of the
    record layout, shared by ``ShardedCompressedStore._build`` and the
    streaming producer in ``repro.datagen`` — their bit-identical-stores
    contract rides on this being one function.
    """
    pay = np.asarray(cf.payload)                          # (c, nb, MAXW)
    ema = np.asarray(cf.emax, np.int32)
    npl = np.asarray(cf.nplanes)
    logical = np.asarray(
        compressed_nbytes_batch(cf, mode="fixed_accuracy")).astype(np.int64)
    records, widths = [], []
    for j in range(pay.shape[0]):
        w = int(np.ceil(npl[j].max() / 2)) or 1
        records.append(np.concatenate(
            [pay[j, :, :w].ravel(), ema[j]]).astype("<i4"))
        widths.append(w)
    return records, np.asarray(widths, np.int64), logical


def build_manifest(shape, padded_shape, block_count: int, shard_size: int,
                   num_samples: int, tolerances, widths,
                   logical_bytes) -> dict:
    """Assemble the store manifest dict (the one source of its schema)."""
    num_shards = -(-num_samples // shard_size)
    return {
        "format": FORMAT_TAG,
        "shape": list(shape),
        "padded_shape": list(padded_shape),
        "block_count": int(block_count),
        "shard_size": int(shard_size),
        "num_samples": int(num_samples),
        "tolerances": [float(t) for t in tolerances],
        "widths": [int(w) for w in widths],
        "logical_bytes": [int(b) for b in logical_bytes],
        "shards": [{"file": _shard_filename(k),
                    "start": k * shard_size,
                    "count": (min((k + 1) * shard_size, num_samples)
                              - k * shard_size)}
                   for k in range(num_shards)],
    }


class ShardedCompressedStore:
    """Error-bounded ZFP store packing ``shard_size`` samples per shard.

    Build from samples + per-sample tolerances (``__init__``) — encoding
    runs through ``encode_fixed_accuracy_batch``, one compiled call per
    shard-sized chunk — or reattach to an existing directory (``open``).
    ``root=None`` keeps the identical record layout in memory.
    """

    def __init__(self, samples: Optional[Sequence[np.ndarray]] = None,
                 tolerances: Optional[Sequence[float]] = None,
                 root: Optional[str] = None,
                 shard_size: int = 32,
                 bandwidth_mbs: Optional[float] = None,
                 _manifest: Optional[dict] = None):
        self.root = root
        self.bandwidth_mbs = bandwidth_mbs
        self.stats = IoStats()
        self._shards: Dict[int, np.ndarray] = {}    # shard id -> int32 words
        if _manifest is not None:
            self._init_from_manifest(_manifest)
            return
        assert samples is not None and tolerances is not None, \
            "build from (samples, tolerances) or use ShardedCompressedStore.open"
        assert len(samples) == len(tolerances)
        assert shard_size > 0
        self.shard_size = int(shard_size)
        self._build(samples, np.asarray(tolerances, np.float32))

    # -- construction --------------------------------------------------------

    def _build(self, samples, tolerances: np.ndarray) -> None:
        xs = np.stack([np.asarray(s, np.float32) for s in samples])
        self.num_samples = xs.shape[0]
        self.shape = tuple(xs.shape[1:])
        self.sample_nbytes = int(np.prod(self.shape)) * 4
        self.tolerances = tolerances

        codec = get_codec("fixed_accuracy")
        records, widths, logical = [], [], []
        for lo in range(0, self.num_samples, self.shard_size):
            chunk = jnp.asarray(xs[lo:lo + self.shard_size])
            cf = codec.encode_batch(
                chunk, jnp.asarray(tolerances[lo:lo + self.shard_size]))
            self._padded_shape = cf.padded_shape
            recs, ws, lb = pack_sample_records(cf)
            records += recs
            widths.append(ws)
            logical.append(lb)
        self.nb = int(np.asarray(cf.emax).shape[-1])
        self.widths = np.concatenate(widths)
        self.logical_bytes_per = np.concatenate(logical)
        self.logical_bytes = int(self.logical_bytes_per.sum())
        self._compute_offsets()

        if self.root is not None:
            os.makedirs(self.root, exist_ok=True)
        for k in range(self.num_shards):
            lo = k * self.shard_size
            hi = min(lo + self.shard_size, self.num_samples)
            words = np.concatenate(records[lo:hi]).astype("<i4")
            if self.root is None:
                self._shards[k] = words
            else:
                words.tofile(os.path.join(self.root, _shard_filename(k)))
        if self.root is not None:
            atomic_write_json(os.path.join(self.root, MANIFEST_NAME),
                              self.manifest())

    def _compute_offsets(self) -> None:
        """Word offset of each sample's record within its shard."""
        rec_words = self.nb * self.widths + self.nb
        self._offsets = np.zeros(self.num_samples, np.int64)
        for k in range(self.num_shards):
            lo = k * self.shard_size
            hi = min(lo + self.shard_size, self.num_samples)
            self._offsets[lo:hi] = (np.cumsum(rec_words[lo:hi])
                                    - rec_words[lo:hi])

    # -- manifest / reopen ---------------------------------------------------

    def manifest(self) -> dict:
        return build_manifest(self.shape, self._padded_shape, self.nb,
                              self.shard_size, self.num_samples,
                              self.tolerances, self.widths,
                              self.logical_bytes_per)

    def _init_from_manifest(self, m: dict) -> None:
        assert m.get("format") == FORMAT_TAG, f"unknown format {m.get('format')}"
        self.shape = tuple(m["shape"])
        self._padded_shape = tuple(m["padded_shape"])
        self.nb = int(m["block_count"])
        self.shard_size = int(m["shard_size"])
        self.num_samples = int(m["num_samples"])
        self.sample_nbytes = int(np.prod(self.shape)) * 4
        self.tolerances = np.asarray(m["tolerances"], np.float32)
        self.widths = np.asarray(m["widths"], np.int64)
        self.logical_bytes_per = np.asarray(m["logical_bytes"], np.int64)
        self.logical_bytes = int(self.logical_bytes_per.sum())
        self._compute_offsets()

    @classmethod
    def open(cls, root: str,
             bandwidth_mbs: Optional[float] = None) -> "ShardedCompressedStore":
        """Reattach to an on-disk store; shards memory-map lazily."""
        with open(os.path.join(root, MANIFEST_NAME)) as f:
            m = json.load(f)
        return cls(root=root, bandwidth_mbs=bandwidth_mbs, _manifest=m)

    # -- store protocol ------------------------------------------------------

    @property
    def num_shards(self) -> int:
        return -(-self.num_samples // self.shard_size)

    @property
    def stored_bytes(self) -> int:
        return self.logical_bytes

    @property
    def ratio(self) -> float:
        return self.sample_nbytes * self.num_samples / max(self.logical_bytes, 1)

    def shard_of(self, i: int) -> int:
        return i // self.shard_size

    def _shard_words(self, k: int) -> np.ndarray:
        words = self._shards.get(k)
        if words is None:
            words = np.memmap(os.path.join(self.root, _shard_filename(k)),
                              dtype="<i4", mode="r")
            self._shards[k] = words
        return words

    def get_batch(self, idx: np.ndarray) -> jnp.ndarray:
        """Fetch + decode a batch with one kernel call.

        Records are gathered shard-by-shard (sorted so each touched shard's
        reads are contiguous), payloads padded to the in-batch max width,
        and the whole (B * nb, wmax) stack decoded at once.
        """
        with obs_trace.span("data.get_batch", cat="data", store="sharded",
                            batch=len(idx)):
            idx = np.asarray(idx)
            t0 = time.perf_counter()
            b = len(idx)
            wmax = int(self.widths[idx].max())
            payload = np.zeros((b, self.nb, wmax), np.int32)
            emax = np.empty((b, self.nb), np.int32)
            nbytes = 0
            for pos in np.argsort(idx // self.shard_size, kind="stable"):
                i = int(idx[pos])
                words = self._shard_words(self.shard_of(i))
                off, w = int(self._offsets[i]), int(self.widths[i])
                rec = np.asarray(words[off:off + self.nb * (w + 1)])
                payload[pos, :, :w] = rec[:self.nb * w].reshape(self.nb, w)
                emax[pos] = rec[self.nb * w:]
                nbytes += rec.nbytes
            throttle(nbytes, t0, self.bandwidth_mbs)
            t1 = time.perf_counter()
            batch = decode_stacked_payloads(payload, emax, self._padded_shape,
                                            self.shape)
            batch.block_until_ready()
            self.stats.account(nbytes, read_seconds=t1 - t0,
                               decode_seconds=time.perf_counter() - t1)
            return batch

    def as_device_resident(self):
        """Upload the whole store to device memory once.

        Returns a ``DeviceResidentCompressedStore`` whose batches gather +
        decode inside the jitted train step — zero host bytes moved per
        batch, decoded values bit-identical to :meth:`get_batch`.
        """
        from repro.data.device_store import DeviceResidentCompressedStore
        return DeviceResidentCompressedStore.from_store(self)
