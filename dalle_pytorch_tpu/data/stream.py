"""Streaming ingestion: webdataset-style tar shards behind the DataLoader
contract.

The folder datasets (data/dataset.py) list every file up front and read one
file per member — fine for CUB's 11k birds on one host, fatal at corpus
scale: a million-sample dataset is two million inodes, every host lists all
of them, and shared-filesystem metadata becomes the input bottleneck.  This
module replaces the *storage* layer while keeping the *iteration* contract:

* **Shard format** — plain tar files of ``(image, caption)`` members plus an
  ``index.json`` manifest recording, per shard, its size + crc32 and every
  member's byte offset inside the tar (``tools/make_shards.py`` writes
  both).  The offsets make the shard set randomly addressable: a sample
  read is one ``pread`` per member, no tar scan — so the global shuffle
  that keeps training order identical to the folder loader costs nothing
  extra.
* **Per-host shard assignment** — host ``h`` of ``H`` owns shards
  ``[h::H]``: each host stores/reads ONLY its own shards (the point of
  sharding a corpus), and every host runs the same number of steps per
  epoch (the batch count is the min over hosts, so SPMD loops stay
  collective).  On one host this degrades to the folder loader's exact
  permutation, which is what the cross-format bitwise tests pin.
* **Iteration contract** — :class:`StreamingDataLoader` subclasses
  ``DataLoader``: same bounded worker pool, same ordered prefetch with
  backpressure, same cursor semantics.  ``state_dict()`` extends the
  (seed, epoch, cursor) cursor with the **shard-list fingerprint** (crc32
  over every shard's name/size/crc32) and the (shard, member) coordinate of
  the next unconsumed sample — resume refuses a changed shard list loudly
  instead of silently training on different data, and mid-shard resume
  replays bitwise (same permutation, consumed batches skipped).
* **Degradation** — a failing shard read (``shard_read`` faultpoint:
  transient failure or a truncated member) is retried once, then the whole
  shard is quarantined (logged, capped at max(1, 5%) of the shard list —
  the cap trips loudly) and the walk continues in the next healthy shard,
  mirroring the per-sample quarantine policy of ``TextImageDataset``.

:class:`DevicePrefetcher` is the last host stall remover: it pulls (and
optionally device-places) the next batch while the current step runs, and
meters the time the step loop actually waited on the input pipeline — the
``loader_stall_s`` metric the heartbeat and the monitor report, so
an input-bound run is visible instead of mislabeled "slow chip".
"""
from __future__ import annotations

import json
import os
import threading
import zlib
from bisect import bisect_right
from pathlib import Path
from typing import Callable, List, Optional, Sequence

import numpy as np

from ..obs import telemetry
from ..utils import faults
from ..utils import locks
from ..utils.helpers import atomic_write_json
from .dataset import DataLoader, IMAGE_EXTS, center_crop_resize, make_pair

INDEX_NAME = "index.json"
INDEX_SCHEMA = 1


class ShardIndexError(RuntimeError):
    """The shard set is unusable (missing/short/changed shards, bad index)."""


def _decode_image_bytes(data: bytes):
    """Bytes -> RGB PIL image, decode forced NOW (mirrors
    ``dataset._load_image``: the retry/quarantine handler must see
    truncated-member errors here, not lazily mid-augmentation)."""
    import io

    from PIL import Image

    img = Image.open(io.BytesIO(data))
    img.load()
    if img.mode != "RGB":
        img = img.convert("RGB")
    return img


def shard_fingerprint(shards: Sequence[dict]) -> str:
    """crc32 over every shard's identity (name, size, crc32) — the cursor
    contract's "same shard list" check.  Member offsets are implied by the
    shard bytes, so this is exactly the identity a resume must match."""
    blob = json.dumps([[s["name"], int(s["size"]), s["crc32"]]
                       for s in shards]).encode()
    return f"{zlib.crc32(blob):08x}"


class ShardIndex:
    """Parsed + size-checked ``index.json`` over a directory of tar shards.

    Size mismatches are caught at open (cheap stat per shard — a truncated
    shard fails before training starts); full per-shard crc32 verification
    is :meth:`verify` (make_shards ``--verify``, tests) since crc'ing a
    multi-GB corpus at every trainer start would be its own stall.
    """

    def __init__(self, root, check_sizes: bool = True):
        self.root = Path(root)
        ipath = self.root / INDEX_NAME
        if not ipath.is_file():
            raise ShardIndexError(f"no {INDEX_NAME} under {self.root} — "
                                  "build shards with tools/make_shards.py")
        try:
            index = json.loads(ipath.read_text())
        except (json.JSONDecodeError, OSError) as e:
            raise ShardIndexError(f"unreadable {ipath}: {e}") from e
        if int(index.get("schema", 0)) > INDEX_SCHEMA:
            raise ShardIndexError(
                f"index schema {index.get('schema')} is newer than this "
                f"build's {INDEX_SCHEMA}")
        self.shards: List[dict] = list(index["shards"])
        if not self.shards:
            raise ShardIndexError(f"{ipath} lists no shards")
        self.has_captions = bool(index.get("has_captions", False))
        self.fingerprint = shard_fingerprint(self.shards)
        # cumulative sample counts: locate() maps a global index to its
        # (shard, member) coordinate with one bisect
        counts = [int(s["count"]) for s in self.shards]
        self._cum = np.cumsum(counts)
        self.num_samples = int(self._cum[-1])
        if check_sizes:
            for s in self.shards:
                p = self.shard_path(s["name"])
                if not p.is_file():
                    raise ShardIndexError(f"shard {s['name']} missing under "
                                          f"{self.root}")
                size = p.stat().st_size
                if size != int(s["size"]):
                    raise ShardIndexError(
                        f"shard {s['name']} is {size} bytes, index says "
                        f"{s['size']} (truncated or swapped?)")

    def shard_path(self, name: str) -> Path:
        return self.root / name

    def locate(self, g: int) -> tuple:
        """Global sample index -> (shard index, member index)."""
        s = int(bisect_right(self._cum, g))
        prev = int(self._cum[s - 1]) if s else 0
        return s, g - prev

    def shard_start(self, s: int) -> int:
        """Global index of shard ``s``'s first sample."""
        return int(self._cum[s - 1]) if s else 0

    def verify(self) -> None:
        """Full integrity pass: every shard's bytes match the recorded
        crc32.  Raises :class:`ShardIndexError` on the first mismatch."""
        for s in self.shards:
            p = self.shard_path(s["name"])
            crc = 0
            with open(p, "rb") as f:
                while True:
                    buf = f.read(1 << 20)
                    if not buf:
                        break
                    crc = zlib.crc32(buf, crc)
            if f"{crc:08x}" != s["crc32"]:
                raise ShardIndexError(f"shard {s['name']} fails its crc32 "
                                      "(corrupt)")


class ShardStreamDataset:
    """Random-access (image, caption) samples out of a tar shard set.

    ``item(idx, epoch)`` matches ``TextImageDataset.item`` bitwise when the
    shards were built from the same folder (make_shards preserves the
    sorted-key sample order and this class reuses the one shared
    decode/augment sequence, ``dataset.make_pair``).  ``image_only=True``
    yields center-cropped images exactly like ``ImageFolderDataset`` (the
    VAE trainer's diet).
    """

    def __init__(self, root, tokenizer=None, text_len: int = 256,
                 image_size: int = 128, resize_ratio: float = 0.6,
                 truncate_captions: bool = False, image_only: bool = False,
                 seed: int = 0):
        self.index = ShardIndex(root)
        if not image_only and not self.index.has_captions:
            raise ShardIndexError(
                f"shard set {root} has no captions; rebuild with captions "
                "or use --image_only mode (train_vae)")
        self.tokenizer = tokenizer
        self.text_len = text_len
        self.image_size = image_size
        self.resize_ratio = resize_ratio
        self.truncate_captions = truncate_captions
        self.image_only = image_only
        self.seed = seed
        self._fds: dict = {}
        self._fd_lock = locks.TracedLock("stream.fds")
        # shard-granular quarantine, mirroring TextImageDataset's per-sample
        # policy: skip what keeps failing, but a rotten shard SET must still
        # fail loudly — the cap is on shards, not samples, because one bad
        # shard takes all of its samples with it.
        self._quarantined: set = set()
        self._quarantine_lock = locks.TracedLock("stream.quarantine")
        self.max_quarantine = max(1, len(self.index.shards) // 20)

    def __len__(self):
        return self.index.num_samples

    def close(self) -> None:
        with self._fd_lock:
            for fd in self._fds.values():
                try:
                    os.close(fd)
                except OSError:
                    pass
            self._fds.clear()

    def _fd_for(self, s: int) -> int:
        """Cached read-only fd per shard; ``os.pread`` is positionless, so
        one fd serves every prefetch worker concurrently."""
        with self._fd_lock:
            fd = self._fds.get(s)
            if fd is None:
                fd = os.open(self.index.shard_path(
                    self.index.shards[s]["name"]), os.O_RDONLY)
                self._fds[s] = fd
            return fd

    def _read_bytes(self, s: int, offset: int, size: int) -> bytes:
        data = os.pread(self._fd_for(s), size, offset)
        if len(data) != size:
            raise OSError(f"short read from shard "
                          f"{self.index.shards[s]['name']} at {offset}: "
                          f"{len(data)}/{size} bytes")
        return data

    def _quarantine(self, s: int, err: Exception) -> None:
        with self._quarantine_lock:
            self._quarantined.add(s)
            n = len(self._quarantined)
        name = self.index.shards[s]["name"]
        telemetry.note(
            "data", "shard_quarantine",
            f"quarantining shard {name} "
            f"({n}/{self.max_quarantine} quarantined): {err}",
            prefix="warning:", stream="stdout", shard=name, quarantined=n)
        if n > self.max_quarantine:
            raise RuntimeError(
                f"ShardStreamDataset: {n} shards quarantined (cap "
                f"{self.max_quarantine}) — the shard set is rotten, "
                "refusing to silently train on what is left")

    def _read_sample(self, g: int, rng):
        """One sample at global index ``g``.  The ``shard_read`` faultpoint
        fires per attempt: ``fail_after``/``every`` model transient I/O
        failures, ``truncate`` hands back a half-read image member (the
        torn-shard case a crc would catch offline) — both must end in the
        retry/quarantine path, never a crashed epoch."""
        s, j = self.index.locate(g)
        actions = faults.fire("shard_read")
        rec = self.index.shards[s]["samples"][j]
        img_bytes = self._read_bytes(s, int(rec["image_offset"]),
                                     int(rec["image_size"]))
        if "truncate" in actions:
            img_bytes = img_bytes[: max(len(img_bytes) // 2, 1)]
        if self.image_only:
            return center_crop_resize(_decode_image_bytes(img_bytes),
                                      self.image_size)
        caption = self._read_bytes(s, int(rec["caption_offset"]),
                                   int(rec["caption_size"])).decode("utf-8")
        return make_pair(caption, lambda: _decode_image_bytes(img_bytes),
                         self.tokenizer, self.text_len,
                         self.truncate_captions, self.image_size,
                         self.resize_ratio, rng)

    def __getitem__(self, idx: int):
        return self.item(idx, 0)

    def item(self, idx: int, epoch: int):
        # per-call Generator seeded by (seed, GLOBAL idx, epoch): identical
        # construction to TextImageDataset.item, so the folder and shard
        # formats draw the same caption lines and crops for the same sample
        rng = np.random.default_rng((self.seed, idx, epoch))
        n = len(self)
        g = idx % n
        # walk: retry the sample once, then quarantine its SHARD and hop to
        # the next shard's first sample (a dead shard must cost one hop,
        # not one failed attempt per sample it holds).  Bounded by the
        # shard count plus a few retries; the quarantine cap bounds it too.
        for _ in range(len(self.index.shards) + 8):
            s, _j = self.index.locate(g)
            if s in self._quarantined:
                g = self.index.shard_start(
                    (s + 1) % len(self.index.shards)) % n
                continue
            last_err = None
            for _retry in range(2):
                try:
                    return self._read_sample(g, rng)
                except (OSError, ValueError) as e:
                    last_err = e
            self._quarantine(s, last_err)
        raise RuntimeError(
            f"ShardStreamDataset: no readable shard found walking from "
            f"index {idx} — check the shard directory")


def _normalize_fp(value) -> Optional[str]:
    if value is None:
        return None
    if isinstance(value, bytes):
        return value.decode("utf-8", "replace")
    return str(value)


class StreamingDataLoader(DataLoader):
    """``DataLoader`` over a :class:`ShardStreamDataset` with shard-granular
    host assignment and a fingerprinted resume cursor.

    Host ``h`` of ``H`` owns shards ``[h::H]`` and never opens another
    host's shards.  Every host runs ``min_h(len(own_h) // batch)`` batches
    per epoch so the SPMD step loops stay collective even when shard sizes
    differ.  On one host the epoch order is the folder loader's exact
    permutation — the property the cross-format bitwise tests pin.
    Batching, the bounded worker pool, ordered prefetch, and the cursor
    bookkeeping are all inherited; only *which indices make an epoch* and
    the state_dict contract differ.
    """

    def __init__(self, dataset: ShardStreamDataset, batch_size: int,
                 shuffle: bool = True, drop_last: bool = True, seed: int = 0,
                 shard_num_hosts: int = 1, shard_index: int = 0,
                 num_workers: int = 8, prefetch: int = 4):
        super().__init__(dataset, batch_size, shuffle=shuffle,
                         drop_last=drop_last, seed=seed,
                         shard_num_hosts=shard_num_hosts,
                         shard_index=shard_index, num_workers=num_workers,
                         prefetch=prefetch)
        index = dataset.index
        n_shards = len(index.shards)
        if shard_num_hosts > n_shards:
            raise ShardIndexError(
                f"{shard_num_hosts} hosts but only {n_shards} shards — "
                "rebuild with more (smaller) shards so every host owns at "
                "least one")
        # deterministic round-robin shard ownership + the collective batch
        # count (min over hosts) — computed once from the index, identically
        # on every host
        per_host_counts = []
        for h in range(shard_num_hosts):
            per_host_counts.append(sum(
                int(index.shards[s]["count"])
                for s in range(h, n_shards, shard_num_hosts)))
        self._own_shards = list(range(shard_index, n_shards, shard_num_hosts))
        self._own = np.concatenate([
            np.arange(index.shard_start(s),
                      index.shard_start(s) + int(index.shards[s]["count"]))
            for s in self._own_shards])
        if drop_last:
            self._n_batches = min(per_host_counts) // batch_size
        else:
            self._n_batches = -(-min(per_host_counts) // batch_size)

    def __len__(self):
        return self._n_batches

    def _indices_for_epoch(self, epoch: int) -> np.ndarray:
        own = self._own
        if self.shuffle:
            rng = np.random.default_rng(self.seed + epoch)
            own = own[rng.permutation(len(own))]
        if self.drop_last:
            own = own[: self._n_batches * self.batch_size]
        return own

    def _epoch_indices(self) -> np.ndarray:
        return self._indices_for_epoch(self.epoch)

    # --- cursor contract -------------------------------------------------

    def state_dict(self) -> dict:
        """The folder loader's (seed, epoch, cursor) triple PLUS the shard
        cursor: the shard-list fingerprint (resume must see the same shard
        set) and the (shard, offset) coordinate of the next unconsumed
        sample — redundant with (seed, epoch, cursor) for replay, but it
        is the operator-readable "where in the corpus was I" answer and a
        cross-check that the restored permutation still maps to the same
        physical bytes."""
        state = super().state_dict()
        state["fingerprint"] = self.ds.index.fingerprint
        indices = self._indices_for_epoch(state["epoch"])
        pos = state["cursor"] * self.batch_size
        if 0 <= pos < len(indices):
            s, j = self.ds.index.locate(int(indices[pos]))
            state["shard"], state["offset"] = int(s), int(j)
        else:  # epoch boundary: nothing left to consume
            state["shard"], state["offset"] = -1, -1
        return state

    def load_state_dict(self, state: dict) -> None:
        state = dict(state)
        fp = _normalize_fp(state.pop("fingerprint", None))
        if fp is not None and fp != self.ds.index.fingerprint:
            raise ShardIndexError(
                f"resume cursor was written against shard fingerprint {fp} "
                f"but this shard set is {self.ds.index.fingerprint} — the "
                "shard list changed; a bitwise resume is impossible "
                "(rebuild the shards or start fresh)")
        state.pop("shard", None)
        state.pop("offset", None)
        super().load_state_dict(state)
        # cross-check the diagnostic coordinate when present: a stale index
        # with the same fingerprint cannot happen (fingerprint covers size
        # + crc), so this only guards cursor arithmetic drift
        # (intentionally no hard failure — replay is pinned by the triple)


class DevicePrefetcher:
    """Double-buffer between a loader and the step loop: pull (and
    optionally device-place) the next batch while the current step runs,
    and meter what the step loop actually waited.

    Yields ``(host_batch, placed_batch)`` when ``place`` is given (the
    trainers pass ``Partitioner.shard_batch``), else ``host_batch``.
    ``depth`` batches are pulled ahead; ``jax.device_put`` is async, so a
    placed batch costs host time only when the *host-side* pipeline is the
    bottleneck — which is exactly what ``last_wait_s`` then shows.

    Cursor correctness: the wrapped loader counts batches it *produced*,
    which runs ``depth`` ahead of what the trainer has consumed — a
    checkpoint recording the producer cursor would SKIP never-trained
    batches on resume.  This wrapper snapshots ``loader.state_dict()`` at
    pull time of each batch and republishes the snapshot of the batch
    currently held by the consumer, so ``state_dict()`` here is always the
    resume-correct cursor.  Trainers must checkpoint THIS state_dict, not
    the loader's.
    """

    def __init__(self, loader, place: Optional[Callable] = None,
                 depth: int = 1, stall_event_s: float = 1.0):
        self.loader = loader
        self.place = place
        self.depth = max(0, int(depth))
        # substantial stalls (>= stall_event_s of host wait for one batch)
        # become discrete telemetry events; the continuous metric still
        # rides every step record via last_wait_s, so this only marks the
        # outliers an operator would want on the timeline
        self.stall_event_s = float(stall_event_s)
        self._state: Optional[dict] = None
        self.last_wait_s = 0.0
        self.total_wait_s = 0.0
        self.batches = 0

    def state_dict(self) -> dict:
        if self._state is not None:
            return dict(self._state)
        return self.loader.state_dict()

    def load_state_dict(self, state: dict) -> None:
        self._state = None
        self.loader.load_state_dict(state)

    def __len__(self):
        return len(self.loader)

    def __iter__(self):
        import time
        from collections import deque

        inner = iter(self.loader)
        pending: deque = deque()
        waited = [0.0]  # host time spent pulling since the last yield

        def pull() -> bool:
            t0 = time.perf_counter()
            try:
                batch = next(inner)
            except StopIteration:
                return False
            placed = self.place(batch) if self.place is not None else None
            snap = self.loader.state_dict()
            waited[0] += time.perf_counter() - t0
            pending.append((batch, placed, snap))
            return True

        for _ in range(1 + self.depth):
            if not pull():
                break
        while pending:
            batch, placed, snap = pending.popleft()
            self._state = snap
            self.last_wait_s, waited[0] = waited[0], 0.0
            self.total_wait_s += self.last_wait_s
            self.batches += 1
            if self.last_wait_s >= self.stall_event_s:
                telemetry.emit("data", "loader_stall",
                               wait_s=self.last_wait_s, batch=self.batches)
            yield (batch, placed) if self.place is not None else batch
            pull()


# --- shard building (the library half of tools/make_shards.py) ------------


def _discover_pairs(folder: Path):
    """(key, image path, caption path) triples by file stem, exactly the
    pairing rule of ``TextImageDataset`` — sorted keys, so global sample
    index ``g`` in the shard set equals index ``g`` of the folder dataset
    (the property the cross-format bitwise tests rest on)."""
    text_files = {p.stem: p for p in folder.rglob("*.txt")}
    image_files = {p.stem: p for p in folder.rglob("*")
                   if p.suffix.lower() in IMAGE_EXTS}
    keys = sorted(image_files.keys() & text_files.keys())
    return [(k, image_files[k], text_files[k]) for k in keys]


def _discover_images(folder: Path):
    """Images in sorted-path order, the ``ImageFolderDataset`` rule."""
    paths = sorted(p for p in folder.rglob("*")
                   if p.suffix.lower() in IMAGE_EXTS)
    # keys must be unique and filesystem-safe: relative path with / -> __
    return [(str(p.relative_to(folder).with_suffix("")).replace("/", "__"),
             p, None) for p in paths]


def build_shards(src, out, samples_per_shard: int = 512,
                 image_only: bool = False) -> dict:
    """Convert a folder dataset into tar shards + an ``index.json``.

    Deterministic end to end: samples in sorted-key order, fixed tar
    metadata (mtime 0, uid/gid 0, USTAR-compatible GNU format), member
    bytes copied verbatim — rebuilding from the same folder reproduces the
    same shard bytes and therefore the same fingerprint.  Shard files land
    via temp + ``os.replace`` and the index publishes LAST (the index is
    the shard set's manifest: a crash mid-build leaves temps, never a
    readable-but-wrong shard set).  Returns the index dict.
    """
    import io
    import tarfile

    src, out = Path(src), Path(out)
    samples = (_discover_images(src) if image_only else _discover_pairs(src))
    if not samples:
        raise ShardIndexError(f"no samples found under {src}")
    out.mkdir(parents=True, exist_ok=True)
    samples_per_shard = max(1, int(samples_per_shard))
    shards = []
    for si in range(0, len(samples), samples_per_shard):
        chunk = samples[si:si + samples_per_shard]
        name = f"shard-{si // samples_per_shard:06d}.tar"
        tmp = out / f".tmp-{name}"
        member_names = []
        with tarfile.open(tmp, "w", format=tarfile.GNU_FORMAT) as tar:
            for key, img_path, txt_path in chunk:
                for path, suffix in ((img_path, img_path.suffix.lower()),
                                     (txt_path, ".txt")):
                    if path is None:
                        continue
                    data = path.read_bytes()
                    ti = tarfile.TarInfo(name=f"{key}{suffix}")
                    ti.size = len(data)
                    ti.mtime = 0
                    ti.uid = ti.gid = 0
                    ti.uname = ti.gname = ""
                    tar.addfile(ti, io.BytesIO(data))
                member_names.append(key)
        # second pass over the finished tar: record every member's payload
        # offset (offset_data) for pread-addressable sample reads, and the
        # shard's size + crc32 for the index manifest
        offsets = {}
        with tarfile.open(tmp, "r") as tar:
            for m in tar.getmembers():
                offsets[m.name] = (int(m.offset_data), int(m.size))
        crc = 0
        size = 0
        with open(tmp, "rb") as f:
            while True:
                buf = f.read(1 << 20)
                if not buf:
                    break
                size += len(buf)
                crc = zlib.crc32(buf, crc)
        recs = []
        for key, img_path, txt_path in chunk:
            img_name = f"{key}{img_path.suffix.lower()}"
            rec = {"key": key, "image": img_name,
                   "image_offset": offsets[img_name][0],
                   "image_size": offsets[img_name][1]}
            if txt_path is not None:
                rec.update(caption=f"{key}.txt",
                           caption_offset=offsets[f"{key}.txt"][0],
                           caption_size=offsets[f"{key}.txt"][1])
            recs.append(rec)
        os.replace(tmp, out / name)
        shards.append({"name": name, "count": len(chunk), "size": size,
                       "crc32": f"{crc:08x}", "samples": recs})
    index = {"schema": INDEX_SCHEMA, "num_samples": len(samples),
             "has_captions": not image_only, "shards": shards}
    # the index IS the shard set's commit record — atomic publish, last
    atomic_write_json(out / INDEX_NAME, index)
    return index
