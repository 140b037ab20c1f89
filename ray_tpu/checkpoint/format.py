"""Checkpoint wire format v1: sharded per-rank layout with an atomic manifest.

Layout of one checkpoint directory (``<storage>/<experiment>/checkpoint_<step>``)::

    shard-00000-of-00002.bin      per-rank data: concatenated raw leaf chunks
    shard-00000-of-00002.index.json  per-rank chunk index (leaf -> offsets/slices)
    skeleton.pkl                  pytree structure with _LeafMarker leaves (rank 0)
    manifest.json                 global commit record (coordinator, atomic)

Commit protocol: every rank writes only its shard pair (each file lands via
tmp-file + ``os.replace``), then acks the coordinator; the coordinator writes
``manifest.json`` — also tmp + ``os.replace`` — only after ALL ranks acked.
A directory without a valid manifest is, by definition, not a checkpoint: a
crash at any point mid-save can never corrupt "latest".

The manifest carries a self-checksum (sha256 over its canonical JSON minus
the ``checksum`` field) plus per-shard byte sizes and crc32s, so torn or
bit-rotted checkpoints fail closed at restore/inspect time.

Resharding: each leaf chunk records the slice of the *global* array it holds
(``index`` = per-dim [start, stop]).  Restore assembles any target slicing
from any saved world size — exact-match chunks take a fast path (single
contiguous read), partial overlaps go through the generic gather in
``sharding.py``.
"""

from __future__ import annotations

import hashlib
import io
import json
import os
import pickle
import tempfile
import time
import zlib
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

from . import sharding

FORMAT_NAME = "ray_tpu_ckpt_v1"
MANIFEST = "manifest.json"
SKELETON = "skeleton.pkl"


class CheckpointError(Exception):
    """A checkpoint failed to serialize, commit, validate, or restore."""


class _LeafMarker:
    """Placeholder leaf in the pickled structure skeleton.

    ``jax.tree.map(lambda x: None, tree)`` would NOT work here: None is not
    a pytree leaf, so the skeleton would flatten to zero leaves.  A marker
    instance survives flattening and pickles from a stable module path.
    """

    def __repr__(self):
        return "<leaf>"


def _key_str(path) -> str:
    """Stable "a/b/0" string for a jax key path."""
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        elif hasattr(p, "name"):
            parts.append(str(p.name))
        else:
            parts.append(str(p))
    return "/".join(parts)


@dataclass
class LeafChunk:
    """One rank-local piece of one leaf: ``array`` covers ``index`` of the
    leaf's global shape."""
    index: Tuple[Tuple[int, int], ...]
    array: Any  # np.ndarray (host)


@dataclass
class LeafSnapshot:
    dtype: str
    global_shape: Tuple[int, ...]
    chunks: List[LeafChunk] = field(default_factory=list)
    #: Non-array leaf (int/str/config object...): pickled payload instead
    #: of chunks.
    obj_payload: Optional[bytes] = None


@dataclass
class Snapshot:
    """Host-side copy of this rank's pytree shards — the only thing whose
    creation blocks the train step; everything downstream of it runs on
    the writer thread."""
    leaves: Dict[str, LeafSnapshot]
    skeleton_pkl: bytes
    nbytes: int


def _is_array(x: Any) -> bool:
    return hasattr(x, "shape") and hasattr(x, "dtype")


def snapshot_tree(tree: Any,
                  shard_spec: Optional[Callable] = None) -> Snapshot:
    """Device arrays -> host numpy chunks (the blocking part of a save).

    ``shard_spec(key, leaf)`` may return ``(global_shape, index)`` to declare
    that this rank holds only ``index`` of a larger global array (CPU/numpy
    leaves default to fully-owned).  jax Arrays with a non-trivial sharding
    are decomposed through ``addressable_shards`` automatically; replicas
    (replica_id != 0) are skipped so a replicated leaf is written once.

    Plain numpy / fully-replicated leaves WITHOUT a shard_spec are written
    in full by every rank (no cross-rank protocol exists at snapshot time
    to elect a writer): restore dedups identical regions preferring the
    lowest rank, so rank-divergent unsharded leaves (per-rank rng state)
    restore rank 0's values everywhere — declare a shard_spec for leaves
    where that matters, and to avoid world_size x write amplification on
    large replicated trees.
    """
    import jax
    import numpy as np

    flat, _treedef = jax.tree_util.tree_flatten_with_path(tree)
    skeleton = jax.tree.map(lambda x: _LeafMarker(), tree)
    leaves: Dict[str, LeafSnapshot] = {}
    nbytes = 0
    for path, leaf in flat:
        key = _key_str(path)
        if not _is_array(leaf):
            leaves[key] = LeafSnapshot(
                dtype="object", global_shape=(),
                obj_payload=pickle.dumps(leaf, protocol=5))
            nbytes += len(leaves[key].obj_payload)
            continue
        spec = shard_spec(key, leaf) if shard_spec is not None else None
        shards = getattr(leaf, "addressable_shards", None)
        if spec is not None:
            global_shape, index = spec
            arr = np.asarray(jax.device_get(leaf))
            snap = LeafSnapshot(str(arr.dtype), tuple(global_shape))
            snap.chunks.append(
                LeafChunk(sharding.normalize_index(index, global_shape),
                          np.ascontiguousarray(arr)))
        elif shards is not None and not getattr(
                leaf, "is_fully_replicated", True):
            snap = LeafSnapshot(str(np.dtype(leaf.dtype)), tuple(leaf.shape))
            for sh in shards:
                if getattr(sh, "replica_id", 0) != 0:
                    continue
                arr = np.ascontiguousarray(np.asarray(sh.data))
                snap.chunks.append(LeafChunk(
                    sharding.index_from_slices(sh.index, leaf.shape), arr))
        else:
            arr = np.ascontiguousarray(np.asarray(jax.device_get(leaf)))
            snap = LeafSnapshot(str(arr.dtype), tuple(arr.shape))
            snap.chunks.append(
                LeafChunk(sharding.full_index(arr.shape), arr))
        leaves[key] = snap
        nbytes += sum(c.array.nbytes for c in snap.chunks)
    return Snapshot(leaves=leaves, skeleton_pkl=pickle.dumps(
        skeleton, protocol=5), nbytes=nbytes)


# -- shard build/write ------------------------------------------------------


def shard_basename(rank: int, world: int) -> str:
    return f"shard-{rank:05d}-of-{world:05d}"


def build_shard(snapshot: Snapshot, rank: int, world: int,
                step: int) -> Tuple[Dict[str, Any], bytes]:
    """Serialize one rank's snapshot into (index dict, data blob)."""
    buf = io.BytesIO()
    index_leaves: Dict[str, Any] = {}
    for key, snap in snapshot.leaves.items():
        if snap.obj_payload is not None:
            off = buf.tell()
            buf.write(snap.obj_payload)
            index_leaves[key] = {
                "kind": "object", "offset": off,
                "nbytes": len(snap.obj_payload),
                "crc32": zlib.crc32(snap.obj_payload) & 0xFFFFFFFF}
            continue
        chunks = []
        for c in snap.chunks:
            off = buf.tell()
            raw = c.array.tobytes()  # C-order raw bytes
            buf.write(raw)
            # Per-chunk crc: restores verify every byte range they
            # actually read, so bit-rot fails closed even on partial
            # (resharded) reads that never touch the whole file.
            chunks.append({"offset": off, "nbytes": len(raw),
                           "crc32": zlib.crc32(raw) & 0xFFFFFFFF,
                           "index": [list(p) for p in c.index]})
        index_leaves[key] = {
            "kind": "array", "dtype": snap.dtype,
            "global_shape": list(snap.global_shape), "chunks": chunks}
    blob = buf.getvalue()
    index = {
        "format": FORMAT_NAME,
        "step": step,
        "rank": rank,
        "world_size": world,
        "data_file": shard_basename(rank, world) + ".bin",
        "nbytes": len(blob),
        "crc32": zlib.crc32(blob) & 0xFFFFFFFF,
        "leaves": index_leaves,
    }
    return index, blob


def write_bytes_atomic(path: str, data: bytes) -> None:
    """tmp-file + fsync + ``os.replace``: the path either holds the
    complete bytes or does not exist — never a torn prefix, and (with
    the fsync) never a size-correct zero-filled file after power loss
    on delayed-allocation filesystems."""
    from .._private import sanitizer
    fd, tmp = tempfile.mkstemp(dir=os.path.dirname(path) or ".",
                               prefix=os.path.basename(path) + ".tmp")
    os.close(fd)
    try:
        # tracked_open: checkpoint write handles register with the leak
        # sanitizer while open (RAY_TPU_SANITIZE=1), so a writer that
        # wedges mid-publish is attributable in the shutdown diff.
        with sanitizer.tracked_open(tmp, "wb") as f:
            f.write(data)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


def write_shard(dirpath: str, index: Dict[str, Any], blob: bytes,
                skeleton_pkl: Optional[bytes] = None) -> None:
    """Publish one rank's shard pair (and, on rank 0, the skeleton)."""
    os.makedirs(dirpath, exist_ok=True)
    write_bytes_atomic(os.path.join(dirpath, index["data_file"]), blob)
    if skeleton_pkl is not None:
        write_bytes_atomic(os.path.join(dirpath, SKELETON), skeleton_pkl)
    base = shard_basename(index["rank"], index["world_size"])
    write_bytes_atomic(os.path.join(dirpath, base + ".index.json"),
                       json.dumps(index).encode())


# -- manifest ----------------------------------------------------------------


def manifest_checksum(manifest: Dict[str, Any]) -> str:
    body = {k: v for k, v in manifest.items() if k != "checksum"}
    return hashlib.sha256(
        json.dumps(body, sort_keys=True).encode()).hexdigest()


def build_manifest(dirpath: str, step: int, world: int,
                   metrics: Optional[Dict[str, Any]] = None,
                   replica: bool = False) -> Dict[str, Any]:
    """Assemble the global manifest from the per-rank shard indexes.

    Raises CheckpointError when any rank's shard pair is missing or its
    data file does not match the index — the coordinator must never
    commit a checkpoint it cannot prove complete.
    """
    shards = []
    leaves: Dict[str, Any] = {}
    for rank in range(world):
        base = shard_basename(rank, world)
        ipath = os.path.join(dirpath, base + ".index.json")
        try:
            with open(ipath, "rb") as f:
                index = json.loads(f.read())
        except (OSError, ValueError) as e:
            raise CheckpointError(
                f"rank {rank} shard index missing/unreadable: {e}")
        dpath = os.path.join(dirpath, index["data_file"])
        try:
            size = os.path.getsize(dpath)
        except OSError:
            raise CheckpointError(f"rank {rank} data file missing: {dpath}")
        if size != index["nbytes"]:
            raise CheckpointError(
                f"rank {rank} data file is {size}B, index says "
                f"{index['nbytes']}B")
        shards.append({"rank": rank, "data_file": index["data_file"],
                       "index_file": base + ".index.json",
                       "nbytes": index["nbytes"], "crc32": index["crc32"]})
        for key, spec in index["leaves"].items():
            if spec["kind"] == "array" and key not in leaves:
                leaves[key] = {"dtype": spec["dtype"],
                               "global_shape": spec["global_shape"]}
    manifest = {
        "format": FORMAT_NAME,
        "step": step,
        "world_size": world,
        "time": time.time(),
        "replica": bool(replica),
        "metrics": dict(metrics or {}),
        "shards": shards,
        "leaves": leaves,
        "total_bytes": sum(s["nbytes"] for s in shards),
    }
    manifest["checksum"] = manifest_checksum(manifest)
    return manifest


def commit_manifest(dirpath: str, manifest: Dict[str, Any]) -> None:
    """The commit point: after this replace, the checkpoint exists."""
    write_bytes_atomic(os.path.join(dirpath, MANIFEST),
                       json.dumps(manifest, indent=1).encode())


def read_manifest(dirpath: str) -> Dict[str, Any]:
    with open(os.path.join(dirpath, MANIFEST), "rb") as f:
        manifest = json.loads(f.read())
    if manifest.get("checksum") != manifest_checksum(manifest):
        raise CheckpointError(f"manifest checksum mismatch in {dirpath}")
    return manifest


def is_committed(dirpath: str) -> bool:
    return os.path.exists(os.path.join(dirpath, MANIFEST))


def verify_checkpoint(dirpath: str, deep: bool = False) -> List[str]:
    """Validity problems for a checkpoint dir ([] = valid).

    Shallow: manifest parses, self-checksum matches, every shard file
    exists with the manifest's byte size.  ``deep`` additionally re-reads
    every data file and checks its crc32.
    """
    problems: List[str] = []
    try:
        manifest = read_manifest(dirpath)
    except FileNotFoundError:
        return ["no manifest (uncommitted or not a checkpoint)"]
    except (CheckpointError, ValueError, OSError) as e:
        return [f"manifest invalid: {e}"]
    for sh in manifest["shards"]:
        dpath = os.path.join(dirpath, sh["data_file"])
        if not os.path.exists(dpath):
            problems.append(f"missing {sh['data_file']}")
            continue
        size = os.path.getsize(dpath)
        if size != sh["nbytes"]:
            problems.append(
                f"{sh['data_file']}: {size}B on disk, manifest says "
                f"{sh['nbytes']}B")
            continue
        if deep:
            with open(dpath, "rb") as f:
                crc = zlib.crc32(f.read()) & 0xFFFFFFFF
            if crc != sh["crc32"]:
                problems.append(f"{sh['data_file']}: crc32 mismatch")
    return problems


# -- restore -----------------------------------------------------------------


class _FileShardSource:
    """Reads leaf chunks of one rank's shard straight off its data file —
    only the byte ranges a restore actually needs are read."""

    def __init__(self, dirpath: str, index: Dict[str, Any]):
        self.index = index
        self._path = os.path.join(dirpath, index["data_file"])

    def read(self, offset: int, nbytes: int) -> bytes:
        with open(self._path, "rb") as f:
            f.seek(offset)
            return f.read(nbytes)


class _BlobShardSource:
    """In-memory shard (emergency replica restore path)."""

    def __init__(self, index: Dict[str, Any], blob: bytes):
        self.index = index
        self._blob = blob

    def read(self, offset: int, nbytes: int) -> bytes:
        return self._blob[offset:offset + nbytes]


def _load_skeleton(dirpath: str):
    with open(os.path.join(dirpath, SKELETON), "rb") as f:
        return pickle.loads(f.read())


def _assemble(sources: List[Any], placement: Optional[Callable],
              skeleton: Any) -> Any:
    """Gather this rank's slices of every leaf from the shard sources.

    ``placement(key, global_shape) -> index`` names the slice the caller
    wants (None = the full global array).  The single-host overlap fast
    path — a stored chunk exactly matching the requested index — is one
    contiguous read with no copy-assembly; anything else goes through the
    generic region gather.
    """
    import jax
    import numpy as np

    # leaf key -> (spec, [(source, chunk_meta)])
    by_key: Dict[str, Tuple[Dict[str, Any], List[Tuple[Any, Dict]]]] = {}
    for src in sources:
        for key, spec in src.index["leaves"].items():
            entry = by_key.setdefault(key, (spec, []))
            if spec["kind"] == "array":
                for c in src.index["leaves"][key]["chunks"]:
                    entry[1].append((src, c))
            else:
                entry[1].append((src, spec))

    def _checked_read(src, meta) -> bytes:
        raw = src.read(meta["offset"], meta["nbytes"])
        crc = meta.get("crc32")
        if len(raw) != meta["nbytes"] or (
                crc is not None and
                (zlib.crc32(raw) & 0xFFFFFFFF) != crc):
            raise CheckpointError(
                f"shard chunk at offset {meta['offset']} failed crc/size "
                f"verification (bit rot or torn write)")
        return raw

    def _restore_leaf(key: str):
        if key not in by_key:
            raise CheckpointError(f"leaf {key!r} absent from all shards")
        spec, stored = by_key[key]
        if spec["kind"] == "object":
            src, meta = stored[0]
            return pickle.loads(_checked_read(src, meta))
        global_shape = tuple(spec["global_shape"])
        dtype = np.dtype(spec["dtype"])
        target = sharding.normalize_index(
            placement(key, global_shape) if placement is not None else None,
            global_shape)
        # Dedup identical stored regions (replicated leaves written by
        # several ranks): keep the first occurrence of each index.
        seen = set()
        chunks = []
        for src, c in stored:
            cidx = tuple(tuple(p) for p in c["index"])
            if cidx in seen:
                continue
            seen.add(cidx)
            chunks.append((src, c, cidx))
        # Fast path: a stored chunk IS the requested slice.
        for src, c, cidx in chunks:
            if cidx == target:
                raw = _checked_read(src, c)
                return np.frombuffer(raw, dtype=dtype).reshape(
                    sharding.index_shape(target)).copy()
        # Generic gather: copy every overlapping region.  Coverage is
        # tracked as a mask UNION — overlapping chunks must not be able
        # to sum past a hole and hand back uninitialized memory.
        out = np.empty(sharding.index_shape(target), dtype=dtype)
        covered = np.zeros(sharding.index_shape(target), dtype=bool)
        for src, c, cidx in chunks:
            inter = sharding.intersect(cidx, target)
            if inter is None:
                continue
            raw = _checked_read(src, c)
            arr = np.frombuffer(raw, dtype=dtype).reshape(
                sharding.index_shape(cidx))
            sharding.copy_region(out, target, arr, cidx, inter)
            sharding.copy_region(covered, target, None, None, inter,
                                 fill=True)
        missing = covered.size - int(np.count_nonzero(covered))
        if missing:
            raise CheckpointError(
                f"leaf {key!r}: stored shards leave {missing} of "
                f"{covered.size} requested elements uncovered "
                f"(target {target})")
        return out

    flat, treedef = jax.tree_util.tree_flatten_with_path(
        skeleton, is_leaf=lambda x: isinstance(x, _LeafMarker))
    restored = [_restore_leaf(_key_str(path)) for path, _ in flat]
    return jax.tree_util.tree_unflatten(treedef, restored)


def restore_tree(dirpath: str, placement: Optional[Callable] = None,
                 blobs: Optional[Dict[int, Tuple[Dict, bytes]]] = None) -> Any:
    """Restore a pytree from a committed checkpoint directory.

    ``placement(key, global_shape) -> index`` reshards on the fly (None =
    assemble full global arrays).  ``blobs`` maps rank -> (index, data
    bytes) for shards already resident in memory (emergency replicas);
    ranks absent from ``blobs`` fall back to their on-disk files.
    """
    manifest = read_manifest(dirpath)
    skeleton = _load_skeleton(dirpath)
    sources: List[Any] = []
    for sh in manifest["shards"]:
        if blobs is not None and sh["rank"] in blobs:
            index, blob = blobs[sh["rank"]]
            sources.append(_BlobShardSource(index, blob))
            continue
        ipath = os.path.join(dirpath, sh["index_file"])
        with open(ipath, "rb") as f:
            index = json.loads(f.read())
        sources.append(_FileShardSource(dirpath, index))
    return _assemble(sources, placement, skeleton)


# -- legacy single-file pickle format (pre-subsystem compat) ----------------


def save_pytree(tree: Any, path: str, use_orbax: bool = False) -> None:
    """Legacy synchronous save: device arrays -> host numpy -> one pickle.

    Kept as the compat path behind ``train._checkpoint.save_pytree``.
    """
    import time as _time

    import jax
    import numpy as np
    t0 = _time.perf_counter()
    host = jax.tree.map(lambda x: np.asarray(jax.device_get(x)), tree)
    if use_orbax:
        import orbax.checkpoint as ocp
        ckptr = ocp.PyTreeCheckpointer()
        ckptr.save(os.path.join(path, "orbax"), host)
    else:
        buf = pickle.dumps(host, protocol=5)
        write_bytes_atomic(os.path.join(path, "pytree.pkl"), buf)
    _note_legacy("save", _time.perf_counter() - t0)


def load_pytree(path: str, use_orbax: bool = False) -> Any:
    import time as _time
    t0 = _time.perf_counter()
    if use_orbax:
        import orbax.checkpoint as ocp
        ckptr = ocp.PyTreeCheckpointer()
        out = ckptr.restore(os.path.join(path, "orbax"))
    elif is_committed(path):
        out = restore_tree(path)
    else:
        with open(os.path.join(path, "pytree.pkl"), "rb") as f:
            out = pickle.load(f)
    _note_legacy("restore", _time.perf_counter() - t0)
    return out


def _note_legacy(op: str, seconds: float) -> None:
    try:
        from ..util import telemetry
    except Exception:
        return
    telemetry.observe("ray_tpu_train_checkpoint_seconds", seconds,
                      tags={"op": op})
    telemetry.note_checkpoint_seconds(seconds)
    if op == "restore":
        telemetry.observe("ray_tpu_ckpt_restore_seconds", seconds,
                          tags={"source": "disk"})
