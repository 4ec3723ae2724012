"""Sharded checkpointing: one file per leaf, encoded on a pool of host
threads, async writer.

Layout: <dir>/step_<N>/{manifest.msgpack, leaf_<i>.bin}. Each leaf is the
full (unsharded) array — on restore, ``jax.device_put`` with the target
shardings re-shards for whatever mesh the restart runs on (elastic
restart). The MigrOS container path reuses the same serialisation for user
state inside migration images.

A leaf file is a msgpack header (dtype, shape, raw byte length) and then
one zstd frame (level 1) of the array's raw bytes. The frame is
compressed from a ``uint8`` view of the host array, and decompressed from
the leaf file mapped into memory straight into the restored array: no
full-size copy beyond zstd's output and the decode's destination. The
leaves of one save (one restore) are encoded (decoded) at once on a
``ThreadPoolExecutor`` as wide as the leaves and the process's CPUs
allow; zstd releases the interpreter lock.

Host-clock spans (``repro.obs.host``) by phase: ``ckpt.save`` holds
``ckpt.save.to_host`` (device to host), one ``ckpt.save.encode`` around
the parallel encode, and ``ckpt.save.write`` per leaf file (the last one
also writes the manifest and publishes). With ``async_write`` the encode
and write spans are the writer thread's own. ``ckpt.restore`` holds
``ckpt.restore.read`` per file (a leaf's header read and its file
mapped) and one ``ckpt.restore.decode`` around the parallel decode. Each
leaf's own work is a ``ckpt.save.encode.leaf`` or
``ckpt.restore.decode.leaf`` span on its worker thread; their sum over
the phase's span is the parallelism the pool reached.
"""
from __future__ import annotations

import mmap
import os
import shutil
import threading
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, Optional

import jax
import msgpack
import numpy as np
import zstandard

from repro.obs import host


def _bytes_of(a: np.ndarray) -> np.ndarray:
    """A flat ``uint8`` view of ``a``'s bytes (a copy only if ``a`` is not
    C-contiguous); bfloat16 has no buffer format of its own."""
    return np.ascontiguousarray(a).reshape(-1).view(np.uint8)


def _encode_leaf(a: np.ndarray):
    """(header, zstd frame) of one host array."""
    with host.span(host.CKPT_SAVE_ENCODE_LEAF):
        header = msgpack.packb({"dtype": str(a.dtype), "shape": list(a.shape),
                                "nbytes": a.nbytes})
        return header, zstandard.ZstdCompressor(level=1).compress(_bytes_of(a))


def _decode_leaf(leaf) -> np.ndarray:
    """The array of one (header, frame); raises unless the frame fills it."""
    header, frame = leaf
    with host.span(host.CKPT_RESTORE_DECODE_LEAF):
        out = np.empty(header["shape"], np.dtype(header["dtype"]))
        with zstandard.ZstdDecompressor().stream_reader(frame) as r:
            got = r.readinto(_bytes_of(out))
        if got != header["nbytes"] or got != out.nbytes:
            raise ValueError(f"leaf decoded to {got} bytes, header says "
                             f"{header['nbytes']} for {out.nbytes}")
        return out


def _read_leaf(fname: str):
    """(header, frame) of one leaf file; the frame is a view of the file
    mapped read-only, not a copy (dropping the view unmaps it)."""
    with open(fname, "rb") as f:
        up = msgpack.Unpacker(f, raw=False)
        header = up.unpack()
        mapped = mmap.mmap(f.fileno(), 0, access=mmap.ACCESS_READ)
    return header, memoryview(mapped)[up.tell():]


def _pool_map(fn, items: list) -> list:
    """``fn`` over ``items`` on a pool of host threads, in order."""
    width = min(len(items), len(os.sched_getaffinity(0)))
    with ThreadPoolExecutor(max(width, 1)) as pool:
        return list(pool.map(fn, items))


def save(path: str, tree: Any, *, step: int, extra: Optional[Dict] = None,
         async_write: bool = False):
    """Save a pytree of arrays. Returns the checkpoint directory."""
    with host.span(host.CKPT_SAVE):
        d = os.path.join(path, f"step_{step:08d}")
        tmp = d + ".tmp"
        os.makedirs(tmp, exist_ok=True)
        leaves, treedef = jax.tree.flatten(tree)
        with host.span(host.CKPT_SAVE_TO_HOST):
            host_leaves = [np.asarray(x) for x in leaves]  # before async

        def _write():
            with host.span(host.CKPT_SAVE_ENCODE):
                # every leaf's compressed image is held until it is written
                encoded = _pool_map(_encode_leaf, host_leaves)
            for i, (header, frame) in enumerate(encoded):
                with host.span(host.CKPT_SAVE_WRITE):
                    with open(os.path.join(tmp, f"leaf_{i:05d}.bin"),
                              "wb") as f:
                        f.write(header)
                        f.write(frame)
                encoded[i] = None
            with host.span(host.CKPT_SAVE_WRITE):
                manifest = {"n_leaves": len(host_leaves), "step": step,
                            "treedef": str(treedef), "extra": extra or {}}
                with open(os.path.join(tmp, "manifest.msgpack"), "wb") as f:
                    f.write(msgpack.packb(manifest))
                if os.path.isdir(d):                 # re-save after restart
                    shutil.rmtree(d)
                os.replace(tmp, d)                   # atomic publish

        if async_write:
            t = threading.Thread(target=_write, daemon=True)
            t.start()
            return d, t
        _write()
        return d


def restore(ckpt_dir: str, like: Any, *, shardings: Any = None) -> Any:
    """Restore into the structure of `like` (pytree of arrays/SDS)."""
    with host.span(host.CKPT_RESTORE):
        with host.span(host.CKPT_RESTORE_READ):
            with open(os.path.join(ckpt_dir, "manifest.msgpack"), "rb") as f:
                manifest = msgpack.unpackb(f.read(), raw=False)
        leaves, treedef = jax.tree.flatten(like)
        assert manifest["n_leaves"] == len(leaves), "structure mismatch"
        encoded = []
        for i in range(len(leaves)):
            with host.span(host.CKPT_RESTORE_READ):
                encoded.append(_read_leaf(
                    os.path.join(ckpt_dir, f"leaf_{i:05d}.bin")))
        with host.span(host.CKPT_RESTORE_DECODE):
            out = _pool_map(_decode_leaf, encoded)
        del encoded                                 # unmaps the files
        tree = jax.tree.unflatten(treedef, out)
        if shardings is not None:
            tree = jax.device_put(tree, shardings)
        return tree


def latest(path: str) -> Optional[str]:
    if not os.path.isdir(path):
        return None
    steps = sorted(d for d in os.listdir(path) if d.startswith("step_")
                   and not d.endswith(".tmp"))
    return os.path.join(path, steps[-1]) if steps else None


def manifest_extra(ckpt_dir: str) -> Dict:
    with open(os.path.join(ckpt_dir, "manifest.msgpack"), "rb") as f:
        return msgpack.unpackb(f.read(), raw=False)["extra"]
