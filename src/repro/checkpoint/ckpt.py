"""Sharded checkpointing: msgpack + zstd, per-leaf streaming, async
writer.

Layout: <dir>/step_<N>/{manifest.msgpack, leaf_<i>.bin}. Each leaf is the
full (unsharded) array — on restore, ``jax.device_put`` with the target
shardings re-shards for whatever mesh the restart runs on (elastic
restart). The MigrOS container path reuses the same serialisation for user
state inside migration images.

Host-clock spans (``repro.obs.host``) by phase: ``ckpt.save`` holds
``ckpt.save.to_host`` (device to host), then per leaf
``ckpt.save.encode`` (msgpack + zstd) and ``ckpt.save.write`` (the file;
the last one also writes the manifest and publishes). With
``async_write`` the encode and write spans are the writer thread's own.
``ckpt.restore`` holds ``ckpt.restore.read`` and ``ckpt.restore.decode``
per leaf.
"""
from __future__ import annotations

import os
import shutil
import threading
from typing import Any, Dict, Optional

import jax
import msgpack
import numpy as np
import zstandard

from repro.obs import host


def _compress(raw: bytes) -> bytes:
    return zstandard.ZstdCompressor(level=1).compress(raw)


def _decompress(blob: bytes) -> bytes:
    return zstandard.ZstdDecompressor().decompress(blob)


def _pack_leaf(arr) -> bytes:
    a = np.asarray(arr)
    meta = {"dtype": str(a.dtype), "shape": list(a.shape)}
    raw = msgpack.packb(meta) + bytes(a.tobytes())
    return _compress(raw)


def _unpack_leaf(blob: bytes) -> np.ndarray:
    raw = _decompress(blob)
    # msgpack's default buffer (100 MiB) refuses a larger leaf
    up = msgpack.Unpacker(max_buffer_size=len(raw))
    up.feed(raw)
    meta = up.unpack()
    off = up.tell()
    a = np.frombuffer(raw[off:], dtype=np.dtype(meta["dtype"]))
    return a.reshape(meta["shape"])


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
            for i, a in enumerate(host_leaves):
                with host.span(host.CKPT_SAVE_ENCODE):
                    blob = _pack_leaf(a)
                with host.span(host.CKPT_SAVE_WRITE):
                    with open(os.path.join(tmp, f"leaf_{i:05d}.bin"),
                              "wb") as f:
                        f.write(blob)
                del blob            # one leaf's image held at a time
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
        out = []
        for i in range(len(leaves)):
            with host.span(host.CKPT_RESTORE_READ):
                with open(os.path.join(ckpt_dir, f"leaf_{i:05d}.bin"),
                          "rb") as f:
                    blob = f.read()
            with host.span(host.CKPT_RESTORE_DECODE):
                out.append(_unpack_leaf(blob))
            del blob
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
