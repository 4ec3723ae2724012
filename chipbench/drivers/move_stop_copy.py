"""The move: stop-and-copy of device state through the program's own calls.

The source has stopped (its last step has returned). Its state is saved
with ``ckpt.save`` into a temporary directory (device -> host, msgpack +
zstd per leaf, written to disk) and the device copy is deleted. Then the
destination - a new ``ServingEngine``, or a template of the train state -
takes ``ckpt.restore`` -> ``jax.device_put``, and the first step on the
restored state returns, which closes the stop.

A checksum of the state is enqueued before the save and after the restore;
the check compares them after the window (bits must not change).
"""
from __future__ import annotations

import os
import shutil
import tempfile

import jax
import numpy as np

from chipbench.common import checksum
from repro.checkpoint import ckpt
from repro.serving.engine import ServingEngine


def _save(state, step, spans):
    """Save ``state`` and delete its device copy; returns (dir, path, bytes
    written)."""
    d = tempfile.mkdtemp(prefix="chipbench-move-")
    try:
        with spans("move.save"):
            path = ckpt.save(d, state, step=step)
            jax.tree.map(lambda a: a.delete(), state)
    except BaseException:
        shutil.rmtree(d, ignore_errors=True)
        raise
    return d, path, sum(e.stat().st_size for e in os.scandir(path))


def move_engine(eng, spans, first_step):
    """Move a serving engine; the new engine keeps the source's slots and
    requests in flight. ``first_step(engine)`` runs on the restored engine
    inside the restore span and returns when its step returned. Returns
    (new engine, checksums before, after, that time, image bytes)."""
    before = checksum(eng.cache)
    d, path, nbytes = _save(eng.cache, eng.steps, spans)
    try:
        with spans("move.restore"):
            dst = ServingEngine(eng.lm, eng.params, slots=eng.slots,
                                capacity=eng.capacity)
            dst.load_state_dict({"cache": jax.device_put(
                ckpt.restore(path, dst.cache)), "steps": eng.steps})
            dst.active = eng.active
            after = checksum(dst.cache)
            resumed = first_step(dst)
    finally:
        shutil.rmtree(d, ignore_errors=True)
    return dst, before, after, resumed, nbytes


def move_train_state(state, template, step, spans, first_step):
    """Move a train state; ``template`` is its abstract shape tree.
    ``first_step(state)`` runs on the restored state inside the restore span
    and returns (state after it, time it returned). Returns (that state,
    checksums before, after, that time, image bytes, the image's directory).
    The caller deletes the directory once its window has closed: a training
    image is the whole state, and deleting it would take the time of the
    steps that follow the move."""
    before = checksum(state)
    d, path, nbytes = _save(state, step, spans)
    try:
        with spans("move.restore"):
            state = jax.device_put(ckpt.restore(path, template))
            after = checksum(state)
            state, resumed = first_step(state)
    except BaseException:
        shutil.rmtree(d, ignore_errors=True)
        raise
    return state, before, after, resumed, nbytes, d


def bits_differ(moves):
    """Leaves whose checksum changed through a move, over the window's
    moves; no move at all reads as one difference (nothing was checked)."""
    if not moves:
        return 1
    return int(sum(int(np.sum(np.asarray(b) != np.asarray(a)))
                   for _, _, b, a, _ in moves))
