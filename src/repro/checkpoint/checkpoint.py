"""Pytree checkpointing: npz-serialized model snapshots.

Used both for durable checkpoints (train loop) and for the *model snapshot*
blobs the Florida server distributes to clients each round (paper §1: the
orchestrator "distribut[es] a model snapshot to a client ... running client
code to update the model").

Format: npz with flattened leaf arrays keyed "leaf_<i>" plus a json header
encoding the treedef path structure. Handles nested dicts/lists/tuples of
jnp/np arrays (the param structures used throughout this repo).
"""
from __future__ import annotations

import io
import json
import os

import jax
import numpy as np

from repro import tracing


def _paths_and_leaves(tree):
    leaves_with_paths = jax.tree_util.tree_flatten_with_path(tree)[0]
    paths = [jax.tree_util.keystr(p) for p, _ in leaves_with_paths]
    leaves = [np.asarray(v) for _, v in leaves_with_paths]
    return paths, leaves


def serialize_pytree(tree) -> bytes:
    paths, leaves = _paths_and_leaves(tree)
    buf = io.BytesIO()
    arrays = {f"leaf_{i}": leaf for i, leaf in enumerate(leaves)}
    np.savez(buf, __paths__=np.frombuffer(
        json.dumps(paths).encode(), dtype=np.uint8), **arrays)
    return buf.getvalue()


def deserialize_pytree(blob: bytes, like=None):
    """If ``like`` is given, restore into its exact treedef; otherwise
    return {path: array}."""
    with tracing.span("deserialize"):
        with np.load(io.BytesIO(blob)) as z:
            paths = json.loads(bytes(z["__paths__"]).decode())
            leaves = [z[f"leaf_{i}"] for i in range(len(paths))]
        if like is None:
            return dict(zip(paths, leaves))
        like_paths, _ = _paths_and_leaves(like)
        if like_paths != paths:
            raise ValueError("checkpoint structure mismatch")
        treedef = jax.tree_util.tree_structure(like)
        return jax.tree_util.tree_unflatten(treedef, leaves)


def save_checkpoint(path: str, tree, step: int | None = None):
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    blob = serialize_pytree(tree if step is None
                            else {"step": np.int64(step), "tree": tree})
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        f.write(blob)
    os.replace(tmp, path)  # atomic


def load_checkpoint(path: str, like=None, with_step=False):
    with open(path, "rb") as f:
        blob = f.read()
    if with_step:
        restored = deserialize_pytree(
            blob, {"step": np.int64(0), "tree": like} if like is not None
            else None)
        if like is not None:
            return restored["tree"], int(restored["step"])
        return restored
    return deserialize_pytree(blob, like)
