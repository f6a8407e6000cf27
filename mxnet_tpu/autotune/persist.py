"""Winner persistence: tuned configs survive the process, keyed like the
XLA compile cache they sit next to.

Winners live in ONE JSON file (``winners.json``) under, in order of
preference: the ``autotune.cache_dir`` knob, the persistent XLA compile
cache directory (``_compile_cache.cache_dir()``: JAX_COMPILATION_CACHE_DIR,
else the ``compilation_cache_dir`` knob — "next to the XLA cache", so one
cache volume carries both the compiled executables and the configs that
produced them), or ``<mxnet home>/autotune``.

Keys are ``<model fingerprint>|<device_kind>|dp<N>[|mesh:<axes>]``: the
fingerprint hashes the parameter inventory (structural name, shape,
dtype) plus the block/loss/optimizer identities, so any architecture
change invalidates the entry; device_kind, dp size and the mesh shape
(every axis with size > 1, e.g. ``mesh:dp2xtp2``) key the hardware point
the measurement is only valid for — a winner tuned on one topology never
loads on another.  Writes are atomic (tmp + rename) — a preempted run
never leaves a torn winners file.

Schema 2 adds kernel-level winners in the SAME file, keyed
``<kernel>|<shape bucket>|<device_kind>`` (kernel records carry
``"kind": "kernel"``; step records are unmarked), plus a bounded
``"trials"`` plane of raw measured kernel trials — the training set the
learned cost model (learned.py) fits.  Schema-1 files migrate on load:
step-winner records pass through unchanged, so a PR-7-era cache keeps
answering searches with zero re-trials.
"""
from __future__ import annotations

import hashlib
import json
import os
import tempfile

from .. import config as _config

__all__ = ["cache_dir", "winners_path", "model_fingerprint", "winner_key",
           "kernel_key", "load_winner", "save_winner", "load_all",
           "append_trials", "load_trials"]

_FILE = "winners.json"
_SCHEMA = 2
#: schema versions load_all accepts; 1 is the PR-7 step-winner format
#: whose records are forward-compatible verbatim
_COMPAT_SCHEMAS = (1, 2)
#: cap on persisted raw trial records (oldest evicted first)
_TRIALS_CAP = 512


def cache_dir():
    """Resolve the winners directory (see module docstring)."""
    from .._compile_cache import cache_dir as _xla_cache_dir
    path = (_config.get("autotune.cache_dir") or _xla_cache_dir()
            or os.path.join(_config.get("home"), "autotune"))
    return os.path.abspath(os.path.expanduser(path))


def winners_path():
    return os.path.join(cache_dir(), _FILE)


def model_fingerprint(block, loss_fn=None, optimizer=None):
    """Hash of everything a stale winner must not survive: the parameter
    inventory (name, shape, dtype — sorted, so dict order is irrelevant),
    the block class, and the loss/optimizer identities."""
    from .. import functional
    trainable, aux = functional.split_params(block)
    items = []
    for n, v in sorted({**trainable, **aux}.items()):
        items.append(f"{n}:{tuple(v.shape)}:{v.dtype}")
    items.append(f"block={type(block).__module__}.{type(block).__qualname__}")
    if loss_fn is not None:
        items.append(f"loss={getattr(loss_fn, '__qualname__', None) or type(loss_fn).__qualname__}")
    if optimizer is not None:
        items.append(f"opt={type(optimizer).__qualname__}")
    h = hashlib.sha256("\n".join(items).encode()).hexdigest()
    return h[:16]


def winner_key(fingerprint, device_kind, dp, mesh=None):
    """``mesh`` (a MeshConfig, jax Mesh or {axis: size} dict) appends the
    topology to the key so a winner measured on dp2xtp2 never loads on
    dp4; omit it for the pre-mesh key format (dp-only searches)."""
    key = f"{fingerprint}|{device_kind}|dp{int(dp)}"
    if mesh is not None:
        shape = dict(getattr(mesh, "shape", mesh))
        axes = "x".join(f"{a}{int(s)}" for a, s in sorted(shape.items())
                        if int(s) > 1)
        key += f"|mesh:{axes or '1'}"
    return key


def kernel_key(kernel, bucket, device_kind):
    """Key for one kernel-level winner: the kernel name, its shape
    bucket (problem dims rounded to powers of two, joined with ``x``)
    and the device kind the tile timing is only valid for."""
    if isinstance(bucket, (tuple, list)):
        bucket = "x".join(str(int(d)) for d in bucket)
    return f"{kernel}|{bucket}|{device_kind}"


def _load_doc(path):
    """Parse the full winners document (any compatible schema) ->
    ``{"winners": {...}, "trials": [...]}``; empty planes when the file
    is absent, corrupt, or from an unknown schema."""
    empty = {"winners": {}, "trials": []}
    try:
        with open(path) as f:
            data = json.load(f)
    except (OSError, ValueError):
        return empty
    if not isinstance(data, dict):
        return empty
    # schema 1 files carry only {"version": 1, "winners": ...}; their
    # step-winner records are schema-2-compatible verbatim (kernel
    # records are distinguished by "kind", which schema 1 never wrote)
    schema = data.get("schema", data.get("version"))
    if schema not in _COMPAT_SCHEMAS:
        return empty
    winners = data.get("winners")
    trials = data.get("trials")
    return {"winners": winners if isinstance(winners, dict) else {},
            "trials": trials if isinstance(trials, list) else []}


def _save_doc(doc, path):
    """Atomically write the full document at the current schema."""
    d = os.path.dirname(path)
    os.makedirs(d, exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=d, prefix=".winners.", suffix=".tmp")
    try:
        with os.fdopen(fd, "w") as f:
            json.dump({"schema": _SCHEMA, "version": _SCHEMA,
                       "winners": doc["winners"],
                       "trials": doc["trials"][-_TRIALS_CAP:]},
                      f, indent=1, sort_keys=True)
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise
    return path


def load_all(path=None):
    """Parse a winners file -> {key: record}; {} when absent/corrupt.
    Accepts schema 1 (step winners only) and schema 2."""
    return _load_doc(path or winners_path())["winners"]


def load_winner(key, path=None):
    return load_all(path).get(key)


def save_winner(key, record, path=None):
    """Merge one winner into the file atomically; returns the path.
    A schema-1 file is migrated to schema 2 in place on first write —
    every existing step winner survives verbatim."""
    path = path or winners_path()
    doc = _load_doc(path)
    doc["winners"][key] = record
    return _save_doc(doc, path)


def append_trials(records, path=None):
    """Append raw measured trial records (bounded ring, oldest evicted)
    — the persisted training set for the learned cost model."""
    path = path or winners_path()
    doc = _load_doc(path)
    doc["trials"].extend(records)
    return _save_doc(doc, path)


def load_trials(path=None):
    """The persisted raw kernel-trial records (possibly empty)."""
    return _load_doc(path or winners_path())["trials"]
