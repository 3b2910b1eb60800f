"""A store of serialised executables — a worker starts on the programs its
last start built.

JAX's persistent compile cache skips the backend's compile and nothing
before it: a warm start still traces every program in Python and lowers it
to MLIR, which is most of a decode worker's warm-up (``PERF.md`` §5). An
entry here is one compiled program as ``jax.experimental.
serialize_executable`` writes it — the executable and its argument and
result trees — so a start that finds it loads it and runs no Python of the
model at all. ``runtime/kvcache.py`` is the one caller: it asks ``load``
for every program it is about to build and hands ``save`` every one it
built.

**The key** is a digest of everything the executable depends on, and errs
towards a miss (a stale executable answers wrongly in silence):

- the store's ``context`` — a fingerprint of the source the programs are
  traced from (every ``*.py`` under ``ai4e_tpu/``, bytes and names: no
  hand-kept list of modules, and scope names count, which JAX's own cache
  key leaves out), the versions of jax, jaxlib, libtpu and flax, the
  backend's own version string, platform, device kind and count, process
  count, and the environment and configuration that reach the compiler;
- what the caller adds: the model, the cache's geometry, the program's
  name, its static values, donation and compiler options, and
  ``signature(args)`` — the arguments' tree, types and shardings.

**Where it lives**: ``<compile cache>/executables/<source>/<key>``, one
directory a source tree, so every process of a checkout resolves the same
path and a first start fills what the next one loads. JAX's own LRU counts
only JAX's files; this store bounds itself: a process's first write drops
every other tree's directory but the newest (an old and a new generation
of one rollout may share a host; a third never does).

**Every failure is a miss**: a missing, truncated, foreign, wrong-version
or wrong-topology entry is built again and overwritten, with one INFO line.
Writes are atomic (``.tmp`` + ``os.replace``): two writers of one key leave
one whole file.

A build goes through JAX's persistent compile cache as it always did, so a
miss here (a byte of source changed) still skips the backend's compile where
JAX's key held. One backend cannot take that: XLA:CPU serialises an
executable it loaded from its own cache entry without its functions
(jaxlib 0.9.0: the entry loads, and its first run fails with ``Function …
not found``), so on the CPU such a build is served and not stored. The
TPU's re-serialise whole (``PERF.md`` §6, PR 57).
"""

from __future__ import annotations

import hashlib
import json
import logging
import os
import pickle
import shutil
import tempfile
import threading

log = logging.getLogger("ai4e_tpu.executables")

_PACKAGE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# What reaches the compiler from outside the arguments.
_ENVIRONMENT = ("XLA_FLAGS", "LIBTPU_INIT_ARGS")
_CONFIG = ("jax_enable_x64", "jax_default_matmul_precision",
           "jax_numpy_dtype_promotion", "jax_threefry_partitionable")


def source_fingerprint(root: str = _PACKAGE) -> str:
    """A digest of every ``*.py`` file under ``root``, names and bytes: a
    byte of any of them changed is another fingerprint."""
    digest = hashlib.sha256()
    for directory, subdirs, files in os.walk(root):
        subdirs.sort()
        for name in sorted(files):
            if name.endswith(".py"):
                path = os.path.join(directory, name)
                digest.update(os.path.relpath(path, root).encode() + b"\0")
                with open(path, "rb") as f:
                    digest.update(f.read() + b"\0")
    return digest.hexdigest()


def environment() -> dict:
    """What an executable depends on beside the program: the installation,
    the device, and the settings the compiler reads."""
    from importlib import metadata

    import jax

    from .registry import device_report
    report = device_report()
    return {
        "versions": {**report["versions"], "flax": metadata.version("flax")},
        "platform": report["platform"],
        "platform_version": jax.devices()[0].client.platform_version,
        "device_kind": report["device_kind"],
        "device_count": report["device_count"],
        "process_count": jax.process_count(),
        "environment": {name: os.environ.get(name) for name in _ENVIRONMENT},
        "config": {name: str(getattr(jax.config, name)) for name in _CONFIG},
    }


def _compile(lowered):
    """``lowered.compile()``, and whether JAX's persistent cache gave the
    executable: it reports a hit on the thread that compiles."""
    import jax.monitoring
    thread, hits = threading.get_ident(), []

    def listen(event: str, **kw) -> None:
        if (event == "/jax/compilation_cache/cache_hits"
                and threading.get_ident() == thread):
            hits.append(event)

    jax.monitoring.register_event_listener(listen)
    try:
        return lowered.compile(), bool(hits)
    finally:
        jax.monitoring.unregister_event_listener(listen)


def signature(args) -> tuple[list, object] | None:
    """``(what the key holds of a call's arguments, the device they run
    on)``: the tree, and every leaf's type (shape, dtype, weak or not) and
    sharding. None where an argument spans devices — a sharded program is
    not stored."""
    import jax
    leaves, tree = jax.tree.flatten(args)
    devices = set()
    described = [str(tree)]
    for leaf in leaves:
        sharding = getattr(leaf, "sharding", None)
        if sharding is not None:
            devices |= sharding.device_set
        described.append(f"{jax.typeof(leaf)} {sharding}")
    if len(devices) > 1:
        return None
    return described, (devices.pop() if devices else jax.devices()[0])


class ExecutableStore:
    """The executables under one directory, for the source tree and the
    installation this process runs."""

    def __init__(self, directory: str):
        self.context = {"source": source_fingerprint(), **environment()}
        self.root = directory
        self._swept = False
        self._told = set()

    @property
    def directory(self) -> str:
        """This source tree's entries."""
        return os.path.join(self.root, self.context["source"][:16])

    def key(self, **parts) -> str:
        """The digest of the store's context and the caller's ``parts``
        (anything ``json`` or ``str`` can write)."""
        text = json.dumps({"context": self.context, **parts}, sort_keys=True,
                          default=str)
        return hashlib.sha256(text.encode()).hexdigest()

    def _path(self, key: str) -> str:
        return os.path.join(self.directory, key)

    def _tell(self, key: str, what: str) -> None:
        """One INFO line an entry, however often it fails."""
        if key not in self._told:
            self._told.add(key)
            log.info("executable %s %s", key[:12], what)

    def load(self, key: str, device):
        """The program stored under ``key``, loaded onto ``device``; None —
        a miss — where there is none or it cannot be loaded."""
        from jax.experimental import serialize_executable
        try:
            with open(self._path(key), "rb") as f:
                # Only this program writes here (docs/operations.md).
                stored, payload, in_tree, out_tree = pickle.load(f)
            if stored != key:
                raise ValueError(f"holds the entry of {stored!r:.14}")
            return serialize_executable.deserialize_and_load(
                payload, in_tree, out_tree, execution_devices=[device])
        except FileNotFoundError:
            return None
        except Exception as exc:  # noqa: BLE001; ai4e: noqa[AIL005] — every failure is a miss, told once at INFO
            self._tell(key, f"cannot be loaded ({type(exc).__name__}: {exc}): "
                            "building it again")
            return None

    def build(self, key: str, lowered):
        """Compile ``lowered`` — a miss — and store the result under
        ``key``."""
        compiled, retrieved = _compile(lowered)
        if retrieved and self.context["platform"] == "cpu":
            self._tell(key, "came from the compile cache, which XLA:CPU "
                            "cannot serialise again: not stored")
        else:
            self.save(key, compiled)
        return compiled

    def save(self, key: str, compiled) -> None:
        """Write ``compiled`` under ``key``, whole or not at all. A program
        that cannot be serialised, or a disk that cannot take it, costs the
        next start its build and this one nothing."""
        from jax.experimental import serialize_executable
        try:
            entry = pickle.dumps((key, *serialize_executable.serialize(
                compiled)))
            self._sweep()
            os.makedirs(self.directory, exist_ok=True)
            # A name of its own a writer: two of one key never share a file.
            fd, tmp = tempfile.mkstemp(dir=self.directory, suffix=".tmp")
            with os.fdopen(fd, "wb") as f:
                f.write(entry)
            os.replace(tmp, self._path(key))
        except Exception as exc:  # noqa: BLE001; ai4e: noqa[AIL005] — the program still serves; told once at INFO
            self._tell(key, f"cannot be stored ({type(exc).__name__}: {exc})")

    def _sweep(self) -> None:
        """Before this process's first write: drop every other source
        tree's entries but the tree written to last."""
        if self._swept:
            return
        self._swept = True
        try:
            others = sorted(
                (entry for entry in os.scandir(self.root) if entry.is_dir()
                 and entry.path != self.directory),
                key=lambda entry: entry.stat().st_mtime)
        except OSError:
            return
        for entry in others[:-1]:
            shutil.rmtree(entry.path, ignore_errors=True)

    def nbytes(self) -> int:
        """Bytes of this source tree's entries."""
        try:
            return sum(entry.stat().st_size
                       for entry in os.scandir(self.directory))
        except OSError:
            return 0
