"""Persistent AOT lowering cache: compile once per (entry, shape-bucket).

The serving runtime compiles one executable per (kernel entry, shape
bucket, statics). Those compiles are pure functions of the traced program
— nothing about them depends on graph *content* — yet every fresh process
pays them again: BENCH r04 burned 18.7 s of ``plan_build``-adjacent
compile time, and a cold ``ServeRuntime`` spends its first deadline
windows inside XLA instead of serving. This module makes the compile a
cache lookup: ``jax.jit(...).lower().compile()`` products are serialized
(``jax.experimental.serialize_executable``) into a **fingerprinted
on-disk directory** and loaded back in milliseconds.

Key anatomy (see README "AOT cache"):

- the cache **directory** is fingerprinted by environment —
  ``<root>/<jax-version>_<backend>/`` — so upgrading jax or moving
  between backends can never replay a stale executable;
- the **entry file name** is ``<entry>__<sha256 of (entry, arg avals,
  execution devices, statics, content_key)>.aot``; avals cover every
  dynamic argument's shape/dtype (the shape bucket), the devices are the
  ones the arguments pin the program to (:func:`execution_devices` — the
  executable is loaded back onto exactly those), statics are the
  jit-static kwargs, and
  ``content_key`` is the caller's optional data fingerprint (serving
  passes ``ellbfs.snapshot_fingerprint``-style keys when results must be
  pinned to a snapshot generation);
- each file carries a JSON header (format version, jax/backend versions,
  entry, content_key, wall compile seconds) ahead of the pickled
  executable payload.

Invalidation rules, mirroring ``ellbfs.StalePlans``:

- a WELL-FORMED entry whose header disagrees (format bump, jax/backend
  version, content_key) raises :class:`StaleEntry` internally and is
  treated as a quiet miss → rebuild (counted in ``stats.stale``);
- an unreadable/corrupt file is logged at WARNING, counted in
  ``stats.corrupt``, and rebuilt — a damaged cache must never take the
  process down;
- stores are write-then-rename, so a crashed writer leaves no torn entry.

``JAX_PLATFORMS=cpu`` behavior: everything works (CPU executables
serialize fine), so the lifecycle is testable in tier-1; only the
*callers'* Pallas gates differ per backend.
"""

from __future__ import annotations

import hashlib
import json
import logging
import os
import pickle
import time
from dataclasses import dataclass, field
from typing import Any, Optional

log = logging.getLogger("hypergraphdb_tpu.aot")

#: bumped when the on-disk layout changes; mismatched entries are stale
FORMAT = 1

_MAGIC = b"HGAOT1\n"


class StaleEntry(ValueError):
    """Well-formed cache entry for a different environment/content —
    the quiet-rebuild case, deliberately distinct from a corrupt file."""


@dataclass
class AOTStats:
    """Counters of one cache instance. ``hits``/``misses`` count compile
    avoidance (a memory hit after a disk hit is still a hit — the point
    is whether XLA ran); the rest classify why a miss happened."""

    hits: int = 0
    misses: int = 0
    disk_hits: int = 0      # hits served by deserializing from disk
    mem_hits: int = 0       # hits served by the in-process memo
    stale: int = 0
    corrupt: int = 0
    puts: int = 0
    gc_removed: int = 0     # superseded entries deleted by the open sweep
    compile_s: float = 0.0  # wall seconds spent actually compiling

    def as_dict(self) -> dict:
        return {
            "hits": self.hits, "misses": self.misses,
            "disk_hits": self.disk_hits, "mem_hits": self.mem_hits,
            "stale": self.stale, "corrupt": self.corrupt,
            "puts": self.puts, "gc_removed": self.gc_removed,
            "compile_s": round(self.compile_s, 3),
        }


def env_fingerprint(backend: Optional[str] = None) -> str:
    """The environment half of the key: jax version + backend platform.
    Anything that changes the emitted executable format must be here."""
    import jax

    return f"jax{jax.__version__}_{backend or jax.default_backend()}"


def _is_device(obj: Any) -> bool:
    try:
        import jax

        return isinstance(obj, jax.Device)
    except Exception:  # noqa: BLE001 - exotic jax versions
        return type(obj).__name__ == "Device"


def _dumps(obj: Any) -> bytes:
    """Pickle with jax ``Device`` objects swapped for their ids: the
    in/out treedefs of MESH-sharded entries carry the ``Mesh`` (and so
    its device array) in pytree aux data, and devices are process
    handles no pickler can serialize. The env fingerprint already pins
    the backend, so re-resolving by id at load time is exact."""
    import io

    buf = io.BytesIO()
    p = pickle.Pickler(buf)

    def persistent_id(o):
        if _is_device(o):
            return ("hg_device", int(o.id))
        return None

    p.persistent_id = persistent_id
    p.dump(obj)
    return buf.getvalue()


def _loads(data: bytes) -> Any:
    import io

    up = pickle.Unpickler(io.BytesIO(data))

    def persistent_load(pid):
        kind, did = pid
        if kind != "hg_device":
            raise pickle.UnpicklingError(f"unknown persistent id {pid!r}")
        import jax

        for d in jax.devices():
            if int(d.id) == int(did):
                return d
        # fewer/different devices than the writer: a stale-shaped entry,
        # surfaced as unreadable → quiet rebuild
        raise pickle.UnpicklingError(f"device id {did} not present")

    up.persistent_load = persistent_load
    return up.load()


def _aval_sig(x: Any) -> str:
    import jax

    leaves = jax.tree_util.tree_leaves(x)
    parts = []
    for leaf in leaves:
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", type(leaf).__name__)
        parts.append(f"{tuple(shape) if shape is not None else ()}:{dtype}")
    return ";".join(parts)


def execution_devices(args: Any) -> tuple:
    """The devices ``jit_fn(*args)`` executes on, in the program's own
    device order: the committed arguments' shardings (a mesh keeps its
    ``devices.flat`` order), else — every argument uncommitted or a host
    value — the default device, exactly where jit would place it.

    A serialized executable records no devices of its own:
    ``deserialize_and_load`` binds it to whatever it is handed, and by
    default to EVERY local device, which turns a one-device program into
    an n-shard one that fails at its first execute. So the cache derives
    the devices from the arguments, keys the entry on them and loads the
    executable onto them."""
    import jax

    committed: dict = {}
    uncommitted: dict = {}
    for leaf in jax.tree_util.tree_leaves(args):
        sharding = getattr(leaf, "sharding", None)
        if sharding is None:
            continue
        mesh = getattr(sharding, "mesh", None)
        devs = (tuple(mesh.devices.flat) if mesh is not None
                else sorted(sharding.device_set, key=lambda d: d.id))
        # a ShapeDtypeStruct that names a sharding pins it like a
        # committed array does
        into = committed if getattr(leaf, "committed", True) else uncommitted
        for d in devs:
            into.setdefault(d.id, d)
    found = committed or uncommitted
    if found:
        return tuple(found.values())
    default = jax.config.jax_default_device
    return (default if isinstance(default, jax.Device) else jax.devices()[0],)


@dataclass
class AOTCache:
    """One fingerprinted cache directory + an in-process compiled memo.

    Thread-safety: lookups and stores are idempotent (same key → same
    executable) and writes are atomic renames, so concurrent runtimes
    sharing a directory at worst duplicate a compile.
    """

    root: str
    content_key: str = ""
    backend: Optional[str] = None
    stats: AOTStats = field(default_factory=AOTStats)
    #: open-time GC bounds (ROADMAP 4f): superseded content generations'
    #: files older than ``gc_max_age_s`` are deleted, and oldest-first
    #: beyond ``gc_max_bytes`` of directory total — a long-lived replica
    #: otherwise accumulates multi-MB orphaned executables across every
    #: compaction generation. ``gc_max_age_s=None`` disables the sweep.
    gc_max_age_s: Optional[float] = 7 * 86400.0
    gc_max_bytes: int = 256 * 1024 * 1024

    def __post_init__(self):
        self.dir = os.path.join(self.root, env_fingerprint(self.backend))
        os.makedirs(self.dir, exist_ok=True)
        self._mem: dict[str, Any] = {}
        if self.gc_max_age_s is not None:
            try:
                self.gc()
            except Exception:  # noqa: BLE001 - a broken sweep never gates
                log.warning("aot cache gc failed in %s", self.dir,
                            exc_info=True)

    # -- open-time GC ---------------------------------------------------------
    def _entry_content_key(self, path: str) -> Optional[str]:
        """The entry's header content_key, reading ONLY magic + header
        line (never the multi-MB payload); None for unreadable files —
        those would be rebuilt on load anyway, so GC treats them as
        superseded."""
        try:
            with open(path, "rb") as f:
                if f.read(len(_MAGIC)) != _MAGIC:
                    return None
                header = json.loads(f.readline().decode("utf-8"))
            return str(header.get("content_key", ""))
        except Exception:  # noqa: BLE001 - damaged header
            return None

    def gc(self, now: Optional[float] = None) -> int:
        """Sweep the cache directory (called at open): delete entries of
        SUPERSEDED content generations — files whose header content_key
        differs from this cache's — once older than ``gc_max_age_s``,
        then oldest-superseded-first while the directory's total size
        exceeds ``gc_max_bytes``. Current-generation entries are never
        touched (the prewarm relies on them), and abandoned ``*.tmp.*``
        writer leftovers past the age bound go too. Returns how many
        files were removed (also counted in ``stats.gc_removed``)."""
        if self.gc_max_age_s is None:
            # the documented off switch — without this, a MANUAL gc()
            # would read None as age 0 and delete every superseded entry
            # plus any tmp a concurrent writer is mid-writing
            return 0
        if now is None:
            now = time.time()
        removed = 0
        superseded: list[tuple[float, int, str]] = []  # (mtime, size, path)
        total = 0
        for name in os.listdir(self.dir):
            path = os.path.join(self.dir, name)
            try:
                st = os.stat(path)
            except OSError:
                continue
            if ".tmp." in name:  # crashed writer's leftover
                if now - st.st_mtime > self.gc_max_age_s:
                    try:
                        os.unlink(path)
                        removed += 1
                    except OSError:
                        pass
                continue
            if not name.endswith(".aot"):
                continue
            total += st.st_size
            ck = self._entry_content_key(path)
            if ck != self.content_key:
                superseded.append((st.st_mtime, st.st_size, path))
        superseded.sort()  # oldest first
        for mtime, size, path in superseded:
            if (now - mtime <= self.gc_max_age_s
                    and total <= self.gc_max_bytes):
                continue  # young AND within budget: keep for now
            try:
                os.unlink(path)
            except OSError:
                continue
            removed += 1
            total -= size
        self.stats.gc_removed += removed
        if removed:
            log.info("aot cache gc: removed %d superseded entries from %s",
                     removed, self.dir)
        return removed

    # -- keys -----------------------------------------------------------------
    def key_for(self, entry: str, args: tuple, statics: dict,
                devices: Optional[tuple] = None) -> str:
        if devices is None:
            devices = execution_devices(args)
        h = hashlib.sha256()
        h.update(entry.encode())
        h.update(_aval_sig(args).encode())
        h.update(repr([int(d.id) for d in devices]).encode())
        h.update(repr(sorted(statics.items())).encode())
        h.update(self.content_key.encode())
        safe = "".join(c if c.isalnum() or c in "._-" else "_"
                       for c in entry)[:80]
        return f"{safe}__{h.hexdigest()[:24]}"

    def _path(self, key: str) -> str:
        return os.path.join(self.dir, f"{key}.aot")

    # -- the one entry point --------------------------------------------------
    def get_or_compile(self, entry: str, jit_fn, args: tuple,
                       statics: Optional[dict] = None,
                       persist: bool = True):
        """The compiled executable for ``jit_fn(*args, **statics)`` —
        memory, then disk, then a real ``lower().compile()`` persisted
        for next time. Returns the compiled object; call it with the
        DYNAMIC args only (statics are baked in).

        ``persist=False`` memoizes a fresh compile in-process only:
        dispatch-time shapes the prewarm didn't cover (e.g. a resized
        delta bucket) would otherwise mint a new multi-MB disk entry per
        shape generation, synchronously, on a serving thread — and
        nothing evicts the superseded files."""
        statics = statics or {}
        devices = execution_devices(args)
        key = self.key_for(entry, args, statics, devices)
        compiled = self._mem.get(key)
        if compiled is not None:
            self.stats.hits += 1
            self.stats.mem_hits += 1
            return compiled
        compiled = self._load(key, devices)
        if compiled is not None:
            self.stats.hits += 1
            self.stats.disk_hits += 1
            self._mem[key] = compiled
            return compiled
        self.stats.misses += 1
        t0 = time.perf_counter()
        compiled = jit_fn.lower(*args, **statics).compile()
        dt = time.perf_counter() - t0
        self.stats.compile_s += dt
        self._mem[key] = compiled
        if persist:
            self._store(key, entry, compiled, compile_s=dt)
        return compiled

    def warm(self, entry: str, jit_fn, args: tuple,
             statics: Optional[dict] = None) -> bool:
        """Pre-compile one bucket; True when it was already cached."""
        before = self.stats.hits
        self.get_or_compile(entry, jit_fn, args, statics)
        return self.stats.hits > before

    # -- disk -----------------------------------------------------------------
    def _load(self, key: str, devices: tuple):
        path = self._path(key)
        if not os.path.exists(path):
            return None
        try:
            with open(path, "rb") as f:
                magic = f.read(len(_MAGIC))
                if magic != _MAGIC:
                    raise OSError(f"bad magic {magic!r}")
                header = json.loads(f.readline().decode("utf-8"))
                self._check_header(header, path)
                payload, in_tree, out_tree = _loads(f.read())
        except StaleEntry as e:
            # a different environment/content wrote this — quiet rebuild,
            # exactly the ellbfs.StalePlans discipline
            log.debug("aot cache stale: %s", e)
            self.stats.stale += 1
            return None
        except Exception as e:  # noqa: BLE001 - any damage → rebuild
            log.warning("aot cache entry %s unreadable (%s: %s) — "
                        "rebuilding", path, type(e).__name__, e)
            self.stats.corrupt += 1
            return None
        try:
            from jax.experimental import serialize_executable as se

            return se.deserialize_and_load(
                payload, in_tree, out_tree, execution_devices=devices,
            )
        except Exception as e:  # noqa: BLE001 - runtime rejected the blob
            log.warning("aot cache entry %s failed to deserialize (%s: %s)"
                        " — rebuilding", path, type(e).__name__, e)
            self.stats.corrupt += 1
            return None

    def _check_header(self, header: dict, path: str) -> None:
        import jax

        if header.get("format") != FORMAT:
            raise StaleEntry(f"{path}: format {header.get('format')} != "
                             f"{FORMAT}")
        if header.get("env") != env_fingerprint(self.backend):
            raise StaleEntry(f"{path}: env {header.get('env')!r} != "
                             f"{env_fingerprint(self.backend)!r}")
        if header.get("content_key", "") != self.content_key:
            raise StaleEntry(
                f"{path}: content_key {header.get('content_key')!r} does "
                f"not match ({self.content_key!r}) — stale cache entry"
            )
        _ = jax  # imported for symmetry with env_fingerprint

    def _store(self, key: str, entry: str, compiled,
               compile_s: float = 0.0) -> None:
        """Best-effort persist (an unwritable cache dir must not fail the
        compile that just succeeded)."""
        try:
            from jax.experimental import serialize_executable as se

            payload, in_tree, out_tree = se.serialize(compiled)
            header = {
                "format": FORMAT,
                "env": env_fingerprint(self.backend),
                "entry": entry,
                "content_key": self.content_key,
                "compile_s": round(compile_s, 3),
                "created_unix": int(time.time()),
            }
            path = self._path(key)
            # pid + thread id + monotonic counter: two runtimes in ONE
            # process storing the same key must not interleave into one
            # tmp file (os.replace would publish the torn result)
            import threading

            tmp = (f"{path}.tmp.{os.getpid()}."
                   f"{threading.get_ident()}.{time.monotonic_ns()}")
            with open(tmp, "wb") as f:
                f.write(_MAGIC)
                f.write((json.dumps(header) + "\n").encode("utf-8"))
                f.write(_dumps((payload, in_tree, out_tree)))
            os.replace(tmp, path)
            self.stats.puts += 1
        except Exception as e:  # noqa: BLE001
            log.warning("aot cache store failed for %s (%s: %s)",
                        entry, type(e).__name__, e)

#: env var naming the default cache root (the ``HG_PLAN_CACHE`` twin)
CACHE_ENV = "HG_AOT_CACHE"


def default_cache(content_key: str = "") -> Optional[AOTCache]:
    """Cache rooted at ``$HG_AOT_CACHE``, or None when unset."""
    root = os.environ.get(CACHE_ENV)
    if not root:
        return None
    try:
        return AOTCache(root=root, content_key=content_key)
    except OSError as e:  # pragma: no cover - unwritable root
        log.warning("aot cache root %s unusable: %s", root, e)
        return None
