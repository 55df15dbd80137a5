"""Content-addressed AOT executable store (ProgramCache).

fedtpu launches a *family* of XLA programs per job — one round program
per chunk width, one sweep program per depth bucket, an eval program —
and the cold compile of the 72-slot arch-vmap sweep program took
90-207 s against a 29 s warm run (PERF.md §6 "Before PR 1", a CPU
box). The persistent XLA compilation cache (``--compilation-cache``)
already amortizes the *backend* compile, but the first dispatch still
pays tracing, lowering and executable construction synchronously. This module stores the
**compiled executable itself**: ``lower().compile()`` once, serialize via
``jax.experimental.serialize_executable``, and on the next run
deserialize in tens of milliseconds instead of recompiling.

Keying is content-addressed: a cache key fingerprints the config slice,
mesh shape, abstract argument shapes/dtypes/shardings, and the
jax/jaxlib/runtime versions, so a changed hidden width, client count or
dtype misses the cache instead of loading a stale program. Every entry
carries a sidecar meta JSON with the environment fingerprint and a
payload checksum; a mismatch (version skew, truncated blob, unpickle
failure) falls back to a fresh compile — the cache can make a run
faster, never wrong.

Like the telemetry package this module is import-light: jax is only
imported inside functions, so ``fedtpu.compilation`` can be imported
from lint/CI contexts without dragging in a backend.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import pickle
import re
import tempfile
import time
from typing import Any, Callable, Dict, Optional, Tuple

__all__ = [
    "CACHE_DIR_ENV",
    "CACHE_FORMAT_VERSION",
    "CacheEntry",
    "DEFAULT_CACHE_DIR",
    "PROGRAMS_SUBDIR",
    "ProgramCache",
    "configure_persistent_cache",
    "environment_fingerprint",
    "program_cache_dir",
    "program_fingerprint",
    "resolve_cache_dir",
]

# Bump when the on-disk layout or pickled tuple shape changes; old
# entries are then treated as misses, never deserialized.
CACHE_FORMAT_VERSION = 1

# jax's own variable for the persistent cache directory. Whoever starts
# the process places the cache with it (a chip machine keeps one directory
# between calls and nothing else), so it wins over anything set in code.
CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"

# Where the cache lives when nobody placed it: one fixed path inside the
# checkout. Fixed because a directory that moves between runs (a temp
# dir, a pid or a timestamp in the name) can never hit twice.
CHECKOUT_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_CACHE_DIR = os.path.join(CHECKOUT_ROOT, ".jax_cache")

# Subdirectory of the cache dir holding ProgramCache's serialized
# executables; the remainder is jax's persistent backend cache.
PROGRAMS_SUBDIR = "programs"


def resolve_cache_dir(cache_dir: Optional[str] = None) -> str:
    """The one compile-cache directory of this process:
    ``JAX_COMPILATION_CACHE_DIR`` when set, else the caller's explicit
    ``cache_dir`` (``--compilation-cache`` / ``RunConfig.compilation_cache``),
    else :data:`DEFAULT_CACHE_DIR`."""
    placed = os.environ.get(CACHE_DIR_ENV)
    if placed:
        return placed
    if cache_dir:
        return os.path.abspath(os.path.expanduser(cache_dir))
    return DEFAULT_CACHE_DIR


def program_cache_dir(cache_dir: Optional[str] = None) -> str:
    """Directory of the serialized-executable store under the resolved
    cache dir."""
    return os.path.join(resolve_cache_dir(cache_dir), PROGRAMS_SUBDIR)


def configure_persistent_cache(cache_dir: Optional[str] = None) -> str:
    """Turn on jax's persistent (backend) compilation cache at
    :func:`resolve_cache_dir` and return that directory.

    One shared entry point for the CLI, ``run_experiment``, the sweep,
    ``fedtpu serve``, bench and chip_smoke, so every program of the repo
    caches in the same place. Must run before the programs of interest are
    compiled; safe to call repeatedly. Respects an explicit
    ``JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS`` from the environment.

    The key of an entry holds the program's metadata too (jax leaves it
    out by default): the ``op_name`` of every operation, which is where the
    round program's ``jax.named_scope``s live, and the file and line it was
    traced from. A checkout whose scopes differ from the one that filled
    the directory is therefore not served that one's executable, and a
    traced run's ``program_scopes`` reads its own scopes
    (docs/observability.md). Files are named from the checkout's root
    (``jax_hlo_source_file_canonicalization_regex`` cuts it off), so the
    same source at another path is served, and an operation's location is
    the one line that made it, not the stack of calls that led there
    (``jax_traceback_in_locations_limit`` 1; turning the tracebacks off
    altogether would cut every ``op_name`` to its primitive): a program traced
    again from another caller (every job builds and initialises its
    experiment anew) has the key it had. With the stacks in, a cold run
    compiled the language model's init program once a caller, 6-9 s each
    (PERF.md section 6, PR 35). The price: the first run after an edit that
    shifts a line some program is traced through compiles it again, as the
    first run after any change to the program does.
    """
    import jax

    path = resolve_cache_dir(cache_dir)
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_traceback_in_locations_limit", 1)
    if jax.config.jax_hlo_source_file_canonicalization_regex is None:
        jax.config.update("jax_hlo_source_file_canonicalization_regex",
                          "^" + re.escape(CHECKOUT_ROOT + os.sep))
    if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
        # Default floor skips caching sub-half-second programs; an env var
        # set by the caller (e.g. CPU tests caching tiny programs) wins.
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    return path


def environment_fingerprint() -> Dict[str, Any]:
    """Version facts that invalidate a serialized executable when changed."""
    import jax
    import jaxlib

    env: Dict[str, Any] = {
        "cache_format": CACHE_FORMAT_VERSION,
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "backend": jax.default_backend(),
    }
    try:
        # PJRT exposes the runtime build (XLA revision) here; best-effort —
        # jax/jaxlib versions alone already pin the wheel.
        env["platform_version"] = jax.devices()[0].client.platform_version
    except Exception:  # pragma: no cover - backend-specific attribute
        env["platform_version"] = "unknown"
    return env


def _canonical(obj: Any) -> Any:
    """JSON-stable view of configs/conditions for hashing."""
    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        return _canonical(dataclasses.asdict(obj))
    if isinstance(obj, dict):
        return {str(k): _canonical(v) for k, v in sorted(obj.items())}
    if isinstance(obj, (list, tuple)):
        return [_canonical(v) for v in obj]
    if isinstance(obj, (str, int, float, bool)) or obj is None:
        return obj
    return repr(obj)


def _abstract_signature(args: Tuple[Any, ...]) -> list:
    """Per-leaf (shape, dtype, sharding) of the call arguments plus the
    tree structure — the part of the key that makes a changed client
    count, hidden width or dtype a cache *miss*."""
    import jax

    sig = []
    for a in args:
        leaves, treedef = jax.tree.flatten(a)
        entry = []
        for leaf in leaves:
            shape = tuple(getattr(leaf, "shape", ()))
            dtype = str(getattr(leaf, "dtype", type(leaf).__name__))
            sharding = getattr(leaf, "sharding", None)
            entry.append([list(shape), dtype,
                          repr(getattr(sharding, "spec", sharding))])
        sig.append({"tree": str(treedef), "leaves": entry})
    return sig


def _mesh_signature(mesh: Any) -> Any:
    if mesh is None:
        return None
    try:
        # device_ids makes the *assignment* part of the key, not just the
        # extent: two equal-sized slices of one parent mesh (MPMD client
        # slice vs server slice) compile against different device sets and
        # must never share an executable.
        return {"shape": [[str(k), int(v)] for k, v in mesh.shape.items()],
                "devices": int(mesh.devices.size),
                "device_ids": [[str(getattr(d, "platform", "?")), int(d.id)]
                               for d in mesh.devices.flat]}
    except Exception:
        return repr(mesh)


def program_fingerprint(label: str,
                        *,
                        config: Any = None,
                        mesh: Any = None,
                        args: Tuple[Any, ...] = (),
                        extra: Any = None) -> str:
    """Content-address for one program: sha256 over the program label,
    the config slice that shaped it, the mesh, the abstract argument
    signature and the environment fingerprint. 20 hex chars."""
    material = {
        "label": label,
        "config": _canonical(config),
        "mesh": _mesh_signature(mesh),
        "args": _abstract_signature(tuple(args)),
        "env": environment_fingerprint(),
        "extra": _canonical(extra),
    }
    blob = json.dumps(material, sort_keys=True).encode("utf-8")
    return hashlib.sha256(blob).hexdigest()[:20]


@dataclasses.dataclass
class CacheEntry:
    """Result of a cache lookup-or-compile."""

    compiled: Any                 # the executable (jax ``Compiled``-like)
    key: str
    warm: bool                    # True = deserialized from disk
    seconds: float                # deserialize time (warm) or compile (cold)
    meta: Dict[str, Any] = dataclasses.field(default_factory=dict)


class ProgramCache:
    """Disk store of serialized XLA executables, keyed by fingerprint.

    Layout: ``<dir>/<key>.bin`` (pickled ``serialize_executable`` tuple)
    plus ``<dir>/<key>.json`` (environment fingerprint, payload sha256,
    label, optional flops). Any integrity or version mismatch is a miss;
    any store failure is a warning-level no-op — lookups degrade to the
    eager compile path, never to a wrong program.
    """

    def __init__(self, cache_dir: str, tracer=None, registry=None):
        self.cache_dir = os.path.abspath(os.path.expanduser(cache_dir))
        os.makedirs(self.cache_dir, exist_ok=True)
        if tracer is None:
            from fedtpu.telemetry import NullTracer
            tracer = NullTracer()
        self.tracer = tracer
        self.registry = registry
        self.hits = 0
        self.misses = 0
        self.store_errors = 0
        self.load_errors = 0

    # ------------------------------------------------------------- paths
    def _paths(self, key: str) -> Tuple[str, str]:
        return (os.path.join(self.cache_dir, f"{key}.bin"),
                os.path.join(self.cache_dir, f"{key}.json"))

    def _count(self, name: str, dur_ms: Optional[float] = None) -> None:
        if self.registry is not None:
            self.registry.counter(f"program_cache_{name}").inc()
            if dur_ms is not None:
                self.registry.histogram(
                    f"program_cache_{name}_ms",
                    bins=(1.0, 10.0, 100.0, 1e3, 1e4, 1e5)).observe(dur_ms)

    # ----------------------------------------------------------- queries
    def peek(self, key: str) -> bool:
        """True iff ``key`` has a complete, version-compatible entry on
        disk (no deserialization — cheap enough for manifests)."""
        bin_path, meta_path = self._paths(key)
        meta = self._read_meta(meta_path)
        return (meta is not None and os.path.exists(bin_path)
                and meta.get("env") == _jsonish(environment_fingerprint()))

    def entries(self) -> list:
        """Keys with both payload and sidecar present."""
        out = []
        for fn in sorted(os.listdir(self.cache_dir)):
            if fn.endswith(".bin"):
                key = fn[:-4]
                if os.path.exists(self._paths(key)[1]):
                    out.append(key)
        return out

    def _read_meta(self, meta_path: str) -> Optional[Dict[str, Any]]:
        try:
            with open(meta_path, encoding="utf-8") as fh:
                meta = json.load(fh)
            return meta if isinstance(meta, dict) else None
        except (OSError, ValueError):
            return None

    # ------------------------------------------------------------ load
    def load(self, key: str) -> Optional[CacheEntry]:
        """Deserialize ``key`` or return None (miss / guard failure)."""
        bin_path, meta_path = self._paths(key)
        meta = self._read_meta(meta_path)
        if meta is None or not os.path.exists(bin_path):
            return None
        if meta.get("env") != _jsonish(environment_fingerprint()):
            return None                       # version skew: recompile
        t0 = time.perf_counter()
        try:
            with open(bin_path, "rb") as fh:
                raw = fh.read()
            if hashlib.sha256(raw).hexdigest() != meta.get("payload_sha256"):
                raise ValueError("payload checksum mismatch (truncated or "
                                 "corrupted blob)")
            payload, in_tree, out_tree = pickle.loads(raw)
            from jax.experimental import serialize_executable as se
            compiled = se.deserialize_and_load(payload, in_tree, out_tree)
        except Exception as exc:
            # Graceful fallback: an entry that is there and current but
            # does not load (corrupt blob, a runtime that refuses the
            # serialized executable) degrades to a recompile — counted
            # and traced, so a cache that never serves is visible.
            self.load_errors += 1
            self._count("load_errors")
            self.tracer.event("program_cache", phase="load_error", key=key,
                              error=repr(exc))
            return None
        dur = time.perf_counter() - t0
        return CacheEntry(compiled=compiled, key=key, warm=True,
                          seconds=dur, meta=meta)

    # ------------------------------------------------------------ store
    def store(self, key: str, compiled: Any,
              extra_meta: Optional[Dict[str, Any]] = None) -> bool:
        """Serialize ``compiled`` under ``key``; False (never raise) on
        any failure so a broken disk can't take down a run."""
        bin_path, meta_path = self._paths(key)
        t0 = time.perf_counter()
        try:
            from jax.experimental import serialize_executable as se
            payload, in_tree, out_tree = se.serialize(compiled)
            raw = pickle.dumps((payload, in_tree, out_tree))
            meta = {
                "key": key,
                "env": _jsonish(environment_fingerprint()),
                "payload_sha256": hashlib.sha256(raw).hexdigest(),
                "payload_bytes": len(raw),
            }
            if extra_meta:
                meta.update(_jsonish(extra_meta))
            # Atomic publish: payload first, sidecar last — a reader only
            # trusts entries whose sidecar exists and checksums match.
            for path, data, mode in ((bin_path, raw, "wb"),
                                     (meta_path,
                                      json.dumps(meta, sort_keys=True), "w")):
                fd, tmp = tempfile.mkstemp(dir=self.cache_dir)
                try:
                    with os.fdopen(fd, mode) as fh:
                        fh.write(data)
                    os.replace(tmp, path)
                except BaseException:
                    try:
                        os.unlink(tmp)
                    except OSError:
                        pass
                    raise
        except Exception:
            self.store_errors += 1
            self._count("store_errors")
            return False
        self.tracer.event("program_cache", phase="store", key=key,
                          serialize_ms=(time.perf_counter() - t0) * 1e3,
                          payload_bytes=meta["payload_bytes"])
        self._count("stores", (time.perf_counter() - t0) * 1e3)
        return True

    # --------------------------------------------------- lookup-or-build
    def get_or_compile(self, key: str, step: Any, *args: Any,
                       label: str = "program",
                       extra_meta: Optional[Dict[str, Any]] = None,
                       ) -> CacheEntry:
        """Warm path: deserialize ``key``. Cold path: ``step.lower(*args)
        .compile()``, persist, return. Flops are computed at store time
        and carried in the meta sidecar because ``cost_analysis`` is
        cheapest on a fresh build."""
        entry = self.load(key)
        if entry is not None:
            self.hits += 1
            self.tracer.event("program_cache", phase="hit", key=key,
                              label=entry.meta.get("label", label),
                              deserialize_ms=entry.seconds * 1e3)
            self._count("hits", entry.seconds * 1e3)
            return entry

        self.misses += 1
        t0 = time.perf_counter()
        compiled = step.lower(*args).compile()
        compile_s = time.perf_counter() - t0
        meta: Dict[str, Any] = {"label": label, "compile_s": compile_s}
        try:
            from fedtpu.utils.timing import (program_bytes_accessed,
                                             program_flops)
            meta["flops"] = program_flops(compiled)
            # Memory side of the roofline: with flops this gives the
            # program's arithmetic intensity without re-lowering.
            meta["bytes_accessed"] = program_bytes_accessed(compiled)
        except Exception:  # fedtpu: noqa[FTP102] flops are advisory metadata; cost_analysis availability varies by backend
            pass
        if extra_meta:
            meta.update(extra_meta)
        self.tracer.event("program_cache", phase="miss", key=key,
                          label=label, compile_s=compile_s)
        self._count("misses", compile_s * 1e3)
        self.store(key, compiled, extra_meta=meta)
        return CacheEntry(compiled=compiled, key=key, warm=False,
                          seconds=compile_s, meta=meta)

    # -------------------------------------------------------- reporting
    def stats(self) -> Dict[str, Any]:
        return {"dir": self.cache_dir, "hits": self.hits,
                "misses": self.misses, "store_errors": self.store_errors,
                "load_errors": self.load_errors,
                "entries": len(self.entries())}

    def manifest_info(self) -> Dict[str, Any]:
        """Shape recorded into the telemetry run manifest (cache
        directory + hit/miss state)."""
        return {"program_cache": self.stats()}


def _jsonish(obj: Any) -> Any:
    """Round-trip through JSON so stored and freshly-computed metadata
    compare equal (tuples vs lists, int keys vs str)."""
    return json.loads(json.dumps(_canonical(obj), sort_keys=True))
