"""Run manifest: the attribution record every artifact needs.

Emitted once at run start as the sink's ``manifest`` event — config dump +
stable hash, mesh shape, device kinds, backend, package/jax/python
versions, process topology, and a best-effort git revision. A result file
or events log found on disk six months later answers "what exactly
produced this?" from the manifest alone.

The config hash is sha256 over the sorted-key JSON of the dataclass dump,
so two runs with identical configs hash identically regardless of field
order or how the config object was built.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import subprocess
import sys
from typing import Optional


def config_digest(cfg) -> str:
    """Stable 16-hex-char digest of an ExperimentConfig (or any
    dataclass/dict tree)."""
    if dataclasses.is_dataclass(cfg):
        cfg = dataclasses.asdict(cfg)
    canon = json.dumps(cfg, sort_keys=True, default=str)
    return hashlib.sha256(canon.encode()).hexdigest()[:16]


def _git_rev() -> Optional[str]:
    here = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    try:
        out = subprocess.run(["git", "-C", here, "rev-parse", "HEAD"],
                             capture_output=True, text=True, timeout=5)
        return out.stdout.strip() or None if out.returncode == 0 else None
    except Exception:
        return None


def build_manifest(cfg=None, mesh=None, extra: Optional[dict] = None) -> dict:
    """Assemble the manifest payload. ``cfg`` is the ExperimentConfig (or
    None for programs without one, e.g. bench); ``mesh`` supplies the
    shape/axis names when the caller has one. Backend/device fields are
    best-effort — a backend-free caller still gets config + versions."""
    import fedtpu

    out: dict = {
        "package": "fedtpu",
        "package_version": fedtpu.__version__,
        "python_version": sys.version.split()[0],
        "git_rev": _git_rev(),
        "argv": list(sys.argv),
    }
    if cfg is not None:
        out["config"] = dataclasses.asdict(cfg) \
            if dataclasses.is_dataclass(cfg) else dict(cfg)
        out["config_hash"] = config_digest(cfg)
    try:
        import jax
        out["jax_version"] = jax.__version__
        devs = jax.devices()
        out["backend"] = devs[0].platform
        out["device_count"] = len(devs)
        out["device_kinds"] = sorted({d.device_kind for d in devs})
        out["process_index"] = jax.process_index()
        out["process_count"] = jax.process_count()
        # Where this run's XLA compiles were persisted (None when the
        # persistent compilation cache is off) — the half of "why was
        # startup fast/slow?" the config dump alone can't answer.
        out["compilation_cache"] = jax.config.jax_compilation_cache_dir
    except Exception:  # fedtpu: noqa[FTP102] manifest is best-effort; no backend must not kill the run
        pass
    if mesh is not None:
        try:
            out["mesh_shape"] = {axis: int(n) for axis, n
                                 in mesh.shape.items()}
        except Exception:  # fedtpu: noqa[FTP102] mesh introspection differs across jax versions; manifest stays best-effort
            pass
    if extra:
        out.update(extra)
    return out
