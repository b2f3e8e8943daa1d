"""Tuning cache: persist search winners keyed by workload identity.

A measured search is expensive by design (it compiles and times real
candidates), so its verdict must be durable: the SECOND run of any
workload — same program, same mesh, same chip, same jax — loads the
winning config from disk and compiles nothing but the winner itself.
The key therefore contains everything that can change the verdict:

  * ``workload``   — the program hash (``incubate.checkpoint.program_hash``)
                     or a caller-built workload id for non-Program
                     searches (flash shapes, bucket ladders, step knobs);
  * ``mesh``       — axis names + sizes of the ambient DeviceMesh
                     (a winner tuned for dp=8 is meaningless on dp=2);
  * ``platform`` / ``chip`` — jax backend + the resolved ChipSpec
                     (name, peak FLOP/s, HBM BW): a v5e winner must not
                     be served on a v4, nor a TPU winner on CPU;
  * ``jax``        — ``jax.__version__``: a compiler upgrade re-opens
                     the search;
  * ``schema``     — the tuner's own schema version.

Entries live under ``<compile-cache-dir>/paddle_tpu_tune/`` — the same
directory jax's persistent compilation cache uses (PR-2
``AnalysisConfig.enable_compilation_cache``), so the tuned CONFIG and
the tuned EXECUTABLES travel together: a warm cache dir gives the
second process both the decision and the binary.

Writes are atomic (tmp + rename, the repo-wide commit idiom) and reads
treat corrupt/alien files as misses — the cache can only ever cost a
re-search, never wrong behavior.
"""

from __future__ import annotations

import hashlib
import json
import os
import tempfile

__all__ = [
    "TUNE_SCHEMA_VERSION",
    "TuningCache",
    "cache_key_parts",
    "default_cache_dir",
]

TUNE_SCHEMA_VERSION = 1

CACHE_DIR_ENV = "PADDLE_TPU_TUNE_CACHE"
_SUBDIR = "paddle_tpu_tune"


def default_cache_dir():
    """$PADDLE_TPU_TUNE_CACHE, else a subdirectory of the compile
    cache's directory (`fluid.core.compile_cache`), so tuned configs and
    compiled executables travel together and never collide."""
    from ..fluid.core.compile_cache import compile_cache_dir

    return os.path.join(os.getenv(CACHE_DIR_ENV) or compile_cache_dir(),
                        _SUBDIR)


def _mesh_desc(mesh):
    """Stable description of a DeviceMesh (or None): axis names+sizes."""
    if mesh is None:
        return None
    shape = getattr(mesh, "shape", None)
    if isinstance(shape, dict):
        return [[str(a), int(n)] for a, n in sorted(shape.items())]
    names = getattr(mesh, "axis_names", ())
    try:
        return [[str(a), int(mesh.axis_size(a))] for a in names]
    except Exception:
        return [[str(a), -1] for a in names]


def cache_key_parts(workload, mesh=None, chip=None, platform=None,
                    jax_version=None):
    """The dict hashed into a cache key.  ``platform``/``jax_version``
    overrides exist for tests and cross-platform pre-tuning; production
    callers let them resolve from the live process."""
    if platform is None:
        try:
            import jax

            platform = jax.default_backend()
        except Exception:
            platform = "unknown"
    if jax_version is None:
        try:
            import jax

            jax_version = jax.__version__
        except Exception:
            jax_version = "unknown"
    chip_desc = None
    if chip is not None:
        chip_desc = {"name": chip.name, "peak_flops": chip.peak_flops,
                     "hbm_bw": chip.hbm_bw}
    return {
        "schema": TUNE_SCHEMA_VERSION,
        "workload": str(workload),
        "mesh": _mesh_desc(mesh),
        "platform": str(platform),
        "chip": chip_desc,
        "jax": str(jax_version),
    }


class TuningCache:
    """get/put of winner records under one directory, atomic writes."""

    def __init__(self, cache_dir=None):
        self.dir = cache_dir or default_cache_dir()

    @staticmethod
    def key(parts):
        """Hex digest of the canonicalized key parts."""
        blob = json.dumps(parts, sort_keys=True, separators=(",", ":"))
        return hashlib.sha256(blob.encode("utf-8")).hexdigest()[:32]

    def path_for(self, parts):
        return os.path.join(self.dir, "%s.json" % self.key(parts))

    def get(self, parts):
        """The stored entry dict, or None on miss/corruption/schema or
        key-part drift (a hash collision across drifted parts is
        re-checked structurally — never trust the filename alone)."""
        path = self.path_for(parts)
        try:
            with open(path, "r") as f:
                entry = json.load(f)
        except (OSError, ValueError):
            return None
        if not isinstance(entry, dict):
            return None
        if entry.get("key_parts") != parts:
            return None
        if not isinstance(entry.get("winner"), dict):
            return None
        return entry

    def put(self, parts, winner, extra=None):
        """Persist a winner record; returns the path.  ``winner`` is a
        plain dict ({kind, params, measured_s, ...}); ``extra`` merges
        additional report fields (default/speedup/summary)."""
        os.makedirs(self.dir, exist_ok=True)
        entry = {"schema": TUNE_SCHEMA_VERSION, "key_parts": parts,
                 "winner": winner}
        if extra:
            entry.update(extra)
        path = self.path_for(parts)
        fd, tmp = tempfile.mkstemp(dir=self.dir, suffix=".tmp")
        try:
            with os.fdopen(fd, "w") as f:
                json.dump(entry, f, indent=1, sort_keys=True)
            os.replace(tmp, path)   # atomic commit: readers never see a tear
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise
        return path

    def invalidate(self, parts):
        """Drop one entry (missing is fine)."""
        try:
            os.unlink(self.path_for(parts))
            return True
        except OSError:
            return False
