"""Fast restart: persistent compile cache + AOT-serialized step executables.

Elasticity is only cheap if (re)starting a process is cheap, and today a
(re)start pays full JIT — multi-minute on GoogLeNet (it is why the async
tier's FIRST-clock gate needed a generously scaled timeout). Two layers
attack that, both keyed so a restarted-or-new worker with the same job
config hits them:

1. **Persistent XLA compile cache** (``jax.experimental.compilation_cache``
   riding the ``jax_compilation_cache_dir`` config): every XLA compile is
   content-addressed into ``cache_dir``; a restart re-traces but the
   multi-minute backend compile becomes a disk read. Wired through train,
   serve, bench_serve and tune, because a serving replica's bucket
   warm-up is the same cold-start bill. Where it lives is
   :func:`resolve_cache_dir`'s one rule, not a flag.

2. **AOT step-executable store** (``jax.experimental.serialize_executable``):
   the compiled train-step executable itself, serialized under
   ``<cache_dir>/aot/`` keyed by what reaches the traced program (sources,
   layers, shapes, mesh, backend, policy, solver: ``step_key``) and by
   nothing else: not the seed, not the run's length. A start that matches
   the key (a restart, a resume, a new seed) skips tracing AND compilation —
   the engine loads the executable and dispatches it directly (building on the
   abstract-topology lower/compile flow of ``scripts/aot_tpu_check.py``,
   but serialized for the REAL local topology and reloaded across process
   boundaries).

Layer 2 is strictly best-effort: any mismatch (jax version, device kind,
donation flags, numeric policy — all folded into the key) or
deserialization failure falls back to the jit path, which layer 1 still
makes fast. Nothing here is load-bearing for numerics: the executable IS
the jit-compiled program, byte-identified by its lowering.
"""

from __future__ import annotations

import contextlib
import functools
import hashlib
import json
import os
import pickle
import zlib
from typing import Any, Optional

from .spans import recorder

__all__ = ["resolve_cache_dir", "enable_compile_cache",
           "cache_entries", "watch_cache_hits", "step_key",
           "code_fingerprint",
           "save_step_executable", "load_step_executable", "load_step_note",
           "aot_entries"]

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def resolve_cache_dir() -> str:
    """THE cache location, for every layer (XLA cache, ``aot/``) and
    every entry point: ``JAX_COMPILATION_CACHE_DIR`` when
    the environment sets it — then jax itself already reads it and nothing
    in code sets another — else the fixed ``<checkout>/.jax_cache``
    (git-ignored). The path is part of the cache key, so it never comes
    from ``tempfile``, a pid or the clock; nothing outside the checkout
    (no ``~/.cache``) steers a run."""
    env = os.environ.get(CACHE_ENV, "")
    if env:
        return os.path.abspath(os.path.expanduser(env))
    return os.path.join(_CHECKOUT, ".jax_cache")


def enable_compile_cache(aot_steps: bool = True) -> str:
    """Turn on the persistent compilation cache at
    :func:`resolve_cache_dir` (created if missing) and point the
    ``aot/`` store at the same directory. Every program is
    cached, even sub-second ones. Returns the directory. Must run before
    the programs it should cache are compiled (already-compiled programs
    in this process stay in the in-memory jit cache either way)."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as _cc

    from .. import config

    cache_dir = resolve_cache_dir()
    os.makedirs(cache_dir, exist_ok=True)
    if not os.environ.get(CACHE_ENV):
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # a Pallas kernel's Mosaic module is serialized WITH its MLIR locations
    # into the custom call, where the cache key's debug-info stripping
    # cannot reach; with jax's ten traceback frames in them, every program
    # that holds a kernel keys on its caller's stack — the same eval step
    # reached from another line of a driver script missed the cache on
    # the v5e (PR 21). One frame per location is stable. (Not
    # jax_include_full_tracebacks_in_locations=False, PR 21's first answer:
    # it also cuts every instruction's op_name down to the primitive, and
    # the named scopes the step's layer map is built from are gone.)
    jax.config.update("jax_traceback_in_locations_limit", 1)
    # the cache object memoizes its first initialization: a process that
    # already compiled something (before this call) must reset it or the
    # directory is silently ignored
    _cc.reset_cache()
    config.set_compile_cache_config(cache_dir=cache_dir, aot_steps=aot_steps)
    return cache_dir


def cache_entries(cache_dir: str) -> int:
    """How many compiled programs the persistent cache holds (the ``-atime``
    sidecar files jax writes per entry are not counted)."""
    try:
        return sum(1 for n in os.listdir(cache_dir) if n.endswith("-cache"))
    except OSError:
        return 0


@contextlib.contextmanager
def watch_cache_hits():
    """Yields a list that grows by one for every compile inside the block
    that the persistent XLA cache answered (jax's own monitoring event).
    The Engine does not serialize into the AOT store an executable that
    came back from the cache. The rule was written for XLA:CPU, which
    writes an entry that loads and then dies at its first dispatch
    (``NOT_FOUND: Function ... not found``), and is applied on every
    backend: whether a TPU executable the cache answered survives the
    round trip has not been read on the chip. A start meets that state
    (XLA cache warm, ``aot/`` without the step) only after an edit to the
    package that left the step's HLO as it was, or where ``aot/`` alone was
    emptied; it then pays the trace and the lowering at every start."""
    import jax.monitoring as monitoring
    hits: list = []

    def on_event(event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            hits.append(event)

    monitoring.register_event_listener(on_event)
    try:
        yield hits
    finally:
        monitoring.unregister_event_listener(on_event)


# --------------------------------------------------------------------------- #
# AOT step-executable store
# --------------------------------------------------------------------------- #

def _canon(obj: Any) -> Any:
    """JSON-stable canonicalization for key parts (tuples -> lists, dict
    keys sorted by json, numpy dtypes -> str)."""
    if isinstance(obj, dict):
        return {str(k): _canon(v) for k, v in sorted(obj.items(),
                                                     key=lambda kv: str(kv[0]))}
    if isinstance(obj, (list, tuple)):
        return [_canon(v) for v in obj]
    if isinstance(obj, (str, int, float, bool)) or obj is None:
        return obj
    return str(obj)


def step_key(**parts: Any) -> str:
    """Content key for a serialized step executable. The rule for what a
    caller folds in: a part belongs in the key if and only if it reaches
    the traced program. Everything that does (the sources, the net's
    layers, parameter and batch shapes and dtypes, mesh, backend and
    device kind, jax version, donation, numeric policy, lowering switches,
    remat units, the solver fields the update is traced from) so that ANY
    drift of the program is a clean miss, never a stale load; nothing that
    does not (a seed, a run's length where no schedule reads it, display
    and snapshot cadence, where the data comes from) so that a start whose
    program is the stored one finds it. ``Engine._aot_step_key`` lists the
    parts. Same parts -> same key on a restarted process, whatever the
    order of the keywords."""
    blob = json.dumps(_canon(parts), sort_keys=True).encode()
    return hashlib.sha256(blob).hexdigest()[:32]


@functools.lru_cache(maxsize=1)
def code_fingerprint() -> str:
    """Content hash of the package's own sources. The AOT store rides the
    default cache directory, which outlives an edit to the step builder or
    a kernel; the executable's key is (model, shapes, mesh, policy), none
    of which an edit changes — so the sources are part of the key, and a
    stale executable can never be replayed over new code."""
    h = hashlib.sha256()
    pkg = os.path.join(_CHECKOUT, "poseidon_tpu")
    for root, dirs, files in os.walk(pkg):
        dirs.sort()
        for name in sorted(files):
            if name.endswith(".py"):
                path = os.path.join(root, name)
                h.update(os.path.relpath(path, pkg).encode())
                with open(path, "rb") as f:
                    h.update(f.read())
    return h.hexdigest()[:16]


def _aot_dir(cache_dir: str) -> str:
    return os.path.join(cache_dir, "aot")


def _aot_path(cache_dir: str, key: str) -> str:
    return os.path.join(_aot_dir(cache_dir), f"step_{key}.aotexec")


def aot_entries(cache_dir: str) -> int:
    try:
        return sum(1 for n in os.listdir(_aot_dir(cache_dir))
                   if n.endswith(".aotexec"))
    except OSError:
        return 0


# A v5e step executable for full-width AlexNet serializes to 1.74 GB
# (PR 21, on the chip); the store holds it compressed, with the codec the
# XLA cache itself uses for its entries in this installation.
_ZSTD_MAGIC = b"\x28\xb5\x2f\xfd"


def _pack(payload: bytes) -> bytes:
    try:
        import zstandard
    except ImportError:
        return zlib.compress(payload, 1)
    return zstandard.ZstdCompressor(threads=-1).compress(payload)


def _unpack(blob: bytes) -> bytes:
    if blob[:4] == _ZSTD_MAGIC:
        import zstandard
        return zstandard.ZstdDecompressor().decompress(blob)
    return zlib.decompress(blob)


def save_step_executable(cache_dir: str, key: str, compiled,
                         note: Optional[dict] = None) -> Optional[str]:
    """Serialize a jax Compiled object under the AOT store (atomic tmp +
    rename — a torn write can never shadow a good entry). Returns the
    entry path, or None — logged, never raised — when the program does not
    serialize on this backend or the store cannot be written: the caller
    holds a good executable either way (best-effort by design). ``note``:
    what the program's text no longer says of how it was built (JSON
    beside the entry, written first; ``load_step_note`` reads it)."""
    from jax.experimental.serialize_executable import serialize

    from .metrics import log
    tmp = None
    try:
        with recorder.startup("aot_serialize"):
            payload = pickle.dumps(serialize(compiled))
        with recorder.startup("aot_pack"):
            blob = _pack(payload)
        with recorder.startup("aot_write"):
            path = _aot_path(cache_dir, key)
            os.makedirs(os.path.dirname(path), exist_ok=True)
            if note is not None:
                tmp = f"{_note_path(path)}.tmp.{os.getpid()}"
                with open(tmp, "w") as f:
                    json.dump(note, f)
                os.replace(tmp, _note_path(path))
            tmp = f"{path}.tmp.{os.getpid()}"
            with open(tmp, "wb") as f:
                f.write(blob)
            os.replace(tmp, path)
        recorder.note(aot_bytes_on_disk=len(blob),
                      aot_bytes_serialized=len(payload))
        log(f"compile_cache: step executable stored at {path} "
            f"({len(blob) / 1e6:.1f} MB, {len(payload) / 1e6:.1f} MB "
            f"serialized)")
        return path
    except Exception as e:  # noqa: BLE001 — fall back to the compile cache
        log(f"compile_cache: step executable NOT stored under "
            f"{_aot_dir(cache_dir)} ({type(e).__name__}: {e}); the "
            f"persistent XLA cache still applies")
        if tmp and os.path.exists(tmp):
            os.unlink(tmp)             # no half-written gigabyte left behind
        return None


def _note_path(entry: str) -> str:
    return entry[:-len(".aotexec")] + ".json"


def load_step_note(cache_dir: str, key: str) -> dict:
    """The note ``save_step_executable`` left beside an entry; ``{}`` where
    there is none to read."""
    try:
        with open(_note_path(_aot_path(cache_dir, key))) as f:
            return json.load(f)
    except (OSError, ValueError):
        return {}


def load_step_executable(cache_dir: str, key: str):
    """Reload a serialized step executable; None on miss or ANY failure
    (a stale/foreign entry must degrade to a recompile, never an abort).
    The returned object is directly callable with the original call
    signature. A load's costs have different remedies and a start-up span
    each: the file's read, the unpack (decompress and unpickle), the
    runtime's own ``deserialize_and_load``."""
    path = _aot_path(cache_dir, key)
    if not os.path.exists(path):
        return None
    try:
        with recorder.startup("aot_read"):
            with open(path, "rb") as f:
                blob = f.read()
        with recorder.startup("aot_unpack"):
            unpacked = _unpack(blob)
            recorder.note(aot_bytes_on_disk=len(blob),
                          aot_bytes_serialized=len(unpacked))
            del blob                # a gigabyte each, and the runtime's
            payload, in_tree, out_tree = pickle.loads(unpacked)
            del unpacked            # load below needs the host's memory
        with recorder.startup("aot_deserialize"):
            from jax.experimental.serialize_executable import \
                deserialize_and_load
            return deserialize_and_load(payload, in_tree, out_tree)
    except Exception as e:  # noqa: BLE001 — miss, not abort
        from .metrics import log
        log(f"compile_cache: failed to reload AOT step {key} "
            f"({type(e).__name__}: {e}); recompiling")
        return None
