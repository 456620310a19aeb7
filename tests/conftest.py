"""Test configuration: force an 8-device virtual CPU mesh BEFORE jax imports.

This is the deterministic in-process fake of the distributed substrate
(SURVEY.md §4): every parallel strategy is unit-tested on 8 virtual devices,
no TPU pod required.
"""

import os

# POSEIDON_TEST_TPU=1 runs the suite against the real TPU backend instead
# of the virtual CPU mesh — how tests/test_pallas.py and test_kernels.py
# Mosaic-compile the Pallas kernels on the chip.
_ON_TPU = os.environ.get("POSEIDON_TEST_TPU", "") == "1"

if not _ON_TPU:
    os.environ["JAX_PLATFORMS"] = "cpu"
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8").strip()

# One persistent compile cache for the whole session (and the processes the
# tests spawn): hundreds of tests build the same LeNet-sized programs under
# fresh jit objects, which only a cache keyed on the program can dedupe —
# the 870 s tier-1 sweep runs ~2.5 min faster with it (PR 21, this box).
# A fresh directory per session, so no entry outlives the code that made
# it; an environment that already places the cache is left alone.
if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
    import atexit
    import shutil
    import tempfile
    _session_cache = tempfile.mkdtemp(prefix="poseidon_t1_jax_cache_")
    atexit.register(shutil.rmtree, _session_cache, ignore_errors=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = _session_cache
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")

import jax  # noqa: E402

import faulthandler  # noqa: E402
import threading  # noqa: E402

import numpy as np  # noqa: E402
import pytest  # noqa: E402

# ---- thread sanitizer (ISSUE 8 satellite) -------------------------------- #
# A wedged suite dumps every stack on SIGABRT/timeout instead of dying mute.
faulthandler.enable()

# Uncaught exceptions on background threads historically vanished into
# stderr while the test that caused them passed. Record them and fail the
# test they happened under (mark `allow_thread_exceptions` for tests that
# intentionally kill threads rudely).
_THREAD_ERRORS = []          # (thread object, rendered message)
_ORIG_EXCEPTHOOK = threading.excepthook


def _failing_excepthook(args):
    # keep the Thread OBJECT, not its ident: CPython recycles idents, so
    # an ident-keyed filter could blame (or absolve) the wrong thread
    _THREAD_ERRORS.append((args.thread,
                           f"{getattr(args.thread, 'name', '?')}: "
                           f"{args.exc_type.__name__}: {args.exc_value}"))
    _ORIG_EXCEPTHOOK(args)


threading.excepthook = _failing_excepthook


@pytest.fixture(autouse=True)
def _thread_sanitizer(request):
    """Per-test teardown gate: no uncaught background-thread exception,
    and no NEW non-daemon thread may survive the test (a leaked
    non-daemon thread wedges interpreter shutdown — the repo's own
    threads are all daemonic by policy, so survivors are test bugs).

    Only exceptions from threads STARTED during this test fail it: a
    daemon thread from an earlier test dying late must not be blamed on
    whichever test happens to be running when it unwinds."""
    errs_before = len(_THREAD_ERRORS)
    before = set(threading.enumerate())
    yield
    # run BOTH checks before failing: a test whose thread raises AND
    # wedges must still get its leak joined/reported, or the survivor
    # haunts later tests unattributed
    problems = []
    new_errs = [msg for t, msg in _THREAD_ERRORS[errs_before:]
                if t not in before]
    if new_errs and not request.node.get_closest_marker(
            "allow_thread_exceptions"):
        problems.append("uncaught exception on background thread(s): "
                        + "; ".join(new_errs))
    leaked = [t for t in threading.enumerate()
              if not t.daemon and t.is_alive() and t not in before]
    for t in leaked:
        t.join(timeout=2.0)     # grace: racing a clean close() is fine
    leaked = [t for t in leaked if t.is_alive()]
    if leaked:
        problems.append("non-daemon thread(s) leaked by test: "
                        + ", ".join(t.name for t in leaked))
    if problems:
        pytest.fail("; ".join(problems), pytrace=False)


def _set_jax_cache_dir(path):
    from jax.experimental.compilation_cache import compilation_cache as cc
    jax.config.update("jax_compilation_cache_dir", path)
    cc.reset_cache()    # the cache object memoizes its first directory


@pytest.fixture(autouse=True)
def _compile_cache_scope():
    """The CLI entry points turn the persistent compile cache on for the
    whole process (runtime/compile_cache.py); a test that ran one must not
    leave every later test's compiles going through it."""
    from poseidon_tpu import config
    before = jax.config.jax_compilation_cache_dir
    yield
    config.set_compile_cache_config(cache_dir="", aot_steps=True)
    if jax.config.jax_compilation_cache_dir != before:
        _set_jax_cache_dir(before)


@pytest.fixture
def jax_cache_env(tmp_path, monkeypatch):
    """A process whose environment sets JAX_COMPILATION_CACHE_DIR: jax
    reads that variable at import, so besides setting it this points
    jax's own config at the directory — the one thing the program itself
    must then NOT do (``_compile_cache_scope`` undoes it)."""
    cache = str(tmp_path / "cc")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", cache)
    _set_jax_cache_dir(cache)
    return cache


@pytest.fixture
def rng_np():
    return np.random.RandomState(0)


def pattern_batch(rs, b, s, vocab):
    """The LM test-suite task: t[i+1] = (3 t[i] + 1) mod vocab — learnable
    by a tiny decoder in ~100 steps. Returns (tokens, targets), each (b, s).
    Shared by the transformer/moe/generate/checkpoint suites."""
    import jax.numpy as jnp
    start = rs.randint(0, vocab, size=(b, 1))
    seq = [start]
    for _ in range(s):
        seq.append((seq[-1] * 3 + 1) % vocab)
    full = np.concatenate(seq, axis=1)
    return jnp.asarray(full[:, :s]), jnp.asarray(full[:, 1:s + 1])
