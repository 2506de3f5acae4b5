"""Small shared helpers with no jax/numpy dependencies.

Kept dependency-free so every layer (kernels, runtime, benchmarks) can
import it without ordering concerns.
"""

from __future__ import annotations

import contextlib
import os


def next_pow2(x: int) -> int:
    """Smallest power of two >= max(x, 1).

    The repo's padding convention: block sizes, k_pad, and miss-batch
    shapes are all rounded up to a power of two so the set of compiled
    XLA shapes stays logarithmic in the observed size range.
    """
    return 1 << (max(int(x), 1) - 1).bit_length()


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Called by the entry points (``chip_smoke.py``, ``python -m
    repro.service``, ``python -m repro.launch.serve``,
    ``benchmarks/run.py``), never on import.  Where
    ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and nothing
    else is set here.  Otherwise the cache lives at ``<checkout>/.jax_cache``
    — a fixed path, because the path is part of the cache key."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def fsync_dir(path) -> None:
    """fsync a directory so a rename/replace inside it is durable.

    Platforms without directory fds (or filesystems that reject fsync on
    them) are best-effort: the rename itself is still atomic.
    """
    try:
        fd = os.open(os.fspath(path), os.O_RDONLY)
    except OSError:
        return
    try:
        os.fsync(fd)
    except OSError:
        pass
    finally:
        os.close(fd)


@contextlib.contextmanager
def atomic_write(path, mode: str = "wb"):
    """Crash-safe file write: tmp in the same directory -> flush -> fsync
    -> ``os.replace`` -> directory fsync.

    Readers never observe a torn file: either the old content or the
    complete new one is visible, and a crash at any point leaves (at
    worst) a ``.tmp.*`` orphan next to the target.  Shared by checkpoint
    manifests and the tiered-storage spill files so the crash-safety
    discipline lives in one place.

    Yields the open file object; the commit happens only if the body
    exits cleanly — an exception unlinks the tmp file and re-raises.
    """
    path = os.fspath(path)
    d = os.path.dirname(path) or "."
    tmp = os.path.join(d, f".tmp.{os.path.basename(path)}.{os.getpid()}")
    f = open(tmp, mode)
    try:
        yield f
        f.flush()
        os.fsync(f.fileno())
    except BaseException:
        f.close()
        with contextlib.suppress(OSError):
            os.unlink(tmp)
        raise
    f.close()
    os.replace(tmp, path)
    fsync_dir(d)


def atomic_write_bytes(path, data: bytes) -> None:
    """Write ``data`` to ``path`` with :func:`atomic_write` semantics."""
    with atomic_write(path, "wb") as f:
        f.write(data)


def atomic_write_text(path, text: str) -> None:
    """Write ``text`` to ``path`` with :func:`atomic_write` semantics."""
    with atomic_write(path, "w") as f:
        f.write(text)
