"""Persistent XLA compilation-cache robustness (ISSUE 6 satellite).

The persistent compile cache (api._ensure_runtime) is what kills cold-start
recompiles (ROADMAP open item 3) — but a cache entry truncated by a crash
or a full disk must not take the process down or poison warm starts. Two
defenses:

- :func:`sweep_corrupt_entries` — run when the cache directory is
  configured: deletes zero-length / unreadable entry files (the torn-write
  signature) and logs a warning naming each; the entry simply recompiles.
- :func:`purge_on_error` — the recovery driver's last resort when a
  compile/first-run failure classifies as cache corruption (deserialization
  errors naming the persistent cache): clear the cache directory and let
  the retry recompile from scratch. Only the checkout's own cache is ever
  purged: a directory placed from outside (``JAX_COMPILATION_CACHE_DIR``)
  is shared with whoever placed it, and the error propagates instead.

Both emit ``cache_repair`` events so observability sees every repair.
"""

from __future__ import annotations

import logging
import os
from typing import Optional

from thunder_tpu.observability import events as obs_events

logger = logging.getLogger("thunder_tpu")


def default_cache_dir() -> str:
    """``<checkout>/.jax_cache`` — where the compile cache (and, under
    ``native/``, the built min-cut solver) live when nothing outside placed
    them. Listed in ``.gitignore``; a fixed path, so entries written by one
    process are found by the next."""
    checkout = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    return os.path.join(checkout, ".jax_cache")


def _entry_files(cache_dir: str) -> list[str]:
    try:
        return sorted(
            p for p in (os.path.join(cache_dir, f) for f in os.listdir(cache_dir))
            if os.path.isfile(p)
        )
    except OSError:
        return []


def _looks_corrupt(path: str) -> Optional[str]:
    """A reason string when the entry file is definitely unusable, else
    None. Deliberately conservative: only signatures that can never be a
    valid serialized executable (empty file, unreadable) — a false positive
    here would throw away a good compile."""
    try:
        size = os.path.getsize(path)
    except OSError as e:
        return f"unreadable ({e})"
    if size == 0:
        return "zero-length (torn write)"
    try:
        with open(path, "rb") as f:
            if not f.read(1):
                return "unreadable (empty read)"
    except OSError as e:
        return f"unreadable ({e})"
    return None


def sweep_corrupt_entries(cache_dir: str) -> list[str]:
    """Delete corrupted/truncated cache entries under ``cache_dir``; returns
    the removed paths. Each removal logs a warning and emits a
    ``cache_repair`` event — the program recompiles instead of crashing on
    a poisoned deserialize."""
    removed: list[str] = []
    for path in _entry_files(cache_dir):
        reason = _looks_corrupt(path)
        if reason is None:
            continue
        try:
            os.remove(path)
        except OSError:
            continue
        removed.append(path)
        logger.warning(
            "persistent XLA compile cache: removed corrupt entry %s (%s); "
            "it will recompile", path, reason,
        )
        obs_events.emit_event(
            "cache_repair", action="removed_entry", path=path, reason=reason
        )
    return removed


def purge_on_error(exc: BaseException) -> bool:
    """Clear the configured persistent-cache directory after a failure that
    classifies as cache corruption. True when a purge happened (the caller
    retries the compile)."""
    cache_dir = configured_cache_dir()
    if not cache_dir or not os.path.isdir(cache_dir):
        return False
    external = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if external and os.path.realpath(external) == os.path.realpath(cache_dir):
        logger.warning(
            "persistent XLA compile cache: %s came from JAX_COMPILATION_CACHE_DIR "
            "and is not purged after %s: %s", cache_dir, type(exc).__name__, exc,
        )
        return False
    entries = _entry_files(cache_dir)
    for path in entries:
        try:
            os.remove(path)
        except OSError:
            pass
    logger.warning(
        "persistent XLA compile cache: purged %d entr%s from %s after %s; "
        "recompiling", len(entries), "y" if len(entries) == 1 else "ies",
        cache_dir, type(exc).__name__,
    )
    obs_events.emit_event(
        "cache_repair", action="purged", path=cache_dir, reason=str(exc)[:200]
    )
    return True


def configured_cache_dir() -> Optional[str]:
    import jax

    return jax.config.jax_compilation_cache_dir or None
