"""Counts what set-up compiles: jax's persistent-cache monitoring events and the
files of the cache directory before and after (ISSUE 23, rule 2)."""
from __future__ import annotations

import os
from typing import Dict, List

_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "requests",
    "/jax/compilation_cache/cache_hits": "hits",
    "/jax/compilation_cache/cache_misses": "misses",
}


class CacheWatch:
    """Register once, before the first compile; `snapshot()` any time."""

    def __init__(self) -> None:
        self.counts: Dict[str, int] = {v: 0 for v in _EVENTS.values()}
        import jax.monitoring

        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kw) -> None:
        key = _EVENTS.get(event)
        if key is not None:
            self.counts[key] += 1

    def snapshot(self) -> Dict[str, int]:
        return dict(self.counts)


def cache_files(path: str) -> List[str]:
    """Names of the cache's entries (jax names each file from the executable's
    name and its key's hash, so two listings show which executables are new)."""
    try:
        return sorted(n for n in os.listdir(path) if not n.endswith("-atime") and not n.startswith("."))
    except FileNotFoundError:
        return []
