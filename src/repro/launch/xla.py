"""Process-level XLA setup: XLA_FLAGS composition and the compile cache.

XLA_FLAGS composition — append, never clobber.

Every launcher that needs an XLA flag (the dryrun's forced host device
count, the async-collective overlap flags below) must COMPOSE with whatever
the user already exported: overwriting ``XLA_FLAGS`` silently drops
latency-hiding/async-collective flags set in the environment, which is
exactly the bug this module exists to prevent.  Flags must be in the
environment before the jax backend initializes (first device query), so
launchers call these helpers at the top of ``main()``.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Iterable, Sequence, Tuple

#: the checkout's root (``src/repro/launch/xla.py`` -> three levels up)
CHECKOUT = Path(__file__).resolve().parents[3]

#: the async-collective / latency-hiding scheduler set (SNIPPETS §3 idiom):
#: lets XLA run each bucket of the chunked flat-gradient reduce
#: (``core.distributed.lower_fo_round`` with ``--fo-buckets``) on the async
#: collective stream, overlapped with the compute producing the next chunk —
#: the real-path mirror of the sim's ``Overlap`` pricing.
OVERLAP_FLAGS: Tuple[str, ...] = (
    "--xla_gpu_enable_async_collectives=true",
    "--xla_gpu_enable_latency_hiding_scheduler=true",
    "--xla_gpu_enable_highest_priority_async_stream=true",
)


def compose_xla_flags(new_flags: Sequence[str],
                      current: str = "",
                      drop_prefixes: Iterable[str] = ()) -> str:
    """Merge ``new_flags`` into the ``current`` XLA_FLAGS string.

    Existing flags are preserved in order; any existing flag starting with
    one of ``drop_prefixes`` is removed first (the caller owns that knob —
    e.g. the dryrun owns ``--xla_force_host_platform_device_count``); new
    flags already present verbatim are not duplicated.  Pure string
    function so it is directly testable without touching the environment.
    """
    kept = [f for f in current.split()
            if not any(f.startswith(p) for p in drop_prefixes)]
    return " ".join(kept + [f for f in new_flags if f not in kept])


def append_xla_flags(new_flags: Sequence[str],
                     drop_prefixes: Iterable[str] = ()) -> str:
    """Compose ``new_flags`` into ``os.environ['XLA_FLAGS']`` in place and
    return the resulting string."""
    merged = compose_xla_flags(new_flags, os.environ.get("XLA_FLAGS", ""),
                               drop_prefixes)
    os.environ["XLA_FLAGS"] = merged
    return merged


def enable_collective_overlap() -> str:
    """Turn on the async-collective + latency-hiding scheduler flags
    (``--xla-overlap`` in ``launch.train``), composing with — never
    replacing — whatever XLA_FLAGS the user exported."""
    return append_xla_flags(OVERLAP_FLAGS)


def use_compile_cache() -> str:
    """Keep JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
    sets nothing.  Otherwise the cache goes to ``<checkout>/.jax_cache`` (a
    fixed path: the path is part of the cache key).  Call it at the start
    of an entry point, before the first compile — never at import.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
