"""Safe JAX backend introspection for runtime plumbing.

Rule: framework plumbing (daemons, shutdown hooks, usage reports, CLI
status) must NEVER initialize a JAX backend as a side effect.  A chip
belongs to one process at a time, so a daemon or driver that opens it
takes it from the worker the raylet leased it to, and backend init costs
tens of seconds.  The reference has the same discipline for GPUs:
autodetection reads NVML/proc state and never blocks shutdown
(``python/ray/_private/resource_spec.py:287``).

``"jax" in sys.modules`` is NOT evidence that a backend exists (importing
jax opens nothing) — the only safe question is "is a backend *already*
initialized?", answered by inspecting ``jax._src.xla_bridge._backends``
(populated only by a successful ``get_backend()``).
"""

from __future__ import annotations

import os
from typing import Any, Dict, MutableMapping, Optional


def place_compile_cache(env: MutableMapping[str, str]) -> None:
    """Give a process that will compile for the chip (``env`` is its
    environment, before it imports jax) JAX's persistent compile cache:
    where JAX_COMPILATION_CACHE_DIR already says, else ``.jax_cache`` at the
    root of this checkout.  The path is part of every cache key, so it
    follows from where the package is and never from a temporary name, a
    pid or the time."""
    env.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))), ".jax_cache"))


def initialized_backends() -> Dict[str, Any]:
    """Backends that are ALREADY initialized (never triggers init).

    Returns {} when jax isn't imported, has no initialized backend, or
    its internals moved (we fail closed: claiming "no backend" is always
    safe; cold-initializing one never is).
    """
    import sys
    jax = sys.modules.get("jax")
    if jax is None:
        return {}
    try:
        from jax._src import xla_bridge
        backends = getattr(xla_bridge, "_backends", None)
        return dict(backends) if backends else {}
    except Exception:
        return {}


def backend_summary_if_initialized() -> Optional[Dict[str, Any]]:
    """{"backend": name, "device_count": n} if a backend is live, else None.

    Derived ONLY from the already-initialized snapshot.  Calling
    ``jax.default_backend()`` here would be wrong even with backends
    present: it takes ``xla_bridge._backend_lock``, which an init in
    progress on another thread holds for as long as that init takes.
    """
    backends = initialized_backends()
    if not backends:
        return None
    try:
        # Mirror jax's platform priority (accelerator over cpu) without
        # asking jax: prefer any non-cpu platform in the snapshot.
        name = next((p for p in backends if p != "cpu"), None) \
            or next(iter(backends))
        return {"backend": name,
                "device_count": backends[name].device_count()}
    except Exception:
        return None


