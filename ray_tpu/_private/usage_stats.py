"""Usage reporting: what the cluster is and which libraries it exercised.

Design analog: reference ``python/ray/_private/usage/usage_lib.py`` —
cluster metadata + library-usage tags collected at runtime.  The reference
phones home (opt-out); this environment has zero egress by design, so the
report is LOCAL-ONLY: a JSON document written to the head node's log dir
at shutdown (RT_USAGE_STATS=0 disables even that) and accessible via
``ray_tpu.usage_report()`` / the ``usage`` CLI subcommand.  Deployments
that want aggregation ship the file themselves.
"""

from __future__ import annotations

import json
import os
import time
from typing import Any, Dict, Set

_LIBRARIES: Set[str] = set()


def record_library_usage(name: str) -> None:
    """Tag a library as used (importing serve/tune/... calls this)."""
    _LIBRARIES.add(name)


def usage_report() -> Dict[str, Any]:
    """Snapshot of cluster shape + exercised surfaces (local only)."""
    report: Dict[str, Any] = {
        "timestamp": time.time(),
        "libraries": sorted(_LIBRARIES),
        "schema_version": 1,
    }
    try:
        import ray_tpu
        if ray_tpu.is_initialized():
            nodes = ray_tpu.nodes()
            report["cluster"] = {
                "num_nodes": len(nodes),
                "alive_nodes": sum(1 for n in nodes if n["alive"]),
                "total_resources": ray_tpu.cluster_resources(),
            }
    except Exception:
        pass
    try:
        # Report a backend only if one is ALREADY initialized: a shutdown
        # hook must never open the chip (see _private/jaxutil.py).
        from ray_tpu._private.jaxutil import backend_summary_if_initialized
        summary = backend_summary_if_initialized()
        if summary is not None:
            report["jax"] = summary
    except Exception:
        pass
    return report


def write_report_at_shutdown() -> str:
    """Write the report under the log dir; returns the path ('' if off)."""
    if os.environ.get("RT_USAGE_STATS", "1") == "0":
        return ""
    try:
        import tempfile
        d = os.environ.get("RT_LOG_DIR") or os.path.join(
            tempfile.gettempdir(), "ray_tpu")
        os.makedirs(d, exist_ok=True)
        path = os.path.join(d, "usage_report.json")
        with open(path, "w") as f:
            json.dump(usage_report(), f, indent=2)
        return path
    except Exception:
        return ""
