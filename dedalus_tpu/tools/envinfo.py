"""
Host/environment fingerprint for trajectory rows.

Every row appended to benchmarks/results.jsonl (bench headlines, ledger
rows, probe history, served telemetry routed through the bench sink)
carries one `env` dict from `env_fingerprint()` so the perfwatch
sentinel (tools/perfwatch.py) can separate host drift from real
regressions — the PR-16 wall-clock caveat (±15% suite drift on a noisy
shared host) is exactly the ambiguity this resolves: when a number
moves, `env` says whether the machine changed under it.

Two hard rules, both load-bearing:

* **Never initialize the JAX backend.** `jax.devices()` /
  `jax.default_backend()` would spin up the platform as a side effect
  and claim the chip, which belongs to one process at a time. Backend
  fields are reported only when the backend is ALREADY live in this
  process, detected through a guarded private check; otherwise they are
  null — absence is explicit, never forced.
* **Every field degrades independently.** A missing /proc, an
  unimportable jaxlib, or a renamed private attribute nulls that one
  field; the fingerprint itself always comes back.
"""

import hashlib
import os
import platform
import socket
import sys

__all__ = ["env_fingerprint", "stamp_env"]


def _backend_fields():
    """backend / device_count / device_kind — null unless the JAX
    backend is already initialized in this process (reading them must
    never BE the initialization)."""
    fields = {"backend": None, "device_count": None, "device_kind": None}
    try:
        jax = sys.modules.get("jax")
        if jax is None:
            return fields
        # Peek at the bridge through sys.modules rather than importing
        # it: an import could pull private machinery in itself, and a
        # renamed module on a JAX upgrade degrades this to null fields
        # instead of an ImportError.
        xla_bridge = sys.modules.get("jax._src.xla_bridge")
        if xla_bridge is None \
                or not getattr(xla_bridge, "_backends", None):
            return fields
        devices = jax.devices()
        fields["backend"] = str(jax.default_backend())
        fields["device_count"] = len(devices)
        if devices:
            fields["device_kind"] = str(
                getattr(devices[0], "device_kind", None) or None)
    except Exception:
        pass
    return fields


def _version_of(module_name):
    """Version of an already-importable module; importing jax/jaxlib is
    side-effect-safe (only backend *use* initializes platforms)."""
    try:
        module = __import__(module_name)
        return str(getattr(module, "__version__", None) or None)
    except Exception:
        return None


def env_fingerprint():
    """One flat dict describing the host this row was measured on.

    Keys (any may be null): `backend`, `device_count`, `device_kind`,
    `cpu_count`, `loadavg_1m`, `jax`, `jaxlib`, `python`, `host` (a
    short blake2b hash of the hostname — joinable, not identifying),
    plus `env_version` for forward evolution.
    """
    env = {"env_version": 1}
    env.update(_backend_fields())
    try:
        env["cpu_count"] = os.cpu_count()
    except Exception:
        env["cpu_count"] = None
    try:
        env["loadavg_1m"] = round(os.getloadavg()[0], 2)
    except (OSError, AttributeError):
        env["loadavg_1m"] = None
    env["jax"] = _version_of("jax")
    env["jaxlib"] = _version_of("jaxlib")
    try:
        env["python"] = platform.python_version()
    except Exception:
        env["python"] = None
    try:
        name = socket.gethostname().encode()
        env["host"] = hashlib.blake2b(name, digest_size=6).hexdigest()
    except Exception:
        env["host"] = None
    return env


def stamp_env(record):
    """setdefault an `env` fingerprint onto one result row (in place,
    also returned). Rows that already carry one keep it — a re-reported
    row keeps the fingerprint of the host that MEASURED it."""
    if isinstance(record, dict):
        record.setdefault("env", env_fingerprint())
    return record
