"""
Logging setup (reference: dedalus/tools/logging.py).

Process-aware root logger configuration from the [logging] config section:
stdout handler at `stdout_level` (non-initial processes use
`nonroot_level`), plus optional per-process file handlers at `file_level`
under `filename`_p{rank}.log (reference: tools/logging.py:24-47). File
handlers are flushed and closed at interpreter exit so per-process logs
survive abrupt ends of multi-host runs.
"""

import atexit
import logging
import os
import pathlib
import sys

from .config import config


def process_rank():
    """This process's rank for logging purposes. Reads JAX_PROCESS_INDEX
    (set by multi-host launchers) rather than calling jax.process_index():
    that would initialize the backend (and claim the chip) at import
    time. Single-controller runs are rank 0."""
    return int(os.environ.get("JAX_PROCESS_INDEX", "0") or 0)


def _resolve_level(name):
    name = (name or "none").lower()
    if name == "none":
        return None
    return getattr(logging, name.upper())


def _close_handlers(handlers):
    """Detach, flush, and close file handlers at interpreter exit. Mostly
    belt-and-braces over logging.shutdown (which flushes all live
    handlers), but detaching FIRST guarantees no later atexit callback
    logs into a closed stream, and the explicit close survives a
    `logging.raiseExceptions=False`-style global shutdown ordering."""
    root = logging.getLogger("dedalus_tpu")
    for handler in handlers:
        try:
            root.removeHandler(handler)
            handler.flush()
            handler.close()
        except Exception:
            pass


def setup_logging(force=False):
    """Configure the dedalus_tpu root logger from config; idempotent."""
    root = logging.getLogger("dedalus_tpu")
    if root.handlers and not force:
        return root
    rank = process_rank()
    section = config["logging"]
    stdout_level = _resolve_level(
        section.get("stdout_level", "info") if rank == 0
        else section.get("nonroot_level", "warning"))
    file_level = _resolve_level(section.get("file_level", "none"))
    formatter = logging.Formatter(
        "%(asctime)s %(name)s %(levelname)s :: %(message)s")
    root.setLevel(logging.DEBUG)
    added = []
    if stdout_level is not None:
        handler = logging.StreamHandler(sys.stdout)
        handler.setLevel(stdout_level)
        handler.setFormatter(formatter)
        root.addHandler(handler)
    if file_level is not None:
        path = pathlib.Path(section.get("filename", "logs/dedalus_tpu"))
        # parent must exist BEFORE FileHandler opens the stream
        path.parent.mkdir(parents=True, exist_ok=True)
        handler = logging.FileHandler(f"{path}_p{rank}.log")
        handler.setLevel(file_level)
        handler.setFormatter(formatter)
        root.addHandler(handler)
        added.append(handler)
    if added:
        atexit.register(_close_handlers, added)
    return root
