"""Env-var config helpers (reference: docs/env_variable.rst).

The reference configures everything through BLUEFOG_* environment variables
(fusion threshold, cycle time, log level...).  Most have no TPU equivalent
(no fusion buffers, no cycle loop); the ones that survive:

* ``BLUEFOG_TIMELINE``       — timeline output prefix (utils.timeline)
* ``BLUEFOG_LOG_LEVEL``      — python logging level for the "bluefog_tpu" logger
* ``BLUEFOG_NODES_PER_MACHINE`` — virtual machine split for hierarchical ops
  (read by bf.init when nodes_per_machine is not passed explicitly)
"""
from __future__ import annotations

import logging
import os
import re
from typing import Optional

logger = logging.getLogger("bluefog_tpu")


def env_flag(name: str, default: bool = False) -> bool:
    v = os.environ.get(name)
    if v is None:
        return default
    return v.strip().lower() in ("1", "true", "yes", "on")


def env_int(name: str, default: Optional[int] = None) -> Optional[int]:
    v = os.environ.get(name)
    return default if v is None else int(v)


def env_float(name: str, default: Optional[float] = None) -> Optional[float]:
    v = os.environ.get(name)
    return default if v is None else float(v)


# Latency-hiding scheduler flags: let XLA overlap gossip collectives with
# backward compute — the role the reference's background comm thread +
# nonblocking ops play (SURVEY.md §7 "hard parts" (5)).  This is the standard
# public TPU training flag set (async collective fusion across steps).
# libtpu reads them from LIBTPU_INIT_ARGS; jaxlib's own XLA_FLAGS parser
# does not know --xla_tpu_* names and aborts the process on them.
RECOMMENDED_TPU_XLA_FLAGS = (
    "--xla_tpu_enable_async_collective_fusion=true "
    "--xla_tpu_enable_async_collective_fusion_fuse_all_gather=true "
    "--xla_tpu_enable_async_collective_fusion_multiple_steps=true "
    "--xla_tpu_overlap_compute_collective_tc=true "
    "--xla_enable_async_all_gather=true"
)


def add_recommended_tpu_flags(env=None) -> bool:
    """Append the TPU overlap flags to ``LIBTPU_INIT_ARGS`` in ``env``
    (default ``os.environ``).  Idempotent, and a flag the caller already
    set (to either value) is left alone.  Takes effect only if it runs
    before the TPU backend initializes; returns whether anything was added.
    """
    e = os.environ if env is None else env
    current = e.get("LIBTPU_INIT_ARGS", "")
    have = {tok.split("=", 1)[0] for tok in current.split()}
    missing = [f for f in RECOMMENDED_TPU_XLA_FLAGS.split()
               if f.split("=", 1)[0] not in have]
    if not missing:
        return False
    e["LIBTPU_INIT_ARGS"] = " ".join([current] + missing).strip()
    return True


# One fixed location inside the checkout: the directory is part of the
# cache key, so a home directory, a temp name or a pid would never hit.
DEFAULT_COMPILATION_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


# a function's head, or a device scope opened under it
_SCOPE_SITE_RE = re.compile(
    r"^\s*def (\w+)|named_(?:scope|span)\(\s*f?\"([^\"]+)\"", re.M)


def scope_sites_key() -> str:
    """A digest of WHERE the package opens device scopes: every
    ``jax.named_scope("...")`` / ``named_span("...")`` in its sources as
    (file, enclosing function, name), whatever line it stands on.

    JAX keys its persistent compile cache on a program with its debug
    information stripped, and a scope is debug information: a build that
    moved or added scopes and nothing else finds the older build's
    executable under its own key, with the older ``op_name``s, and
    ``tracing.device_scopes()`` then truthfully describes THAT executable.
    With this digest in the key (:func:`enable_compilation_cache`) such a
    build compiles afresh once; a build that only moved lines does not
    (keeping all metadata in the key, ``jax_compilation_cache_include_
    metadata_in_key``, would make it)."""
    import hashlib
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sites = []
    for folder, _, files in sorted(os.walk(root)):
        for name in sorted(files):
            if not name.endswith(".py"):
                continue
            path = os.path.join(folder, name)
            with open(path, encoding="utf-8") as f:
                text = f.read()
            if "named_s" not in text:
                continue
            func = ""
            for m in _SCOPE_SITE_RE.finditer(text):
                if m.group(1):
                    func = m.group(1)
                else:
                    sites.append((os.path.relpath(path, root), func,
                                  m.group(2)))
    return "bluefog-device-scopes-" + hashlib.sha256(
        repr(sites).encode()).hexdigest()[:16]


def enable_compilation_cache() -> str:
    """Point JAX's persistent compilation cache at a directory that
    survives the process; returns the directory in use.

    ``JAX_COMPILATION_CACHE_DIR`` is the installation's switch and JAX
    already honours it, so when it is set nothing is written here.
    Otherwise the cache goes to ``<checkout>/.jax_cache``.
    ``JAX_ENABLE_COMPILATION_CACHE=0`` turns it off either way.  Called by
    ``bf.init`` when the devices are TPUs; CPU runs keep the cache off so
    the sandbox does not grow the tree the chip tool copies.  The cache's
    key also takes in where the package opens device scopes
    (:func:`scope_sites_key`).
    """
    import jax

    if not jax.config.jax_compilation_cache_dir:
        jax.config.update("jax_compilation_cache_dir",
                          DEFAULT_COMPILATION_CACHE_DIR)
    # small collective programs compile in under JAX's 1 s floor and there
    # are dozens of them; lower it unless the user configured one
    if jax.config.jax_persistent_cache_min_compile_time_secs == 1.0:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.2)
    # where the package opens device scopes is part of the key: a scope is
    # debug information, which the key leaves out (scope_sites_key)
    key = scope_sites_key()
    try:
        from jax._src import cache_key
    except ImportError:
        cache_key = None
    if hasattr(cache_key, "custom_hook"):       # what this jax's key calls
        cache_key.custom_hook = lambda: key
    else:
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          True)
    return jax.config.jax_compilation_cache_dir


def looks_like_tpu_environment(env=None) -> bool:
    """Will JAX pick the TPU backend in a process started with ``env``?

    ``JAX_PLATFORMS`` decides when it is set (the v5e machines export
    ``tpu,cpu``).  Unset, JAX takes the TPU when one is attached, which
    shows as the TPU runtime's own variables.
    """
    e = os.environ if env is None else env
    platforms = e.get("JAX_PLATFORMS", "").strip().lower()
    if platforms:
        return "tpu" in platforms.split(",")
    return any(k in e for k in ("TPU_ACCELERATOR_TYPE", "TPU_WORKER_HOSTNAMES",
                                "MEGASCALE_COORDINATOR_ADDRESS"))


def setup_logging() -> None:
    level = os.environ.get("BLUEFOG_LOG_LEVEL", "warning").upper()
    if level in ("TRACE",):
        level = "DEBUG"
    logger.setLevel(getattr(logging, level, logging.WARNING))
    if not logger.handlers:
        h = logging.StreamHandler()
        h.setFormatter(logging.Formatter(
            "[%(asctime)s %(levelname)s bluefog_tpu] %(message)s"))
        logger.addHandler(h)
