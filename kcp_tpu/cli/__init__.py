"""kcp_tpu.cli — the CLI binaries (reference: cmd/).

Each module is runnable with ``python -m kcp_tpu.cli.<name>``:

- ``kcp``                  the control-plane server (cmd/kcp)
- ``cluster_controller``   standalone controllers (cmd/cluster-controller)
- ``syncer``               standalone spec/status syncer (cmd/syncer)
- ``deployment_splitter``  standalone splitter (cmd/deployment-splitter)
- ``crd_puller``           dump cluster APIs as CRD YAML (cmd/crd-puller)
- ``compat``               CRD schema compat / LCD check (cmd/compat)
"""

import os
import sys


# the one compile-cache location of a checkout (already in .gitignore):
# the directory is part of the cache key, so every entry point — the
# binaries, chip_smoke.py, the tests — must agree on it
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache")


def apply_platform_env() -> None:
    """Set-up every binary's main runs before its first jax-using import.

    ``JAX_PLATFORMS`` from the shell is read by JAX itself at import; the
    config update only matters for a caller that imported jax before the
    variable was set (and a child that never imports jax keeps its cheap
    start). Then the compile cache.
    """
    want = os.environ.get("JAX_PLATFORMS", "")
    if want and "jax" in sys.modules:
        sys.modules["jax"].config.update("jax_platforms", want)
    enable_compilation_cache()


def enable_compilation_cache() -> str | None:
    """Persistent XLA compilation cache for every entry point: a recompile
    of the fused step is a seconds-long serving stall (p99 poison), and
    the cache also turns restart warmup from ~30 s of compiles into reads.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and no
    directory is set in code; otherwise the cache lives in the checkout's
    ``.jax_cache``. Opt out with ``KCP_NO_COMPILE_CACHE=1``. Returns the
    directory in use (None when opted out).
    """
    if os.environ.get("KCP_NO_COMPILE_CACHE") == "1":
        return None
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        os.makedirs(REPO_CACHE_DIR, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    return jax.config.jax_compilation_cache_dir
