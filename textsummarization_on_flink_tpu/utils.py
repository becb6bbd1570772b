"""Small host-side utilities (me/littlebo/SysUtils.java parity)."""

from __future__ import annotations

import os
import sys


def get_project_root_dir() -> str:
    """The process working directory (SysUtils.java:4-6 `user.dir`)."""
    return os.getcwd()


def set_default_compile_cache(env=None) -> str:
    """The ONE place the persistent compile cache is chosen; returns the
    directory in use.  A set JAX_COMPILATION_CACHE_DIR is left alone and
    no other directory is set in code (jax reads the variable itself);
    otherwise the cache lives at the fixed path <checkout>/.jax_cache —
    the path is part of the cache key, so it is never made from a temp
    name, a pid or the time.

    `env` given: the child-process form — only that dict is filled in,
    so a supervisor that must stay off jax can hand it to a child.
    `env` None: this process — os.environ, plus jax.config when jax is
    already imported (it reads the variable only at its import)."""
    target = os.environ if env is None else env
    if target.get("JAX_COMPILATION_CACHE_DIR"):
        return target["JAX_COMPILATION_CACHE_DIR"]
    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(checkout, ".jax_cache")
    target["JAX_COMPILATION_CACHE_DIR"] = path
    if env is None and "jax" in sys.modules:
        sys.modules["jax"].config.update("jax_compilation_cache_dir", path)
    return path


def apply_debug_mode(hps) -> None:
    """Wire the --debug flag: the reference attaches tfdbg's
    has_inf_or_nan filter (run_summarization.py:88,216-218); the JAX
    equivalent is jax_debug_nans, which re-runs the offending op
    un-jitted and raises at the first non-finite intermediate.  (The
    Trainer additionally dumps the offending batch under --debug.)"""
    if getattr(hps, "debug", False):
        import jax

        jax.config.update("jax_debug_nans", True)


def local_batch_hps(hps):
    """Per-host view of a global config for BATCHER construction: on a
    multi-host run each host's input pipeline must yield its own
    batch_size/process_count rows (the mesh/step functions keep the
    GLOBAL hps.batch_size)."""
    import jax

    nproc = jax.process_count()
    if nproc <= 1:
        return hps
    if hps.batch_size % nproc != 0:
        raise ValueError(f"batch_size={hps.batch_size} must be divisible "
                         f"by process_count={nproc}")
    return hps.replace(batch_size=hps.batch_size // nproc)
