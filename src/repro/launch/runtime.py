"""Process-level rules for running on TPU chips, kept free of jax imports
so that launchers which must stay off the chip can apply them too.

* ``use_compile_cache`` — where JAX's persistent compilation cache lives.
  Entry points call it before their first compile, never on import.
* ``count_tpu_chips`` / ``chip_env`` — a chip belongs to one process at a
  time: the launcher counts the host's chips without opening any, and
  shows each JAX worker process exactly one chip of its own.
"""
from __future__ import annotations

import glob
import os
import sys
from typing import Dict


def repo_root() -> str:
    """The checkout this code runs from (``src/..``). ``repro`` is a
    namespace package, so it is located through ``__path__``."""
    import repro
    src_dir = os.path.dirname(os.path.abspath(list(repro.__path__)[0]))
    return os.path.dirname(src_dir)


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already uses it and
    nothing is changed. Otherwise the cache goes to ``<repo>/.jax_cache``:
    a fixed path, because the path is part of every cache key. The
    variable is exported so that worker processes use the same
    directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    path = os.path.join(repo_root(), ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax = sys.modules.get("jax")
    if jax is not None:
        # jax read the environment when it was imported
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def count_tpu_chips() -> int:
    """TPU chips this host lets its processes open, counted from their
    device nodes (``/dev/accel<N>``, or ``/dev/vfio/<N>`` from v5e on)
    without loading the TPU runtime, which would claim them. The PCI bus
    can list more chips than are passed through to this machine."""
    accel = glob.glob("/dev/accel[0-9]*")
    vfio = [p for p in glob.glob("/dev/vfio/*")
            if os.path.basename(p).isdigit()]
    return len(accel) + len(vfio)


def chip_env(chip: int) -> Dict[str, str]:
    """Environment that shows one process only chip ``chip`` of its host,
    as a one-chip slice of its own. libtpu then lets several such
    processes load side by side, one per chip."""
    return {"TPU_VISIBLE_CHIPS": str(chip),
            "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_BOUNDS": "1,1,1"}
