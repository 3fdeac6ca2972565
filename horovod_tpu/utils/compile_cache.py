"""Where the persistent XLA compilation cache lives.

One rule for every entry point (``chip_smoke.py``, ``bench.py``, the test
harness): a directory placed from outside with ``JAX_COMPILATION_CACHE_DIR``
is JAX's to read — nothing is set in code, so the operator's directory is
the only one used. Without it the cache sits at a FIXED path inside the
checkout (``<checkout>/.jax_cache``): the path is part of the cache's
identity across runs, so it carries no pid, time or temp component.

The in-checkout directory is owned by this module, which lets it guard the
one hazard JAX's own key does not cover: XLA:CPU entries embed the
compiling host's CPU features, and loading them on a different host warns
of "execution errors such as SIGILL". The directory is stamped with the
host's CPU identity and started empty when the stamp does not match.
"""

from __future__ import annotations

import hashlib
import os
import platform
import shutil

ENV = "JAX_COMPILATION_CACHE_DIR"
_STAMP = "host_cpu"


def _host_cpu_id() -> str:
    flags = ""
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith(("flags", "Features")):
                    flags = line.split(":", 1)[1].strip()
                    break
    except OSError:
        pass
    ident = f"{platform.machine()} {flags}"
    return hashlib.sha256(ident.encode()).hexdigest()[:16]


def _start_empty_on_foreign_host(path: str) -> None:
    os.makedirs(path, exist_ok=True)
    stamp = os.path.join(path, _STAMP)
    host = _host_cpu_id()
    try:
        with open(stamp) as f:
            if f.read().strip() == host:
                return
    except FileNotFoundError:
        pass
    for name in os.listdir(path):
        entry = os.path.join(path, name)
        if os.path.isdir(entry):
            shutil.rmtree(entry)
        else:
            os.remove(entry)
    with open(stamp, "w") as f:
        f.write(host + "\n")


def configure_compile_cache(checkout: str) -> str:
    """Point JAX's persistent compilation cache at the right directory and
    return it. Call before the first compile. ``checkout`` is the root of
    the source tree the caller runs from."""
    placed = os.environ.get(ENV)
    if placed:
        return placed
    import jax

    path = os.path.join(os.path.abspath(checkout), ".jax_cache")
    _start_empty_on_foreign_host(path)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
