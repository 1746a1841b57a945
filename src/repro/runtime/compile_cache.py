"""Compiled-executable cache — the RO-cache analogue (paper §5.2).

MemPool's software-managed read-only cache keeps the instruction stream hot
so 256 PEs never stall on fetch. Our PEs run a compiled XLA program; the
fetch path is lower+compile. The cache memoizes AOT-compiled executables
keyed on (step identity, arch, shapes, mesh, rules fingerprint), so elastic
restarts and repeated launches never pay recompilation ("cold boot" is the
paper's cache-refill phase; see bench Fig. 15).

`use_persistent_cache` places JAX's on-disk compilation cache, which
carries compiled programs across processes: the directory named by
``JAX_COMPILATION_CACHE_DIR`` when the environment sets it, else a fixed
directory inside the checkout. The path is part of each entry's key, so it
never depends on a temp name, a pid or the time.
"""

from __future__ import annotations

import hashlib
import json
import os
from pathlib import Path
from typing import Any, Callable

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_persistent_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is changed here. Otherwise the cache goes to `CHECKOUT_CACHE_DIR`
    (``<checkout>/.jax_cache``, listed in .gitignore).
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)


def _fingerprint(*parts: Any) -> str:
    s = json.dumps([str(p) for p in parts], sort_keys=True)
    return hashlib.sha1(s.encode()).hexdigest()[:16]


class CompileCache:
    def __init__(self):
        self._cache: dict[str, Any] = {}
        self.hits = 0
        self.misses = 0

    def get(self, key_parts: tuple, build: Callable[[], Any]):
        key = _fingerprint(*key_parts)
        if key in self._cache:
            self.hits += 1
            return self._cache[key]
        self.misses += 1
        exe = build()
        self._cache[key] = exe
        return exe

    def compile_step(self, fn, args_sds, in_shardings, out_shardings,
                     donate, mesh, tag: str):
        key = (tag, jax.tree.map(lambda s: (s.shape, str(s.dtype)), args_sds),
               tuple(mesh.shape.items()) if hasattr(mesh.shape, "items")
               else mesh.shape)

        def build():
            with jax.set_mesh(mesh):
                return jax.jit(fn, in_shardings=in_shardings,
                               out_shardings=out_shardings,
                               donate_argnums=donate).lower(*args_sds).compile()

        return self.get(key, build)
