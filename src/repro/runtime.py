"""Process-level JAX setup shared by the engine, the benchmarks, the tests
and ``chip_smoke.py``: mesh construction, named jits, the persistent
compile cache, and forced host devices for CPU rehearsals of
multi-device paths.

Nothing here runs at import time, and importing this module initialises
no JAX backend.
"""
from __future__ import annotations

import functools
import os
from pathlib import Path
from typing import Callable, Optional, Sequence

import jax
from jax.sharding import AxisType, Mesh

REPO_ROOT = Path(__file__).resolve().parents[2]
CACHE_DIR = REPO_ROOT / ".jax_cache"


def make_mesh(shape: Sequence[int], axes: Sequence[str],
              devices: Optional[Sequence] = None) -> Mesh:
    """``jax.make_mesh`` with every axis ``Auto``. The installed JAX
    defaults to ``Explicit`` axes, under which the engine's shard_map
    bodies and sharding constraints are refused; every mesh in the repo
    is built here."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def cc_mesh(n: Optional[int] = None,
            devices: Optional[Sequence] = None) -> Mesh:
    """The record-partitioned store's 1-D ``cc`` mesh over ``n`` devices
    (default: all of ``devices``, else all of ``jax.devices()``)."""
    devices = list(jax.devices() if devices is None else devices)
    if n is not None:
        devices = devices[:n]
    return make_mesh((len(devices),), ("cc",), devices=devices)


def jit_named(fn: Callable, donate_argnums: Sequence[int] = (),
              **bound) -> Callable:
    """``jax.jit`` of ``fn`` with the keyword arguments ``bound`` fixed,
    its program named ``jit_<fn name>`` on the device and in the
    profiler's trace (a bare ``functools.partial`` is named
    ``jit__unknown``). Only the name is copied: a ``__wrapped__``
    (``functools.wraps``) would make JAX bind the arguments against
    ``fn``'s own signature, fail on the bound keywords, and rename the
    program's parameters. ``donate_argnums`` passes through to
    ``jax.jit``: the program may write those positional arguments'
    buffers in place, and the caller's arrays are deleted by the call."""
    phase = functools.partial(fn, **bound)
    phase.__name__ = fn.__name__
    phase.__qualname__ = fn.__qualname__
    return jax.jit(phase, donate_argnums=tuple(donate_argnums))


def setup_compile_cache() -> str:
    """Point JAX's persistent compilation cache at a stable directory and
    return it. ``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads
    it itself and nothing is changed. Otherwise the cache lives at the
    fixed ``<repo>/.jax_cache`` (gitignored) — fixed because the path is
    part of the cache key, so a moving directory never hits."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)


def force_cpu_devices(n: int) -> None:
    """Give the CPU backend ``n`` virtual devices for a multi-device
    rehearsal — only when JAX is held to the CPU (``JAX_PLATFORMS=cpu``);
    on an accelerator the mesh comes from the devices that exist. Must
    run before the first JAX backend initialises."""
    if os.environ.get("JAX_PLATFORMS", "").strip().lower() != "cpu":
        return
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={int(n)}"
        ).strip()
