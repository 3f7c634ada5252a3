"""The device policy and the compile cache — decided here, nowhere else.

Platform: the program runs on the TPU unless the CPU is asked for by
name — ``--device cpu``, or ``JAX_PLATFORMS=cpu`` in the environment
(what the tests set).  :func:`select` pins ``jax_platforms`` to that ONE
name, so a machine without a chip fails at backend start-up instead of
training on the CPU unnoticed; :func:`require` turns that failure into
the typed :class:`NoAcceleratorError`.

Kernels: every ``auto`` kernel selector asks :func:`on_tpu` and every
``pallas_call`` takes its ``interpret=`` from :func:`pallas_interpret`.
Interpret mode and the jnp twins exist for the CPU tests; with the
platform pinned to ``tpu`` neither is reachable.

Processes: a process that initialises the TPU backend opens every local
chip and holds them until it exits, so accelerator work lives in ONE
process at a time — :func:`check_workers` refuses a worker pool that
cannot get the chip before any child starts.

Compile cache: :func:`enable_compile_cache` is called by every process
entry that compiles.  ``JAX_COMPILATION_CACHE_DIR`` wins when set (jax
reads it itself; nothing is set in code); otherwise the cache lives at
one fixed path inside the checkout, the same in every process — the
path is part of the cache key, so a directory that moves never hits.
"""

from __future__ import annotations

import os
from typing import Optional

import jax
from jax._src.xla_bridge import backends_are_initialized

_REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_COMPILE_CACHE_DIR = os.path.join(_REPO_ROOT, ".jax_cache")


class NoAcceleratorError(RuntimeError):
    """The TPU was required (``--device tpu``, or no platform asked
    for) and this machine has none to give."""


class AcceleratorWorkersError(RuntimeError):
    """A worker pool was asked to run on the accelerator but cannot
    have it: more than one worker, or a parent that already holds it."""


def _wanted(device: Optional[str]) -> str:
    """``--device`` if given, else what the environment asked for, else
    the TPU — never "whatever jax finds"."""
    return device or jax.config.jax_platforms or "tpu"


def select(device: Optional[str] = None) -> str:
    """Pin the jax platform and return its name.

    ``device`` is the CLI's ``--device`` (``"tpu"`` / ``"cpu"``).  With
    None the environment decides: a ``JAX_PLATFORMS`` the caller set is
    kept as is, and an unset one means the TPU.  Once a backend is up the
    choice is frozen: the running platform is returned and
    :func:`require` checks it."""
    if backends_are_initialized():
        return jax.default_backend()
    platform = _wanted(device)
    jax.config.update("jax_platforms", platform)
    return platform


def require(device: Optional[str] = None):
    """:func:`select`, then bring the backend up; returns its devices.
    Raises :class:`NoAcceleratorError` where the platform asked for is
    not the one this process gets."""
    platform = select(device)
    try:
        devices = jax.devices()
    except RuntimeError as exc:
        raise NoAcceleratorError(
            f"platform {platform!r} was required and could not be "
            f"initialised ({exc}); pass --device cpu (or set "
            "JAX_PLATFORMS=cpu) to run on the CPU on purpose"
        ) from exc
    if device and devices[0].platform != device:
        raise NoAcceleratorError(
            f"--device {device} was asked for, but this process already "
            f"runs on {devices[0].platform!r}"
        )
    return devices


def on_tpu() -> bool:
    """True when the process computes on a TPU.  Initialises the
    backend — call it where arrays are about to be made anyway."""
    return jax.default_backend() == "tpu"


def pallas_interpret() -> bool:
    """The ``interpret=`` flag of every ``pallas_call``: the kernels run
    compiled on the TPU and interpreted everywhere else (the CPU tests)."""
    return not on_tpu()


def holds_accelerator() -> bool:
    """True when THIS process has already opened the TPU (and so no
    child can).  Never initialises a backend to find out."""
    return backends_are_initialized() and on_tpu()


def check_workers(n_workers: int, device: Optional[str]) -> None:
    """Refuse, before any child starts, a pool of ``n_workers`` spawned
    jax processes that cannot get the accelerator.  ``device`` is the
    workers' ``--device``; None inherits this process's platform."""
    if n_workers < 1:
        return
    platform = _wanted(device)
    if platform == "cpu":
        return
    if n_workers > 1:
        raise AcceleratorWorkersError(
            f"{n_workers} worker processes on platform {platform!r}: "
            "each jax process opens every local chip and holds it, so "
            "the second worker would fail or hang; use one worker, or "
            "--device cpu for concurrent evaluations"
        )
    if holds_accelerator():
        raise AcceleratorWorkersError(
            "this process already holds the accelerator, so a worker "
            "process cannot open it; start the workers before any jax "
            "computation, or pass --device cpu"
        )


def enable_compile_cache() -> str:
    """Turn on jax's persistent compilation cache; returns its
    directory.  See the module docstring for where it lives."""
    from_env = os.environ.get(COMPILE_CACHE_ENV)
    if from_env:
        return from_env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_COMPILE_CACHE_DIR)
    return DEFAULT_COMPILE_CACHE_DIR
