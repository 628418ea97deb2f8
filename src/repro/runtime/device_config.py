"""Host/device environment configuration for the device paths.

The sharded jit dispatcher (``core.simulator_jit``) spreads
independent simulation points over a 1-D mesh of devices.  On a TPU
host those are the chips themselves: a ``--devices N`` request larger
than the live TPU pool is an error (:func:`resolve_device_count`).  On
the CPU backend, which exposes one device per process by default, the
devices are *logical* host devices carved out of the same CPU via
``--xla_force_host_platform_device_count``: each runs its own copy of
the compiled lockstep ``while_loop`` on a shard of the point axis, so
XLA:CPU's per-kernel dispatch queues proceed in parallel.  That is how
the test suite exercises the sharded path without a chip.

:func:`configure_compile_cache` places JAX's persistent compilation
cache; every entry point that compiles for the device calls it first.

Everything here is env/flag plumbing and therefore importable without
JAX (JAX is only touched lazily, to ask whether its backends are
already initialized): the experiments/spec layer uses the validation
helpers without dragging in a backend.

Ordering contract: XLA reads ``XLA_FLAGS`` **once**, when the first
backend initializes (the first ``jax.devices()``/array op).  Both
:func:`configure_host_devices` and :func:`set_platform` therefore warn
loudly — and change nothing about the running process — when called
after that point.  Call them first thing in ``main()``, or set
``REPRO_DEVICES`` in the environment and let the engine do it.
"""
from __future__ import annotations

import os
import re
import sys
import warnings
from pathlib import Path
from typing import Optional

# logical host devices are threads multiplexed onto the same silicon:
# past any plausible host core count the forced device pool only adds
# scheduler pressure, so treat absurd requests as misconfiguration
# rather than oversubscribing quietly
MAX_LOGICAL_DEVICES = 256

_GPU_XLA_FLAGS = (
    "--xla_gpu_enable_triton_softmax_fusion=true "
    "--xla_gpu_triton_gemm_any=True "
    "--xla_gpu_enable_async_collectives=true "
    "--xla_gpu_enable_latency_hiding_scheduler=true "
    "--xla_gpu_enable_highest_priority_async_stream=true")

_DEVICE_COUNT_FLAG = "--xla_force_host_platform_device_count"

# the compile cache's home when JAX_COMPILATION_CACHE_DIR is unset: a
# fixed path (the directory is part of the cache key, so one that moved
# per run would never hit), git-ignored
DEFAULT_COMPILE_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def _env_int(name: str, default: int, minimum: int = 1,
             maximum: Optional[int] = None) -> int:
    """Read an integer env override, rejecting junk loudly.

    Misconfigured performance knobs must fail at startup with the
    variable named, never silently fall back to a default (a campaign
    quietly running unsharded is the worst failure mode).
    """
    raw = os.environ.get(name)
    if raw is None or not raw.strip():
        return default
    try:
        val = int(raw)
    except ValueError:
        raise ValueError(
            f"{name}={raw!r} is not an integer; set {name} to an "
            f"integer >= {minimum} or unset it") from None
    if val < minimum:
        raise ValueError(
            f"{name}={raw!r} must be >= {minimum}; fix or unset {name}")
    if maximum is not None and val > maximum:
        raise ValueError(
            f"{name}={raw!r} exceeds the maximum of {maximum} logical "
            f"devices; fix or unset {name}")
    return val


def _env_path(name: str, default=None) -> Optional[Path]:
    """Read a directory-path env override, rejecting junk loudly:
    ``default`` (as a ``Path``, or ``None``) when unset.

    A blank-but-set variable almost always means a broken launch
    script; failing at startup beats silently writing into ``.``.
    """
    raw = os.environ.get(name)
    if raw is None:
        return None if default is None else Path(default)
    if not raw.strip():
        raise ValueError(
            f"{name} is set but blank; set a directory path or unset it")
    return Path(raw)


def configure_compile_cache() -> str:
    """Place JAX's persistent compilation cache; returns its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX reads the variable
    itself and nothing is set here.  Otherwise the cache goes to
    :data:`DEFAULT_COMPILE_CACHE` (``<repo>/.jax_cache``).  Call it
    before the first compilation.
    """
    env = _env_path("JAX_COMPILATION_CACHE_DIR")
    if env is not None:
        return str(env)
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      str(DEFAULT_COMPILE_CACHE))
    return str(DEFAULT_COMPILE_CACHE)


def default_device_count() -> int:
    """Logical device count requested via ``REPRO_DEVICES`` (default 1).

    Junk, zero/negative, and oversubscribed (> ``MAX_LOGICAL_DEVICES``)
    values raise ``ValueError`` naming the variable.
    """
    return _env_int("REPRO_DEVICES", 1, minimum=1,
                    maximum=MAX_LOGICAL_DEVICES)


def jax_initialized() -> bool:
    """True once any XLA backend is live (XLA_FLAGS no longer read)."""
    mod = sys.modules.get("jax")
    if mod is None:
        return False
    try:
        from jax._src import xla_bridge
        return xla_bridge.backends_are_initialized()
    except (ImportError, AttributeError) as e:  # pragma: no cover
        # jax-internal API drift: assume live so callers warn rather
        # than claim a reconfiguration that cannot take effect
        warnings.warn(
            f"cannot query JAX backend state ({type(e).__name__}: {e}); "
            "assuming a backend is already initialized — device/platform "
            "reconfiguration is skipped for this process",
            RuntimeWarning, stacklevel=2)
        return True


def _warn_if_initialized(what: str) -> bool:
    if jax_initialized():
        warnings.warn(
            f"{what} called after JAX backend initialization — XLA has "
            "already read XLA_FLAGS and the device pool/platform cannot "
            "change for this process.  Call it before the first jax "
            "operation (or set REPRO_DEVICES in the environment before "
            "launch).", RuntimeWarning, stacklevel=3)
        return True
    return False


def configure_host_devices(n: Optional[int] = None) -> int:
    """Force ``n`` logical host (CPU) devices via ``XLA_FLAGS``.

    ``n=None`` reads ``REPRO_DEVICES`` (validated).  Replaces any
    existing ``--xla_force_host_platform_device_count`` flag, preserving
    unrelated flags.  Must run before JAX backend initialization; after
    it, warns loudly and leaves the process untouched.  Returns the
    count requested.
    """
    if n is None:
        n = default_device_count()
    n = int(n)
    if n < 1 or n > MAX_LOGICAL_DEVICES:
        raise ValueError(
            f"device count {n} out of range [1, {MAX_LOGICAL_DEVICES}] "
            "(REPRO_DEVICES semantics)")
    if _warn_if_initialized("configure_host_devices"):
        return n
    flags = os.environ.get("XLA_FLAGS", "")
    flags = re.sub(rf"{_DEVICE_COUNT_FLAG}=\S+", "", flags).strip()
    os.environ["XLA_FLAGS"] = \
        (flags + f" {_DEVICE_COUNT_FLAG}={n}").strip()
    return n


def set_platform(platform: str = "cpu") -> None:
    """Select the JAX platform (``cpu`` / ``gpu`` / ``tpu``).

    The GPU path additionally sets the XLA flags that matter for
    latency-bound dispatch (async collectives, latency-hiding
    scheduler, triton fusion) — the single-flag route from a CPU
    campaign to a GPU one.  Only env/config state is written; no
    accelerator needs to be present at call time (JAX validates the
    platform at backend init).  After JAX initialization this warns
    loudly and changes nothing.
    """
    if platform not in ("cpu", "gpu", "tpu"):
        raise ValueError(
            f"platform {platform!r} not in ('cpu', 'gpu', 'tpu')")
    if platform == "gpu":
        flags = os.environ.get("XLA_FLAGS", "")
        missing = " ".join(f for f in _GPU_XLA_FLAGS.split()
                           if f not in flags)
        if missing:
            os.environ["XLA_FLAGS"] = (flags + " " + missing).strip()
    os.environ["JAX_PLATFORM_NAME"] = platform
    if _warn_if_initialized("set_platform"):
        return
    try:
        import jax
    except ImportError:  # env vars above still steer a later init
        return
    jax.config.update("jax_platform_name", platform)


def resolve_device_count(requested: Optional[int] = None) -> int:
    """Devices the sharded dispatcher may actually use, right now.

    ``requested=None`` means the ``REPRO_DEVICES`` default.  For counts
    above 1 this forces the logical-device flag when the backend is not
    yet live.  On an accelerator a request beyond the live pool raises
    ``ValueError``: the run would otherwise measure fewer chips than it
    names.  On the CPU backend the count is clamped to the logical
    devices that exist, with a loud warning — results are bit-identical
    at any device count, so there clamping is a performance event, not
    a correctness one.
    """
    want = default_device_count() if requested is None else int(requested)
    if want < 1 or want > MAX_LOGICAL_DEVICES:
        raise ValueError(
            f"devices={want} out of range [1, {MAX_LOGICAL_DEVICES}]")
    if want == 1:
        return 1
    if not jax_initialized():
        # force at least the env default so an explicit small request
        # does not lock a later REPRO_DEVICES-sized one out of the pool
        configure_host_devices(max(want, default_device_count()))
    import jax
    have = jax.local_device_count()
    if have < want and jax.default_backend() != "cpu":
        raise ValueError(
            f"devices={want} requested but this host has {have} "
            f"{jax.default_backend()} device(s)")
    if have < want:
        warnings.warn(
            f"requested {want} logical devices but this process has "
            f"{have} (JAX initialized before the device pool was "
            f"forced?) — running on {have}.  Set REPRO_DEVICES or call "
            "configure_host_devices() before the first jax operation.",
            RuntimeWarning, stacklevel=2)
        return have
    return want


def jit_platform(devices: Optional[int] = None) -> str:
    """The platform the jit engine runs ``devices`` devices on in this
    process (``cpu``, ``tpu``, ...).  Sizes the device pool first, as
    the engine would, so asking never locks a smaller pool in."""
    resolve_device_count(devices)
    import jax
    return jax.default_backend()
