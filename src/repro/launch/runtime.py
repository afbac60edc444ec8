"""Process-level runtime settings: XLA flag presets, allocator environment,
and the persistent compilation cache location.

This module is deliberately **jax-free**: XLA reads ``XLA_FLAGS`` once, at
first backend init, so every function here must be callable before ``import
jax`` anywhere in the process.  Entry points (``launch/dryrun.py``,
``launch/train.py`` wrappers, bench drivers) call
:func:`apply_runtime_preset` under their ``__main__`` guard; library imports
never mutate the environment.

Two rules distinguish this from the copy-pasted ``run.sh`` folklore it
replaces (SNIPPETS.md snippets 1-3):

1. **Compose, never clobber.**  Flags are appended to any pre-existing
   ``XLA_FLAGS``; a flag name the user already set wins and the preset's
   value for it is dropped.  (The old ``dryrun.py`` overwrote the whole
   variable at import time, silently erasing user/preset flags for anything
   that merely imported the module.)
2. **Declare, don't shell out.**  Settings that cannot take effect from
   inside a running process (``LD_PRELOAD`` for tcmalloc) are returned as
   advisory shell exports from :func:`shell_exports` instead of being set
   to no effect.
"""

from __future__ import annotations

import os
from pathlib import Path
from typing import Dict, List, Mapping, MutableMapping, Optional, Sequence, Tuple

# The repository checkout (src/repro/launch/runtime.py -> checkout root).
_CHECKOUT = Path(__file__).resolve().parents[3]

# ---------------------------------------------------------------------------
# presets
# ---------------------------------------------------------------------------

# Latency-hiding / async-collective schedule: lets XLA overlap the per-bucket
# reduce-scatters issued by train/step.py with backward compute instead of
# serializing them at step end.  Names follow the GPU backend (snippet 1) and
# are GPU-only: TPU enables the latency-hiding scheduler by default, and
# libtpu rejects flags it does not know, so no TPU entry point applies this
# preset (TPU-side flags belong in ``LIBTPU_INIT_ARGS``, which nothing here
# writes).
_OVERLAP_FLAGS: Tuple[str, ...] = (
    "--xla_gpu_enable_latency_hiding_scheduler=true",
    "--xla_gpu_enable_async_collectives=true",
    "--xla_gpu_enable_highest_priority_async_stream=true",
)

# Host-platform device farm for mesh dry-runs (snippets 2-3 use the same
# mechanism to emulate pods on CPU).
_DRYRUN_FLAGS: Tuple[str, ...] = (
    "--xla_force_host_platform_device_count=512",
)

# Allocator / logging hygiene for long-lived training processes
# (snippets 2-3): silence the huge-allocation warnings tcmalloc emits for
# multi-GB parameter buffers, and keep TF's C++ logging quiet.
_ALLOCATOR_ENV: Dict[str, str] = {
    "TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD": "60000000000",
    "TF_CPP_MIN_LOG_LEVEL": "3",
}

PRESETS: Dict[str, Dict[str, object]] = {
    # Production training: collective/compute overlap + allocator hygiene.
    "overlap": {"xla_flags": _OVERLAP_FLAGS, "env": _ALLOCATOR_ENV},
    # Compile-only multi-pod emulation on the host platform.
    "dryrun": {"xla_flags": _DRYRUN_FLAGS, "env": {"TF_CPP_MIN_LOG_LEVEL": "3"}},
}

# tcmalloc must be preloaded by the dynamic linker -- setting LD_PRELOAD from
# inside an already-running interpreter does nothing.  Surfaced as advisory
# shell exports only.
_SHELL_ONLY: Dict[str, str] = {
    "LD_PRELOAD": "/usr/lib/x86_64-linux-gnu/libtcmalloc.so.4",
}


def _flag_name(flag: str) -> str:
    """``--xla_foo=true`` -> ``--xla_foo`` (flags are keyed by name)."""
    return flag.split("=", 1)[0].strip()


def compose_xla_flags(existing: str, new_flags: Sequence[str]) -> str:
    """Append ``new_flags`` to an existing ``XLA_FLAGS`` string.

    Flags whose name already appears in ``existing`` are skipped -- the
    user's (or an earlier preset's) value wins.  Order of surviving flags is
    preserved: existing first, then additions in the given order.
    """
    have = {_flag_name(f) for f in existing.split() if f.strip()}
    added: List[str] = []
    for flag in new_flags:
        name = _flag_name(flag)
        if name in have:
            continue
        have.add(name)
        added.append(flag)
    parts = ([existing.strip()] if existing.strip() else []) + added
    return " ".join(parts)


def apply_runtime_preset(
    name: str, env: Optional[MutableMapping[str, str]] = None
) -> Mapping[str, str]:
    """Apply preset ``name`` to ``env`` (default ``os.environ``).

    Must run before jax is first imported in the process to affect
    ``XLA_FLAGS``.  Pre-existing ``XLA_FLAGS`` are composed with (appended
    to), never replaced; auxiliary env vars are only set when absent.
    Returns the mapping of keys actually written (useful for logging).
    """
    if name not in PRESETS:
        raise ValueError(f"unknown runtime preset {name!r}; have {sorted(PRESETS)}")
    if env is None:
        env = os.environ
    preset = PRESETS[name]
    written: Dict[str, str] = {}

    flags: Sequence[str] = preset.get("xla_flags", ())  # type: ignore[assignment]
    if flags:
        composed = compose_xla_flags(env.get("XLA_FLAGS", ""), flags)
        if composed != env.get("XLA_FLAGS", ""):
            env["XLA_FLAGS"] = composed
            written["XLA_FLAGS"] = composed

    extra: Mapping[str, str] = preset.get("env", {})  # type: ignore[assignment]
    for key, val in extra.items():
        if key not in env:  # user settings win
            env[key] = val
            written[key] = val
    return written


def shell_exports(name: str = "overlap") -> str:
    """Advisory ``export`` lines for settings a running process can't apply.

    Combine with :func:`apply_runtime_preset`: the launcher script sources
    these, the python entry point applies the rest.
    """
    lines = [f"export {k}={v}" for k, v in _SHELL_ONLY.items()]
    preset = PRESETS[name]
    for key, val in preset.get("env", {}).items():  # type: ignore[union-attr]
        lines.append(f"export {key}={val}")
    return "\n".join(lines)


def configure_compile_cache(
    env: Optional[MutableMapping[str, str]] = None,
) -> str:
    """Place JAX's persistent compilation cache; returns its directory.

    An existing ``JAX_COMPILATION_CACHE_DIR`` wins and nothing else is set.
    Otherwise the cache goes to ``<checkout>/.jax_cache`` -- a fixed path,
    because the path is part of what a later process must find again.  JAX
    reads the variable when it is first imported, so entry points call
    this before ``import jax``.
    """
    if env is None:
        env = os.environ
    if env.get("JAX_COMPILATION_CACHE_DIR"):
        return env["JAX_COMPILATION_CACHE_DIR"]
    path = str(_CHECKOUT / ".jax_cache")
    env["JAX_COMPILATION_CACHE_DIR"] = path
    return path
