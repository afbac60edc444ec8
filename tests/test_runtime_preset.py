"""launch/runtime.py: process-level XLA/allocator presets (ISSUE 7).

The module must be jax-free and compose-never-clobber: pre-existing
``XLA_FLAGS`` survive preset application (a user-set flag name wins over
the preset's value), auxiliary env vars are only written when absent, and
merely importing ``repro.launch.dryrun`` must not touch ``os.environ``
(the old import-time clobber this preset module replaces).
"""
import os
import subprocess
import sys

import pytest

from repro.launch.runtime import (
    PRESETS,
    apply_runtime_preset,
    compose_xla_flags,
    shell_exports,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_compose_appends_and_preserves_user_flags():
    out = compose_xla_flags(
        "--xla_force_host_platform_device_count=8",
        ("--xla_gpu_enable_async_collectives=true",),
    )
    assert out == (
        "--xla_force_host_platform_device_count=8 "
        "--xla_gpu_enable_async_collectives=true"
    )


def test_compose_user_value_wins_on_name_collision():
    # same flag NAME, different value: the existing setting is kept and the
    # preset's value is dropped (never duplicated, never overwritten)
    out = compose_xla_flags(
        "--xla_gpu_enable_async_collectives=false",
        ("--xla_gpu_enable_async_collectives=true", "--xla_new_flag=1"),
    )
    assert out == "--xla_gpu_enable_async_collectives=false --xla_new_flag=1"


def test_compose_from_empty():
    assert compose_xla_flags("", ("--a=1", "--b=2")) == "--a=1 --b=2"
    assert compose_xla_flags("   ", ("--a=1",)) == "--a=1"


def test_apply_preset_composes_with_preexisting_flags():
    env = {"XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    written = apply_runtime_preset("overlap", env=env)
    flags = env["XLA_FLAGS"].split()
    # the user's flag survives, in first position
    assert flags[0] == "--xla_force_host_platform_device_count=4"
    for f in PRESETS["overlap"]["xla_flags"]:
        assert f in flags
    assert written["XLA_FLAGS"] == env["XLA_FLAGS"]
    # allocator hygiene set only where absent
    assert env["TF_CPP_MIN_LOG_LEVEL"] == "3"


def test_apply_preset_never_overwrites_user_env():
    env = {"TF_CPP_MIN_LOG_LEVEL": "0"}
    written = apply_runtime_preset("overlap", env=env)
    assert env["TF_CPP_MIN_LOG_LEVEL"] == "0"  # user setting wins
    assert "TF_CPP_MIN_LOG_LEVEL" not in written


def test_apply_preset_is_idempotent():
    env = {}
    apply_runtime_preset("dryrun", env=env)
    once = dict(env)
    written = apply_runtime_preset("dryrun", env=env)
    assert dict(env) == once
    assert written == {}  # nothing new to write


def test_unknown_preset_rejected():
    with pytest.raises(ValueError, match="unknown runtime preset"):
        apply_runtime_preset("warp", env={})


def test_shell_exports_cover_preload_only_settings():
    text = shell_exports("overlap")
    assert "export LD_PRELOAD=" in text  # cannot be applied in-process
    assert "TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD" in text


def test_importing_dryrun_does_not_mutate_environ():
    """The satellite fix: the old dryrun.py overwrote XLA_FLAGS at IMPORT
    time, silently erasing user flags for anything that imported it.  Now
    the preset applies only under the __main__ guard."""
    code = (
        "import os\n"
        "os.environ['XLA_FLAGS'] = '--xla_foo=1'\n"
        "before = dict(os.environ)\n"
        "import repro.launch.dryrun\n"
        "import repro.launch.runtime\n"
        "assert dict(os.environ) == before, 'import mutated os.environ'\n"
        "assert os.environ['XLA_FLAGS'] == '--xla_foo=1'\n"
        "print('OK')\n"
    )
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=300, env=env,
    )
    assert out.returncode == 0, out.stderr
    assert "OK" in out.stdout


def test_compile_cache_defaults_to_fixed_checkout_path():
    from repro.launch.runtime import configure_compile_cache

    env = {}
    path = configure_compile_cache(env)
    assert path == os.path.join(REPO, ".jax_cache")
    assert env == {"JAX_COMPILATION_CACHE_DIR": path}
    assert configure_compile_cache({}) == path  # same path every call


def test_compile_cache_respects_existing_setting():
    from repro.launch.runtime import configure_compile_cache

    env = {"JAX_COMPILATION_CACHE_DIR": "/elsewhere/cache"}
    assert configure_compile_cache(env) == "/elsewhere/cache"
    assert env == {"JAX_COMPILATION_CACHE_DIR": "/elsewhere/cache"}
