"""launch.xla: XLA_FLAGS composition — append, never clobber (the
launch/dryrun.py fix).  The pure string function carries the contract;
``append_xla_flags`` is pinned against a monkeypatched environment so the
dryrun's device-count override provably survives user-set overlap flags."""
import os

import pytest

from repro.launch.xla import (
    CHECKOUT,
    OVERLAP_FLAGS,
    append_xla_flags,
    compose_xla_flags,
    enable_collective_overlap,
    use_compile_cache,
)

USER = "--xla_gpu_enable_latency_hiding_scheduler=true --xla_dump_to=/tmp/d"


def test_compose_preserves_user_flags_in_order():
    out = compose_xla_flags(["--xla_force_host_platform_device_count=512"],
                            current=USER)
    assert out.split() == USER.split() + [
        "--xla_force_host_platform_device_count=512"]


def test_compose_drop_prefixes_replaces_owned_knob():
    """The dryrun owns the device-count knob: a stale value is dropped, the
    user's other flags survive untouched."""
    current = "--xla_force_host_platform_device_count=8 " + USER
    out = compose_xla_flags(["--xla_force_host_platform_device_count=512"],
                            current=current,
                            drop_prefixes=(
                                "--xla_force_host_platform_device_count",))
    assert out.split() == USER.split() + [
        "--xla_force_host_platform_device_count=512"]


def test_compose_dedupes_verbatim_and_handles_empty():
    assert compose_xla_flags(list(OVERLAP_FLAGS), current=USER).split() == \
        USER.split() + [f for f in OVERLAP_FLAGS if f not in USER.split()]
    assert compose_xla_flags(["--a=1"], current="") == "--a=1"
    assert compose_xla_flags([], current=USER) == USER


def test_append_composes_into_environment(monkeypatch):
    monkeypatch.setenv("XLA_FLAGS", USER)
    got = append_xla_flags(["--xla_force_host_platform_device_count=512"],
                           drop_prefixes=(
                               "--xla_force_host_platform_device_count",))
    assert os.environ["XLA_FLAGS"] == got
    assert got.startswith(USER)                       # user flags kept
    assert "--xla_force_host_platform_device_count=512" in got.split()


def test_append_from_unset_environment(monkeypatch):
    monkeypatch.delenv("XLA_FLAGS", raising=False)
    assert append_xla_flags(["--a=1"]) == "--a=1"
    assert os.environ["XLA_FLAGS"] == "--a=1"


def test_enable_collective_overlap_idempotent(monkeypatch):
    monkeypatch.setenv("XLA_FLAGS", "--xla_dump_to=/tmp/d")
    first = enable_collective_overlap()
    assert set(OVERLAP_FLAGS) <= set(first.split())
    assert "--xla_dump_to=/tmp/d" in first.split()
    assert enable_collective_overlap() == first       # no duplication


def test_dryrun_composes_instead_of_clobbering(monkeypatch):
    """The regression this PR fixes: importing launch.dryrun used to
    overwrite XLA_FLAGS wholesale; it must now preserve user flags while
    owning only the device-count knob."""
    import importlib
    import sys

    monkeypatch.setenv("XLA_FLAGS",
                       "--xla_force_host_platform_device_count=8 " + USER)
    monkeypatch.setenv("REPRO_DRYRUN_DEVICES", "16")
    # re-execute only the module-level env mutation; restore afterwards so
    # the already-imported jax backend state stays untouched elsewhere
    sys.modules.pop("repro.launch.dryrun_flags", None)
    importlib.import_module("repro.launch.dryrun_flags")
    flags = os.environ["XLA_FLAGS"].split()
    assert "--xla_force_host_platform_device_count=16" in flags
    assert "--xla_force_host_platform_device_count=8" not in flags
    for f in USER.split():
        assert f in flags


@pytest.fixture
def cache_dir_config():
    """Restore jax's compile-cache directory after a test changes it."""
    import jax
    saved = jax.config.jax_compilation_cache_dir
    yield jax.config
    jax.config.update("jax_compilation_cache_dir", saved)


def test_compile_cache_env_wins(monkeypatch, tmp_path, cache_dir_config):
    """JAX reads JAX_COMPILATION_CACHE_DIR itself: the helper sets nothing."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = cache_dir_config.jax_compilation_cache_dir
    assert use_compile_cache() == str(tmp_path)
    assert cache_dir_config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_ignored_checkout_dir(monkeypatch,
                                                        cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = use_compile_cache()
    assert path == str(CHECKOUT / ".jax_cache")
    assert cache_dir_config.jax_compilation_cache_dir == path
    assert "/.jax_cache/" in (CHECKOUT / ".gitignore").read_text().split()
