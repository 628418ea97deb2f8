"""Regression suite for ``repro.runtime.device_config``.

The device layer is pure env/flag plumbing with two failure modes that
must stay loud: junk configuration (a campaign silently running
unsharded is the worst outcome, so every knob rejects bad values with
the variable named) and ordering violations (XLA reads ``XLA_FLAGS``
once at backend init — reconfiguring after that must warn, not
pretend).  The suite process has a live JAX backend (conftest forces
the >=4-way pool before anything imports jax), so the post-init paths
here are exercised against the real initialized state, and the
pre-init flag-rewriting paths via a monkeypatched ``jax_initialized``.
"""
import os
from pathlib import Path

import jax
import pytest

from repro.runtime import device_config as dc
from repro.runtime.device_config import (DEFAULT_COMPILE_CACHE,
                                         MAX_LOGICAL_DEVICES, _env_int,
                                         configure_compile_cache,
                                         configure_host_devices,
                                         default_device_count,
                                         jax_initialized,
                                         resolve_device_count,
                                         set_platform)


def _ensure_backend() -> None:
    """Force backend init (first touch uses conftest's >=4-way pool).

    The post-init tests below pin behavior against a *live* backend;
    depending on which test file runs first, this module may be the
    first to touch jax, so initialize explicitly."""
    jax.local_device_count()
    assert jax_initialized()


class TestEnvValidation:
    """REPRO_DEVICES (and _env_int generally) rejects junk loudly."""

    @pytest.mark.parametrize("bad", ["abc", "1.5", "0", "-2", "2x",
                                     str(MAX_LOGICAL_DEVICES + 1)])
    def test_junk_zero_and_oversubscribed_named(self, monkeypatch, bad):
        monkeypatch.setenv("REPRO_DEVICES", bad)
        with pytest.raises(ValueError, match="REPRO_DEVICES"):
            default_device_count()

    def test_valid_default_and_empty(self, monkeypatch):
        monkeypatch.setenv("REPRO_DEVICES", "3")
        assert default_device_count() == 3
        monkeypatch.delenv("REPRO_DEVICES")
        assert default_device_count() == 1
        monkeypatch.setenv("REPRO_DEVICES", "  ")   # blank = unset
        assert default_device_count() == 1

    def test_env_int_bounds_name_the_variable(self, monkeypatch):
        monkeypatch.setenv("SOME_KNOB", "9")
        with pytest.raises(ValueError, match="SOME_KNOB"):
            _env_int("SOME_KNOB", 1, minimum=1, maximum=8)
        monkeypatch.setenv("SOME_KNOB", "2")
        assert _env_int("SOME_KNOB", 1, minimum=1, maximum=8) == 2


class TestConfigureHostDevices:
    def test_out_of_range_rejected(self):
        with pytest.raises(ValueError, match="out of range"):
            configure_host_devices(0)
        with pytest.raises(ValueError, match="out of range"):
            configure_host_devices(MAX_LOGICAL_DEVICES + 1)

    def test_post_init_warns_and_changes_nothing(self, monkeypatch):
        """With the backend live, a reconfiguration attempt must warn
        loudly and leave XLA_FLAGS untouched."""
        _ensure_backend()
        monkeypatch.setenv("XLA_FLAGS", "--sentinel=1")
        with pytest.warns(RuntimeWarning,
                          match="after JAX backend initialization"):
            got = configure_host_devices(8)
        assert got == 8                      # request echoed back
        assert os.environ["XLA_FLAGS"] == "--sentinel=1"

    def test_pre_init_replaces_only_the_device_flag(self, monkeypatch):
        """Flag rewrite (pre-init path, initialization stubbed out):
        an existing device-count flag is replaced in place, unrelated
        flags survive."""
        monkeypatch.setattr(dc, "jax_initialized", lambda: False)
        monkeypatch.setenv(
            "XLA_FLAGS",
            "--foo=1 --xla_force_host_platform_device_count=2 --bar=x")
        assert configure_host_devices(8) == 8
        flags = os.environ["XLA_FLAGS"].split()
        assert "--xla_force_host_platform_device_count=8" in flags
        assert "--xla_force_host_platform_device_count=2" not in flags
        assert "--foo=1" in flags and "--bar=x" in flags

    def test_reads_repro_devices_when_unspecified(self, monkeypatch):
        monkeypatch.setattr(dc, "jax_initialized", lambda: False)
        monkeypatch.setenv("REPRO_DEVICES", "6")
        monkeypatch.setenv("XLA_FLAGS", "")
        assert configure_host_devices() == 6
        assert ("--xla_force_host_platform_device_count=6"
                in os.environ["XLA_FLAGS"])


class TestSetPlatform:
    def test_unknown_platform_rejected(self):
        with pytest.raises(ValueError, match="not in"):
            set_platform("quantum")

    def test_gpu_sets_flags_without_a_gpu_present(self, monkeypatch):
        """The single-flag CPU->GPU route: selecting the gpu platform
        writes the dispatch-latency XLA flags and the platform env var
        even on a host with no GPU (JAX validates at backend init, not
        here).  Post-init it additionally warns — exercised that way
        here because flipping a live process's platform config would
        poison every later jax call in the suite."""
        _ensure_backend()
        monkeypatch.setenv("XLA_FLAGS", "--keep=me")
        monkeypatch.delenv("JAX_PLATFORM_NAME", raising=False)
        with pytest.warns(RuntimeWarning,
                          match="after JAX backend initialization"):
            set_platform("gpu")
        flags = os.environ["XLA_FLAGS"]
        assert "--keep=me" in flags
        for f in dc._GPU_XLA_FLAGS.split():
            assert f in flags
        assert os.environ["JAX_PLATFORM_NAME"] == "gpu"

    def test_gpu_flags_idempotent(self, monkeypatch):
        _ensure_backend()
        monkeypatch.setenv("XLA_FLAGS", "")
        monkeypatch.delenv("JAX_PLATFORM_NAME", raising=False)
        with pytest.warns(RuntimeWarning):
            set_platform("gpu")
        once = os.environ["XLA_FLAGS"]
        with pytest.warns(RuntimeWarning):
            set_platform("gpu")
        assert os.environ["XLA_FLAGS"] == once   # no duplicate flags


class TestResolveDeviceCount:
    def test_single_device_never_touches_jax(self, monkeypatch):
        # want == 1 short-circuits before any backend query
        monkeypatch.setattr(dc, "jax_initialized",
                            lambda: pytest.fail("queried backend"))
        assert resolve_device_count(1) == 1

    @pytest.mark.parametrize("bad", [0, -1, MAX_LOGICAL_DEVICES + 1])
    def test_out_of_range_rejected(self, bad):
        with pytest.raises(ValueError, match="out of range"):
            resolve_device_count(bad)

    def test_within_pool_resolves_exactly(self):
        # conftest forces a >=4-way pool before jax initializes
        _ensure_backend()
        assert jax.local_device_count() >= 4
        assert resolve_device_count(4) == 4
        assert resolve_device_count(2) == 2

    def test_oversized_request_clamps_with_loud_warning(self):
        _ensure_backend()
        have = jax.local_device_count()
        want = min(have + 1, MAX_LOGICAL_DEVICES)
        if want <= have:                      # pragma: no cover
            pytest.skip("pool already at the maximum")
        with pytest.warns(RuntimeWarning, match=f"running on {have}"):
            assert resolve_device_count(want) == have

    def test_oversized_request_on_an_accelerator_raises(self, monkeypatch):
        # chips are not clamped: a run must not measure fewer than it names
        _ensure_backend()
        have = jax.local_device_count()
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        with pytest.raises(ValueError, match=f"has {have} tpu device"):
            resolve_device_count(have + 1)
        assert resolve_device_count(have) == have

    def test_none_reads_env_default(self, monkeypatch):
        # post-init on purpose: pre-init this would legitimately
        # re-force the pool, shrinking it for the rest of the suite
        _ensure_backend()
        monkeypatch.setenv("REPRO_DEVICES", "3")
        assert resolve_device_count(None) == 3
        monkeypatch.delenv("REPRO_DEVICES")
        assert resolve_device_count(None) == 1


class TestCompileCache:
    @pytest.fixture
    def restore_cache_dir(self):
        prev = jax.config.jax_compilation_cache_dir
        yield
        jax.config.update("jax_compilation_cache_dir", prev)

    def test_env_variable_is_left_to_jax(self, monkeypatch, tmp_path,
                                         restore_cache_dir):
        before = jax.config.jax_compilation_cache_dir
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert configure_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before

    def test_blank_variable_is_rejected(self, monkeypatch):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", " ")
        with pytest.raises(ValueError, match="set but blank"):
            configure_compile_cache()

    def test_default_is_the_fixed_ignored_repo_path(self, monkeypatch,
                                                    restore_cache_dir):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        root = Path(__file__).resolve().parents[1]
        assert DEFAULT_COMPILE_CACHE == root / ".jax_cache"
        assert configure_compile_cache() == str(DEFAULT_COMPILE_CACHE)
        assert jax.config.jax_compilation_cache_dir == \
            str(DEFAULT_COMPILE_CACHE)
        assert ".jax_cache/" in \
            (root / ".gitignore").read_text().splitlines()
