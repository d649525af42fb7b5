"""util/backend.py + util/xla_cache.py: the one accelerator decision,
the no-fallback rule of the measurement entry points, and where the
compile cache lives."""

import os

import pytest

from tempo_tpu.util import backend, xla_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestBackend:
    def test_tier1_resolves_cpu_and_turns_device_arms_off(self):
        assert backend.platform() == "cpu"
        assert not backend.on_accelerator()
        d = backend.describe()
        assert d["platform"] == "cpu" and d["pallas"] == "interpret"
        assert d["device_count"] == len(d["devices"]) == 8  # conftest's mesh
        assert isinstance(d["native_codec"], bool)
        assert d["default_codec"] in ("zstd_shuffle", "zlib")

    def test_check_measurable(self):
        backend.check_measurable("tpu", cpu_ok=False)
        backend.check_measurable("cpu", cpu_ok=True)
        with pytest.raises(backend.NoAccelerator, match="'cpu'"):
            backend.check_measurable("cpu", cpu_ok=False)
        with pytest.raises(backend.NoAccelerator, match="'gpu'"):
            backend.check_measurable("gpu", cpu_ok=True)


class TestCompileCache:
    @pytest.fixture()
    def updates(self, monkeypatch):
        import jax

        calls = {}
        monkeypatch.setattr(xla_cache, "_done", False)
        monkeypatch.setattr(jax.config, "update", lambda k, v: calls.__setitem__(k, v))
        monkeypatch.delenv("TEMPO_TPU_XLA_CACHE", raising=False)
        return calls

    def test_default_dir_is_inside_the_checkout(self):
        assert xla_cache.default_cache_dir() == os.path.join(REPO, ".jax_cache")

    def test_env_dir_wins_and_no_dir_is_set_in_code(self, updates, monkeypatch, tmp_path):
        monkeypatch.setattr(backend, "platform", lambda: "tpu")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "elsewhere"))
        xla_cache.ensure_persistent_cache()
        assert "jax_compilation_cache_dir" not in updates
        assert updates == {"jax_persistent_cache_min_compile_time_secs": 0.0,
                           "jax_persistent_cache_min_entry_size_bytes": 0}

    def test_unset_env_uses_the_checkout_dir(self, updates, monkeypatch, tmp_path):
        monkeypatch.setattr(backend, "platform", lambda: "tpu")
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        monkeypatch.setattr(xla_cache, "default_cache_dir", lambda: str(tmp_path / ".jax_cache"))
        xla_cache.ensure_persistent_cache()
        assert updates["jax_compilation_cache_dir"] == str(tmp_path / ".jax_cache")
        assert os.path.isdir(tmp_path / ".jax_cache")
        assert updates["jax_persistent_cache_min_compile_time_secs"] == 0.0

    def test_cpu_backend_arms_nothing(self, updates, monkeypatch):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        xla_cache.ensure_persistent_cache()  # platform() is really cpu here
        assert updates == {}
