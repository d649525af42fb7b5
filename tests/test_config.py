"""Config-tree tests (reference: cmd/tempo/app config loading,
envsubst in main.go, CheckConfig warnings)."""

import subprocess
import sys

import pytest

from tempo_tpu.config import (
    Config,
    ConfigError,
    check_config,
    expand_env,
    load_config,
    parse_config,
)

FULL_YAML = """
target: all
multitenancy_enabled: true
server:
  http_listen_port: 3201
  log_level: warn
storage:
  trace:
    backend: s3
    backend_options:
      bucket: tempo-blocks
      endpoint: ${S3_ENDPOINT:http://localhost:9000}
      access_key: ${S3_ACCESS_KEY}
      secret_key: sk
    cache: memory
    block:
      bloom_fp: 0.02
      row_group_spans: 4096
    compaction:
      window_s: 1800
ingester:
  max_trace_idle_s: 5.0
  concurrent_flushes: 2
query_frontend:
  query_shards: 8
distributor:
  forwarders:
    - name: mirror
      endpoint: http://collector:4318
overrides:
  per_tenant_override_config: /etc/overrides.yaml
  defaults:
    max_traces_per_user: 500
    forwarders: [mirror]
metrics_generator:
  enabled: true
  remote_write:
    endpoint: http://prometheus:9090
usage_report:
  enabled: false
replication_factor: 1
n_ingesters: 2
"""


class TestEnvExpansion:
    def test_var_and_default(self):
        env = {"A": "x"}
        assert expand_env("${A} ${B:fallback} ${C}", env) == "x fallback "


class TestParse:
    def test_full_yaml(self):
        cfg = parse_config(FULL_YAML, env={"S3_ACCESS_KEY": "ak"})
        assert cfg.target == "all"
        assert cfg.server.http_listen_port == 3201
        a = cfg.app
        assert a.multitenancy_enabled
        assert a.db.backend == "s3"
        assert a.db.backend_options["endpoint"] == "http://localhost:9000"  # env default
        assert a.db.backend_options["access_key"] == "ak"  # env substituted
        assert a.db.cache == "memory"
        assert a.db.block.bloom_fp == 0.02
        assert a.db.compaction.window_s == 1800
        assert a.ingester.max_trace_idle_s == 5.0
        assert a.frontend.query_shards == 8
        assert len(a.forwarders) == 1 and a.forwarders[0].name == "mirror"
        assert a.overrides_path == "/etc/overrides.yaml"
        assert a.limits.max_traces_per_user == 500
        assert a.limits.forwarders == ("mirror",)  # list -> tuple coercion
        assert a.remote_write.endpoint == "http://prometheus:9090"
        assert a.n_ingesters == 2

    def test_empty_config_is_defaults(self):
        cfg = parse_config("")
        assert cfg.target == "all" and cfg.app.db.backend == "local"

    def test_unknown_key_rejected(self):
        with pytest.raises(ConfigError, match="typo_key"):
            parse_config("ingester:\n  typo_key: 1\n")
        with pytest.raises(ConfigError, match="unknown top-level"):
            parse_config("no_such_section: {}\n")
        with pytest.raises(ConfigError, match="storage.trace.block"):
            parse_config("storage:\n  trace:\n    block:\n      nope: 1\n")

    def test_load_from_file(self, tmp_path):
        p = tmp_path / "tempo.yaml"
        p.write_text("server:\n  http_listen_port: 9999\n")
        assert load_config(str(p)).server.http_listen_port == 9999


class TestCheckConfig:
    def test_warns_on_footguns(self):
        cfg = parse_config(FULL_YAML, env={})
        cfg.app.replication_factor = 3  # > n_ingesters
        cfg.app.db.cache = "none"  # cloud without cache
        warnings = check_config(cfg)
        assert any("quorum" in w for w in warnings)
        assert any("object-store round trip" in w for w in warnings)

    def test_warns_when_retention_clears_a_compacted_block_a_find_still_reads(self):
        cfg = Config()
        cfg.app.db.compaction.compacted_retention_s = 2 * cfg.app.db.blocklist_poll_s - 1
        assert any("compacted_retention_s" in w for w in check_config(cfg))

    def test_clean_config_has_no_warnings(self):
        assert check_config(Config()) == []


class TestMainEntrypoint:
    def test_config_verify_exits_zero(self, tmp_path):
        p = tmp_path / "tempo.yaml"
        p.write_text("server:\n  http_listen_port: 0\n")
        out = subprocess.run(
            [sys.executable, "-m", "tempo_tpu", "-config.file", str(p), "-config.verify"],
            capture_output=True,
            text=True,
            timeout=120,
        )
        assert out.returncode == 0, out.stderr
        assert "config ok" in out.stdout

    def test_bad_config_fails(self, tmp_path):
        p = tmp_path / "tempo.yaml"
        p.write_text("bogus_section: 1\n")
        out = subprocess.run(
            [sys.executable, "-m", "tempo_tpu", "-config.file", str(p), "-config.verify"],
            capture_output=True,
            text=True,
            timeout=120,
        )
        assert out.returncode != 0
