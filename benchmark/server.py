"""The system under test: ONE `python -m tempo_tpu -target=all` process,
the only process of a run that touches JAX and so the only one that
holds the chip. Copied from chip_smoke.py's `Child`.
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.parse
import urllib.request

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class BenchFailure(Exception):
    pass


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _fill(node, workdir: str, port: int):
    """The configuration's server settings with {dir} and {port} filled in."""
    if isinstance(node, dict):
        return {k: _fill(v, workdir, port) for k, v in node.items()}
    if node == "{port}":
        return port
    if isinstance(node, str):
        return node.replace("{dir}", workdir)
    return node


def merge(base: dict, overlay: dict) -> dict:
    """`overlay` laid over `base`, group by group."""
    out = dict(base)
    for k, v in overlay.items():
        out[k] = merge(out[k], v) if isinstance(v, dict) and isinstance(out.get(k), dict) else v
    return out


class Child:
    def __init__(self, workdir: str, server_config: dict, cpu_dry_run: bool):
        self.port = free_port()
        self.url = f"http://127.0.0.1:{self.port}"
        cfg = os.path.join(workdir, "tempo.yaml")
        doc = _fill(server_config, workdir, self.port)
        with open(cfg, "w") as f:
            json.dump(doc, f, indent=1)  # JSON is YAML
        self.log_path = os.path.join(workdir, "server.log")
        self._log = open(self.log_path, "a")  # appended to, so that nothing written is written over
        env = dict(os.environ)  # passed through untouched: no platform pin
        if cpu_dry_run:
            env["JAX_PLATFORMS"] = "cpu"  # the one explicit opt-in
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "tempo_tpu", "-target=all", f"-config.file={cfg}"],
            stdout=self._log, stderr=subprocess.STDOUT, env=env, cwd=ROOT,
        )

    def request(self, method: str, path: str, body: bytes | None = None,
                headers: dict | None = None, timeout: float = 600.0):
        req = urllib.request.Request(self.url + path, data=body, method=method,
                                     headers=headers or {})
        try:
            with urllib.request.urlopen(req, timeout=timeout) as r:
                return r.status, r.read()
        except urllib.error.HTTPError as e:
            return e.code, e.read()

    def get_json(self, path: str, params: dict | None = None, headers: dict | None = None,
                 timeout: float = 600.0):
        if params:
            path += "?" + urllib.parse.urlencode(params)
        status, body = self.request("GET", path, headers=headers, timeout=timeout)
        if status != 200:
            raise BenchFailure(f"GET {path} -> {status}: {body[:300]!r}")
        return json.loads(body)

    def wait_ready(self, timeout: float) -> None:
        deadline = time.time() + timeout
        while time.time() < deadline:
            if self.proc.poll() is not None:
                raise BenchFailure(f"server exited rc={self.proc.returncode} before /ready:\n"
                                   + self.log_tail())
            try:
                if self.request("GET", "/ready", timeout=2)[0] == 200:
                    return
            except (urllib.error.URLError, OSError):
                pass
            time.sleep(0.25)
        raise BenchFailure(f"server not ready after {timeout:.0f}s:\n" + self.log_tail())

    def metrics(self) -> dict:
        """{'name{labels}': value} of the child's /metrics."""
        out = {}
        for line in self.request("GET", "/metrics")[1].decode().splitlines():
            if line and not line.startswith("#"):
                key, _, val = line.rpartition(" ")
                out[key] = float(val)
        return out

    def log_tail(self, n: int = 40) -> str:
        with open(self.log_path, errors="replace") as f:
            return "".join(f.readlines()[-n:])

    def log_errors(self) -> list:
        """The log's lines at ERROR or CRITICAL, and the heads of exceptions no one
        caught (a traceback follows its line and is not counted again)."""
        with open(self.log_path, errors="replace") as f:
            return [ln.rstrip() for ln in f if " ERROR " in ln or " CRITICAL " in ln
                    or ln.startswith("Exception in thread")]

    def shutdown(self, timeout: float = 120.0) -> int:
        self.request("POST", "/shutdown", b"")
        return self.proc.wait(timeout=timeout)

    def kill(self) -> None:
        if self.proc.poll() is None:
            self.proc.terminate()
            try:
                self.proc.wait(timeout=20)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        if not self._log.closed:
            self._log.close()
