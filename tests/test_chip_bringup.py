"""Bring-up invariants (ISSUE 21): the compile-cache rule, no silent CPU,
canaries that keep the compiler's message, and chip_smoke.py's behaviour
on a host without a chip. All cheap: a few short subprocesses."""

import ast
import json
import os
import shutil
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PRINT_CACHE = (
    "import json, jax, greptimedb_tpu; "
    "print(json.dumps([jax.config.jax_compilation_cache_dir, "
    "jax.config.jax_persistent_cache_min_compile_time_secs]))")


def _cache_config(env: dict):
    """(cache dir, min compile secs) after importing the package in a
    fresh interpreter under `env`. Import only: no backend is
    initialised, so naming a platform that is absent is harmless."""
    full = {k: v for k, v in os.environ.items()
            if k not in ("JAX_PLATFORMS", "JAX_COMPILATION_CACHE_DIR")}
    full.update(env)
    full["PYTHONPATH"] = REPO
    out = subprocess.run([sys.executable, "-c", _PRINT_CACHE], env=full,
                         capture_output=True, text=True, timeout=120,
                         check=True)
    return json.loads(out.stdout.strip().splitlines()[-1])


PLACED = "/placed/from/outside"  # never created: import writes nothing


@pytest.fixture(scope="module")
def cache_configs():
    """One interpreter per environment, all started at once."""
    from concurrent.futures import ThreadPoolExecutor

    envs = {
        "set_tpu": {"JAX_COMPILATION_CACHE_DIR": PLACED,
                    "JAX_PLATFORMS": "tpu,cpu"},
        "set_cpu": {"JAX_COMPILATION_CACHE_DIR": PLACED,
                    "JAX_PLATFORMS": "cpu"},
        "unset_tpu": {"JAX_PLATFORMS": "tpu,cpu"},
        "unset_default": {},
        "unset_cpu": {"JAX_PLATFORMS": "cpu"},
    }
    with ThreadPoolExecutor(len(envs)) as pool:
        return dict(zip(envs, pool.map(_cache_config, envs.values())))


class TestCompileCacheRule:
    def test_variable_set_package_names_no_directory(self, cache_configs):
        # JAX read the variable; nothing overrode it — also for a
        # CPU-pinned process that is told where to cache
        assert cache_configs["set_tpu"] == [PLACED, 0.0]
        assert cache_configs["set_cpu"] == [PLACED, 0.0]

    def test_unset_accelerator_process_uses_fixed_checkout_path(
            self, cache_configs):
        # fixed, inside the checkout: no $HOME, temp dir, pid, time,
        # CPU fingerprint or boot id in it — the chip tool builds a new
        # machine per call and a path that moves never hits
        want = [os.path.join(REPO, ".jax_cache"), 0.0]
        assert cache_configs["unset_tpu"] == want
        assert cache_configs["unset_default"] == want

    def test_cpu_pinned_process_keeps_cache_off(self, cache_configs):
        assert cache_configs["unset_cpu"][0] is None

    def test_one_wiring_in_the_package(self):
        hits = []
        pkg = os.path.join(REPO, "greptimedb_tpu")
        for root, _, files in os.walk(pkg):
            for f in files:
                if f.endswith(".py"):
                    path = os.path.join(root, f)
                    with open(path, encoding="utf-8") as fh:
                        for n, line in enumerate(fh, 1):
                            if "jax_compilation_cache_dir" in line \
                                    and "config.update" in line:
                                hits.append(f"{path}:{n}")
        assert len(hits) == 1 and hits[0].startswith(
            os.path.join(pkg, "__init__.py")), hits


class TestNoSilentCpu:
    def test_service_refuses_a_cpu_nobody_named(self, monkeypatch):
        from greptimedb_tpu import config

        # the test process runs on the CPU backend (conftest); pretend
        # the environment never asked for it — what JAX's own fallback
        # after a failed accelerator init looks like
        monkeypatch.delenv("JAX_PLATFORMS")
        with pytest.raises(RuntimeError, match="JAX_PLATFORMS"):
            config.require_stated_platform()
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        dev = config.require_stated_platform()
        assert dev["platform"] == "cpu" and dev["count"] >= 1

    def test_canary_keeps_the_compilers_message(self):
        from greptimedb_tpu.ops import pallas_segment as ps
        from greptimedb_tpu.utils.metrics import DEVICE_DEGRADATIONS

        def refuse():
            raise RuntimeError("Mosaic: scoped vmem limit exceeded by 25M")

        before = DEVICE_DEGRADATIONS.get(kind="canary_probe_test")
        try:
            assert ps._canary("probe_test", refuse) is False
            assert ps._canary("probe_test", refuse) is False  # one shot
            verdict = ps.canary_status()["probe_test"]
            assert verdict["ok"] is False
            assert "scoped vmem limit exceeded" in verdict["error"]
            assert DEVICE_DEGRADATIONS.get(
                kind="canary_probe_test") == before + 1
        finally:
            ps._CANARY.pop("probe_test", None)

    def test_interpret_mode_follows_the_target_platform(self):
        import jax

        from greptimedb_tpu.ops import pallas_segment as ps

        assert ps.target_platform() == "cpu" and ps.interpret_mode()
        assert ps.dispatch_mode() == "interpret"
        # a TPU process's host tier pins the CPU device: kernels traced
        # there must not be handed to Mosaic
        with jax.default_device(jax.devices("cpu")[0]):
            assert ps.target_platform() == "cpu"

    def test_device_status_and_analyze_tier_over_http(self, tmp_path):
        import http.client
        import urllib.parse

        from greptimedb_tpu.catalog import Catalog, MemoryKv
        from greptimedb_tpu.query import QueryEngine
        from greptimedb_tpu.servers.http import HttpServer
        from greptimedb_tpu.storage import RegionEngine
        from greptimedb_tpu.storage.engine import EngineConfig

        engine = RegionEngine(EngineConfig(data_dir=str(tmp_path)))
        qe = QueryEngine(Catalog(MemoryKv()), engine)
        srv = HttpServer(qe, port=0)
        port = srv.start()
        try:
            conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
            conn.request("GET", "/v1/device")
            st = json.loads(conn.getresponse().read())
            assert st["platform"] == "cpu" and st["count"] == len(
                st["devices"])
            assert st["link"]["colocated"] is True
            assert st["pallas"]["dispatch_mode"] == "interpret"
            assert st["warmup"] == {"warm": 0, "warming": 0, "failed": 0}
            assert st["compile_cache_dir"] is None  # CPU-pinned tests
            assert isinstance(st["native_available"], bool)
            qe.execute_one(
                "CREATE TABLE t (h STRING, ts TIMESTAMP(3) NOT NULL, "
                "v DOUBLE, TIME INDEX (ts), PRIMARY KEY (h))")
            qe.execute_one("INSERT INTO t VALUES ('a', 1000, 1.0)")
            conn.request("POST", "/v1/sql", urllib.parse.urlencode(
                {"sql": "EXPLAIN ANALYZE SELECT h, max(v) FROM t GROUP BY h"}),
                {"Content-Type": "application/x-www-form-urlencoded"})
            out = json.loads(conn.getresponse().read())
            text = "\n".join(
                r[0] for r in out["output"][-1]["records"]["rows"])
            assert "execution path:" in text
            assert "execution tier: " in text
        finally:
            srv.stop()
            engine.close()


class TestChipSmokeWithoutAChip:
    """This sandbox has no accelerator: the smoke must say so and fail,
    fast, without printing a result object."""

    def _run(self, cwd, script):
        env = {k: v for k, v in os.environ.items()
               if k not in ("JAX_PLATFORMS", "PYTHONPATH")}
        t0 = time.monotonic()
        r = subprocess.run([sys.executable, script], cwd=cwd, env=env,
                           capture_output=True, text=True, timeout=170)
        return r, time.monotonic() - t0

    def test_exits_nonzero_naming_the_platform(self):
        r, took = self._run(REPO, os.path.join(REPO, "chip_smoke.py"))
        assert r.returncode != 0
        last = r.stdout.strip().splitlines()[-1]
        assert last.startswith("chip_smoke FAILED")
        assert "tpu" in last.lower()  # names what it could not get
        assert '"ok": true' not in r.stdout
        assert took < 60, took

    def test_fails_alone_in_a_directory(self, tmp_path):
        shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
        r, _ = self._run(str(tmp_path), "chip_smoke.py")
        assert r.returncode != 0
        assert '"ok": true' not in r.stdout

    def test_never_imports_jax_or_the_package(self):
        # a parent that touched JAX would hold the chip the server needs
        with open(os.path.join(REPO, "chip_smoke.py"),
                  encoding="utf-8") as f:
            tree = ast.parse(f.read())
        roots = set()
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                roots.update(a.name.split(".")[0] for a in node.names)
            elif isinstance(node, ast.ImportFrom) and node.module:
                roots.add(node.module.split(".")[0])
        assert not roots & {"jax", "jaxlib", "greptimedb_tpu"}, roots

    def test_last_line_is_the_verdict_and_nothing_else(self):
        # the driver reads the LAST stdout line: exactly "ok" and
        # "device", the device exactly platform/kind/count. The run's
        # record (sizes, per-query tiers, ...) goes on the line before.
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
        smoke = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(smoke)
        line = smoke.verdict_line({
            "ok": True, "queries": [], "claim": None, "sizes": {},
            "device": {"platform": "tpu", "kind": "TPU v5 lite",
                       "count": 1, "id": 0}})
        assert "\n" not in line
        assert json.loads(line) == {
            "ok": True, "device": {"platform": "tpu",
                                   "kind": "TPU v5 lite", "count": 1}}
