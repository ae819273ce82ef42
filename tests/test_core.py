"""Core runtime tests: mesh, init, barrier, topology (SURVEY.md §3.1/§4.1)."""

import os
import subprocess
import sys

import pytest

from multiverso_tpu import core


class TestMesh:
    def test_init_builds_mesh(self, mesh8):
        assert mesh8.shape[core.DATA_AXIS] == 4
        assert mesh8.shape[core.MODEL_AXIS] == 2
        assert core.is_initialized()
        assert core.mesh() is mesh8

    def test_pure_dp_mesh(self, mesh_dp8):
        assert mesh_dp8.shape[core.DATA_AXIS] == 8
        assert mesh_dp8.shape[core.MODEL_AXIS] == 1

    def test_bad_factorisation_raises(self, devices):
        with pytest.raises(ValueError):
            core._build_mesh(devices, data_parallel=3, model_parallel=2)

    def test_idempotent_reinit(self, mesh8):
        assert core.init() is mesh8


class TestTopology:
    def test_counts(self, mesh8):
        assert core.num_workers() == 8
        assert core.num_servers() == 8
        assert core.rank() == 0
        assert core.size() == 1
        assert core.is_worker() and core.is_server()
        assert core.worker_id() == 0
        assert core.data_axis_size() == 4
        assert core.model_axis_size() == 2


class TestBarrier:
    def test_barrier_completes(self, mesh8):
        before = core._RT.barrier_count
        core.barrier()
        core.barrier("named")
        assert core._RT.barrier_count == before + 2


class TestShutdown:
    def test_shutdown_then_reinit(self, devices):
        core.init(devices=devices, data_parallel=8, model_parallel=1)
        core.shutdown()
        assert not core.is_initialized()
        m = core.init(devices=devices, data_parallel=2, model_parallel=4)
        assert m.shape[core.MODEL_AXIS] == 4
        core.shutdown()


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestCompileCachePlacement:
    """One place (core.init, before the first compile), placeable from
    outside: JAX_COMPILATION_CACHE_DIR wins and then NOTHING is set in
    code; otherwise the fixed in-checkout path — never a temp name, pid
    or timestamp (the path is part of the cache key), never both."""

    CODE = (
        "import json, sys\n"
        f"sys.path.insert(0, {REPO!r})\n"
        "import jax\n"
        "before = jax.config.jax_compilation_cache_dir\n"
        "from multiverso_tpu import core\n"
        "core.init()\n"
        "print(json.dumps({'before': before,\n"
        "    'after': jax.config.jax_compilation_cache_dir,\n"
        "    'reported': core.compile_cache_dir(),\n"
        "    'min_s': jax.config.jax_persistent_cache_min_compile_time_secs}))\n"
    )

    def _run(self, env_dir):
        import json
        env = {k: v for k, v in os.environ.items()
               if k != "JAX_COMPILATION_CACHE_DIR"}
        env["JAX_PLATFORMS"] = "cpu"
        if env_dir is not None:
            env["JAX_COMPILATION_CACHE_DIR"] = env_dir
        proc = subprocess.run([sys.executable, "-c", self.CODE], env=env,
                              capture_output=True, text=True, timeout=120)
        assert proc.returncode == 0, proc.stderr[-2000:]
        return json.loads(proc.stdout.strip().splitlines()[-1])

    def test_unset_uses_the_fixed_in_checkout_path(self):
        doc = self._run(None)
        want = os.path.join(REPO, ".jax_cache")
        assert doc["before"] is None
        assert doc["after"] == doc["reported"] == want
        assert doc["min_s"] == 0.0       # every program is cached
        # same answer from another process: nothing run-specific in it
        assert self._run(None)["after"] == want

    def test_env_wins_and_code_sets_no_other(self, tmp_path):
        outside = str(tmp_path / "elsewhere")
        doc = self._run(outside)
        # jax read the variable itself; init() left it alone
        assert doc["before"] == doc["after"] == doc["reported"] == outside

    def test_no_other_cache_directory_is_set_in_code(self):
        """The only writer of the cache-dir config in the program."""
        hits = []
        for root in ("multiverso_tpu", "benchmarks", "tools", "examples"):
            for dirpath, _dirs, files in os.walk(os.path.join(REPO, root)):
                for name in files:
                    if name.endswith(".py"):
                        path = os.path.join(dirpath, name)
                        with open(path) as f:
                            if "compilation_cache_dir" in f.read():
                                hits.append(os.path.relpath(path, REPO))
        for name in ("bench.py", "chip_smoke.py", "__graft_entry__.py"):
            with open(os.path.join(REPO, name)) as f:
                if "jax_compilation_cache_dir" in f.read():
                    hits.append(name)
        assert hits == [os.path.join("multiverso_tpu", "core.py")], hits


class TestPlatform:
    def test_platform_is_the_meshs(self, mesh8):
        assert core.platform() == core.platform(mesh8) == "cpu"
