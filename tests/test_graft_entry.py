"""Driver-contract regression tests for __graft_entry__.

A caller may run ``dryrun_multichip(8)`` in-process on a host with a
chip.  Any jax backend query in that parent — even
``jax.devices("cpu")`` — initialises every platform on its list and
takes the chip, which belongs to one process at a time.  The contract is
therefore: the parent path of ``dryrun_multichip`` touches NO jax API;
it unconditionally re-execs into a pure-CPU child.  These tests simulate
BOTH calling environments (no XLA_FLAGS / 8 forced CPU devices) in
subprocesses and assert the child path runs and the parent never
initialises a backend.
"""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Parent body shared by the env variants: run the dryrun, then assert the
# PARENT process never initialised any xla backend.
PARENT_CODE = (
    "import sys\n"
    f"sys.path.insert(0, {REPO!r})\n"
    "import __graft_entry__\n"
    "__graft_entry__.dryrun_multichip(8)\n"
    "import sys as _s\n"
    "jx = _s.modules.get('jax')\n"
    "if jx is not None:\n"
    "    from jax._src import xla_bridge\n"
    "    assert not xla_bridge._backends, (\n"
    "        'parent initialised backends: %r' % (xla_bridge._backends,))\n"
    "print('PARENT CLEAN', flush=True)\n"
)


def _run_parent(env):
    return subprocess.run([sys.executable, "-c", PARENT_CODE], env=env,
                          capture_output=True, text=True, timeout=560)


def test_dryrun_driver_env_no_xla_flags():
    """Driver variant 1: no XLA_FLAGS (1 CPU device in-parent)."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "MVTPU_DRYRUN_CHILD", "JAX_PLATFORMS")}
    env["MVTPU_DRYRUN_LIGHT"] = "1"   # isolation contract only; full app
    # coverage lives in make dryrun + the in-process placement test
    proc = _run_parent(env)
    assert proc.returncode == 0, \
        f"dryrun failed in simulated driver env:\n{proc.stdout}\n{proc.stderr}"
    assert "dryrun child OK" in proc.stdout, proc.stdout
    assert "PARENT CLEAN" in proc.stdout, proc.stdout


def test_dryrun_driver_env_8_forced_cpu_devices():
    """Driver variant 2 (the env that was red in rounds 1-3): XLA_FLAGS
    forces 8 CPU devices in the PARENT, so an in-process path would be
    possible — and fatal when the default platform list includes a broken
    TPU client.  The child path must be taken anyway."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("MVTPU_DRYRUN_CHILD", "JAX_PLATFORMS")}
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["MVTPU_DRYRUN_LIGHT"] = "1"
    proc = _run_parent(env)
    assert proc.returncode == 0, \
        f"dryrun failed with 8 forced devices:\n{proc.stdout}\n{proc.stderr}"
    assert "dryrun child OK" in proc.stdout, proc.stdout
    assert "PARENT CLEAN" in proc.stdout, proc.stdout


def test_dryrun_child_guard_refuses_recursion():
    # If the child's XLA_FLAGS were ignored it must raise, not re-exec
    # forever.  Simulate by claiming to be the child with 1 CPU device.
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["MVTPU_DRYRUN_CHILD"] = "1"
    code = (
        "import sys\n"
        f"sys.path.insert(0, {REPO!r})\n"
        "import jax\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        "import __graft_entry__\n"
        "try:\n"
        "    __graft_entry__.dryrun_multichip(8)\n"
        "except RuntimeError as e:\n"
        "    assert 'XLA_FLAGS was not honoured' in str(e), e\n"
        "    print('GUARD OK')\n"
        "else:\n"
        "    raise SystemExit('expected RuntimeError')\n"
    )
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    assert "GUARD OK" in proc.stdout
