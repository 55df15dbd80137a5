"""Driver entry-point regression tests.

Round-1 postmortem: the first multichip dry run failed rc=1 because it let
stray ops (``jax.random.key``, numpy→device converts) dispatch to the default
TPU backend, which in the driver environment was live-but-broken (libtpu
version mismatch). The dryrun must be hermetic: CPU-only, regardless of
XLA_FLAGS, and regardless of what the default backend is.

These run in subprocesses because backend initialization is process-global.
"""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, env_overrides: dict) -> subprocess.CompletedProcess:
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    env.update(env_overrides)
    return subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=600)


def test_dryrun_hermetic_no_flags_cpu_only():
    """Without XLA_FLAGS, the dryrun must self-provision 8 CPU devices and
    never initialize any non-CPU backend."""
    proc = _run(
        "from __graft_entry__ import dryrun_multichip\n"
        "dryrun_multichip(8)\n"
        "import jax\n"
        "assert jax.default_backend() == 'cpu', jax.default_backend()\n"
        # Private-API check is best-effort: it is the only way to see that
        # no non-CPU backend was ever *initialized*, but must not turn a
        # JAX-internals rename into a false regression signal.
        "try:\n"
        "    import jax._src.xla_bridge as xb\n"
        "    backends = list(xb._backends.keys())\n"
        "except (ImportError, AttributeError):\n"
        "    backends = ['cpu']\n"
        "assert backends == ['cpu'], backends\n",
        {})
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "dryrun_multichip(8): ok" in proc.stdout


def test_dryrun_with_driver_flags():
    """Driver-style invocation (XLA_FLAGS force-host-device-count) passes."""
    proc = _run(
        "from __graft_entry__ import dryrun_multichip\n"
        "dryrun_multichip(8)\n",
        {"XLA_FLAGS": "--xla_force_host_platform_device_count=8"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "2-D dp x tp mesh (4, 2) ok" in proc.stdout


def test_dryrun_after_backend_init_falls_back():
    """If backends are already initialized (default backend possibly
    non-CPU, e.g. a host with a chip) but the CPU device-count flag is
    set, the dryrun completes via explicit CPU devices + default_device pin."""
    proc = _run(
        "import jax; jax.devices()\n"
        "from __graft_entry__ import dryrun_multichip\n"
        "dryrun_multichip(8)\n"
        "import jax\n"
        "assert any(d.platform == 'cpu' for d in jax.devices('cpu'))\n",
        {"XLA_FLAGS": "--xla_force_host_platform_device_count=8"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "dryrun_multichip(8): ok" in proc.stdout


def test_dryrun_after_backend_init_without_flag_raises_cleanly():
    """The round-1 failure shape: backends pre-initialized, NO CPU
    device-count flag, default backend cannot (or must not) serve the mesh.
    The dryrun must fail with the actionable RuntimeError from _devices_for —
    never by dispatching ops to a possibly-broken accelerator backend.
    (With the CPU pin stripped and no device-count flag, the default backend
    has fewer than 8 devices, so the raise path is exercised for real.)"""
    proc = _run(
        "import jax; jax.devices()\n"
        "from __graft_entry__ import dryrun_multichip\n"
        "try:\n"
        "    dryrun_multichip(8)\n"
        "except RuntimeError as e:\n"
        "    assert 'xla_force_host_platform_device_count' in str(e), e\n"
        "    print('clean-raise-ok')\n"
        "else:\n"
        "    print('ran-ok')\n",
        {})
    assert proc.returncode == 0, proc.stderr[-2000:]
    # Either outcome is acceptable (a healthy >=8-device default backend
    # would legitimately run), but a crash is not.
    assert ("clean-raise-ok" in proc.stdout) or ("ran-ok" in proc.stdout)


def test_entry_compiles():
    """entry() returns (fn, args) that jit-compile on the CPU backend."""
    proc = _run(
        "from __graft_entry__ import entry\n"
        "import jax\n"
        "fn, args = entry()\n"
        "out = jax.jit(fn)(*args)\n"
        "assert out.shape == (32, 2), out.shape\n",
        {"JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-2000:]
