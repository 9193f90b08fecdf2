"""chip_smoke.py's phases at tiny sizes on the CPU (kernels in interpret
mode), its four-chip phase on four virtual CPU devices in a child
process, and its refusal to run without a TPU."""
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")
sys.path.insert(0, ROOT)

import chip_smoke as cs  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.configs.base import reduced  # noqa: E402
from repro.fleet import default_fleet_forest  # noqa: E402


def _env(**kw):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.update(JAX_PLATFORMS="cpu", **kw)
    return env


def test_fleet_phase_tiny():
    line = cs.fleet_phase(default_fleet_forest(), ticks=4, variants=2,
                          n_jobs=3)
    assert line.startswith("[fleet] 3 jobs x 4 ticks")
    assert "tpu_custom_call=False" in line      # interpret mode on CPU


def test_fill_placement_phase():
    line = cs.fill_placement_phase()
    assert line.startswith("[fill+placement] 'congestion' 30 steps")


def test_serve_phase_tiny():
    cfg = reduced(get_config(cs.SERVE_ARCH))
    line = cs.serve_phase(cfg, requests=3, batch=2, max_new=4, s_max=32,
                          k=2)
    assert "3 requests" in line and "gives decode's token 3" in line


_CROSS_POD = textwrap.dedent("""
    import sys
    sys.path.insert(0, {root!r})
    import jax
    import chip_smoke as cs
    from repro.configs import get_config
    from repro.configs.base import reduced
    from repro.fleet import default_fleet_forest
    assert len(jax.devices()) == 4
    print(cs.cross_pod_phase(reduced(get_config(cs.SERVE_ARCH)),
                             default_fleet_forest(), jax.devices(),
                             n_layers=2, steps=2, batch=8, seq=32))
""")


def test_cross_pod_phase_four_virtual_devices():
    env = _env(XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", _CROSS_POD.format(root=ROOT)],
                       capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "4 pods on devices [0, 1, 2, 3]" in r.stdout
    assert "[kv-migrate]" in r.stdout


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_refuses_without_tpu(tmp_path, where):
    """No TPU: a non-zero exit and no result line, whether the script
    runs from the checkout or from a directory holding only itself."""
    script = os.path.join(ROOT, "chip_smoke.py")
    cwd = ROOT
    if where == "alone":
        with open(script) as f:
            src = f.read()
        script = str(tmp_path / "chip_smoke.py")
        with open(script, "w") as f:
            f.write(src)
        cwd = str(tmp_path)
    env = _env()
    env.pop("PYTHONPATH", None)
    r = subprocess.run([sys.executable, script], capture_output=True,
                       text=True, env=env, cwd=cwd, timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


@pytest.mark.parametrize("preset", [True, False])
def test_compile_cache_dir(tmp_path, preset):
    """JAX_COMPILATION_CACHE_DIR wins untouched; otherwise the fixed
    in-checkout path."""
    probe = ("import jax; from repro.launch.compile_cache import "
             "use_compile_cache; p = use_compile_cache(); "
             "print(p); print(jax.config.jax_compilation_cache_dir)")
    env = _env(PYTHONPATH=os.path.join(ROOT, "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    want = os.path.realpath(os.path.join(ROOT, ".jax_cache"))
    if preset:
        want = str(tmp_path / "cache")
        env["JAX_COMPILATION_CACHE_DIR"] = want
    r = subprocess.run([sys.executable, "-c", probe], capture_output=True,
                       text=True, env=env, timeout=120)
    assert r.returncode == 0, r.stderr
    got, cfg_dir = r.stdout.split()
    assert os.path.realpath(got) == os.path.realpath(want)
    assert os.path.realpath(cfg_dir) == os.path.realpath(want)
