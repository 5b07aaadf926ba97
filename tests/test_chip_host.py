"""The guards that keep a run on a chip host honest, checked on the CPU.

* the roofline screen's production mesh comes from the CPU backend's
  placeholder devices, whatever the default backend is;
* on a chip host, a multi-worker fabric with a measured tier is refused
  (one process per chip);
* ``chip_smoke.py`` fails, without its ``"ok"`` line, when JAX is held
  to the CPU or when the package is not beside it;
* the compile-cache helper honours ``JAX_COMPILATION_CACHE_DIR`` and
  otherwise picks one fixed path in the checkout.
"""
import os
import pathlib
import shutil
import subprocess
import sys
import textwrap

import jax
import pytest

from repro.launch import chip

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _env(**extra):
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"))
    env.update(extra)
    return env


def test_roofline_mesh_uses_cpu_placeholders_under_another_default():
    """A chip host's default backend holds 1 or 4 devices; the screen's
    256-device mesh must still come from the CPU placeholders.  The
    child steers ``jax.devices()`` (no backend named) to one device."""
    code = textwrap.dedent("""
        import jax
        real = jax.devices
        jax.devices = lambda backend=None: (
            real(backend) if backend is not None else real()[:1])
        from repro.core.trial import RooflineEvaluator
        mesh = RooflineEvaluator()._mesh_factory(multi_pod=False)
        plats = {d.platform for d in mesh.devices.flat}
        print(mesh.devices.size, sorted(plats))
    """)
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=300, cwd=ROOT,
        env=_env(XLA_FLAGS="--xla_force_host_platform_device_count=256"))
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split() == ["256", "['cpu']"]


@pytest.mark.parametrize("flags", [["--workers", "2"], ["--coordinate"]])
def test_measured_fabric_refused_on_a_chip_host(monkeypatch, capsys, flags):
    from repro.launch import tune
    monkeypatch.setattr(chip, "tpu_host", lambda: True)
    with pytest.raises(SystemExit) as e:
        tune.main(["--cells", "smollm-135m:train_4k", "--measure-top-k",
                   "1"] + flags)
    assert e.value.code == 2
    assert "one process at a time" in capsys.readouterr().err


def test_tpu_host_respects_jax_platforms(monkeypatch):
    monkeypatch.setattr(chip, "tpu_chips", lambda: 1)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert not chip.tpu_host()
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    assert chip.tpu_host()
    monkeypatch.delenv("JAX_PLATFORMS")
    assert chip.tpu_host()
    monkeypatch.setattr(chip, "tpu_chips", lambda: 0)
    assert not chip.tpu_host()


def _smoke(script, cwd, **env):
    return subprocess.run([sys.executable, str(script)], capture_output=True,
                          text=True, timeout=120, cwd=cwd, env=env)


def test_chip_smoke_refuses_the_cpu():
    out = _smoke(ROOT / "chip_smoke.py", ROOT, **_env(JAX_PLATFORMS="cpu"))
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "JAX_PLATFORMS" in out.stderr


def test_chip_smoke_alone_fails(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    env = {k: v for k, v in os.environ.items()
           if k not in ("PYTHONPATH", "JAX_PLATFORMS")}
    out = _smoke(tmp_path / "chip_smoke.py", tmp_path, **env)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


def test_compile_cache_dir(monkeypatch):
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert chip.enable_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == prev   # JAX reads it
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        path = chip.enable_compile_cache()
        assert path == str(ROOT / ".jax_compile_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert ".jax_compile_cache/" in (ROOT / ".gitignore").read_text()
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
        compilation_cache.reset_cache()
    assert not jax.config.jax_enable_compilation_cache   # tests keep it off
