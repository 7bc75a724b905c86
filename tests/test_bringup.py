"""Bring-up contract: where the program refuses to run, and where it puts
the compile cache and the TPU flags.  CPU only, seconds.

The chip side of the same contract is ``chip_smoke.py`` itself, run through
the chip tool (see .claude/skills/verify/SKILL.md).
"""
import inspect
import os
import subprocess
import sys

import jax
import pytest

from bluefog_tpu.run import launcher
from bluefog_tpu.utils import config as bfcfg

_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))


def _run(script, **env):
    return subprocess.run(
        [sys.executable, os.path.join(_ROOT, script)],
        env={**os.environ, "JAX_PLATFORMS": "cpu", **env},
        capture_output=True, text=True, timeout=120)


def test_chip_smoke_refuses_cpu_before_compiling():
    p = _run("chip_smoke.py")
    assert p.returncode != 0
    assert "no TPU" in p.stderr and "cpu" in p.stderr
    # no result line, and it never got as far as a phase
    assert '"ok"' not in p.stdout
    assert "phase" not in p.stdout


@pytest.fixture
def restore_cache_config():
    old = (jax.config.jax_compilation_cache_dir,
           jax.config.jax_persistent_cache_min_compile_time_secs)
    yield
    jax.config.update("jax_compilation_cache_dir", old[0])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", old[1])


def test_compile_cache_is_placed_from_outside(tmp_path, restore_cache_config):
    # JAX reads JAX_COMPILATION_CACHE_DIR into this config value at import;
    # whatever put a directory there, enable_compilation_cache leaves it
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    assert bfcfg.enable_compilation_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)
    # unset: one fixed path inside the checkout
    jax.config.update("jax_compilation_cache_dir", None)
    assert bfcfg.enable_compilation_cache() == os.path.join(
        _ROOT, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == os.path.join(
        _ROOT, ".jax_cache")


def test_compile_cache_key_sees_where_scopes_are_opened(
        tmp_path, monkeypatch, restore_cache_config):
    """A scope is debug information, which JAX's cache key strips: the
    digest of the package's scope sites goes into the key through its
    hook, moves when a scope is added or moved to another function, and
    stays when lines shift."""
    from jax._src import cache_key
    monkeypatch.setattr(cache_key, "custom_hook", cache_key.custom_hook)
    key = bfcfg.scope_sites_key()
    assert key.startswith("bluefog-device-scopes-")
    assert bfcfg.scope_sites_key() == key
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    bfcfg.enable_compilation_cache()
    assert cache_key.custom_hook() == key
    # the digest reads (file, function, name), not line numbers
    pkg = tmp_path / "pkg" / "utils"
    pkg.mkdir(parents=True)
    monkeypatch.setattr(bfcfg, "__file__", str(pkg / "config.py"))
    src = 'def f(x):\n    with jax.named_scope("ffn"):\n        return x\n'
    (tmp_path / "pkg" / "a.py").write_text(src)
    first = bfcfg.scope_sites_key()
    (tmp_path / "pkg" / "a.py").write_text("# a moved line\n\n" + src)
    assert bfcfg.scope_sites_key() == first != key
    (tmp_path / "pkg" / "a.py").write_text(src.replace("ffn", "readout"))
    assert bfcfg.scope_sites_key() != first
    (tmp_path / "pkg" / "a.py").write_text(src.replace("def f", "def g"))
    assert bfcfg.scope_sites_key() != first


def test_compile_cache_env_var_reaches_the_config(tmp_path):
    p = subprocess.run(
        [sys.executable, "-c",
         "import jax; from bluefog_tpu.utils.config import "
         "enable_compilation_cache as e; print(e())"],
        env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": _ROOT,
             "JAX_COMPILATION_CACHE_DIR": str(tmp_path)},
        capture_output=True, text=True, timeout=120)
    assert p.stdout.strip() == str(tmp_path), p.stderr


def test_init_keeps_the_cache_off_on_cpu(cpu_devices):
    import bluefog_tpu as bf
    old = jax.config.jax_compilation_cache_dir
    bf.init(devices=cpu_devices)
    try:
        assert jax.config.jax_compilation_cache_dir == old
    finally:
        bf.shutdown()


def test_no_xla_tpu_flag_goes_to_xla_flags(monkeypatch):
    """Nothing in the package puts --xla_tpu_* into XLA_FLAGS: jaxlib's
    parser aborts the process on them.  They go to LIBTPU_INIT_ARGS."""
    for mod in (bfcfg, launcher):
        src = inspect.getsource(mod)
        assert not any("XLA_FLAGS" in ln and "xla_tpu" in ln
                       for ln in src.splitlines())
    env = {"XLA_FLAGS": "--xla_force_host_platform_device_count=8"}
    assert bfcfg.add_recommended_tpu_flags(env)
    assert env["XLA_FLAGS"] == "--xla_force_host_platform_device_count=8"
    assert "--xla_tpu_enable_async_collective_fusion=true" in \
        env["LIBTPU_INIT_ARGS"]
    assert not bfcfg.add_recommended_tpu_flags(env)        # idempotent


def test_np_on_tpu_is_refused(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    with pytest.raises(SystemExit, match="CPU emulation of 2 hosts"):
        launcher.main(["-np", "2", sys.executable, "-c", "pass"])
    # one process is what a chip wants: not refused
    assert launcher._refuse_np_on_tpu(1, {"JAX_PLATFORMS": "tpu"}) is None
    assert launcher._refuse_np_on_tpu(4, {"JAX_PLATFORMS": "cpu"}) is None
