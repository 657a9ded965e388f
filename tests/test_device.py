"""The process's jax backend (limitador_tpu/device.py) and the chip
smoke that refuses a server on the wrong one: where the compile cache
lives, the decision to refuse a CPU nobody asked for, and
``chip_smoke.py`` as a jax-free client."""

import ast
import os
import subprocess
import sys
from pathlib import Path

import pytest

from limitador_tpu import device

REPO_ROOT = Path(__file__).resolve().parent.parent


# -- the compile cache ---------------------------------------------------------


@pytest.fixture
def config_updates(monkeypatch):
    """Record ``jax.config.update`` calls instead of applying them."""
    import jax

    calls = {}
    monkeypatch.setattr(
        jax.config, "update", lambda name, value: calls.update({name: value})
    )
    return calls


def test_cache_env_set_means_no_directory_set_in_code(
    monkeypatch, config_updates
):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/from/outside")
    assert device.enable_compile_cache() == "/placed/from/outside"
    # jax reads the variable itself; the program only keeps the small
    # bucket programs from being skipped
    assert config_updates == {
        "jax_persistent_cache_min_compile_time_secs": 0.0,
        "jax_persistent_cache_min_entry_size_bytes": 0,
    }


def test_cache_env_unset_means_the_checkout(monkeypatch, config_updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = str(REPO_ROOT / ".jax_cache")
    assert device.enable_compile_cache() == want
    assert config_updates["jax_compilation_cache_dir"] == want
    assert config_updates["jax_persistent_cache_min_compile_time_secs"] == 0.0
    assert config_updates["jax_persistent_cache_min_entry_size_bytes"] == 0


def test_cache_path_is_the_same_from_two_processes(tmp_path):
    """A cache that moves never hits: no pid, time or cwd in the path."""
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = str(REPO_ROOT)
    seen = {
        subprocess.run(
            [sys.executable, "-c",
             "from limitador_tpu.device import compile_cache_dir; "
             "print(compile_cache_dir())"],
            capture_output=True, text=True, timeout=60, env=env, cwd=cwd,
            check=True,
        ).stdout.strip()
        for cwd in (str(REPO_ROOT), str(tmp_path))
    }
    assert seen == {str(REPO_ROOT / ".jax_cache")}


# -- no silent CPU ---------------------------------------------------------------


@pytest.mark.parametrize("platform, jax_platforms, refused", [
    ("cpu", None, True),        # chip missing or held: jax fell back
    ("cpu", "", True),
    ("cpu", "cpu", False),      # JAX_PLATFORMS=cpu / LIMITADOR_TPU_PLATFORM=cpu
    ("cpu", "tpu,cpu", False),  # cpu named as the fallback
    ("tpu", None, False),
    ("tpu", "tpu,cpu", False),
    ("gpu", None, False),
])
def test_cpu_is_refused_only_when_nobody_asked(platform, jax_platforms, refused):
    assert device.cpu_unasked(platform, jax_platforms) is refused


def test_require_accelerator_exits_naming_the_backend(monkeypatch):
    import jax

    monkeypatch.setattr(device, "cpu_unasked", lambda *_a: True)
    with pytest.raises(SystemExit) as exc:
        device.require_accelerator("storage 'tpu'")
    assert "storage 'tpu'" in str(exc.value)
    assert f"platform {jax.devices()[0].platform}" in str(exc.value)


def test_require_accelerator_reports_the_device_when_asked():
    import jax

    # the suite runs under JAX_PLATFORMS=cpu: asked for by name
    report = device.require_accelerator("test")
    assert report == {
        "platform": jax.devices()[0].platform,
        "kind": jax.devices()[0].device_kind,
        "count": len(jax.devices()),
    }


# -- chip_smoke.py -----------------------------------------------------------------


def test_chip_smoke_never_imports_jax():
    """The chip belongs to the server the smoke starts: no ``import jax``
    in its source, and none reached through what it does import."""
    tree = ast.parse((REPO_ROOT / "chip_smoke.py").read_text())
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names = [alias.name for alias in node.names]
        elif isinstance(node, ast.ImportFrom):
            names = [node.module or ""]
        else:
            continue
        assert not any(n == "jax" or n.startswith("jax.") for n in names)
    subprocess.run(
        [sys.executable, "-c",
         "import sys, chip_smoke; assert 'jax' not in sys.modules"],
        timeout=60, cwd=str(REPO_ROOT), check=True,
    )


def test_chip_smoke_refuses_a_cpu_server_after_its_oracle_passed(tmp_path):
    """Against a server that serves from the CPU by name the smoke runs
    every phase, its oracle comparison passes, and it still fails,
    naming ``platform cpu``: a healthy-looking server on the wrong
    device is exactly what it exists to refuse. The suite's eight
    virtual devices make it run leg two (sharded storage) as well."""
    pytest.importorskip("grpc")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    assert "xla_force_host_platform_device_count=8" in env["XLA_FLAGS"]
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py", "--keys", "400", "--repeats",
         "800", "--inflight", "64"],
        capture_output=True, text=True, timeout=300, env=env,
        cwd=str(REPO_ROOT),
    )
    out = proc.stdout
    assert proc.returncode != 0, out
    parity = out.index("[ok] oracle parity, fixed window: 200/200")
    assert "[ok] oracle parity, token bucket: 200/200" in out
    assert "[ok] 1200 requests answered: 0 failed, 0 UNKNOWN" in out
    refusal = out.index("[FAIL] server runs on platform cpu")
    assert parity < refusal
    # the platform is the ONLY thing wrong with a CPU-pinned server, in
    # either leg
    assert out.count("[FAIL]") == 2, out
    assert "FAIL: leg one: server runs on platform cpu" in proc.stderr
    assert "FAIL: leg two: server runs on platform cpu" in proc.stderr
    assert "[ok] global namespace: 50 of 200 serial requests" in out
    assert "[ok] 8 shards hold" in out
    assert '"ok"' not in out.strip().splitlines()[-1]
