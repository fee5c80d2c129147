"""chip_smoke.py's phases, in-process on the CPU at a tiny size (bucket 64,
width 0.125): every check passes, and the script still refuses to report
``ok`` because the platform is not a TPU.  Plus the compile-cache helper
the smoke and the entry points share."""
import json
import os
import sys
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[1]
TINY = ["--width", "0.125", "--buckets", "64", "--sides", "40", "64",
        "--requests", "4", "--max-batch", "2"]


def _smoke():
    sys.path.insert(0, str(ROOT))
    import chip_smoke

    return chip_smoke


def test_single_chip_phases_pass_and_cpu_is_refused(monkeypatch, tmp_path,
                                                    capsys):
    # a cache dir set from outside: the helper must leave it to JAX
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    smoke = _smoke()
    args = smoke.parse(TINY)
    check = smoke.run(args)
    failed = [name for name, ok in check.results if not ok]
    assert check.ok, failed
    names = {name for name, _ in check.results}
    assert {"parity_f32_vs_reference", "parity_bfp_vs_f32",
            "cc_kernel_matches_numpy_oracle", "f32_served_matches_sync",
            "bfp_served_matches_sync"} <= names
    out = capsys.readouterr().out
    assert f"cache dir={tmp_path}" in out
    rc = smoke.verdict(check, smoke.device_info(), args.chips)
    out = capsys.readouterr().out
    assert rc != 0
    assert "platform is 'cpu'" in out
    assert not any(line.startswith("{") for line in out.splitlines())


def test_main_refuses_cpu_before_any_work(capsys):
    smoke = _smoke()
    assert smoke.main(TINY) != 0
    out = capsys.readouterr().out
    assert "compile" not in out
    for line in out.splitlines():
        if line.startswith("{"):
            assert not json.loads(line).get("ok")


def test_compile_cache_honours_env(monkeypatch, tmp_path):
    from repro.launch.compile_cache import use_compile_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert use_compile_cache() == tmp_path
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_is_fixed_checkout_path(monkeypatch,
                                                       tmp_path):
    from jax.experimental.compilation_cache import compilation_cache

    from repro.launch import compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        got = compile_cache.use_compile_cache()
        assert got == ROOT / ".jax_cache"
        assert jax.config.jax_compilation_cache_dir == str(got)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        compilation_cache.reset_cache()
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()
    (tmp_path / "k1-cache").write_bytes(b"")
    (tmp_path / "k1-atime").write_bytes(b"")
    assert compile_cache.cache_entries(tmp_path) == 1
    assert compile_cache.cache_entries(tmp_path / "absent") == 0


def test_device_pin_refuses_mesh_plans():
    """A pinned replica runs SingleDevice engines; a mesh plan places its
    own shards, so the two together are refused at construction."""
    from repro.launch.mesh import make_mesh
    from repro.launch.serve import STDService
    from repro.runtime.executor import DataParallel

    mesh = make_mesh((1, 1), ("data", "model"))
    with pytest.raises(ValueError, match="device pins"):
        STDService(width=0.125, buckets=(64,), device=jax.devices()[0],
                   plan=DataParallel(mesh))


@pytest.mark.parametrize("kw", [{"width": 0.5}, {"mode": "reference"}])
def test_config_refuses_its_own_fields_as_kwargs(kw):
    """A base STDConfig carries width and mode; passing one of them
    beside it is refused instead of silently ignored."""
    from repro.configs.pixellink_std import SMOKE
    from repro.launch.serve import STDService

    with pytest.raises(ValueError, match="come from config"):
        STDService(config=SMOKE, buckets=(64,), **kw)


def test_four_chip_phase_on_virtual_devices(tmp_path):
    """``--chips 4`` on four virtual CPU devices: DataParallel(4) and
    GridPlan(2x2) boxes equal SingleDevice's, and the four router
    replicas each ran on their own device."""
    import subprocess
    import textwrap

    code = textwrap.dedent(f"""
        import os, sys
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        os.environ["JAX_PLATFORMS"] = "cpu"
        sys.path.insert(0, {str(ROOT)!r})
        import chip_smoke
        args = chip_smoke.parse(["--chips", "4"] + {TINY[:-4]!r}
                                + ["--max-batch", "4"])
        check = chip_smoke.run(args)
        print("FAILED", [n for n, ok in check.results if not ok])
        print("NAMES", sorted(n for n, _ in check.results))
    """)
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=600, env=env, cwd=str(ROOT))
    assert out.returncode == 0, out.stderr[-4000:]
    assert "FAILED []" in out.stdout, out.stdout[-4000:]
    for name in ("data_parallel4_boxes_equal_single_device",
                 "grid2x2_boxes_equal_single_device",
                 "replicas_on_distinct_devices",
                 "replica_boxes_equal_single_device"):
        assert name in out.stdout
