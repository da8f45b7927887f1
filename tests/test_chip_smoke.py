"""chip_smoke.py's phases at a tiny size on the CPU.

The script itself needs a chip and is run through the chip tool; these
tests keep its control flow, checks and oracle honest between chip runs:
each phase function runs here with the same code path, small sizes and
``platform="cpu"``, and the script as a whole must refuse to print a
result when JAX finds no accelerator.
"""

import os
import subprocess
import sys

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402


def test_phase_device_names_the_device_and_refuses_the_cpu(capsys):
    dev, cache = chip_smoke.phase_device(require=None, count=None)
    assert cache == jax.config.jax_compilation_cache_dir
    assert dev["platform"] == "cpu" and dev["count"] == len(jax.devices())
    out = capsys.readouterr().out
    for field in ("platform=cpu", "device_kind=", "jax=", "jaxlib=", "libtpu=",
                  "compile_cache_dir=", "native_library="):
        assert field in out, field
    with pytest.raises(SystemExit) as e:
        chip_smoke.phase_device(require="tpu")
    assert e.value.code not in (0, None)


def test_phase_served_tiny(tmp_path):
    out = chip_smoke.phase_served(n_clusters=20, objs_per=5, writers=4,
                                  platform="cpu", root=str(tmp_path / "kcp"),
                                  timeout=120)
    assert out["clusters"] == 20 and out["objects"] == 100
    assert out["rows_resident"] == 98  # 100 written, one delete per 10 tenants
    assert out["fused_ticks"] > 0
    assert not (tmp_path / "kcp").exists()


def test_phase_fused_core_tiny():
    # k keeps the full size's ratio to the churn (8192 : 768): a patch
    # overflow doubles the capacity, which is a recompile
    out = chip_smoke.phase_fused_core(b=2048, s=64, churn=32, warmup=12,
                                      ticks=30, r=256, d=64, k=512,
                                      timeout=120)
    assert out["on"] == {"cpu"}
    assert out["c_run"] == 0 and out["patch_rows"] > 0
    assert out["step"]["patches"] > 0


def test_oracle_step_catches_a_wrong_decision():
    """The numpy oracle is a real judge: a state it was not given must
    not pass as equal."""
    import numpy as np

    from kcp_tpu.models.reconcile_model import example_deltas, example_state

    st = example_state(b=256, s=16, r=16, p=8, l=4, c=8, seed=3)
    dl = example_deltas(b=256, s=16, d=16, seed=4)
    a = chip_smoke._oracle_step(st, dl, k=256)
    row = next(r for r in range(256) if r not in set(dl.idx.tolist()))
    assert row not in a["rows"] or a["code"][list(a["rows"]).index(row)] != 1
    st.down_exists[row] = False  # an untouched row becomes a CREATE
    b = chip_smoke._oracle_step(st, dl, k=256)
    assert b["code"][list(b["rows"]).index(row)] == 1
    assert b["stats"][1] == a["stats"][1] + 1
    assert np.array_equal(np.delete(a["stats"], [1, 2]), np.delete(b["stats"], [1, 2]))


def test_phase_pallas_rehearsal():
    out = chip_smoke.phase_pallas(b=2048, s=64, r=256, d=64, k=256,
                                  block_rows=256, compiled=False)
    assert out["tpu_custom_call"] is False  # the interpreter, on the CPU
    with pytest.raises(AssertionError, match="default_interpret"):
        chip_smoke.phase_pallas(b=256, compiled=True)


def test_phase_mesh_on_four_virtual_devices():
    assert len(jax.devices()) >= 4
    out = chip_smoke.phase_mesh(n_devices=4, b=2048, s=64, churn=32, steps=6,
                                timeout=120)
    assert out["patches"] > 0
    assert sorted(d for d, _shape in out["shards"]) == [0, 1, 2, 3]
    assert all(shape == (512, 64) for _d, shape in out["shards"])


@pytest.mark.parametrize("args", [[], ["--chips", "4"]], ids=["one", "four"])
def test_script_prints_no_result_without_a_chip(args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py"), *args],
                       env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "platform=cpu" in r.stdout


def test_compile_cache_is_placed_from_outside_or_in_the_checkout(monkeypatch, tmp_path):
    from kcp_tpu import cli

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.delenv("KCP_NO_COMPILE_CACHE", raising=False)
        # set from outside: no directory is set in code
        jax.config.update("jax_compilation_cache_dir", "untouched")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert cli.enable_compilation_cache() == "untouched"
        assert jax.config.jax_compilation_cache_dir == "untouched"
        # unset: the checkout's .jax_cache, from every entry point
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert cli.enable_compilation_cache() == cli.REPO_CACHE_DIR
        assert cli.REPO_CACHE_DIR == os.path.join(ROOT, ".jax_cache")
        # opted out: nothing is touched
        jax.config.update("jax_compilation_cache_dir", "untouched")
        monkeypatch.setenv("KCP_NO_COMPILE_CACHE", "1")
        assert cli.enable_compilation_cache() is None
        assert jax.config.jax_compilation_cache_dir == "untouched"
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
