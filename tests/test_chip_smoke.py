"""`chip_smoke.py` and the compile-cache function, as far as the CPU can
show: the smoke must FAIL where there is no TPU (it has no CPU mode), and
the cache directory must be placeable from outside."""

import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from distributed_llm_training_and_inference_system_tpu.utils import platform

ROOT = Path(__file__).resolve().parent.parent


def test_chip_smoke_fails_without_a_tpu():
    env = {k: v for k, v in os.environ.items()
           if k not in ("CHIP_SMOKE_REHEARSAL", "CHIP_SMOKE_PHASE")}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["ok"] is False
    assert last["device"]["platform"] == "cpu"
    assert '"platform": "tpu"' not in proc.stdout


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_obeys_env_else_fixed_checkout_path(
        monkeypatch, tmp_path, restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "c"))
    assert platform.enable_compile_cache() == str(tmp_path / "c")
    assert jax.config.jax_compilation_cache_dir == str(tmp_path / "c")

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    fixed = platform.enable_compile_cache()
    assert fixed == str(ROOT / ".jax_cache")
    # the choice travels to children through the environment, and a second
    # call (another entry point of the same process) names the same place
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == fixed
    assert platform.enable_compile_cache() == fixed


@pytest.mark.parametrize("kind,family", [("TPU v5 lite", "v5e"),
                                         ("TPU v5e", "v5e"),
                                         ("TPU v4", "v4")])
def test_chip_peaks_known_kinds(kind, family):
    assert platform.chip_peaks("tpu", kind)["chip_family"] == family


def test_chip_peaks_cpu_has_none_and_unknown_tpu_is_an_error():
    assert platform.chip_peaks("cpu", "cpu") is None
    with pytest.raises(platform.UnknownChipError):
        platform.chip_peaks("tpu", "TPU v99")


_TILES_1 = ("12:00:01 INFO llmctl.impl: impl window_page_write=tiles "
            "(T=1 over bfloat16(4, 1430, 4, 256, 128))")
_TILES_2 = ("12:00:01 INFO llmctl.impl: impl window_page_write=tiles "
            "(T=2 over bfloat16(9, 1017, 1, 256, 640))")
_PAGES_1 = ("12:00:01 INFO llmctl.impl: impl window_page_write=pages "
            "(T=1 over int8(16, 715, 8, 64, 128))")
_PAGES_256 = ("12:00:02 INFO llmctl.impl: impl window_page_write=pages "
              "(T=256 over bfloat16(4, 1430, 4, 256, 128))")


@pytest.mark.parametrize("log,rows,passes", [
    ([_TILES_1, _PAGES_256], 1, True),      # a decode step and its piece
    ([_TILES_2, _PAGES_256], 2, True),      # a draft-and-verify step
    ([_TILES_2, _PAGES_256], 1, False),     # tiles, but not the one row
    ([_PAGES_1, _PAGES_256], 1, False),     # the one row by whole pages
    ([], 1, False),
], ids=["one-row", "two-rows", "other-window", "whole-pages", "no-line"])
def test_smoke_demands_the_tile_write_of_the_window_it_names(log, rows,
                                                             passes):
    """The ``serve`` and ``shortconv`` phases fail unless the server's log
    says a decode step's one row staged tiles, the ``selfdraft`` phase
    unless its window of two did."""
    import chip_smoke
    text = "\n".join(["server ready", *log, "bye"])
    if passes:
        chip_smoke.require_tile_write(text, rows, "the window")
    else:
        with pytest.raises(chip_smoke.SmokeFailure, match=f"T={rows} "):
            chip_smoke.require_tile_write(text, rows, "the window")


def test_the_kernels_phase_prints_a_record_of_any_check(monkeypatch, capsys):
    """A child's ``SMOKE kernel`` record is a difference beside its limit,
    or a check of another kind (bits equal, a wrong reference's distance):
    the phase prints each and reaches its own checks behind them."""
    import chip_smoke
    child = "\n".join([
        'SMOKE device {"platform": "cpu", "kind": "cpu", "count": 1}',
        'SMOKE kernel {"name": "a", "tol": 0.02, "normalised": 0.001, '
        '"max_abs_diff": 0.002, "ref_max": 2.0}',
        'SMOKE kernel {"name": "bits", "equal": true}',
        'SMOKE kernel {"name": "moved by", "max_abs_diff": 0.1, '
        '"program_off_by": 1e-7}'])
    monkeypatch.setattr(chip_smoke, "run_child", lambda *a, **kw: child)
    assert chip_smoke.phase_kernels({})["platform"] == "cpu"
    out = capsys.readouterr().out
    assert "kernel a: max_abs_diff 2.000e-03" in out
    assert "kernel bits: {'equal': True}" in out
    assert "kernel moved by: {'max_abs_diff': 0.1" in out
