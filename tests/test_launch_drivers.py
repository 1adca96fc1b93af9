"""Smoke tests for the real launch drivers (train/serve) on reduced
configs, including checkpoint resume."""
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

pytestmark = pytest.mark.slow  # deselect via -m 'not slow'


def run_cli(module_main, argv):
    old = sys.argv
    sys.argv = argv
    try:
        module_main()
    finally:
        sys.argv = old


def test_train_driver_runs_and_resumes(tmp_path, capsys):
    from repro.launch.train import main
    ckpt = str(tmp_path / "ckpt")
    run_cli(main, ["train", "--arch", "smollm-360m", "--reduced",
                   "--steps", "6", "--batch", "2", "--seq", "32",
                   "--ckpt-dir", ckpt, "--ckpt-every", "3"])
    out1 = capsys.readouterr().out
    assert "done: final loss" in out1
    # resume from checkpoint: should start at step 6 and exit immediately
    run_cli(main, ["train", "--arch", "smollm-360m", "--reduced",
                   "--steps", "8", "--batch", "2", "--seq", "32",
                   "--ckpt-dir", ckpt])
    out2 = capsys.readouterr().out
    assert "resumed from step 6" in out2


def test_train_driver_loss_decreases(capsys):
    from repro.launch.train import main
    run_cli(main, ["train", "--arch", "granite-3-2b", "--reduced",
                   "--steps", "60", "--batch", "8", "--seq", "64",
                   "--lr", "5e-3", "--log-every", "59"])
    out = capsys.readouterr().out
    losses = [float(l.split("loss")[1].split()[0])
              for l in out.splitlines() if l.startswith("step")]
    # the bigram structure is learnable: expect a clear drop from ln(512)
    assert losses[-1] < losses[0] - 1.0, out


@pytest.mark.parametrize("env_dir", ["", "/elsewhere/cache"])
def test_compile_cache_dir(env_dir, monkeypatch):
    """JAX_COMPILATION_CACHE_DIR, when set, is left to JAX; otherwise the
    cache goes to the one fixed directory at the root of the checkout."""
    from pathlib import Path

    from repro import compile_cache
    calls = []
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    monkeypatch.setattr(compile_cache.jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    compile_cache.enable_compile_cache()
    if env_dir:
        assert calls == []
    else:
        assert calls == [("jax_compilation_cache_dir",
                          str(compile_cache.CACHE_DIR))]
        assert compile_cache.CACHE_DIR.parent == \
            Path(__file__).resolve().parents[1]


def test_inference_demo_driver_runs(capsys):
    from repro.launch.inference_demo import main
    run_cli(main, ["inference_demo", "--arch", "smollm-360m", "--reduced",
                   "--batch", "2", "--prompt-len", "8", "--gen", "4"])
    out = capsys.readouterr().out
    assert "decoded" in out


def test_serve_shim_warns_and_forwards():
    # the old (misleading) name stays importable but deprecated
    import importlib
    import repro.launch.inference_demo as demo
    with pytest.warns(DeprecationWarning, match="inference_demo"):
        import repro.launch.serve as shim
        importlib.reload(shim)
    assert shim.main is demo.main
