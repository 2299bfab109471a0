"""End-to-end system behaviour: train -> checkpoint -> crash -> resume ->
serve, through the public launchers (the paths a user actually runs)."""
import os

import numpy as np


def test_train_resume_serve_roundtrip(tmp_path):
    """Train a smoke model, stop it, resume from the checkpoint, verify the
    loss continues from where it left off."""
    from repro.launch.train import main as train_main

    ckpt = str(tmp_path / "ckpt")
    args = ["--arch", "smollm-135m", "--smoke", "--seq", "64", "--batch",
            "4", "--lr", "5e-3", "--ckpt", ckpt, "--ckpt-every", "10",
            "--log-every", "50", "--data-branch", "2", "--data-docs", "4"]
    loss_a = train_main(args + ["--steps", "20"])
    # resume for 10 more steps — must restore step 20's state
    loss_b = train_main(args + ["--steps", "30", "--resume"])
    assert np.isfinite(loss_a) and np.isfinite(loss_b)
    assert loss_b < loss_a + 0.5  # no reset-to-init blowup

    from repro.ft.checkpoint import latest_step
    assert latest_step(ckpt) == 30


def test_training_learns_smoke():
    """The smoke LM must actually learn the synthetic Markov structure."""
    from repro.launch.train import main as train_main
    final = train_main(["--arch", "smollm-135m", "--smoke", "--steps", "60",
                        "--seq", "64", "--batch", "8", "--lr", "1e-2",
                        "--log-every", "30",
                        "--data-branch", "2", "--data-docs", "2"])
    import math
    start = math.log(256)  # smoke vocab
    assert final < start - 1.0, f"loss {final} vs start {start}"


def test_serve_driver_end_to_end():
    from repro.launch.serve import main as serve_main
    toks = serve_main(["--arch", "smollm-135m", "--smoke", "--batch", "2",
                       "--prompt-len", "8", "--new-tokens", "8"])
    assert np.asarray(toks).size == 16


def test_compile_cache_dir(monkeypatch):
    """The entry points' compile cache: ``$JAX_COMPILATION_CACHE_DIR`` when
    set (left for JAX to read), else the one fixed, gitignored directory
    inside the checkout."""
    import jax
    from repro.launch.compile_cache import setup_compile_cache

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    was = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/jax")
        assert setup_compile_cache() == "/elsewhere/jax"
        assert jax.config.jax_compilation_cache_dir is None
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = setup_compile_cache()
        assert path == os.path.join(repo, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert setup_compile_cache() == path      # fixed, not per run
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
    with open(os.path.join(repo, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_dryrun_single_cell_smoke(tmp_path):
    """The dry-run machinery itself (lower+compile+roofline) on a tiny mesh,
    via a subprocess with forced devices."""
    import subprocess
    import sys
    import textwrap
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax
        from repro.configs import get_smoke
        from repro.configs.base import ShapeCell
        from repro.launch.specs import build_cell
        from repro.roofline import analysis
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        cfg = get_smoke("gemma-7b")
        for shape in (ShapeCell("t", 64, 4, "train"),
                      ShapeCell("d", 64, 4, "decode")):
            fn, args, in_sh, out_sh, rules = build_cell(cfg, shape, mesh)
            with mesh:
                c = jax.jit(fn, in_shardings=in_sh,
                            out_shardings=out_sh).lower(*args).compile()
            roof = analysis.analyze(c.cost_analysis(), c.as_text(), 8,
                                    analysis.model_flops(cfg, shape))
            assert roof.compute_s > 0 or roof.memory_s > 0
            assert roof.dominant in ("compute", "memory", "collective")
        print("DRYRUN-SMOKE-OK")
    """)
    r = subprocess.run([sys.executable, "-c", prog],
                       env={**os.environ, "PYTHONPATH": src},
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "DRYRUN-SMOKE-OK" in r.stdout
