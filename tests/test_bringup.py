"""What the chip bring-up (PR 21) added: chip_smoke.py refuses to run
without an accelerator, the compile cache can be placed from outside and is
otherwise one fixed in-checkout directory, more than one jax process per
TPU host is refused before any fork, and a Pallas kernel asked for by name
("on") raises instead of degrading."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code_or_argv, *, cwd=REPO, env=None, timeout=120):
    argv = ([sys.executable, "-c", code_or_argv]
            if isinstance(code_or_argv, str) else code_or_argv)
    full = {k: v for k, v in os.environ.items()
            if k != "JAX_COMPILATION_CACHE_DIR"}
    full["PYTHONPATH"] = REPO
    full.update(env or {})
    return subprocess.run(argv, cwd=cwd, env=full, capture_output=True,
                          text=True, timeout=timeout)


def test_chip_smoke_without_a_chip_fails_at_once():
    r = _run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
             env={"JAX_PLATFORMS": "cpu"}, timeout=60)
    assert r.returncode != 0
    assert "no accelerator" in r.stderr and "'cpu'" in r.stderr
    # no result object, and no phase ran
    assert '"ok"' not in r.stdout and "DATA written" not in r.stdout


_CACHE_PROBE = (
    "import jax\n"
    "from deepfm_tpu.core.platform import configure_runtime\n"
    "print(configure_runtime())\n"
    "print(jax.config.jax_compilation_cache_dir)\n"
)


def test_compile_cache_is_one_fixed_in_checkout_directory(tmp_path):
    """Unset: the same git-ignored path inside the checkout from two
    working directories and two processes."""
    a = _run(_CACHE_PROBE, cwd=REPO).stdout.split()
    b = _run(_CACHE_PROBE, cwd=str(tmp_path)).stdout.split()
    want = os.path.join(REPO, ".jax_cache")
    assert a == b == [want, want]
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_compile_cache_dir_from_the_environment_wins(tmp_path):
    """Set: jax reads the variable itself, the program sets no directory
    in code and reports the one the environment named."""
    placed = str(tmp_path / "placed")
    out = _run(_CACHE_PROBE, cwd=str(tmp_path),
               env={"JAX_COMPILATION_CACHE_DIR": placed}).stdout.split()
    assert out == [placed, placed]


def test_more_than_one_jax_process_per_tpu_host_is_refused(monkeypatch):
    from deepfm_tpu.core.platform import expected_platform, refuse_shared_chip
    from deepfm_tpu.serve.pool.__main__ import main as pool_main
    from deepfm_tpu.serve.server import serve_pool

    # the platform is injected through the variable jax itself obeys; no
    # chip is needed and nothing is forked: each refusal comes before any
    # socket, signal handler or child exists
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    assert expected_platform() == "tpu"
    with pytest.raises(SystemExit, match="one process at a time"):
        serve_pool("/nonexistent", workers=2, port=0)
    with pytest.raises(SystemExit, match="one process at a time"):
        pool_main(["--servable", "/nonexistent", "--groups", "2"])
    with pytest.raises(SystemExit, match="one process at a time"):
        pool_main(["--servable", "/nonexistent", "--groups", "1",
                   "--router", "--autoscale"])
    refuse_shared_chip(1, "one process is always fine")

    # on the CPU the same layouts are allowed (tests/test_serving_endpoint.py
    # and tests/test_serve_pool.py run them for real)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert expected_platform() == "cpu"
    refuse_shared_chip(2, "serve --workers")


def test_resume_across_the_data_parallel_boundary(tmp_path):
    """A checkpoint written under [1,4] (dp=1: replicated optimizer state)
    resumes under [2,2] (dp=2: the dp-sharded layout) through the trainer's
    own ``restore_latest`` — the exact restore fails on the tree structure,
    not on a shape, and must still reach the resharding restore."""
    import jax
    import numpy as np

    from deepfm_tpu.checkpoint import make_checkpointer
    from deepfm_tpu.core.config import Config, MeshConfig
    from deepfm_tpu.parallel import build_mesh, create_spmd_state, make_context
    from deepfm_tpu.train.loop import restore_latest

    cfg = Config.from_dict({"model": {
        "feature_size": 117, "field_size": 6, "embedding_size": 4,
        "deep_layers": (16,), "dropout_keep": (1.0,)}})

    def ctx_for(dp, mp):
        mesh = build_mesh(MeshConfig(data_parallel=dp, model_parallel=mp),
                          devices=jax.devices()[: dp * mp])
        return make_context(
            cfg.with_overrides(mesh={"data_parallel": dp,
                                     "model_parallel": mp}), mesh)

    src = ctx_for(1, 4)
    saved = create_spmd_state(src)
    ckpt = make_checkpointer(str(tmp_path))
    ckpt.save(saved, block=True)
    ckpt.close()

    dst = ctx_for(2, 2)
    assert dst.zero_layout and not src.zero_layout
    events = []

    class Log:
        def event(self, kind, **fields):
            events.append(kind)

    ckpt = make_checkpointer(str(tmp_path))
    restored = restore_latest(ckpt, dst, create_spmd_state(dst), Log())
    ckpt.close()
    assert events == ["resume_reshard"]
    np.testing.assert_array_equal(
        np.asarray(restored.params["fm_v"])[:117],
        np.asarray(saved.params["fm_v"])[:117])
