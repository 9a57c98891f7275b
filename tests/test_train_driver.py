"""The training driver (repro.launch.train) at smoke width: the simulated
kill restores the newest checkpoint, and every failure exits non-zero
instead of training on unprotected."""
import jax
import numpy as np
import pytest

from repro import runtime
from repro.core import Cluster, TierTopology, VelocClient
from repro.core import format as fmt
from repro.launch import train


@pytest.fixture(autouse=True)
def no_checkout_cache(monkeypatch, tmp_path):
    """Keep the persistent compile cache out of the checkout: with the
    variable set, ``use_compile_cache`` leaves JAX's config alone."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax"))


def _main(scratch, *extra):
    return train.main(["--smoke", "--steps", "6", "--ckpt-every", "2",
                       "--seq-len", "16", "--batch", "2",
                       "--scratch", str(scratch), *extra])


def test_fail_at_restores_newest_version_and_replays(tmp_path):
    run = _main(tmp_path, "--mode", "async", "--fail-at", "5")
    assert run.restored_from == 4 and run.version == 6
    assert len(run.losses) == 7  # steps 1..5, then 5..6 replayed from v4
    client = VelocClient(run.pipeline,
                         Cluster(TierTopology(scratch=str(tmp_path))))
    try:
        v, restored = client.restart_latest(run.state)
    finally:
        client.shutdown()
    assert v == 6
    for a, b in zip(jax.tree.leaves(run.state), jax.tree.leaves(restored)):
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype and a.tobytes() == b.tobytes()


def test_resume_without_checkpoint_is_an_error(tmp_path):
    with pytest.raises(RuntimeError, match="no restorable checkpoint"):
        _main(tmp_path, "--resume", "--mode", "sync")
    run = _main(tmp_path, "--resume", "--cold-start-ok", "--mode", "sync",
                "--steps", "2")
    assert run.version == 2 and run.restored_from is None


@pytest.mark.parametrize("mode", ["sync", "async"])
def test_failed_checkpoint_fails_the_run(tmp_path, monkeypatch, mode):
    def broken(*_, **__):
        raise OSError("disk full")

    monkeypatch.setattr(fmt, "serialize_shard", broken)
    with pytest.raises(Exception, match="disk full"):
        _main(tmp_path, "--mode", mode, "--steps", "2")


def test_compile_cache_placement(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    before = jax.config.jax_compilation_cache_dir
    try:
        assert runtime.use_compile_cache() == str(
            runtime.DEFAULT_COMPILE_CACHE)
        assert jax.config.jax_compilation_cache_dir == str(
            runtime.DEFAULT_COMPILE_CACHE)
        assert runtime.DEFAULT_COMPILE_CACHE.name == ".jax_cache"
        jax.config.update("jax_compilation_cache_dir", before)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert runtime.use_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == before
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
