"""Persistent compile cache: --compile_cache_dir populates an XLA cache a
second invocation of the same config loads from; the one helper that places
the cache (tpu_dist/compile_cache.py) yields to the environment.

The cache setting is process-global jax.config state (that is how XLA's
persistent cache works); this test restores it afterwards so later tests in
the same process don't keep writing into the tmp dir.
"""

import os

import jax
import numpy as np

from tpu_dist.config import TrainConfig
from tpu_dist.train.trainer import Trainer, register_model
from tests.helpers import tiny_resnet

register_model("tiny_resnet_cc", lambda num_classes=10: tiny_resnet(num_classes))


def test_compile_cache_populated_and_reused(tmp_path):
    cache = str(tmp_path / "xla_cache")
    cfg = TrainConfig(
        dataset="synthetic", model="tiny_resnet_cc", num_classes=10,
        batch_size=64, epochs=1, steps_per_epoch=1, log_every=10,
        eval_every=0, lr=0.05, synthetic_n=640, compile_cache_dir=cache,
    )
    # the persistent cache initializes ONCE per process (lazily, at the
    # first compile): when earlier tests in the suite have already compiled
    # with no cache dir, the config update below would be a silent no-op —
    # reset so it re-initializes against this test's tmp dir
    from jax._src import compilation_cache as _cc

    _cc.reset_cache()
    jax.config.update("jax_enable_compilation_cache", True)  # conftest: off
    try:
        # both runs come from one line: the cache's key holds the program's
        # metadata (compile_cache.enable) and, in it, the frames that call
        # the step, so the same call made two lines down is another key
        seen = []
        for _ in range(2):
            t = Trainer(cfg)
            # the tiny model can compile in <1s; persist everything so the
            # assertion below can't fail on a fast host
            jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
            out = t.train_epoch(0)
            assert np.isfinite(out["loss"])
            seen.append({e: os.path.getmtime(os.path.join(cache, e)) for e in os.listdir(cache)})
        mtimes, entries = seen[0], list(seen[0])
        assert entries, "compile cache dir is empty — nothing was persisted"

        # same config again: loaded from cache (no new entries, mtimes unchanged)
        entries2 = set(os.listdir(cache))
        assert entries2 == set(entries)
        for e, t_ in mtimes.items():
            if e.endswith("-atime"):
                # some JAX versions track cache reads in an -atime sidecar
                # that is rewritten on every hit — only the artifact
                # entries must stay untouched
                continue
            assert os.path.getmtime(os.path.join(cache, e)) == t_
    finally:
        jax.config.update("jax_enable_compilation_cache", False)
        jax.config.update("jax_compilation_cache_dir", None)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
        _cc.reset_cache()  # later tests must not keep writing into tmp


def test_enable_yields_to_env_and_is_stable_otherwise(monkeypatch):
    from tpu_dist import compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/from/outside")
    assert compile_cache.enable() == "/placed/from/outside"
    # --compile_cache_dir yields to it too
    assert compile_cache.enable("/from/the/flag") == "/placed/from/outside"
    assert jax.config.jax_compilation_cache_dir == before  # untouched

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    try:
        first, second = compile_cache.enable(), compile_cache.enable()
        assert first == second == os.path.join(repo, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == first
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
