"""Interleaved (virtual-stage) pipeline schedule: numerics identical to
GPipe/sequential, bubble accounting strictly smaller (VERDICT r1 #7)."""

import jax
import numpy as np
import pytest
from jax.sharding import NamedSharding

from tpu_dist.comm import mesh as mesh_lib
from tpu_dist.config import TrainConfig
from tpu_dist.nn.vit import ViTDef
from tpu_dist.nn.vit_pp import ViTPipelineDef
from tpu_dist.parallel.pipeline import bubble_fraction
from tpu_dist.train.optim import SGD
from tpu_dist.train.state import TrainState
from tpu_dist.train.step import make_train_step
from tpu_dist.train.trainer import Trainer, register_model


def _model(interleave=1):
    return ViTPipelineDef(
        image_size=16, patch_size=4, dim=32, depth=8, heads=4, num_classes=5,
        interleave=interleave, pp_stages=4 if interleave > 1 else 0,
    )


def test_bubble_fraction_shrinks_with_interleave():
    g = bubble_fraction(4, 4)              # GPipe: 3/7
    i2 = bubble_fraction(4, 4, interleave=2)  # 3/11
    assert abs(g - 3 / 7) < 1e-12
    assert abs(i2 - 3 / 11) < 1e-12
    assert i2 < g


def test_interleaved_sequential_forward_matches_plain_vit():
    """Device-major storage + un-permutation: the sequential path of an
    interleaved def must equal the plain ViT forward from the same key."""
    import jax.numpy as jnp

    pp = _model(interleave=2)
    plain = ViTDef(image_size=16, patch_size=4, dim=32, depth=8, heads=4,
                   num_classes=5)
    p_pp, s = pp.init(jax.random.PRNGKey(0))
    p_plain, _ = plain.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 16, 3), jnp.float32)
    out_pp, _ = pp.apply(p_pp, s, x)
    out_plain, _ = plain.apply(p_plain, {}, x)
    np.testing.assert_allclose(np.asarray(out_pp), np.asarray(out_plain),
                               rtol=2e-5, atol=2e-6)


def test_interleaved_pp_training_matches_single_device():
    model = _model(interleave=2)
    opt = SGD()
    mesh2d = mesh_lib.device_mesh([2, 4], ["data", "pipe"])
    mesh1 = mesh_lib.device_mesh([1], ["data"], jax.devices()[:1])
    specs = model.pp_param_specs("pipe")

    params, s = model.init(jax.random.PRNGKey(0))
    st = TrainState.create(params, s, opt)
    place = lambda tree: jax.tree_util.tree_map(
        lambda leaf, spec: jax.device_put(leaf, NamedSharding(mesh2d, spec)), tree, specs
    )
    s_pp = TrainState(
        params=place(st.params),
        bn_state=jax.device_put(st.bn_state, mesh_lib.replicated(mesh2d)),
        opt_state=place(st.opt_state),
        step=jax.device_put(st.step, mesh_lib.replicated(mesh2d)),
    )
    s_1 = jax.device_put(st, mesh_lib.replicated(mesh1))

    step_pp = make_train_step(
        model.apply, opt, mesh2d, sync_bn=False, donate=False,
        pp_axis="pipe", param_specs=specs,
    )
    step_1 = make_train_step(model.apply, opt, mesh1, sync_bn=False, donate=False)

    rng = np.random.default_rng(0)
    for _ in range(3):
        x = rng.normal(size=(8, 16, 16, 3)).astype(np.float32)
        y = rng.integers(0, 5, 8).astype(np.int32)
        s_pp, m_pp = step_pp(
            s_pp, mesh_lib.shard_batch(mesh2d, x), mesh_lib.shard_batch(mesh2d, y), 0.05
        )
        s_1, m_1 = step_1(
            s_1, mesh_lib.shard_batch(mesh1, x), mesh_lib.shard_batch(mesh1, y), 0.05
        )

    np.testing.assert_allclose(float(m_pp["loss"]), float(m_1["loss"]), rtol=1e-4)
    for a, b in zip(
        jax.tree_util.tree_leaves(jax.device_get(s_pp.params)),
        jax.tree_util.tree_leaves(jax.device_get(s_1.params)),
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-5)


def test_trainer_pp_interleaved_e2e():
    register_model(
        "vit_pp_d8",
        lambda num_classes=10: ViTPipelineDef(
            image_size=32, dim=32, depth=8, heads=4, num_classes=num_classes
        ),
    )
    cfg = TrainConfig(
        dataset="synthetic", model="vit_pp_d8", num_classes=10, batch_size=16,
        epochs=1, steps_per_epoch=2, log_every=1, eval_every=0, lr=0.05,
        pp=4, pp_interleave=2, sync_bn=False, synthetic_n=160,
    )
    out = Trainer(cfg).train_epoch(0)
    assert np.isfinite(out["loss"])


def test_interleaved_m2s_matches_single_device():
    """M = 2S: the buffered lap-boundary handoff (depth M-S+1 ring buffer)
    must reproduce sequential numerics exactly (VERDICT r2 #7)."""
    model = _model(interleave=2)
    opt = SGD()
    mesh2d = mesh_lib.device_mesh([2, 4], ["data", "pipe"])
    mesh1 = mesh_lib.device_mesh([1], ["data"], jax.devices()[:1])
    specs = model.pp_param_specs("pipe")

    params, s = model.init(jax.random.PRNGKey(2))
    st = TrainState.create(params, s, opt)
    place = lambda tree: jax.tree_util.tree_map(
        lambda leaf, spec: jax.device_put(leaf, NamedSharding(mesh2d, spec)), tree, specs
    )
    s_pp = TrainState(
        params=place(st.params),
        bn_state=jax.device_put(st.bn_state, mesh_lib.replicated(mesh2d)),
        opt_state=place(st.opt_state),
        step=jax.device_put(st.step, mesh_lib.replicated(mesh2d)),
    )
    s_1 = jax.device_put(st, mesh_lib.replicated(mesh1))

    step_pp = make_train_step(
        model.apply, opt, mesh2d, sync_bn=False, donate=False,
        pp_axis="pipe", param_specs=specs,
        model_kwargs={"n_microbatches": 8},  # M = 2S with S = 4
    )
    step_1 = make_train_step(model.apply, opt, mesh1, sync_bn=False, donate=False)

    rng = np.random.default_rng(3)
    for _ in range(2):
        x = rng.normal(size=(32, 16, 16, 3)).astype(np.float32)
        y = rng.integers(0, 5, 32).astype(np.int32)
        s_pp, m_pp = step_pp(
            s_pp, mesh_lib.shard_batch(mesh2d, x), mesh_lib.shard_batch(mesh2d, y), 0.05
        )
        s_1, m_1 = step_1(
            s_1, mesh_lib.shard_batch(mesh1, x), mesh_lib.shard_batch(mesh1, y), 0.05
        )

    np.testing.assert_allclose(float(m_pp["loss"]), float(m_1["loss"]), rtol=1e-4)
    for a, b in zip(
        jax.tree_util.tree_leaves(jax.device_get(s_pp.params)),
        jax.tree_util.tree_leaves(jax.device_get(s_1.params)),
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-5)


def test_bubble_shrinks_past_the_m_eq_s_corner():
    # the whole point of lifting M == S: more microbatches, smaller bubble
    assert bubble_fraction(4, 8, interleave=2) < bubble_fraction(4, 4, interleave=2)


def test_interleave_rejects_bad_configs():
    import pytest

    with pytest.raises(ValueError, match="pp_microbatches >= pp"):
        Trainer(TrainConfig(
            dataset="synthetic", model="vit_pp_tiny", num_classes=10,
            batch_size=16, pp=4, pp_interleave=2, pp_microbatches=2,
            sync_bn=False, synthetic_n=160,
        ))
    with pytest.raises(ValueError, match="n_microbatches >= n_stages"):
        # direct API misuse: interleaved schedule with M < S
        from tpu_dist.parallel.pipeline import pipeline_apply_interleaved

        import jax.numpy as jnp
        from tpu_dist.comm.compat import shard_map
        from jax.sharding import PartitionSpec as P

        mesh = mesh_lib.device_mesh([4], ["pipe"], jax.devices()[:4])
        shard_map(
            lambda x: pipeline_apply_interleaved(
                lambda p, h: h, None, x, "pipe", 4, 2
            ),
            mesh=mesh, in_specs=(P(),), out_specs=P(), check_vma=False,
        )(jnp.zeros((2, 2, 4)))


def test_interleaved_ckpt_refuses_layout_mismatch(tmp_path):
    """Interleaved storage permutes block order on disk — resuming under a
    different pp/pp_interleave must be refused, not run silently wrong."""
    import pytest

    register_model(
        "vit_pp_d8b",
        lambda num_classes=10: ViTPipelineDef(
            image_size=32, dim=32, depth=8, heads=4, num_classes=num_classes
        ),
    )
    cfg = TrainConfig(
        dataset="synthetic", model="vit_pp_d8b", num_classes=10, batch_size=16,
        epochs=1, steps_per_epoch=1, log_every=1, eval_every=0, lr=0.05,
        pp=4, pp_interleave=2, sync_bn=False, synthetic_n=160,
        ckpt_dir=str(tmp_path), save_every=1,
    )
    Trainer(cfg).fit()

    # same layout: resumes fine
    t2 = Trainer(cfg.replace(resume=True, epochs=1))
    assert t2.start_epoch == 1

    # different interleave: refused with a clear message
    with pytest.raises(ValueError, match="layout-specific"):
        Trainer(cfg.replace(resume=True, pp_interleave=1, pp_microbatches=0))


def test_interleave_without_pp_is_refused():
    import pytest

    with pytest.raises(ValueError, match="no effect without pp"):
        Trainer(TrainConfig(
            dataset="synthetic", model="vit_tiny", num_classes=10,
            batch_size=16, pp_interleave=2, sync_bn=False, synthetic_n=160,
        ))


def test_untagged_ckpt_refused_by_interleaved_resume(tmp_path):
    """A pre-layout-tag checkpoint (logical block order) must not be
    resumed by an interleaved config."""
    import json
    import numpy as np
    import pytest
    from tpu_dist import ckpt as ckpt_lib

    register_model(
        "vit_pp_d8c",
        lambda num_classes=10: ViTPipelineDef(
            image_size=32, dim=32, depth=8, heads=4, num_classes=num_classes
        ),
    )
    cfg = TrainConfig(
        dataset="synthetic", model="vit_pp_d8c", num_classes=10, batch_size=16,
        epochs=1, steps_per_epoch=1, log_every=1, eval_every=0, lr=0.05,
        pp=4, sync_bn=False, synthetic_n=160,
        ckpt_dir=str(tmp_path), save_every=1,
    )
    Trainer(cfg).fit()
    # strip the layout tag to simulate an old checkpoint
    path = ckpt_lib.latest_checkpoint(str(tmp_path))[0]
    with np.load(path) as z:
        flat = {k: z[k] for k in z.files}
    meta = json.loads(bytes(flat["__meta__"].tobytes()).decode())
    meta.pop("pp_interleave"); meta.pop("pp")
    flat["__meta__"] = np.frombuffer(json.dumps(meta).encode(), dtype=np.uint8)
    with open(path, "wb") as f:
        np.savez(f, **flat)

    with pytest.raises(ValueError, match="no pipeline-layout tag"):
        Trainer(cfg.replace(resume=True, pp_interleave=2))


def test_interleave_on_unsupporting_model_is_refused():
    """A registered pp-capable model without interleave fields gets a clean
    ValueError, not a dataclasses TypeError."""
    import pytest

    class PPButNoInterleave:
        depth = 4
        def init(self, key):  # pragma: no cover - never reached
            raise NotImplementedError
        def apply(self, params, state, x, *, train=False, axis_name=None,
                  pp_axis=None, n_microbatches=0):  # pragma: no cover
            raise NotImplementedError
        def pp_param_specs(self, axis):  # pragma: no cover
            raise NotImplementedError

    register_model("pp_no_ilv", lambda num_classes=10: PPButNoInterleave())
    with pytest.raises(ValueError, match="interleaved schedule"):
        Trainer(TrainConfig(
            dataset="synthetic", model="pp_no_ilv", num_classes=10,
            batch_size=16, pp=4, pp_interleave=2, sync_bn=False,
            synthetic_n=160,
        ))
