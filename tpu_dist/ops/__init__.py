from tpu_dist.ops.fused_sgd import fused_sgd_leaf  # noqa: F401
