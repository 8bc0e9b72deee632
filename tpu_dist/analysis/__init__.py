"""Static + trace-time + compile-time analysis of the training system.

Three layers (see ``docs/analysis.md``):

* **Layer 1 — AST lint** (:mod:`tpu_dist.analysis.lint`): walks the package
  source with ``ast`` and flags TPU-hostile idioms — host syncs in jitted
  step functions, unguarded non-rank-0 I/O, hot-path ``jax.jit`` without
  donation, version-fragile JAX imports, trace-time nondeterminism,
  rank-guarded collective call sites. Rules TD001-TD008. No jax import
  needed; runs in milliseconds.
* **Layer 2 — jaxpr audit** (:mod:`tpu_dist.analysis.jaxpr_audit`):
  abstractly traces the registered train-step builders on an emulated CPU
  mesh and inspects the closed jaxpr — collective counts asserted against
  the parallelism config's budget, unexpected transfer ops, bf16→f32
  promotion creep, quantized wire-byte ratios, the armed-vs-off no-op
  contracts. Rules TD101-TD115.
* **Layer 3 — HLO shard audit** (:mod:`tpu_dist.analysis.shardlint`):
  lowers and compiles every config family and parses the OPTIMIZED HLO —
  the program GSPMD actually emitted — into a structured collective
  inventory; the compiled accounting must agree with the jaxpr ring model
  (TD116) and carry no unpredicted reshard (TD117). Emits
  ``shard_report.json`` (docs/shard_report.md).

CLI: ``python -m tpu_dist.analysis [--format text|json] [--baseline F]``
for Layers 1+2; ``python -m tpu_dist.analysis shard [--out F]`` for
Layer 3. Exit 0 = clean (after suppressions + baseline), 1 = violations,
2 = error.

Keep this ``__init__`` import-light: the CLI must be able to configure the
emulated mesh before anything touches a jax backend.
"""

from tpu_dist.analysis.rules import RULES, Rule, Violation  # noqa: F401


def lint_paths(*args, **kwargs):
    from tpu_dist.analysis.lint import lint_paths as _impl

    return _impl(*args, **kwargs)


def audit_all(*args, **kwargs):
    from tpu_dist.analysis.jaxpr_audit import audit_all as _impl

    return _impl(*args, **kwargs)


def shard_all(*args, **kwargs):
    from tpu_dist.analysis.shardlint import shard_all as _impl

    return _impl(*args, **kwargs)
