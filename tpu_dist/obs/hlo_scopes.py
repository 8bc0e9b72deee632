"""Which ops of the compiled train step belong to which named scope, and to
which phase of the step.

The program opens every scope through :func:`scope` (``jax.named_scope`` and a
note of the name): the step's phases in ``train/step.py`` / ``train/epoch.py``
(``step/loss_grad``, ``step/optimizer``, ...) and every block of the models in
``nn/`` (``ssm/scan``, ``vit/mlp``, ``resnet/stage1``, ...); :data:`SCOPES` is
the whole list. A scope reaches every HLO instruction's ``op_name`` metadata,
forward, transposed and recomputed (:func:`phase_of` has the forms), but not
the device trace: a v5e capture names an op event by its HLO text without the
metadata and attaches no stat that holds it (read off a capture, PR 33). So
the program keeps the join itself: where the trainer compiles its step for the
cost capture (``obs/costmodel.analyze_jitted``, the first dispatch of a run on
a TPU), the executable's text is parsed once into ``instruction name ->
op_name``, and a reader of a trace asks :func:`ops_in` for the instruction
names of a scope, :func:`ops_in_phase` for those of a phase.

Scopes are matched as substrings of ``op_name``: no scope's name holds
another's and none is a primitive's name (``tests/test_hlo_scopes_phases.py``
holds the sites to the table).

The table is only as good as the executable's metadata. JAX's persistent
compile cache keys a program without its debug info unless
``jax_compilation_cache_include_metadata_in_key`` is set
(``compile_cache.enable`` sets it), so an executable compiled from another
tree's names can be served: :func:`record` is given the names this process
opened while tracing and counts those the text lacks (gauge
``hlo_scopes.missing``); a reader trusts the table only where that is 0.

Host-side, once a run, no device work. A fusion carries one ``op_name``, so
an op that XLA fused across a scope's edge counts on one side of it (on the
v5e a convolution's weight gradient fused with its SGD update carries the
convolution's and counts as ``backward``); an instruction XLA made itself (a
copy, a rewritten custom call) may carry none and is in no scope and no phase.
"""

from __future__ import annotations

import re
import warnings
from typing import Dict, FrozenSet, Iterable, NamedTuple, Optional, Tuple

from tpu_dist.obs import counters as counters_lib


class Scope(NamedTuple):
    file: str     # where it is opened, under tpu_dist/
    layer: str    # PERF.md's layer
    kind: str     # "block": a region of a model; "step": a phase of the step;
                  # "collective": a phase that compiles to nothing on one device
    metric: str   # the benchmark's metric that reads it
    wraps: str


_SPLIT = "device_fwd_ms, device_bwd_ms, device_recompute_ms"
_BLOCK = "device_unscoped_share (its complement)"
_NH, _VIT, _RESNET = "nn/nemotron_h.py", "nn/vit.py", "nn/resnet.py"
_STEP = "train/step.py, train/epoch.py"

#: every scope the program opens (``docs/observability.md`` has the same table)
SCOPES: Dict[str, Scope] = {
    "step/loss_grad": Scope(_STEP, "train step", "step", _SPLIT,
                            "value_and_grad of the loss, the accumulating scan with it"),
    "step/grad_reduce": Scope(_STEP, "comm", "collective", "device_fwd_ms's partition line",
                              "the gradients' pmean / compressed reduce / psum_scatter"),
    "step/optimizer": Scope(_STEP, "train step", "step", "device_opt_ms",
                            "clip_grads and optimizer.update"),
    "step/metrics": Scope(_STEP, "train step", "step", "device_fwd_ms's partition line",
                          "top-k hits and the metrics' reductions"),
    "data/take_crop": Scope("train/epoch.py", "input", "step", "device_fwd_ms's partition line",
                            "the fused epoch's jnp.take, random crop and normalise"),
    "lm/embed": Scope(_NH, "train step", "block", _BLOCK, "the embedding's rows"),
    "lm/final_norm": Scope(_NH, "train step", "block", _BLOCK, "the norm before the head"),
    "lm/head_loss": Scope(_NH, "kernels", "block", _BLOCK, "the blocked head product and loss"),
    "block/norm": Scope(_NH, "train step", "block", _BLOCK, "a block's rms_norm"),
    "block/residual": Scope(_NH, "train step", "block", _BLOCK, "x + block(x)"),
    "ssm/in_proj": Scope(_NH, "train step", "block", _BLOCK, "the mixer's input product and its splits"),
    "ssm/conv1d": Scope(_NH, "train step", "block", _BLOCK,
                        "depthwise taps, bias and silu over x, B, C (ops/causal_conv1d.py where it "
                        "fits), the softplus of dt"),
    "ssm/scan": Scope(_NH, "kernels", "block", "ssm_scan_roofline_share", "the chunked scan and the skip"),
    "ssm/gate_norm": Scope(_NH, "train step", "block", _BLOCK, "gate, grouped norm, gnorm"),
    "ssm/out_proj": Scope(_NH, "train step", "block", _BLOCK, "the mixer's output product"),
    "conv/in_proj": Scope(_NH, "train step", "block", _BLOCK, "the short convolution's input product"),
    "conv/short": Scope(_NH, "kernels", "block", "short_conv_roofline_share", "B * u, the taps, C * w"),
    "conv/out_proj": Scope(_NH, "train step", "block", _BLOCK, "the short convolution's output product"),
    "ffn/dense": Scope(_NH, "train step", "block", _BLOCK, "the dense gated feed-forward"),
    "attn/qkv": Scope(_NH, "train step", "block", _BLOCK, "the three projections"),
    "attn/rope": Scope(_NH, "train step", "block", _BLOCK, "q/k norm and rotation"),
    "attn/causal": Scope(_NH, "kernels", "block", "lm_attn_roofline_share", "causal attention"),
    "attn/out": Scope(_NH, "train step", "block", _BLOCK, "attention's output product"),
    "moe/route": Scope(_NH, "train step", "block", _BLOCK, "router scores, top-k, the load count"),
    "moe/experts": Scope(_NH, "kernels", "block", "moe_gmm_roofline_share", "dropless_experts"),
    "moe/shared": Scope(_NH, "train step", "block", _BLOCK, "the shared expert"),
    "vit/patch_embed": Scope(_VIT, "train step", "block", _BLOCK, "patchify, the patch product, positions"),
    "vit/norm": Scope(_VIT, "train step", "block", _BLOCK, "every LayerNorm"),
    "vit/qkv": Scope(_VIT, "train step", "block", _BLOCK,
                     "projected_attention: the qkv product and the attention behind it "
                     "(the kernel pair keeps its op names short_attn_fwd / short_attn_bwd)"),
    "vit/attn_out": Scope(_VIT, "train step", "block", _BLOCK, "attention's output product and residual"),
    "vit/mlp": Scope(_VIT, "train step", "block", _BLOCK, "mlp1, GELU, mlp2 and residual"),
    "vit/head": Scope(_VIT, "train step", "block", _BLOCK, "the token mean and the classifier"),
    "resnet/stem": Scope(_RESNET, "train step", "block", _BLOCK, "stem convolution, BN, ReLU, max-pool"),
    "resnet/stage1": Scope(_RESNET, "train step", "block", _BLOCK, "the first stage's blocks"),
    "resnet/stage2": Scope(_RESNET, "train step", "block", _BLOCK, "the second stage's blocks"),
    "resnet/stage3": Scope(_RESNET, "train step", "block", _BLOCK, "the third stage's blocks"),
    "resnet/stage4": Scope(_RESNET, "train step", "block", _BLOCK, "the fourth stage's blocks"),
    "resnet/head": Scope(_RESNET, "train step", "block", _BLOCK, "global average pool and the classifier"),
}
BLOCK_SCOPES = tuple(name for name, s in SCOPES.items() if s.kind == "block")

#: the step's phases; every instruction falls in exactly one (:func:`phase_of`)
PHASES = ("forward", "backward", "recompute", "optimizer", "grad_reduce", "metrics", "data", "other")
_PHASE_SCOPES = (("step/optimizer", "optimizer"), ("step/grad_reduce", "grad_reduce"),
                 ("step/metrics", "metrics"), ("data/", "data"))

_INSTRUCTION = re.compile(
    r'^\s*(?:ROOT )?%?(?P<op>[^\s=]+) = .*?metadata=\{[^}]*?op_name="(?P<name>[^"]*)"'
)
_OP_NAMES: Dict[str, str] = {}
_OPENED: set = set()
_MISSING: Tuple[str, ...] = ()


def scope(name: str):
    """``jax.named_scope(name)``, and a note that this process's traces opened
    ``name``; the one way the program opens a scope."""
    import jax  # noqa: PLC0415

    _OPENED.add(name)
    return jax.named_scope(name)


def opened() -> FrozenSet[str]:
    """The scopes this process's traces opened so far (all its programs')."""
    return frozenset(_OPENED)


def forget_opened() -> None:
    _OPENED.clear()


def record(hlo_text: str, opened: Optional[Iterable[str]] = None) -> int:
    """Replace the table by the instructions of ``hlo_text`` (a compiled
    executable's ``as_text()``); returns how many carry an ``op_name``.
    ``opened``: the scopes the program opened while tracing; those no
    ``op_name`` holds are counted in the gauge ``hlo_scopes.missing`` and
    named in one warning (the executable came from other names: a compile
    cache keyed without metadata). A collective's scope is not held to this:
    a mean over one device compiles to nothing."""
    global _MISSING
    _OP_NAMES.clear()
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m:
            _OP_NAMES[m.group("op")] = m.group("name")
    names = set(_OP_NAMES.values())
    _MISSING = tuple(sorted(
        s for s in opened or ()
        if not any(s in n for n in names) and not (s in SCOPES and SCOPES[s].kind == "collective")
    ))
    counters_lib.set_gauge("hlo_scopes.missing", len(_MISSING))
    if _MISSING:
        warnings.warn(
            f"hlo_scopes: the compiled step names none of its ops under {len(_MISSING)} scope(s) "
            f"this process opened ({', '.join(_MISSING)}): the executable was compiled from other "
            "names (see compile_cache.enable), so no reader trusts this table",
            RuntimeWarning, stacklevel=2)
    return len(_OP_NAMES)


def missing() -> int:
    """How many opened scopes the recorded text lacks; 0 is a table to trust."""
    return len(_MISSING)


def ops_in(scope: str) -> FrozenSet[str]:
    """Names of the recorded instructions whose ``op_name`` holds ``scope``."""
    return frozenset(op for op, name in _OP_NAMES.items() if scope in name)


def recorded() -> int:
    return len(_OP_NAMES)


def name_of(op: str) -> str:
    """The recorded ``op_name`` of instruction ``op``; empty where it has none."""
    return _OP_NAMES.get(op, "")


def phase_of(op_name: str) -> str:
    """The phase of an instruction by its ``op_name``, in the forms jax 0.9.0
    writes: ``recompute`` for ``.../checkpoint/rematted_computation/blk/tanh``
    (a recomputed forward inside the backward pass); else ``backward`` for
    ``.../transpose(jvp(blk))/dot_general`` and, a recomputed block's,
    ``.../transpose(jvp(step/loss_grad))/jvp()/checkpoint/blk/mul``; else
    ``forward`` for ``jit(step)/step/loss_grad/jvp(blk)/tanh``; ``optimizer``,
    ``grad_reduce``, ``metrics``, ``data`` by their scopes; else ``other``."""
    if "rematted_computation" in op_name:
        return "recompute"
    if "transpose(" in op_name:
        return "backward"
    if "step/loss_grad" in op_name:
        return "forward"
    for held, phase in _PHASE_SCOPES:
        if held in op_name:
            return phase
    return "other"


def ops_in_phase(phase: str) -> FrozenSet[str]:
    """Names of the recorded instructions of ``phase``; an instruction with
    no ``op_name`` is in no table and counts as ``other`` with its reader."""
    return frozenset(op for op, name in _OP_NAMES.items() if phase_of(name) == phase)


def has_phases() -> bool:
    """Whether the recorded program opened ``step/loss_grad``: without it
    there is no split to read."""
    return any("step/loss_grad" in name for name in _OP_NAMES.values())


def attributed_ops() -> FrozenSet[str]:
    """Names of the recorded instructions under a block's scope or in one of
    the phases ``optimizer``, ``grad_reduce``, ``metrics``, ``data``: what a
    reader can give an address; the rest of the step is "outside every scope"."""
    placed = {phase for _, phase in _PHASE_SCOPES}
    return frozenset(
        op for op, name in _OP_NAMES.items()
        if any(s in name for s in BLOCK_SCOPES) or phase_of(name) in placed
    )
