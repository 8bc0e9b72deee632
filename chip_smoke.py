"""On-chip bring-up check: does the system still start on the TPU?

    python chip_smoke.py            # on a machine with 1 or 4 TPU chips

One process drives every chip of the host through the entry points a user
calls, at the full width of the repo's headline model, with random weights
made from a seed:

* ``train`` / ``resume`` — ``tpu_dist.cli.train.main``: ResNet-18, 100
  classes, 32x32, global batch 256, bf16, SyncBN, synthetic data; two epochs
  of a few steps with eval and a checkpoint each, then the newest checkpoint
  is removed and a ``--resume`` run must replay the lost epoch to the losses
  the first run's history recorded.
* ``placement`` / ``dp_equivalence`` / ``ring_flash`` — state and batch
  shards on every chip; one ``make_train_step`` step on a 1-device mesh
  against all devices (fp32, same global batch); one ``--sp N
  --flash_attention`` step (ppermute + Pallas inside ``shard_map``).
* ``fused_sgd`` / ``flash_kernels`` / ``gmm_kernel`` / ``conv_kernel`` /
  ``combine_kernel`` / ``vit_b16_flash_step``
  — every Pallas kernel compiled (``interpret=False``) and compared with its
  jnp/XLA reference (the experts' grouped product inside ``dropless_experts``
  at the two token cells' shapes, the mixers' convolution reading the
  Nemotron cell's projection in place, the experts' combine and the
  dispatch's backward at the LFM2 cell's shapes against the scatter-adds);
  ViT-B/16 at 224 px takes its steps
  through ``bench.run``.

It prints one ``PASS``/``FAIL <phase>: <reason>`` line per phase and, as the
last line of stdout, ``{"ok": true, "device": {...}}`` — only when every
phase passed on a TPU. Without a TPU (an inherited ``JAX_PLATFORMS=cpu``
included) it exits non-zero and prints no result. Any time it prints is
informational: speed is ``bench.py``'s business.

``--rehearse-on-cpu`` runs the same phases at cut batch and sequence sizes
on whatever backend JAX has (kernels interpreted off-TPU) so the script
itself can be debugged without a chip. It proves nothing about the chip and
never prints the result line.
"""

from __future__ import annotations

import argparse
import contextlib
import functools
import importlib.metadata
import io
import json
import math
import os
import shutil
import sys
import tempfile
import time
import traceback

# (B, S, H, D, causal) in bf16: ViT-B/16's attention shape, a long aligned
# one causal and not, and a short one that fills no tile
FLASH_SHAPES = [
    (4, 197, 12, 64, False),
    (1, 4096, 8, 128, False),
    (1, 4096, 8, 128, True),
    (2, 65, 4, 64, False),
]
REHEARSAL_FLASH_SHAPES = [(1, 197, 2, 64, False), (1, 256, 2, 128, True),
                          (2, 65, 2, 64, False)]
FLASH_TOL = 2e-2  # bf16 inputs/outputs: max error over the reference's max
# (name, tokens, hidden, expert width, experts, top-k, buffer rows, gated): the
# expert layers of lfm2_24b_a2b_share and nemotron3_nano_share, 8 experts held
GMM_SHAPES = [("lfm2", 32768, 2048, 1536, 64, 4, 32768, True),
              ("nemotron", 16384, 2688, 1856, 128, 6, 12288, False)]
REHEARSAL_GMM_SHAPES = [("toy_gated", 1024, 256, 384, 16, 2, 2048, True),
                        ("toy_whole_width", 1024, 256, 464, 16, 2, 2048, False)]
# (sequences, tokens, the column borders of x | B | C in proj, taps): the mixer
# of nemotron3_nano_share, whose proj is gate 4096 | x 4096 | B 1024 | C 1024 | dt 64
CONV_SHAPE = (2, 8192, (4096, 8192, 9216, 10240), 4)
REHEARSAL_CONV_SHAPE = (2, 64, (128, 384, 512, 640), 4)
# (tokens, hidden, experts, top-k, buffer rows): the expert layer of
# lfm2_24b_a2b_share, 8 experts held
COMBINE_SHAPE = (32768, 2048, 64, 4, 32768)
REHEARSAL_COMBINE_SHAPE = (1024, 256, 64, 4, 1024)


class SmokeFailure(Exception):
    """A check that did not hold."""


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def finite(x) -> bool:
    return isinstance(x, (int, float)) and math.isfinite(x)


class PallasSpy:
    """Records ``interpret`` of every ``pl.pallas_call`` traced while armed."""

    def __init__(self):
        self.calls = []  # (kernel name, interpret)

    def __enter__(self):
        from jax.experimental import pallas as pl

        self._pl, self._orig = pl, pl.pallas_call

        def spy(kernel, *args, **kwargs):
            name = getattr(getattr(kernel, "func", kernel), "__name__", "?")
            self.calls.append((name, bool(kwargs.get("interpret", False))))
            return self._orig(kernel, *args, **kwargs)

        pl.pallas_call = spy
        self.calls.clear()
        return self

    def __exit__(self, *exc):
        self._pl.pallas_call = self._orig

    def check_compiled(self, on_tpu: bool) -> str:
        check(bool(self.calls), "no Pallas call was traced")
        interpreted = sorted({n for n, interp in self.calls if interp})
        if on_tpu:
            check(not interpreted, f"ran interpreted: {interpreted}")
        kernels = sorted({n for n, _ in self.calls})
        mode = "interpret=False" if not interpreted else "INTERPRETED"
        return f"{len(self.calls)} pallas_call(s) {mode} {kernels}"


class Tee(io.TextIOBase):
    """stdout that also keeps what went through it."""

    def __init__(self, stream):
        self.stream, self.kept = stream, io.StringIO()

    def write(self, s):
        self.kept.write(s)
        return self.stream.write(s)

    def flush(self):
        self.stream.flush()


def read_history(path: str) -> dict:
    """``--log_file`` JSONL -> {kind: [records]} plus the last counters."""
    from tpu_dist.obs.summarize import load_records

    records, bad = load_records(path)
    check(bad == 0, f"{bad} unreadable line(s) in {path}")
    by_kind: dict = {}
    counters: dict = {}
    for rec in records:
        by_kind.setdefault(rec["kind"], []).append(rec)
        counters = rec.get("counters", counters)
    by_kind["counters"] = counters
    return by_kind


def check_history(h: dict, *, epochs, steps: int) -> None:
    train = {r["epoch"]: r for r in h.get("train_epoch", [])}
    evals = {r["epoch"]: r for r in h.get("eval", [])}
    check(sorted(train) == list(epochs), f"train epochs {sorted(train)}")
    check(sorted(evals) == list(epochs), f"eval epochs {sorted(evals)}")
    for e in epochs:
        check(train[e]["steps"] == steps, f"epoch {e}: {train[e]['steps']} steps")
        check(finite(train[e]["loss"]), f"epoch {e} train loss {train[e]['loss']}")
        check(finite(evals[e]["loss"]), f"epoch {e} eval loss {evals[e]['loss']}")
    retraces = h["counters"].get("compile.retraces", 0)
    check(retraces == 0, f"compile.retraces={retraces}")


def train_argv(size: dict, workdir: str, tag: str, *extra: str) -> list:
    return [
        "--dataset", "synthetic", "--synthetic_n", str(size["synthetic_n"]),
        "--model", "resnet18", "--num_classes", "100",
        "--batch_size", str(size["batch"]), "--bf16", "--seed", "0",
        "--steps_per_epoch", str(size["steps"]), "--log_every", "1",
        "--log_file", os.path.join(workdir, f"{tag}.jsonl"), *extra,
    ]


# -- phases: each returns a one-line note, or raises -------------------------


def phase_train(ctx: dict) -> str:
    """§1: the main path through the CLI entry point."""
    from tpu_dist.cli import train as cli_train

    size, wd = ctx["size"], ctx["workdir"]
    ckpt_dir = os.path.join(wd, "ckpt")
    trainer = cli_train.main(train_argv(
        size, wd, "train", "--epochs", "2", "--eval_every", "1",
        "--ckpt_dir", ckpt_dir, "--save_every", "1",
    ))
    h = read_history(os.path.join(wd, "train.jsonl"))
    check_history(h, epochs=(0, 1), steps=size["steps"])
    for e in (0, 1):
        check(os.path.exists(os.path.join(ckpt_dir, f"ckpt_{e}.npz")),
              f"ckpt_{e}.npz was not saved")
    mem = (h.get("memory") or [{}])[0]
    source = (mem.get("reconciliation") or {}).get("source")
    mfu = h["train_epoch"][-1].get("mfu")
    if ctx["on_tpu"]:
        check(source == "allocator", f"HBM ledger source: {source}")
        check(finite(mfu) and 0 < mfu <= 1, f"MFU is {mfu!r}")
        check(mem.get("feasibility", {}).get("fits") is True,
              f"HBM pre-flight: {mem.get('feasibility')}")
    ctx["trainer"], ctx["train_history"] = trainer, h
    return (
        f"2 epochs x {size['steps']} steps of resnet18 b{size['batch']} bf16 "
        f"SyncBN, eval + ckpt each; loss {h['train_epoch'][-1]['loss']:.4f}, "
        f"HBM ledger source: {source}, MFU {mfu} (informational), "
        f"compile.retraces 0, compile.seconds "
        f"{h['counters'].get('compile.seconds', 0):.1f}"
    )


def phase_resume(ctx: dict) -> str:
    """§1: lose the newest checkpoint, ``--resume``, replay the epoch."""
    from tpu_dist.cli import train as cli_train

    check("train_history" in ctx, "needs the train phase")
    size, wd = ctx["size"], ctx["workdir"]
    ckpt_dir = os.path.join(wd, "ckpt")
    os.remove(os.path.join(ckpt_dir, "ckpt_1.npz"))
    tee = Tee(sys.stdout)
    with contextlib.redirect_stdout(tee):
        trainer = cli_train.main(train_argv(
            size, wd, "resume", "--epochs", "2", "--eval_every", "1",
            "--ckpt_dir", ckpt_dir, "--save_every", "1", "--resume",
        ))
    check("=> resumed from" in tee.kept.getvalue(), "no '=> resumed from' line")
    check(trainer.start_epoch == 1, f"start_epoch {trainer.start_epoch}")
    h = read_history(os.path.join(wd, "resume.jsonl"))
    check_history(h, epochs=(1,), steps=size["steps"])
    first = ctx["train_history"]
    for kind in ("train_epoch", "eval"):
        want = [r for r in first[kind] if r["epoch"] == 1][0]["loss"]
        got = h[kind][0]["loss"]
        check(math.isclose(got, want, rel_tol=1e-6),
              f"replayed epoch 1 {kind} loss {got!r} != recorded {want!r}")
    return (
        "resumed from ckpt_0, replayed epoch 1 to the recorded train loss "
        f"{h['train_epoch'][0]['loss']:.6f} and eval loss "
        f"{h['eval'][0]['loss']:.6f}; compile.seconds "
        f"{h['counters'].get('compile.seconds', 0):.1f}"
    )


def phase_placement(ctx: dict) -> str:
    """§3: nothing quietly landed on device 0."""
    import jax

    check("trainer" in ctx, "needs the train phase")
    trainer = ctx["trainer"]
    every = set(jax.devices())
    for name in ("params", "opt_state", "bn_state"):
        for leaf in jax.tree_util.tree_leaves(getattr(trainer.state, name)):
            check(leaf.sharding.device_set == every,
                  f"{name} leaf on {len(leaf.sharding.device_set)} device(s)")
    batches = iter(trainer.train_loader)
    try:
        images, _ = next(batches)
    finally:
        batches.close()  # stops the loader's producer thread
    homes = {s.device for s in images.addressable_shards}
    check(homes == every, f"batch shards on {len(homes)} of {len(every)} devices")
    peaks = {}
    if ctx["on_tpu"]:
        for d in jax.devices():
            peaks[d.id] = d.memory_stats()["peak_bytes_in_use"]
            check(peaks[d.id] > 0, f"device {d.id} peak_bytes_in_use is 0")
    return (
        f"state and batch shards on all {len(every)} device(s); per-chip "
        f"peak bytes {peaks}"
    )


def phase_dp_equivalence(ctx: dict) -> str:
    """§3: the DP claim — one step on 1 device == one step on all of them
    (on-chip twin of tests/test_train_step.py::test_dp_equivalence_8dev_vs_1dev)."""
    import jax
    import numpy as np

    from tpu_dist.comm import mesh as mesh_lib
    from tpu_dist.nn import resnet18
    from tpu_dist.train.optim import SGD
    from tpu_dist.train.state import TrainState
    from tpu_dist.train.step import make_train_step

    batch = 256  # smaller batches condition BN worse: round-off alone then
    #              moves the update by more than the tolerance below
    model, opt = resnet18(num_classes=100), SGD(momentum=0.9, weight_decay=1e-4)
    params, bn = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    x = rng.normal(size=(batch, 32, 32, 3)).astype(np.float32)
    y = rng.integers(0, 100, batch).astype(np.int32)
    n_all = len(jax.devices())
    meshes = {
        1: mesh_lib.device_mesh([1], [mesh_lib.DATA_AXIS], jax.devices()[:1]),
        n_all: mesh_lib.data_parallel_mesh(),
    }
    out = {}
    # true fp32 on the MXU: default precision would round operands to bf16
    with jax.default_matmul_precision("highest"):
        for n, mesh in meshes.items():
            state = jax.device_put(
                TrainState.create(params, bn, opt), mesh_lib.replicated(mesh)
            )
            step = make_train_step(model.apply, opt, mesh, sync_bn=True,
                                   donate=False)
            new, metrics = step(
                state, mesh_lib.shard_batch(mesh, x),
                mesh_lib.shard_batch(mesh, y), 0.1,
            )
            out[n] = (float(metrics["loss"]),
                      jax.device_get(jax.tree_util.tree_leaves(new.params)))
    (loss1, p1), (lossn, pn) = out[1], out[n_all]
    check(math.isclose(loss1, lossn, rel_tol=1e-5),
          f"loss {loss1!r} on 1 device, {lossn!r} on {n_all}")
    worst = max(float(np.max(np.abs(a - b))) for a, b in zip(p1, pn))
    # summation-order noise alone is ~1e-5 here (a shuffled batch on ONE
    # device moves the update as much); an update is ~4e-3
    check(worst <= 1e-4, f"updated params differ by {worst:.3e}")
    return (f"fp32 SyncBN step, global batch {batch}: 1 vs {n_all} device(s) "
            f"loss {loss1:.6f} / {lossn:.6f}, max param diff {worst:.2e}")


def phase_ring_flash(ctx: dict) -> str:
    """§3: ``--sp N --flash_attention`` — ppermute + Pallas in shard_map."""
    import jax

    from tpu_dist.cli import train as cli_train

    n, wd = len(jax.devices()), ctx["workdir"]
    with PallasSpy() as spy:
        cli_train.main([
            "--dataset", "synthetic", "--synthetic_n", "256",
            "--model", "vit_tiny", "--num_classes", "10", "--no_sync_bn",
            "--batch_size", "16", "--sp", str(n), "--flash_attention",
            "--seed", "0", "--epochs", "1", "--steps_per_epoch", "2",
            "--eval_every", "0", "--log_every", "1",
            "--log_file", os.path.join(wd, "ring.jsonl"),
        ])
    h = read_history(os.path.join(wd, "ring.jsonl"))
    loss = h["train_epoch"][0]["loss"]
    check(finite(loss), f"ring-flash loss {loss!r}")
    check(h["counters"].get("compile.retraces", 0) == 0, "retraced")
    return (f"vit_tiny --sp {n} --flash_attention: 2 steps, loss {loss:.4f}; "
            + spy.check_compiled(ctx["on_tpu"]))


def phase_fused_sgd(ctx: dict) -> str:
    """§4: ``ops/fused_sgd.py`` against the jnp update, then through the flag."""
    import jax
    import jax.numpy as jnp

    from tpu_dist.cli import train as cli_train
    from tpu_dist.nn import resnet18
    from tpu_dist.train.optim import SGD

    # every leaf shape of the real model, fused against the plain update
    params, _ = resnet18(num_classes=100).init(jax.random.PRNGKey(0))
    leaves, treedef = jax.tree_util.tree_flatten(params)

    @jax.jit
    def noise_like_params(seed):
        keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
        return jax.tree_util.tree_unflatten(treedef, [
            jax.random.normal(k, l.shape, l.dtype) for k, l in zip(keys, leaves)])

    grads, moms = noise_like_params(1), noise_like_params(2)
    with PallasSpy() as spy:
        got, want = (
            jax.jit(SGD(momentum=0.9, weight_decay=1e-4, fused=fused).update)(
                grads, moms, params, 0.1)
            for fused in (True, False))
        worst = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(
            jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)))
        check(worst <= 1e-6, f"kernel differs from the jnp update by {worst:.3e}")

        size, wd = ctx["size"], ctx["workdir"]
        cli_train.main(train_argv(
            size, wd, "fused", "--epochs", "1", "--eval_every", "0",
            "--fused_optimizer",
        ))
    h = read_history(os.path.join(wd, "fused.jsonl"))
    check(h["counters"].get("compile.retraces", 0) == 0, "retraced")
    loss = h["train_epoch"][0]["loss"]
    check(finite(loss), f"fused loss {loss!r}")
    return (f"{len(leaves)} resnet18 leaves, fused vs plain update max diff "
            f"{worst:.1e}; --fused_optimizer: {size['steps']} steps, loss "
            f"{loss:.4f}; " + spy.check_compiled(ctx["on_tpu"]))


def _nerr(a, b) -> float:
    import jax.numpy as jnp

    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))


def phase_flash_kernels(ctx: dict) -> str:
    """§4: forward and Pallas backward, compiled, against the XLA path."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu_dist.nn.attention import full_attention
    from tpu_dist.ops.flash_attention import flash_attention

    notes = []
    with PallasSpy() as spy:
        for b, s, h, d, causal in ctx["flash_shapes"]:
            rng = np.random.default_rng(s + d)
            q, k, v, ct = (jnp.asarray(rng.normal(size=(b, s, h, d)),
                                       jnp.bfloat16) for _ in range(4))

            def fwd_and_grads(attn, *qkv):
                def loss(q, k, v):
                    out = attn(q, k, v)
                    return jnp.vdot(out.astype(jnp.float32),
                                    ct.astype(jnp.float32)), out
                (_, out), grads = jax.value_and_grad(
                    loss, argnums=(0, 1, 2), has_aux=True)(*qkv)
                return (out, *grads)

            def via_flash(q, k, v):
                return flash_attention(q, k, v, causal=causal, bwd="pallas")

            def via_xla(q, k, v):
                return full_attention(q, k, v, causal=causal, impl="xla")

            flash = jax.jit(functools.partial(fwd_and_grads, via_flash))(q, k, v)
            xla = jax.jit(functools.partial(fwd_and_grads, via_xla))(q, k, v)
            # the yardstick both are held to: the same math in true fp32
            with jax.default_matmul_precision("highest"):
                truth = jax.jit(functools.partial(fwd_and_grads, via_xla))(
                    *(t.astype(jnp.float32) for t in (q, k, v)))
            errs = {}
            for name, f, x, t in zip(("out", "dq", "dk", "dv"), flash, xla, truth):
                check(bool(jnp.all(jnp.isfinite(f.astype(jnp.float32)))),
                      f"S={s} D={d} causal={causal}: {name} not finite")
                check(f.shape == x.shape and f.dtype == x.dtype,
                      f"S={s}: {name} is {f.dtype}{f.shape}, XLA path "
                      f"{x.dtype}{x.shape}")
                errs[name] = (_nerr(f, t), _nerr(x, t), _nerr(f, x))
                check(errs[name][0] <= FLASH_TOL and errs[name][2] <= 2 * FLASH_TOL,
                      f"S={s} D={d} causal={causal}: {name} off fp32 by "
                      f"{errs[name][0]:.3e} (XLA bf16 path: {errs[name][1]:.3e}), "
                      f"off the XLA path by {errs[name][2]:.3e}")
            worst = max(e[0] for e in errs.values())
            worst_xla = max(e[1] for e in errs.values())
            notes.append(f"S={s}/H={h}/D={d}{'/causal' if causal else ''} "
                         f"err {worst:.1e} (xla {worst_xla:.1e})")
    return ("fwd+bwd vs fp32 reference, max normalized error (XLA bf16 path's "
            "in brackets): " + "; ".join(notes) + "; "
            + spy.check_compiled(ctx["on_tpu"]))


def phase_vit_b16_flash_step(ctx: dict) -> str:
    """§4: the flash kernels inside the real shard_map step, ViT-B/16 224 px."""
    import dataclasses

    import jax

    import bench

    cfg = bench.CONFIGS["vit_b16_imagenet_flash"]
    if ctx["size"]["vit_batch"] is not None:
        cfg = dataclasses.replace(
            cfg, global_batch=ctx["size"]["vit_batch"] * len(jax.devices()))
    with PallasSpy() as spy:
        rec = bench.run(cfg, steps=3, warmup=1)
    check(finite(rec["value"]) and rec["value"] > 0, f"throughput {rec['value']!r}")
    if ctx["on_tpu"]:
        check(finite(rec["mfu"]), f"mfu {rec['mfu']!r}")
    return (f"{cfg.name} b{rec['global_batch']} on {rec['n_devices']} "
            f"device(s): 1+3 steps, {rec['step_ms']} ms/step (informational); "
            + spy.check_compiled(ctx["on_tpu"]))


def phase_gmm_kernel(ctx: dict) -> str:
    """The experts' grouped product (``ops/grouped_matmul.py``) inside
    ``dropless_experts`` at the two token cells' shapes, bf16: the layer's
    value and its gradients to the rows and to every expert matrix, the
    kernel pair compiled against the XLA loop."""
    import jax
    import jax.numpy as jnp

    from tpu_dist.obs import counters
    from tpu_dist.parallel import expert

    rule = expert.takes_gmm_kernel
    notes = []
    with PallasSpy() as spy:
        for name, tokens, d, f, n_experts, top_k, capacity, gated in ctx["gmm_shapes"]:
            ks = jax.random.split(jax.random.PRNGKey(d), 6)
            x = jax.random.normal(ks[0], (tokens, d), jnp.bfloat16)
            chosen = jax.vmap(lambda k: jax.random.permutation(k, n_experts)[:top_k])(
                jax.random.split(ks[1], tokens))
            weights = jax.random.uniform(ks[2], (tokens, top_k)).astype(jnp.bfloat16)
            ws = [(jax.random.normal(k, shape) * shape[1] ** -0.5).astype(jnp.bfloat16)
                  for k, shape in zip(ks[3:], [(8, d, f), (8, f, d)] + [(8, d, f)] * gated)]

            def value_and_grads(x, *ws):
                def loss(x, *ws):
                    out, rows = expert.dropless_experts(
                        x, chosen, weights, *ws[:2], held=(0, 8), capacity=capacity,
                        activation=jax.nn.silu, **({"w_gate": ws[2]} if gated else {}))
                    return jnp.sum(out.astype(jnp.float32) ** 2), (out, rows)
                (_, (out, rows)), grads = jax.value_and_grad(
                    loss, argnums=tuple(range(1 + len(ws))), has_aux=True)(x, *ws)
                return (out, *grads), rows

            try:
                if not ctx["on_tpu"]:  # the rehearsal: forced, and interpreted
                    expert.takes_gmm_kernel = lambda *a: True
                before = counters.get("moe.sites_gmm_xla")
                kernel, rows = jax.jit(value_and_grads)(x, *ws)
                check(counters.get("moe.sites_gmm_xla") == before,
                      f"{name}: a product of the layer took the XLA loop")
                expert.takes_gmm_kernel = lambda *a: False
                loop, _ = jax.jit(value_and_grads)(x, *ws)
            finally:
                expert.takes_gmm_kernel = rule
            check(int(rows["rows_over_cap"]) == 0, f"{name}: rows over the buffer")
            errs = []
            for what, k, l in zip(("out", "dx", "dw_up", "dw_down", "dw_gate"), kernel, loop):
                check(bool(jnp.all(jnp.isfinite(k.astype(jnp.float32)))), f"{name}: {what} not finite")
                check(k.shape == l.shape and k.dtype == l.dtype, f"{name}: {what} is {k.dtype}{k.shape}")
                errs.append(_nerr(k, l))
                check(errs[-1] <= FLASH_TOL / 2, f"{name}: {what} off the loop's by {errs[-1]:.3e}")
            notes.append(f"{name} {int(rows['rows_live'])} live rows, {len(errs)} arrays, "
                         f"worst {max(errs):.1e}")
    return ("dropless_experts, kernel pair vs XLA loop, max normalized difference: "
            + "; ".join(notes) + "; " + spy.check_compiled(ctx["on_tpu"]))


def phase_conv_kernel(ctx: dict) -> str:
    """The mixers' depthwise convolution (``ops/causal_conv1d.py``) at the
    Nemotron cell's shape, bf16: x, B and C read in ``proj`` where they lie,
    and the gradients to ``proj``, the taps and the bias, the kernel pair
    compiled against the XLA chain of ``nn/nemotron_h.py::_mixer``."""
    import jax
    import jax.numpy as jnp

    from tpu_dist.ops import causal_conv1d as K

    bsz, seq, borders, taps = ctx["conv_shape"]
    lo, hi, width = borders[0], borders[-1], borders[-1] + 64
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    proj = jax.random.normal(ks[0], (bsz, seq, width), jnp.bfloat16)
    w = jax.random.uniform(ks[1], (taps, hi - lo), jnp.float32, -0.5, 0.5)
    bias = jax.random.uniform(ks[2], (hi - lo,), jnp.float32, -0.5, 0.5)
    ct = jax.random.normal(ks[3], (bsz, seq, hi - lo), jnp.bfloat16)

    def chain(proj, w, bias):
        padded = jnp.pad(proj[..., lo:hi], ((0, 0), (taps - 1, 0), (0, 0))).astype(jnp.float32)
        conv = sum(padded[:, i:i + seq] * w[i] for i in range(taps)) + bias
        return jax.nn.silu(conv).astype(proj.dtype)

    def kernel(proj, w, bias):
        ys = K.causal_conv1d(proj, w, bias, borders=borders, activation="silu",
                             interpret=not ctx["on_tpu"])
        return jnp.concatenate(ys[1:-1], axis=-1)

    def value_and_grads(f):
        loss = lambda *a: jnp.sum(f(*a).astype(jnp.float32) * ct.astype(jnp.float32))  # noqa: E731
        return jax.jit(lambda *a: (f(*a), *jax.grad(loss, argnums=(0, 1, 2))(*a)))(proj, w, bias)

    check(K.fits(seq, borders, taps, proj.dtype), f"fits refuses {seq} tokens, borders {borders}")
    with PallasSpy() as spy:
        got = value_and_grads(kernel)
    want = value_and_grads(chain)
    errs = []
    for what, k, x in zip(("y", "dproj", "dw", "dbias"), got, want):
        check(bool(jnp.all(jnp.isfinite(k.astype(jnp.float32)))), f"{what} not finite")
        check(k.shape == x.shape and k.dtype == x.dtype, f"{what} is {k.dtype}{k.shape}")
        errs.append(_nerr(k, x))
        check(errs[-1] <= FLASH_TOL / 2, f"{what} off the chain's by {errs[-1]:.3e}")
    return (f"causal_conv1d {bsz} x {seq} tokens, sections {borders}, {taps} taps, kernel pair vs "
            f"XLA chain, max normalized difference: " + ", ".join(
                f"{n} {e:.1e}" for n, e in zip(("y", "dproj", "dw", "dbias"), errs))
            + "; " + spy.check_compiled(ctx["on_tpu"]))


def phase_combine_kernel(ctx: dict) -> str:
    """The expert layer's combine and the dispatch's backward
    (``ops/expert_combine.py``) at the LFM2 cell's shapes, bf16: the
    combine's value and its gradients to the rows and the routing weights,
    and the dispatch gather's gradient to x, the kernel through the two
    ``custom_vjp``s of ``parallel/expert.py`` compiled against XLA's
    scatter-add forms."""
    import jax
    import jax.numpy as jnp

    from tpu_dist.ops import expert_combine as K
    from tpu_dist.parallel import expert

    tokens, d, n_experts, top_k, capacity = ctx["combine_shape"]
    ks = jax.random.split(jax.random.PRNGKey(41), 6)
    chosen = jax.vmap(lambda k: jax.random.permutation(k, n_experts)[:top_k])(
        jax.random.split(ks[0], tokens))
    buf = expert._buffer(chosen, (0, 8), capacity)
    runs = expert._block_runs(buf, K.TOKEN_BLOCK)
    token, valid, over, pair = buf["token"], buf["valid"], buf["over"], buf["pair"]
    x = jax.random.normal(ks[1], (tokens, d), jnp.bfloat16)
    y = jnp.where(valid, jax.random.normal(ks[2], (token.shape[0], d)), 0).astype(jnp.bfloat16)
    weights = jax.random.uniform(ks[3], (tokens, top_k)).astype(jnp.bfloat16)
    d_out = jax.random.normal(ks[4], (tokens, d), jnp.bfloat16)
    d_rows = jnp.where(valid, jax.random.normal(ks[5], y.shape), 0).astype(jnp.bfloat16)

    def kernel(x, y, weights):
        out, vjp = jax.vjp(lambda y, w: expert._combine(
            tokens, y, w.reshape(-1)[pair], token, valid, runs, over), y, weights)
        dx = jax.vjp(lambda x: expert._dispatch(tokens, x, token, valid, runs), x)[1](d_rows)[0]
        return (out, dx, *vjp(d_out))

    def scatter(x, y, weights):
        def combine(y, w):
            v = jnp.where(valid, y.astype(jnp.float32) * w.reshape(-1)[pair][:, None], 0)
            out = jnp.zeros((tokens, d), jnp.float32).at[token].add(v)
            return jnp.where(over > 0, jnp.nan, out).astype(y.dtype)
        out, vjp = jax.vjp(combine, y, weights)
        dx = jax.vjp(lambda x: jnp.where(valid, x[token], 0), x)[1](d_rows)[0]
        return (out, dx, *vjp(d_out))

    with PallasSpy() as spy:
        got = jax.jit(kernel)(x, y, weights)
    want = jax.jit(scatter)(x, y, weights)
    check(int(over) == 0, f"{int(over)} rows over the buffer")
    errs = []
    for what, k, s in zip(("out", "dx", "d_y", "d_weight"), got, want):
        check(bool(jnp.all(jnp.isfinite(k.astype(jnp.float32)))), f"{what} not finite")
        check(k.shape == s.shape and k.dtype == s.dtype, f"{what} is {k.dtype}{k.shape}")
        errs.append(_nerr(k, s))
        check(errs[-1] <= FLASH_TOL / 2, f"{what} off the scatter's by {errs[-1]:.3e}")
    return (f"combine {tokens} tokens x {d} from {int(buf['live'])} live rows of {token.shape[0]}, "
            f"kernel vs scatter-add, max difference over the largest value: " + ", ".join(
                f"{n} {e:.1e}" for n, e in zip(("out", "dx", "d_y", "d_weight"), errs))
            + "; " + spy.check_compiled(ctx["on_tpu"]))


def phase_token_step(ctx: dict) -> str:
    """The token path end to end at toy widths: one epoch of the tiny hybrid
    decoder (mixer, expert layer, causal grouped attention) through
    ``cli.train.main``, bf16. Informational: the widths are toys."""
    from tpu_dist.cli import train as cli
    from tpu_dist.obs import counters

    tr = cli.main([
        "--dataset", "synthetic_tokens", "--synthetic_n", "64", "--model", "nemotron_h_tiny",
        "--batch_size", "16", "--optimizer", "adamw", "--lr", "0.01", "--bf16",
        "--epochs", "1", "--steps_per_epoch", "2", "--log_every", "1", "--eval_every", "0",
    ])
    check(int(tr.state.step) == 2, f"state.step {int(tr.state.step)}, expected 2")
    check(counters.get("moe.rows_live") > 0 and counters.get("moe.rows_over_cap") == 0,
          f"moe.rows_live {counters.get('moe.rows_live')}, "
          f"moe.rows_over_cap {counters.get('moe.rows_over_cap')}")
    return (f"nemotron_h_tiny: 2 steps, {counters.get('lm.tokens'):.0f} tokens, "
            f"{counters.get('moe.rows_live'):.0f} live expert rows of "
            f"{counters.get('moe.rows_balanced'):.0f} balanced")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--rehearse-on-cpu", action="store_true",
        help="debug this script without a chip at cut sizes; proves nothing "
             "about the chip and prints no result line",
    )
    args = ap.parse_args(argv)

    import jax

    from tpu_dist import compile_cache
    from tpu_dist.data import native
    from tpu_dist.obs import costmodel

    cache_dir = compile_cache.enable()
    # whole-run seconds spent in XLA compile requests, cache loads included
    # (the trainer's own compile.seconds counter restarts with every
    # Trainer): what a warm compile cache must shrink
    compile_s = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: compile_s.append(secs)
        if "backend_compile" in event else None)
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    on_tpu = dev.platform == "tpu"
    row = costmodel.require_chip_row(dev) if on_tpu else None
    print(
        f"chip_smoke: platform={dev.platform} device_kind={dev.device_kind!r} "
        f"count={device['count']} peak_row={row} jax={jax.__version__} "
        f"jaxlib={importlib.metadata.version('jaxlib')} "
        f"libtpu={importlib.metadata.version('libtpu')} "
        f"compile_cache={cache_dir} augment={native.path_in_use()}",
        flush=True,
    )
    if args.rehearse_on_cpu:
        print("chip_smoke: REHEARSAL at cut sizes — this run proves nothing "
              "about the chip", flush=True)
        size = {"batch": 32, "steps": 2, "synthetic_n": 256, "vit_batch": 1}
        shapes, gmm_shapes = REHEARSAL_FLASH_SHAPES, REHEARSAL_GMM_SHAPES
    elif not on_tpu:
        print(f"FAIL device: platform is {dev.platform!r}, not 'tpu' "
              f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r})", flush=True)
        return 2
    else:
        size = {"batch": 256, "steps": 4, "synthetic_n": 2048, "vit_batch": None}
        shapes, gmm_shapes = FLASH_SHAPES, GMM_SHAPES

    phases = [phase_train, phase_resume, phase_placement]
    if device["count"] > 1:
        phases += [phase_dp_equivalence, phase_ring_flash]
    phases += [phase_fused_sgd, phase_flash_kernels, phase_gmm_kernel, phase_conv_kernel,
               phase_combine_kernel, phase_vit_b16_flash_step, phase_token_step]

    workdir = tempfile.mkdtemp(prefix="chip_smoke_")
    ctx = {"size": size, "flash_shapes": shapes, "gmm_shapes": gmm_shapes,
           "conv_shape": CONV_SHAPE if on_tpu and not args.rehearse_on_cpu else REHEARSAL_CONV_SHAPE,
           "combine_shape": COMBINE_SHAPE if on_tpu and not args.rehearse_on_cpu else REHEARSAL_COMBINE_SHAPE,
           "workdir": workdir, "on_tpu": on_tpu}
    failed = []
    try:
        for phase in phases:
            name = phase.__name__.removeprefix("phase_")
            t0 = time.perf_counter()
            try:
                note = phase(ctx)
            except Exception as e:  # the phase boundary: report it, go on
                traceback.print_exc()
                failed.append(name)
                reason = (str(e).strip().splitlines() or [""])[0][:400]
                print(f"FAIL {name}: {type(e).__name__}: {reason}", flush=True)
            else:
                print(f"PASS {name}: {note} "
                      f"[{time.perf_counter() - t0:.1f}s, informational]",
                      flush=True)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)

    print(f"chip_smoke: compile.seconds={sum(compile_s):.1f} over "
          f"{len(compile_s)} compile request(s) in the whole run "
          "(informational; a warm compile cache shrinks the seconds)",
          flush=True)
    if failed:
        print(f"chip_smoke: FAILED phases: {', '.join(failed)}", flush=True)
        return 1
    if args.rehearse_on_cpu:
        print("chip_smoke: rehearsal passed (no result line: not a chip run)")
        return 0
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
