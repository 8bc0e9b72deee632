"""The control of the comparison that decides ``correct``.

    python3 benchmarks/control.py --workload <cell> --seed <n> [--only a,b] [--by_leaf]

``benchmarks/run.py`` says of every run whether the program's first update
agrees with the plain float32 reference within the configuration's limits
(``reference_check``). This says whether those limits can tell anything: it
puts wrong first updates through the same ``harness/reference.py::compare``
under the same limits and prints what each reads. Same seed, same weights,
same first batch as ``run.py``; no window is measured.

* ``as_it_is``: the program's own first update. Has to be correct.
* ``reference_bfloat16``: the configuration's plain reference computed in the
  nearest precision below the one the configuration states: every parameter,
  and with them every activation, state and the loss, in bfloat16; its loss
  and the first update its gradient implies. Has to be not correct.
* the faults of ``benchmarks/controls/<reference>.py``, where the cell's
  reference has such a file: the program's step built with one fault planted,
  each with ``must_fail`` as the file gives it.

A line a control, then one JSON line: ``{"holds": ..., "controls": {...}}``;
exit 0 if every control came out as it has to, else 1. ``--only`` judges the
named controls alone (the chip's minutes: a control of a 528M-parameter model
is two minutes of the host's arithmetic in ``compare``); ``--by_leaf`` adds
the sign agreement a kind of leaf to each line. A number read here on the CPU
says nothing of the chip's: ``on_chip=False`` is for the tests.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
from typing import Any, Dict, Optional

import numpy as np

LOWER = "bfloat16"


def lowered_reference(model, dtype):
    """``model`` with its parameters (and float inputs) cast to ``dtype``
    inside the loss: a reference file computes in the dtype it is handed."""
    import jax  # noqa: PLC0415
    import jax.numpy as jnp  # noqa: PLC0415

    from benchmarks.harness import reference as reference_lib  # noqa: PLC0415

    base = getattr(model, "loss_sum", None) or reference_lib.default_loss_sum(model)
    cast = lambda a: a.astype(dtype) if jnp.issubdtype(a.dtype, jnp.floating) else a  # noqa: E731

    class Lowered:
        WHOLE_BATCH = model.WHOLE_BATCH
        INPUT_DTYPE = getattr(model, "INPUT_DTYPE", np.float32)

        @staticmethod
        def loss_sum(arch, params, inputs, targets):
            return base(arch, jax.tree_util.tree_map(cast, params), cast(inputs),
                        targets).astype(jnp.float32)

    return Lowered


def implied_update(loss: float, grads, before, lr: float, train_config: Dict[str, Any]):
    """The first update an optimizer makes of ``grads`` from zero moments, in
    the words ``reference.implied_gradient`` reads back: SGD ``p - lr (g + wd
    p)``, AdamW ``p - lr (sign g + wd_leaf p)``; computed in float64 and
    stored as the program stores its parameters, in ``before``'s dtype."""
    import jax  # noqa: PLC0415

    wd = float(train_config.get("weight_decay", 1e-4))
    adamw = train_config.get("optimizer", "sgd") == "adamw"

    def leaf(p0, g):
        dtype = np.asarray(p0).dtype
        p0, g = np.asarray(p0, np.float64), np.asarray(g, np.float64)
        decay = wd if (not adamw or p0.ndim > 1) else 0.0
        return (p0 - lr * ((np.sign(g) if adamw else g) + decay * p0)).astype(dtype)

    return {"loss": loss, "lr": lr, "before": before,
            "after": jax.tree_util.tree_map(leaf, before, grads)}


def by_leaf(update, ref_grads, train_config) -> Dict[str, float]:
    """Sign agreement a kind of leaf (its last key), over every element whose
    reference gradient is not zero: what the one number over all elements
    cannot show of the small leaves. A leaf at a time, so that no second
    float64 copy of the whole tree is made: the host holds 40 GiB and
    ``compare`` needs half of it for a 528M-parameter model (eight leaves at
    a time in threads ran out of it)."""
    import jax  # noqa: PLC0415

    from benchmarks.harness import reference as reference_lib  # noqa: PLC0415

    hit: Dict[str, list] = {}
    for (path, p0), p1, g in zip(jax.tree_util.tree_leaves_with_path(update["before"]),
                                 jax.tree_util.tree_leaves(update["after"]),
                                 jax.tree_util.tree_leaves(ref_grads)):
        implied, _ = reference_lib.implied_gradient(
            {"lr": update["lr"], "before": p0, "after": p1}, train_config)
        g = np.asarray(g)
        live = g != 0
        count = hit.setdefault(str(getattr(path[-1], "key", path[-1])), [0, 0])
        count[0] += int(live.sum())
        count[1] += int((np.sign(implied)[live] == np.sign(g)[live]).sum())
    return {kind: round(k / n, 6) for kind, (n, k) in sorted(hit.items()) if n}


def run_controls(root: str, workload: str, seed: int, *, only: Optional[set] = None,
                 leaves: bool = False, on_chip: bool = True) -> Dict[str, Any]:
    import jax  # noqa: PLC0415
    import jax.numpy as jnp  # noqa: PLC0415

    from benchmarks.harness import manifest as manifest_lib  # noqa: PLC0415
    from benchmarks.harness import reference as reference_lib  # noqa: PLC0415
    from benchmarks.harness.adapter import Adapter  # noqa: PLC0415
    from benchmarks.harness.window import check_devices  # noqa: PLC0415
    from tpu_dist.train.state import TrainState  # noqa: PLC0415

    cell = manifest_lib.load_cell(root, workload)
    devices = check_devices(cell) if on_chip else jax.devices()
    if on_chip:
        from tpu_dist import compile_cache  # noqa: PLC0415

        compile_cache.enable()
    model = manifest_lib.load_module(root, "models", cell.config["reference"])
    chk, train_config = cell.reference_check, cell.config.get("train_config", {})
    sign_only = train_config.get("optimizer", "sgd") == "adamw"
    ad = Adapter(cell, seed, devices)
    tr = ad.trainer
    n_check = chk.get("samples_per_chip")
    inputs, targets = ad.first_batch(None if n_check is None else int(n_check) * cell.chips)
    model_state = jax.device_get(tr.state.bn_state)
    out: Dict[str, Any] = {}

    def judge(name: str, update, must_fail: bool) -> None:
        if only is not None and name not in only:
            return
        verdict = reference_lib.compare(update, ref_loss, ref_grads, train_config, chk)
        if sign_only and leaves:
            verdict["by_leaf"] = by_leaf(update, ref_grads, train_config)
        verdict["must_fail"] = must_fail
        out[name] = verdict
        print(f"[control] {name}: {verdict}", flush=True)
        gc.collect()

    t = time.perf_counter()
    update = ad.first_update(inputs, targets)
    before = update["before"]
    first = (np.asarray(inputs), np.asarray(targets))
    tr.state = None  # one copy of the state on the chip at a time
    ref_loss, ref_grads = reference_lib.reference_loss_and_grads(
        model, cell.config["arch"], before, first[0], first[1], int(chk.get("chunk", 0)), devices[0])
    print(f"[control] first update and float32 reference: {time.perf_counter() - t:.1f} s", flush=True)
    judge("as_it_is", update, False)
    lr = update["lr"]
    del update

    name = f"reference_{LOWER}"
    if only is None or name in only:
        t = time.perf_counter()
        loss, grads = reference_lib.reference_loss_and_grads(
            lowered_reference(model, jnp.dtype(LOWER)), cell.config["arch"], before,
            first[0], first[1], int(chk.get("chunk", 0)), devices[0])
        print(f"[control] {name}: {time.perf_counter() - t:.1f} s", flush=True)
        update = implied_update(loss, grads, before, lr, train_config)
        del grads
        judge(name, update, True)
        del update

    try:
        faults = manifest_lib.load_module(root, "controls", cell.config["reference"]).FAULTS
    except manifest_lib.ManifestError:
        faults = {}
    for name, (must_fail, plant) in faults.items():
        if only is not None and name not in only:
            continue
        t = time.perf_counter()
        with plant():
            tr.train_step = tr._build_train_step(
                tr.cfg, jnp.bfloat16 if tr.cfg.bf16 else jnp.float32)
            # the seeded initial state again: the parameters and the model's
            # state as they were, the optimizer's from nothing
            tr.state = tr._place_state(TrainState.create(before, model_state, tr.optimizer))
            update = ad.first_update(inputs, targets)
        tr.state = None
        update["before"] = before  # one host copy of the parameters, not two
        print(f"[control] {name}: {time.perf_counter() - t:.1f} s", flush=True)
        judge(name, update, must_fail)
        del update
    holds = all(v["ok"] != v["must_fail"] for v in out.values())
    return {"workload": workload, "seed": seed, "holds": holds, "controls": out}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--only", default=None, help="comma-separated controls to judge (default: all)")
    p.add_argument("--by_leaf", action="store_true")
    args = p.parse_args(argv)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    from benchmarks.harness import RefusedError  # noqa: PLC0415

    try:
        result = run_controls(root, args.workload, args.seed,
                              only=set(args.only.split(",")) if args.only else None,
                              leaves=args.by_leaf)
    except RefusedError as e:
        print(f"control refused: {e}", file=sys.stderr)
        return 2
    sys.stdout.flush()
    print(json.dumps(result))
    return 0 if result["holds"] else 1


if __name__ == "__main__":
    sys.exit(main())
