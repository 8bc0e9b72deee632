"""Multi-host path: 2 processes × 4 devices, real jax.distributed rendezvous.

The TPU-world equivalent of launching the reference with
``torch.distributed.launch --nproc_per_node=2`` (SURVEY §2.2 N8): the
coordinator replaces the TCP store, each process owns its local devices and
feeds its data shard, and the replicated state must come out identical.
"""

import os
import pytest
import socket
import subprocess
import sys

_HERE = os.path.dirname(__file__)
_REPO_ROOT = os.path.dirname(os.path.abspath(_HERE))

# set by the first test that discovers this jaxlib's CPU backend cannot run
# cross-process collectives (one mutable cell, module-session scope)
_NO_MP_CPU = [False]


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _launch_workers(worker_script: str, result_prefix: str, nprocs: int = 2,
                    extra_args: tuple = ()):
    """Fan out ``worker_script`` over ``nprocs`` rendezvoused processes and
    parse its ``<result_prefix> <pid> <fields...>`` lines.

    Returns ``{pid: (fields...)}`` with every process's result; asserts all
    workers exited 0. One place owns the CPU-forcing env recipe, so a
    future env fix lands once, not per-test."""
    if _NO_MP_CPU[0]:
        pytest.skip("CPU backend lacks multiprocess collectives in this jaxlib")
    worker = os.path.join(_HERE, worker_script)
    coord = f"127.0.0.1:{_free_port()}"
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = _REPO_ROOT
    env.pop("XLA_FLAGS", None)

    procs = [
        subprocess.Popen(
            [sys.executable, worker, coord, str(nprocs), str(i), *extra_args],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=env, cwd=_REPO_ROOT,
        )
        for i in range(nprocs)
    ]
    outs = []
    failed = []
    for p in procs:
        out, _ = p.communicate(timeout=240)
        outs.append(out)
        if p.returncode != 0:
            failed.append(out)
    if failed:
        if any(
            "Multiprocess computations aren't implemented on the CPU backend"
            in out
            for out in failed
        ):
            # this jaxlib's CPU backend has no cross-process collectives at
            # all (newer jaxlibs route them through gloo) — environmental,
            # not a code failure; remember so sibling tests skip without
            # paying the two-process boot cost again
            _NO_MP_CPU[0] = True
            pytest.skip("CPU backend lacks multiprocess collectives in this jaxlib")
        raise AssertionError(failed[0])

    results = {}
    for out in outs:
        for line in out.splitlines():
            if line.startswith(result_prefix + " "):
                fields = line.split()
                results[fields[1]] = tuple(fields[2:])
    assert set(results) == {str(i) for i in range(nprocs)}, outs
    return results, outs


def test_two_process_training_agrees():
    results, outs = _launch_workers("_mp_worker.py", "RESULT")
    # both hosts see the same reduced loss and identical replicated params
    assert results["0"] == results["1"], results
    # fused device-resident epoch also agrees across hosts
    fused, _ = {}, None
    for out in outs:
        for line in out.splitlines():
            if line.startswith("FUSED "):
                _, pid, loss = line.split()
                fused[pid] = loss
    assert set(fused) == {"0", "1"}, outs
    assert fused["0"] == fused["1"], fused


def test_two_process_tensor_parallel_matches_single_process():
    """2 hosts × 4 devices, tp=2 on a host-major [data=4, model=2] mesh
    (VERDICT r1 #6): every tp group intra-host, workers agree with each
    other AND with the same training run on a single-process 8-device mesh.
    """
    results, _ = _launch_workers("_mp_worker_tp.py", "TPRESULT")
    assert results["0"] == results["1"], results

    # single-process reference on this test process's own 8-device mesh
    from tests._mp_worker_tp import run_tp_training

    ref_loss, ref_rep, ref_tp = run_tp_training()
    loss, fp_rep, fp_tp = (float(v) for v in results["0"])
    assert abs(loss - ref_loss) < 1e-4, (loss, ref_loss)
    assert abs(fp_rep - ref_rep) < 1e-4, (fp_rep, ref_rep)
    assert abs(fp_tp - ref_tp) < 1e-3, (fp_tp, ref_tp)


def test_two_process_expert_parallel_matches_single_process():
    """2 hosts × 4 devices, ep=2 on a host-major [data=4, expert=2] mesh:
    every expert group (and its all_to_all dispatch) intra-host; workers
    agree with each other AND with the same run on a single-process
    8-device mesh."""
    results, _ = _launch_workers("_mp_worker_ep.py", "EPRESULT")
    assert results["0"] == results["1"], results

    # single-process reference on this test process's own 8-device mesh
    from tests._mp_worker_ep import run_ep_training

    ref_loss, ref_rep, ref_ep = run_ep_training()
    loss, fp_rep, fp_ep = (float(v) for v in results["0"])
    assert abs(loss - ref_loss) < 1e-4, (loss, ref_loss)
    assert abs(fp_rep - ref_rep) < 1e-4, (fp_rep, ref_rep)
    assert abs(fp_ep - ref_ep) < 1e-3, (fp_ep, ref_ep)


def test_two_process_pp_tp_matches_single_process():
    """2 hosts × 4 devices, pp=2 × tp=2 on a host-major
    [data=2, pipe=2, model=2] mesh (the Megatron layout): the stage ring's
    ppermute AND each block's TP psums stay intra-host while the data axis
    crosses processes. Workers agree with each other AND with the same run
    on a single-process 8-device mesh."""
    results, _ = _launch_workers("_mp_worker_pp_tp.py", "PPTPRESULT")
    assert results["0"] == results["1"], results

    from tests._mp_worker_pp_tp import run_pp_tp_training

    ref_loss, ref_rep, ref_blk = run_pp_tp_training()
    loss, fp_rep, fp_blk = (float(v) for v in results["0"])
    assert abs(loss - ref_loss) < 1e-4, (loss, ref_loss)
    assert abs(fp_rep - ref_rep) < 1e-4, (fp_rep, ref_rep)
    assert abs(fp_blk - ref_blk) < 1e-3, (fp_blk, ref_blk)


def test_two_process_ring_flash_sp_matches_single_process():
    """2 hosts × 4 devices, sp=4 RING-FLASH on a host-major [data=2, seq=4]
    mesh: the ring's ppermute neighborhood stays intra-host while the data
    axis crosses processes; the Pallas local tiles (interpret mode) run
    the full ring-flash composition across a real jax.distributed
    rendezvous. Workers agree with each other AND with the same training
    run on a single-process 8-device mesh."""
    results, _ = _launch_workers("_mp_worker_sp.py", "SPRESULT")
    assert results["0"] == results["1"], results

    from tests._mp_worker_sp import run_sp_training

    ref_loss, ref_fp = run_sp_training()
    loss, fp = (float(v) for v in results["0"])
    assert abs(loss - ref_loss) < 1e-4, (loss, ref_loss)
    assert abs(fp - ref_fp) < 1e-3, (fp, ref_fp)


def test_two_process_sharded_ckpt_no_gather(tmp_path):
    """2 hosts × 4 devices, params P('data') over the global mesh: each
    process writes ONLY its own 1/2 of the sharded leaves (byte-checked in
    the worker — the no-gather-at-save property), the rank-0 manifest
    commits, and a cross-process overlap-only restore hands every process
    its partition back, equal to the original values."""
    results, _ = _launch_workers(
        "_mp_worker_ckpt.py", "CKRESULT", extra_args=(str(tmp_path),)
    )
    assert results["0"] == results["1"], results
    # exactly two shard files + one manifest on the shared dir
    names = sorted(os.listdir(tmp_path))
    assert names == [
        "ckpt_5.manifest.json",
        "ckpt_5.shard0of2.npz",
        "ckpt_5.shard1of2.npz",
    ], names
