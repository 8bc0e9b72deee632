"""The analyzer analyzed: fixture snippets per lint rule, suppression and
baseline mechanics, jaxpr-audit budgets, and the CLI gate contract
(exit 0 on the real repo, non-zero on a planted violation)."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from tpu_dist.analysis import baseline as baseline_lib
from tpu_dist.analysis.jaxpr_audit import (
    CollectiveBudget,
    _compare,
    audit_all,
    audit_case,
)
from tpu_dist.analysis.lint import lint_paths, lint_source

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _lint(snippet: str, path: str = "tpu_dist/fake/mod.py"):
    return lint_source(textwrap.dedent(snippet), path)


def _rules(violations):
    return [v.rule for v in violations]


# -- TD001: host sync inside traced functions -------------------------------


def test_td001_item_in_jitted_fn():
    vs = _lint(
        """
        import jax

        @jax.jit
        def step(x):
            return x.item()
        """
    )
    assert _rules(vs) == ["TD001"]
    assert vs[0].line == 6


def test_td001_nested_factory_shard_map():
    # the factory itself is host code; its nested fn passed to shard_map is
    # traced — and helpers the traced fn calls are traced transitively
    vs = _lint(
        """
        import numpy as np
        from tpu_dist.comm.compat import shard_map

        def helper(x):
            return np.asarray(x)

        def make_step(mesh):
            def step_local(x):
                return helper(x) + 1
            return shard_map(step_local, mesh=mesh, in_specs=None, out_specs=None)
        """
    )
    assert _rules(vs) == ["TD001"]


def test_td001_host_code_not_flagged():
    vs = _lint(
        """
        import numpy as np

        def host_metrics(x):
            return float(np.asarray(x).mean())
        """
    )
    assert vs == []


# -- TD002: unguarded non-rank-0 I/O ---------------------------------------


def test_td002_unguarded_print():
    # an unguarded bare print is BOTH violations: every process duplicates
    # it (TD002) and it bypasses the logging layer (TD007)
    vs = _lint(
        """
        def log_epoch(loss):
            print(f"loss {loss}")
        """
    )
    assert _rules(vs) == ["TD002", "TD007"]


def test_td002_guard_spellings_pass():
    vs = _lint(
        """
        import jax
        from tpu_dist.comm.mesh import is_primary

        def a(loss):
            if jax.process_index() == 0:
                print(loss)

        def b(loss):
            if is_primary():
                print(loss)

        def c(rank, loss):
            if rank != 0:
                return
            print(loss)

        def d(path, rec):
            pid = jax.process_index()
            if pid != 0:
                return
            with open(path, "w") as f:
                f.write(rec)
        """
    )
    # every guard spelling satisfies TD002; the guarded PRINTS still carry
    # TD007 (the bare-print rule is guard-agnostic — route through
    # rank0_print), while the guarded file write carries nothing
    assert _rules(vs) == ["TD007", "TD007", "TD007"]


def test_td002_file_write_and_logger():
    vs = _lint(
        """
        import logging

        def dump(path, logger):
            logging.info("hi")
            logger.warning("hi")
            with open(path, "a") as f:
                f.write("x")
        """
    )
    assert sorted(_rules(vs)) == ["TD002", "TD002", "TD002"]


# -- TD007: bare print outside the logging layer ----------------------------


def test_td007_allowlist_paths():
    # the logging layer itself may print (it IS the sink)...
    vs = _lint("def f(x):\n    print(x)\n", "tpu_dist/metrics/logging.py")
    assert "TD007" not in _rules(vs)  # (TD002 still applies there)
    # ...as may the CLI report modules, exempt from both rules
    vs = _lint("def f(x):\n    print(x)\n", "tpu_dist/obs/__main__.py")
    assert _rules(vs) == []
    # everywhere else the print is flagged even under a rank-0 guard
    vs = _lint(
        """
        import jax

        def f(x):
            if jax.process_index() == 0:
                print(x)
        """
    )
    assert _rules(vs) == ["TD007"]


# -- TD003: hot-path jit without donation ----------------------------------


def test_td003_hot_factory_flagged_cold_not():
    vs = _lint(
        """
        import jax

        def make_train_step(f):
            return jax.jit(f)

        def make_eval_renderer(f):
            return jax.jit(f)

        def make_fused_epoch(f):
            return jax.jit(f, donate_argnums=(0,))
        """
    )
    assert _rules(vs) == ["TD003"]
    assert "make_train_step" in vs[0].message


# -- TD004: version-fragile imports ----------------------------------------


def test_td004_fragile_import_spellings():
    vs = _lint(
        """
        from jax import shard_map
        from jax.experimental.shard_map import shard_map as sm
        from jax.experimental import pjit
        """
    )
    assert _rules(vs) == ["TD004", "TD004", "TD004"]


def test_td004_compat_module_exempt_and_clean_import():
    assert _lint("from jax import shard_map\n", "tpu_dist/comm/compat.py") == []
    assert _lint("from tpu_dist.comm.compat import shard_map\n") == []


# -- TD005: trace-time nondeterminism --------------------------------------


def test_td005_np_random_and_time_in_trace():
    vs = _lint(
        """
        import time
        import numpy as np
        import jax

        @jax.jit
        def step(x):
            noise = np.random.rand(*x.shape)
            t0 = time.time()
            return x + noise + t0
        """
    )
    assert sorted(_rules(vs)) == ["TD005", "TD005"]


def test_td005_jax_random_and_host_np_random_ok():
    vs = _lint(
        """
        import numpy as np
        import jax

        @jax.jit
        def step(x, key):
            return x + jax.random.normal(key, x.shape)

        def host_shuffle(n):
            return np.random.default_rng(0).permutation(n)
        """
    )
    assert vs == []


# -- TD006: silently swallowed exceptions -----------------------------------


def test_td006_silent_pass_and_bare_except_flagged():
    vs = _lint(
        """
        def prune(path):
            try:
                remove(path)
            except OSError:
                pass

        def anything(x):
            try:
                return x()
            except:
                return None
        """
    )
    assert _rules(vs) == ["TD006", "TD006"]
    assert "OSError" in vs[0].message
    assert "bare" in vs[1].message


def test_td006_allowlisted_types_and_handled_bodies_pass():
    vs = _lint(
        """
        import queue

        def probe():
            try:
                import optional_dep
            except ImportError:
                pass
            try:
                cleanup()
            except FileNotFoundError:
                pass
            try:
                q.get_nowait()
            except queue.Empty:
                pass

        def handled():
            try:
                risky()
            except OSError as e:
                raise RuntimeError("risky failed") from e
        """
    )
    assert vs == []


def test_td006_tuple_needs_every_type_allowlisted():
    vs = _lint(
        """
        def mixed():
            try:
                go()
            except (FileNotFoundError, OSError):
                pass
        """
    )
    assert _rules(vs) == ["TD006"]


def test_td006_inline_suppression():
    vs = _lint(
        """
        def prune(path):
            try:
                remove(path)
            except OSError:  # tpu-dist: ignore[TD006] — best-effort prune
                pass
        """
    )
    assert vs == []


# -- TD008: rank-guarded collective call sites ------------------------------


def test_td008_rank_guarded_collective_flagged():
    vs = _lint(
        """
        import jax
        from jax import lax
        from tpu_dist.comm.collectives import barrier

        def bad_branch(x, rank):
            if rank == 0:
                return lax.pmean(x, "data")
            return x

        def bad_early_return(x, rank):
            if rank != 0:
                return x
            barrier()
            return x
        """
    )
    assert _rules(vs) == ["TD008", "TD008"]
    assert "pmean" in vs[0].message
    assert "deadlock" in vs[0].message


def test_td008_unguarded_and_host_guard_pass():
    # the correct shape: collective on EVERY rank, rank guard only
    # around the host-side action — plus the audited inline-ignore
    vs = _lint(
        """
        from jax import lax
        from tpu_dist.metrics.logging import rank0_print

        def good(x, rank):
            y = lax.pmean(x, "data")
            if rank == 0:
                rank0_print(y)
            return y

        def audited(x, rank):
            if rank == 0:
                return lax.pmean(x, "data")  # tpu-dist: ignore[TD008] — single-process tool
            return x
        """
    )
    assert vs == []


def test_td008_multihost_utils_and_polarity_inversion():
    vs = _lint(
        """
        from jax.experimental import multihost_utils

        def bad(tree, rank):
            if not rank:
                multihost_utils.sync_global_devices("ckpt")
        """
    )
    assert _rules(vs) == ["TD008"]


# -- suppressions & baseline ------------------------------------------------


def test_inline_and_block_suppressions():
    vs = _lint(
        """
        def a(loss):
            print(loss)  # tpu-dist: ignore[TD002,TD007]

        def b(loss):
            # tpu-dist: ignore[TD002, TD007] — multi-line explanation of why
            # this print is deliberate on every process
            print(loss)

        def c(loss):
            print(loss)  # tpu-dist: ignore[TD001]  (wrong rule: still flagged)
        """
    )
    assert _rules(vs) == ["TD002", "TD007"]
    assert vs[0].line == 11


def test_baseline_filters_and_reports_stale():
    vs = _lint(
        """
        def a(loss):
            print(loss)
        """
    )
    assert _rules(vs) == ["TD002", "TD007"]
    entries = [
        {"rule": "TD002", "path": "tpu_dist/fake/mod.py", "snippet": "print(loss)"},
        {"rule": "TD007", "path": "tpu_dist/fake/mod.py", "snippet": "print(loss)"},
        {"rule": "TD002", "path": "tpu_dist/fake/mod.py", "snippet": "print(gone)"},
    ]
    new, stale = baseline_lib.apply(vs, entries)
    assert new == []
    assert [e["snippet"] for e in stale] == ["print(gone)"]


# -- clean-file negative ----------------------------------------------------


def test_clean_realistic_module():
    vs = _lint(
        """
        import jax
        import jax.numpy as jnp
        from tpu_dist.comm.compat import shard_map
        from tpu_dist.metrics.logging import rank0_print

        def make_train_step(opt, mesh):
            def step_local(state, batch, key):
                x = batch + jax.random.normal(key, batch.shape)
                return state, jnp.mean(x)
            sharded = shard_map(
                step_local, mesh=mesh, in_specs=None, out_specs=None
            )
            return jax.jit(sharded, donate_argnums=(0,))

        def report(metrics):
            rank0_print(f"loss {metrics['loss']:.3f}")
        """
    )
    assert vs == []


@pytest.mark.quick  # the quick-slice analysis representative: pure-AST,
# no subprocess/jaxpr compile (test_cli_clean_on_repo moved to slow,
# ISSUE 17 tier-1 budget)
def test_repo_is_lint_clean():
    vs = lint_paths([os.path.join(REPO, "tpu_dist")], root=REPO)
    assert vs == [], "\n".join(v.format_text() for v in vs)


# -- Layer 2: jaxpr audit ---------------------------------------------------


def test_dp_step_collective_count():
    counts, violations = audit_case("dp_sgd")
    # THE data-parallel budget: one multi-operand grad pmean + three metric
    # reduces, nothing else (no transfers inside the step)
    assert counts["collectives"] == {"psum": 4}
    assert counts["transfers"] == 0
    assert violations == []


def test_grad_accum_adds_no_collectives():
    plain, _ = audit_case("dp_sgd")
    accum, violations = audit_case("dp_sgd_accum4")
    assert accum["collectives"] == plain["collectives"]  # no_sync contract
    assert violations == []


def test_zero1_swaps_allreduce_for_rs_ag():
    counts, violations = audit_case("zero1_sgd")
    assert counts["collectives"]["reduce_scatter"] == 1
    assert counts["collectives"]["all_gather"] == 1
    assert violations == []


def test_scan_body_collectives_count_per_trip():
    """A collective INSIDE a scan body multiplies by the trip count — the
    property that lets TD101 catch a grad reduce accidentally moved inside
    the accumulation scan (the no_sync violation), which would otherwise
    count the same as the single post-scan reduce."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from tpu_dist.analysis.jaxpr_audit import trace_counts
    from tpu_dist.comm import mesh as mesh_lib
    from tpu_dist.comm.compat import shard_map

    mesh = mesh_lib.data_parallel_mesh()
    n = mesh.devices.size

    def local(x):  # 3 rows per device -> scan of length 3, one pmean per trip
        def body(c, t):
            return c + lax.pmean(t, mesh_lib.DATA_AXIS), None

        out, _ = lax.scan(body, jnp.zeros_like(x[0]), x)
        return out

    f = shard_map(local, mesh=mesh, in_specs=P("data"), out_specs=P(), check_vma=False)
    counts = trace_counts(f, jax.ShapeDtypeStruct((3 * n, 4), jnp.float32))
    assert counts["collectives"]["psum"] == 3, counts


def test_audit_all_clean_and_budget_mismatch_detected():
    report, violations = audit_all()
    assert violations == []
    assert set(report) >= {"dp_sgd", "dp_sgd_accum4", "dp_bf16", "zero1_sgd"}
    # a drifted budget must produce TD101
    counts, _ = audit_case("dp_sgd")
    vs = _compare("dp_sgd", counts, CollectiveBudget({"psum": 3}))
    assert [v.rule for v in vs] == ["TD101"]
    # and an undeclared bf16 promotion must produce TD103
    bf16, _ = audit_case("dp_bf16")
    vs = _compare(
        "dp_bf16",
        bf16,
        CollectiveBudget({"psum": 4}, bf16_to_f32=bf16["bf16_to_f32"] - 1),
    )
    assert [v.rule for v in vs] == ["TD103"]


# -- CLI gate contract ------------------------------------------------------


def _run_cli(args, cwd=REPO):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)  # the CLI configures its own backend
    return subprocess.run(
        [sys.executable, "-m", "tpu_dist.analysis", *args],
        cwd=cwd,
        env=env,
        capture_output=True,
        text=True,
        timeout=300,
    )


def test_cli_nonzero_on_planted_violation(tmp_path):
    bad = tmp_path / "bad_mod.py"
    bad.write_text(
        "from jax import shard_map\n"
        "def noisy(loss):\n"
        "    print(loss)\n"
    )
    r = _run_cli([str(bad), "--no-jaxpr", "--format", "json"])
    assert r.returncode == 1, r.stdout + r.stderr
    out = json.loads(r.stdout)
    assert {v["rule"] for v in out["violations"]} == {"TD002", "TD004", "TD007"}


def test_cli_clean_on_repo():
    # the acceptance gate: lint + jaxpr audit over the real package, exit 0
    r = _run_cli(["--format", "json"])
    assert r.returncode == 0, r.stdout + r.stderr
    out = json.loads(r.stdout)
    assert out["counts"]["new"] == 0
    assert out["jaxpr_report"]["dp_sgd"]["collectives"] == {"psum": 4}
