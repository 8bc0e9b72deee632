"""The device step's address (ISSUE 37): every instruction of the compiled
train step falls in one phase, every block of the four model families lies
under a scope of ``obs/hlo_scopes.py``'s table, the scopes PR 33 / PR 35
opened read what they read, and the table is made from this tree's names and
not from a compile cache's."""

import ast
import contextlib
import os
import warnings

import jax
import jax.numpy as jnp
import pytest

from benchmarks.harness import phases
from tests.helpers import tiny_resnet
from tpu_dist import compile_cache
from tpu_dist.comm import mesh as mesh_lib
from tpu_dist.nn import nemotron_h, vit
from tpu_dist.obs import counters, hlo_scopes
from tpu_dist.train import optim
from tpu_dist.train.epoch import make_fused_epoch
from tpu_dist.train.state import TrainState
from tpu_dist.train.step import make_train_step

OLD_NINE = ("ssm/scan", "moe/route", "moe/experts", "moe/shared", "attn/causal", "lm/head_loss",
            "conv/short", "ffn/dense", "attn/rope")
FAMILIES = {
    # name: (model, token model, recomputes, devices)
    "nemotron_h_tiny": (nemotron_h.nemotron_h_tiny, True, True, 1),
    "lfm2_moe_tiny": (nemotron_h.lfm2_moe_tiny, True, True, 1),
    "vit_tiny": (vit.vit_tiny, False, False, 2),
    "tiny_resnet": (tiny_resnet, False, False, 1),
    "tiny_resnet.fused": (tiny_resnet, False, False, 1),
}


def _compiled_text(family: str) -> str:
    make, token, _, n_dev = FAMILIES[family]
    model, opt = make(), optim.SGD()
    mesh = mesh_lib.device_mesh([n_dev], [mesh_lib.DATA_AXIS], jax.devices()[:n_dev])
    params, model_state = model.init(jax.random.PRNGKey(0))
    state = TrainState.create(params, model_state, opt)
    if family.endswith(".fused"):
        epoch = make_fused_epoch(model.apply, opt, mesh, batch_per_device=4, compute_dtype=jnp.float32)
        args = (state, jnp.zeros((8, 32, 32, 3), jnp.uint8), jnp.zeros((8,), jnp.int32), 0.1, 0)
        return epoch.lower(*args).compile().as_text()
    if token:
        step = make_train_step(model.apply, opt, mesh, model_loss=model.loss, sync_bn=False)
        batch = (jnp.zeros((2, model.seq_len), jnp.int32),) * 2
    else:
        step = make_train_step(model.apply, opt, mesh)
        batch = (jnp.zeros((4, 32, 32, 3), jnp.float32), jnp.zeros((4,), jnp.int32))
    return step.lower(state, *batch, 0.1).compile().as_text()


@pytest.fixture(scope="module")
def programs():
    """``family -> (the compiled step's text, the scopes its trace opened)``,
    compiled once a module."""
    out = {}
    for family in FAMILIES:
        hlo_scopes.forget_opened()
        out[family] = (_compiled_text(family), hlo_scopes.opened())
    yield out
    hlo_scopes.forget_opened()
    hlo_scopes.record("")


@pytest.fixture
def table(programs, request):
    text, opened = programs[request.param]
    hlo_scopes.record(text, opened)
    yield request.param
    hlo_scopes.record("")


@pytest.mark.parametrize("table", list(FAMILIES), indirect=True)
def test_every_named_instruction_falls_in_exactly_one_phase(table):
    by_phase = {phase: hlo_scopes.ops_in_phase(phase) for phase in hlo_scopes.PHASES}
    assert sum(len(ops) for ops in by_phase.values()) == hlo_scopes.recorded() > 0
    assert frozenset().union(*by_phase.values()) == hlo_scopes.ops_in("")
    for phase in ("forward", "backward", "optimizer", "metrics"):
        assert by_phase[phase], phase
    assert bool(by_phase["recompute"]) == FAMILIES[table][2]
    assert bool(by_phase["data"]) == table.endswith(".fused")
    # (the TPU's compiler drops a mean over one device; this backend keeps it)
    assert by_phase["grad_reduce"] or FAMILIES[table][3] == 1
    assert hlo_scopes.missing() == 0 and hlo_scopes.has_phases()
    assert counters.snapshot()["hlo_scopes.missing"] == 0


@pytest.mark.parametrize("op_name, phase", [
    ("jit(step)/step/loss_grad/jvp(blk/mm)/tanh", "forward"),
    ("jit(step)/step/loss_grad/transpose(jvp(blk/mm))/dot_general", "backward"),
    ("jit(step)/step/loss_grad/transpose(jvp(step/loss_grad))/jvp()/checkpoint/rematted_computation/blk/mm/tanh",
     "recompute"),
    ("jit(step)/step/loss_grad/transpose(jvp(step/loss_grad))/jvp()/checkpoint/blk/mm/mul", "backward"),
    ("jit(epoch_local)/while/body/closed_call/step/optimizer/sub", "optimizer"),
    ("jit(step)/step/grad_reduce/psum", "grad_reduce"),
    ("step/metrics/reduce_sum", "metrics"),
    ("jit(epoch_local)/while/body/closed_call/data/take_crop/jit(_randint)/iota", "data"),
    ("jit(step)/shard_map/transpose", "other"),      # the primitive, not the transform
    ("", "other"),
])
def test_phase_of_reads_the_forms_jax_writes(op_name, phase):
    assert hlo_scopes.phase_of(op_name) == phase


@pytest.mark.parametrize("table", ["nemotron_h_tiny", "lfm2_moe_tiny"], indirect=True)
def test_the_token_models_leave_little_of_loss_grad_outside_every_block(table):
    in_loss_grad = hlo_scopes.ops_in("step/loss_grad")
    outside = in_loss_grad - hlo_scopes.attributed_ops()
    assert len(outside) <= 0.10 * len(in_loss_grad), (len(outside), len(in_loss_grad))


@pytest.mark.parametrize("table", ["vit_tiny", "tiny_resnet.fused"], indirect=True)
def test_the_image_models_blocks_are_all_there(table):
    prefix = "vit/" if table == "vit_tiny" else "resnet/"
    for name in (s for s in hlo_scopes.SCOPES if s.startswith(prefix)):
        assert hlo_scopes.ops_in(name), name
    outside = hlo_scopes.ops_in("step/loss_grad") - hlo_scopes.attributed_ops()
    # what is left is the cross-entropy, which has no block of its own
    assert all("log_softmax" in hlo_scopes.name_of(op) or "take_along_axis" in hlo_scopes.name_of(op)
               or "jvp()" in hlo_scopes.name_of(op) for op in outside)


@pytest.mark.parametrize("family", ["nemotron_h_tiny", "lfm2_moe_tiny"])
def test_the_nine_old_scopes_read_what_they_read_on_the_parent(programs, family, monkeypatch):
    """The parent's program is this one with only PR 33's and PR 35's scopes
    open: the same instructions lie under each of them."""
    hlo_scopes.record(programs[family][0])
    mine = {name: hlo_scopes.ops_in(name) for name in OLD_NINE}
    monkeypatch.setattr(
        hlo_scopes, "scope",
        lambda name: jax.named_scope(name) if name in OLD_NINE else contextlib.nullcontext())
    hlo_scopes.record(_compiled_text(family))
    parents = {name: hlo_scopes.ops_in(name) for name in OLD_NINE}
    hlo_scopes.record("")
    assert {k: len(v) for k, v in mine.items()} == {k: len(v) for k, v in parents.items()}
    assert sum(bool(v) for v in mine.values()) >= 6


# -- the sites and the table ---------------------------------------------------------------------

def _scope_sites():
    """``(file, name or None)`` of every call of ``named_scope`` or
    ``hlo_scopes.scope`` under ``tpu_dist/``; None where the name is no
    string literal."""
    root = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tpu_dist")
    sites = []
    for folder, _, files in os.walk(root):
        for file in files:
            if not file.endswith(".py"):
                continue
            path = os.path.join(folder, file)
            with open(path, encoding="utf-8") as f:
                tree = ast.parse(f.read())
            for node in ast.walk(tree):
                if not (isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute)):
                    continue
                owner = getattr(node.func.value, "id", None)
                if node.func.attr == "named_scope" or (node.func.attr, owner) == ("scope", "hlo_scopes"):
                    arg = node.args[0] if node.args else None
                    literal = arg.value if isinstance(arg, ast.Constant) and isinstance(arg.value, str) else None
                    sites.append((os.path.relpath(path, root), node.func.attr, literal))
    return sites


def test_every_site_takes_its_name_from_the_table_and_no_name_holds_another():
    sites = _scope_sites()
    # jax.named_scope is called in one place: hlo_scopes.scope itself
    assert [file for file, attr, _ in sites if attr == "named_scope"] == [os.path.join("obs", "hlo_scopes.py")]
    opened = {name for _, attr, name in sites if attr == "scope"}
    assert None not in opened, "a scope's name must be a string literal, so that this test can read it"
    assert opened == set(hlo_scopes.SCOPES)
    by_file = {}
    for file, attr, name in sites:
        if attr == "scope":
            by_file.setdefault(name, set()).add(file.replace(os.sep, "/"))
    for name, entry in hlo_scopes.SCOPES.items():
        assert by_file[name] == set(entry.file.split(", ")), name
        assert entry.kind in ("block", "step", "collective") and entry.layer and entry.metric
    names = list(hlo_scopes.SCOPES)
    for a in names:
        assert not any(a in b for b in names if b != a), a
        # the last part of an op_name is the primitive's name: no scope may read as one
        assert "/" in a and not a.endswith(("/transpose", "/while", "/checkpoint"))
    assert set(OLD_NINE) < set(names)


def test_the_docs_table_names_every_scope_with_its_file_and_metric():
    docs = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "docs", "observability.md")
    with open(docs, encoding="utf-8") as f:
        rows = {line.split("|")[1].strip(" `"): line for line in f if line.startswith("| `") and "/" in line}
    for name, entry in hlo_scopes.SCOPES.items():
        assert name in rows, name
        assert all(f"`{file}`" in rows[name] for file in entry.file.split(", ")), name
        assert entry.layer in rows[name] and entry.metric.split(" ")[0].rstrip(",'s") in rows[name], name


# -- the trap: a compile cache keyed without metadata serves another tree's names -------------------

def _one_scope(name):
    def f(x):
        with hlo_scopes.scope("step/loss_grad"):
            with hlo_scopes.scope(name):
                return jnp.tanh(x) * 2.0
    return jax.jit(f)


@pytest.fixture
def persistent_cache(tmp_path, monkeypatch):
    from jax._src import compilation_cache as _cc

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs",
        "jax_compilation_cache_include_metadata_in_key", "jax_enable_compilation_cache")}
    _cc.reset_cache()
    jax.config.update("jax_enable_compilation_cache", True)   # conftest: off
    assert compile_cache.enable(str(tmp_path)) == str(tmp_path)
    yield str(tmp_path)
    for k, v in before.items():
        jax.config.update(k, v)
    _cc.reset_cache()
    hlo_scopes.forget_opened()
    hlo_scopes.record("")


def _table_of(name, x):
    hlo_scopes.forget_opened()
    text = _one_scope(name).lower(x).compile().as_text()
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        hlo_scopes.record(text, hlo_scopes.opened())
    return caught


def test_two_programs_that_differ_in_a_scope_name_alone_each_read_their_own(persistent_cache):
    assert jax.config.jax_compilation_cache_include_metadata_in_key
    x = jnp.ones((8, 8))
    _table_of("vit/mlp", x)
    assert os.listdir(persistent_cache) and hlo_scopes.ops_in("vit/mlp")
    caught = _table_of("vit/head", x)
    assert hlo_scopes.ops_in("vit/head") and not hlo_scopes.ops_in("vit/mlp")
    assert hlo_scopes.missing() == 0 and not caught
    assert phases.table() is hlo_scopes     # the benchmark's readers trust it


def test_without_the_setting_the_second_is_served_the_firsts_names_and_says_so(persistent_cache):
    jax.config.update("jax_compilation_cache_include_metadata_in_key", False)
    x = jnp.ones((8, 8))
    _table_of("vit/mlp", x)
    caught = _table_of("vit/head", x)
    assert hlo_scopes.ops_in("vit/mlp") and not hlo_scopes.ops_in("vit/head")
    assert hlo_scopes.missing() == 1 and counters.snapshot()["hlo_scopes.missing"] == 1
    assert len(caught) == 1 and "vit/head" in str(caught[0].message)
    assert phases.table() is None           # and every reader reports nothing


def test_enable_sets_the_key_also_where_the_environment_places_the_cache(monkeypatch):
    before = jax.config.jax_compilation_cache_include_metadata_in_key
    try:
        jax.config.update("jax_compilation_cache_include_metadata_in_key", False)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/from/outside")
        assert compile_cache.enable() == "/placed/from/outside"
        assert jax.config.jax_compilation_cache_include_metadata_in_key
    finally:
        jax.config.update("jax_compilation_cache_include_metadata_in_key", before)
