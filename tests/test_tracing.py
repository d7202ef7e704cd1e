"""Profiler names of the PRF path (``core/tracing``).

* every device scope reaches the ``op_name`` metadata of the compiled
  program that runs it (grow with sibling reuse on and off, binning,
  DSI, dimension reduction, the tree walk and OOB scoring at leaves);
* every primitive of the grow program's level loop runs under a
  ``prf.`` scope. Checked on the jaxpr, which is what the program hands
  the compiler: the compiler's own layout copies and bitcast-rooted
  fusions carry no metadata at all;
* the mesh trainer (``make_prf_train_fn``) carries the mesh plane's
  scopes, and every collective of its level loop runs under a
  ``prf.mesh.`` scope;
* ``train_prf`` writes its eight host spans in call order, nested under
  ``prf.train``, into a profiler trace;
* a single-process ``fit_bins_sharded`` merges its shard sketches under
  ``prf.bin.merge`` inside ``prf.bin.fit``, with no ``prf.bin.exchange``.
"""
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ForestConfig, train_prf
from jax.sharding import Mesh

from repro.core.binning import apply_bins
from repro.core.dimred import random_feature_mask, select_features
from repro.core.distributed import make_prf_train_fn
from repro.core.dsi import bootstrap_counts
from repro.core.engine import init_forest
from repro.core.forest import _grow_forest_impl, route_to_leaves
from repro.core.voting import oob_accuracy_at_leaves

N, F, K, B = 256, 6, 4, 8
ENGINE_SCOPES = {"prf.task_group", "prf.tgr", "prf.tns", "prf.plan_write", "prf.route"}


def _cfg(reuse: str) -> ForestConfig:
    return ForestConfig(
        n_trees=K, max_depth=3, n_bins=B, n_classes=2, hist_reuse=reuse
    ).resolved(F)


def _grow_args(reuse: str):
    rng = np.random.default_rng(0)
    return (
        jnp.asarray(rng.integers(0, B, (N, F)), jnp.uint8),
        jnp.asarray(rng.integers(0, 2, N), jnp.int32),
        jnp.ones((K, N), jnp.float32),
        _cfg(reuse),
        jnp.ones((K, F), jnp.bool_),
    )


def _scopes(hlo_text: str) -> set:
    """Every ``prf.`` segment of the program's ``op_name`` metadata."""
    return {
        seg
        for op_name in re.findall(r'op_name="([^"]*)"', hlo_text)
        for seg in op_name.split("/")
        if seg.startswith("prf.")
    }


@pytest.mark.parametrize("reuse", ["auto", "off"])
def test_grow_program_carries_engine_scopes(reuse):
    text = _grow_forest_impl.lower(*_grow_args(reuse)).compile().as_text()
    assert ENGINE_SCOPES <= _scopes(text)


def _lowered(program: str):
    key = jax.random.PRNGKey(0)
    if program == "bin.apply":
        x = jnp.linspace(0.0, 1.0, N * F, dtype=jnp.float32).reshape(N, F)
        return apply_bins.lower(x, jnp.tile(jnp.linspace(0.1, 0.9, B - 1), (F, 1)))
    if program == "dsi":
        return bootstrap_counts.lower(key, K, N)
    if program == "dimred.select":
        return select_features.lower(
            jnp.ones((K, F), jnp.float32), key, n_selected=3, n_important=1
        )
    if program == "dimred.random":
        return random_feature_mask.lower(key, n_trees=K, n_features=F, n_selected=3)
    forest = init_forest(_cfg("off"))
    if program == "oob":
        return oob_accuracy_at_leaves.lower(
            forest, jnp.zeros((K, N), jnp.int32), jnp.zeros((N,), jnp.int32),
            jnp.ones((K, N), jnp.float32),
        )
    return route_to_leaves.lower(forest, jnp.zeros((N, F), jnp.uint8))


@pytest.mark.parametrize("program, scope", [
    ("bin.apply", "prf.bin.apply"),
    ("dsi", "prf.dsi"),
    ("dimred.select", "prf.dimred"),
    ("dimred.random", "prf.dimred"),
    ("walk", "prf.walk"),
    ("oob", "prf.oob"),
])
def test_program_carries_its_scope(program, scope):
    assert scope in _scopes(_lowered(program).compile().as_text())


def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        for x in v if isinstance(v, (tuple, list)) else (v,):
            j = getattr(x, "jaxpr", x)
            if hasattr(j, "eqns"):
                yield j


def _unscoped(jaxpr) -> list:
    """Primitives of ``jaxpr`` that no ``prf.`` scope covers: an equation
    under a scope covers everything inside it; a container without one
    (a call, loop or branch) is looked into."""
    out = []
    for eqn in jaxpr.eqns:
        if "prf." in str(eqn.source_info.name_stack):
            continue
        subs = list(_sub_jaxprs(eqn))
        if not subs:
            out.append(eqn.primitive.name)
        for j in subs:
            out += _unscoped(j)
    return out


def _level_loops(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "while":
            yield eqn.params["body_jaxpr"].jaxpr
        for j in _sub_jaxprs(eqn):
            yield from _level_loops(j)


@pytest.mark.parametrize("reuse", ["auto", "off"])
def test_every_level_loop_primitive_is_scoped(reuse):
    args = _grow_args(reuse)
    closed = jax.make_jaxpr(_grow_forest_impl, static_argnums=(3,))(*args)
    # The level loop is the outermost while; the loops inside it (the
    # feature-slab and tree-chunk loops) sit under its scopes.
    body = next(_level_loops(closed.jaxpr))
    assert _unscoped(body) == []


MESH_SCOPES = {"prf.mesh.combine", "prf.mesh.merge", "prf.mesh.route", "prf.walk",
               "prf.dimred", "prf.dsi", "prf.oob"}
COLLECTIVES = {"psum", "all_gather", "reduce_scatter", "all_to_all", "ppermute", "pmax",
               "pmin"}


def _mesh_train(reduce: str):
    """The mesh trainer on a (data=1, model=1) mesh of this process's
    device, and its arguments: the same program a larger mesh runs, with
    every collective over axes of size one."""
    cfg = ForestConfig(n_trees=K, max_depth=3, n_bins=B, n_classes=2, hist_reuse="off",
                       hist_reduce=reduce)
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    fn, _ = make_prf_train_fn(cfg, mesh)
    return fn, (jnp.zeros((N, F), jnp.uint8), jnp.zeros((N,), jnp.int32),
                jax.random.PRNGKey(0))


@pytest.mark.parametrize("reduce", ["psum", "psum_scatter"])
def test_mesh_program_carries_mesh_scopes(reduce):
    fn, args = _mesh_train(reduce)
    assert MESH_SCOPES <= _scopes(fn.lower(*args).compile().as_text())


def _equations(jaxpr) -> list:
    out = []
    for eqn in jaxpr.eqns:
        out.append(eqn)
        for j in _sub_jaxprs(eqn):
            out += _equations(j)
    return out


@pytest.mark.parametrize("reduce", ["psum", "psum_scatter"])
def test_every_mesh_level_collective_is_under_a_mesh_scope(reduce):
    fn, args = _mesh_train(reduce)
    body = next(_level_loops(jax.make_jaxpr(fn)(*args).jaxpr))
    stacks = [str(e.source_info.name_stack) for e in _equations(body)
              if e.primitive.name in COLLECTIVES]
    # The histogram combine, the winner merge's gathers and psums, the route bit.
    assert len(stacks) >= 4
    assert all("prf.mesh." in s for s in stacks), stacks


def _host_spans(trace_dir: str) -> list:
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                spans += [(e.name, e.start_ns, e.end_ns) for e in line.events
                          if e.name.startswith("prf.")]
    return sorted(spans, key=lambda s: s[1])


def test_train_prf_host_spans_nest_in_call_order(tmp_path):
    rng = np.random.default_rng(1)
    x = rng.normal(size=(N, F)).astype(np.float32)
    y = (x[:, 0] > 0).astype(np.int32)
    cfg = ForestConfig(n_trees=K, max_depth=3, n_bins=B, n_classes=2)
    with jax.profiler.trace(str(tmp_path)):
        model = train_prf(x, y, cfg, seed=3)
        jax.block_until_ready(model.forest)
    spans = _host_spans(str(tmp_path))
    assert [s[0] for s in spans] == [
        "prf.train", "prf.screen", "prf.bin.fit", "prf.bin.apply",
        "prf.dsi", "prf.dimred", "prf.grow", "prf.oob",
    ]
    _, t0, t1 = spans[0]
    assert all(t0 <= s <= e <= t1 for _, s, e in spans[1:])
    assert all(a[2] <= b[1] for a, b in zip(spans[1:], spans[2:]))


def test_single_process_fit_bins_sharded_merges_without_the_exchange(tmp_path):
    from repro.core.distributed import fit_bins_sharded

    x = np.random.default_rng(2).normal(size=(N, F)).astype(np.float32)
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    with jax.profiler.trace(str(tmp_path)):
        fit_bins_sharded(x, B, mesh, sample_block=64)
    spans = _host_spans(str(tmp_path))
    assert [s[0] for s in spans] == ["prf.bin.fit", "prf.bin.merge"]
    (_, t0, t1), (_, s, e) = spans
    assert t0 <= s <= e <= t1
