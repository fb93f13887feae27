"""Tracing inside the discover stage: the jitted stages' device scopes,
the program-kept compile counter, and the host spans around the dense
paths' pulls."""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rdfind_tpu.data import CindTable
from rdfind_tpu.models import allatonce
from rdfind_tpu.obs import report, tracer
from rdfind_tpu.ops import minimality
from rdfind_tpu.runtime import driver
from rdfind_tpu.utils import synth

N = 64  # padded rows of every stage below


def _i32(n=N, fill=0):
    return jnp.full(n, fill, jnp.int32)


def _lower_prepare():
    return allatonce._stage_prepare.lower(
        jnp.zeros((N, 3), jnp.int32), jnp.int32(N), jnp.int32(2),
        projections="spo", use_fc_filter=True)


def _lower_candidates():
    return allatonce._stage_candidates.lower(
        jnp.zeros((N, 3), jnp.int32), jnp.int32(N), jnp.int32(2),
        projections="spo", use_fc_filter=True)


def _lower_membership():
    return allatonce._stage_membership.lower(
        jnp.zeros((1, N), jnp.int32), _i32(3 * N), jnp.ones(3 * N, bool),
        jnp.int32(2), l_pad=128, c_pad=128, membership_dtype=None)


def _lower_keep_mask():
    return minimality._stage_keep_mask.lower(*[_i32() for _ in range(6)],
                                             jnp.int32(N))


STAGES = {
    "_stage_prepare": (_lower_prepare, tracer.DEVICE_SCOPES),
    "_stage_candidates": (_lower_candidates, tracer.DEVICE_SCOPES),
    "_stage_membership": (_lower_membership, (tracer.SCOPE_MEMBERSHIP,)),
    "_stage_keep_mask": (_lower_keep_mask, (tracer.SCOPE_MINIMALITY,)),
}


@pytest.mark.parametrize("stage", sorted(STAGES))
def test_stage_ops_carry_their_scopes(stage):
    """Every scope name reaches the compiled module's op_name metadata,
    which is what a profiler trace reports per op."""
    lower, names = STAGES[stage]
    text = lower().compile().as_text()
    paths = {p for line in text.splitlines() if 'op_name="' in line
             for p in [line.split('op_name="', 1)[1].split('"', 1)[0]]}
    parts = {part for p in paths for part in p.split("/")}
    for name in names:
        assert name in parts, (stage, name)


def _sorted_rows_by_scope(text):
    """{scope: the row counts of the sorts under it} in a compiled module."""
    out = {}
    for line in text.splitlines():
        if " sort(" in line and 'op_name="' in line:
            parts = line.split('op_name="', 1)[1].split('"', 1)[0].split("/")
            rows = int(re.search(r"\[(\d+)\]", line.split(" sort(")[0])[1])
            for name in tracer.DEVICE_SCOPES:
                if name in parts:
                    out.setdefault(name, []).append(rows)
    return out


@pytest.mark.parametrize("stage,line_sort_rows", [
    ("_stage_prepare", 3 * N), ("_stage_candidates", 9 * N)])
def test_join_line_build_never_sorts_candidate_rows_to_intern(stage,
                                                              line_sort_rows):
    """The condition sorts of the N triple rows read under
    frequent_conditions; interning only moves their rows back by position;
    line ids sort one join-value column per projection in _stage_prepare
    (the (value, capture) dedupe of _stage_candidates sorts the 9N
    candidate rows)."""
    sorts = _sorted_rows_by_scope(STAGES[stage][0]().compile().as_text())
    assert set(sorts[tracer.SCOPE_FREQUENT_CONDITIONS]) == {N}
    assert set(sorts[tracer.SCOPE_INTERN_CAPTURES]) == {N}
    assert max(sorts[tracer.SCOPE_LINE_IDS]) == line_sort_rows


def _write_planted(tmp_path, n=400):
    triples = synth.inject_cind_structure(
        synth.generate_triples(n, seed=3, n_predicates=8, n_entities=40),
        n_rules=4, ref_size=12, dep_size=8)
    path = str(tmp_path / "planted.nt")
    synth.write_nt(path, triples)
    return path


def test_compile_counter_names_each_compile(tmp_path, monkeypatch):
    """A fresh jit inside a driver run adds one named compile to the run's
    counters, under the stage it compiled in; a second run of the same
    shape compiles it no more."""

    def compile_probe(x):
        return x * 3 + 1

    fresh = jax.jit(compile_probe)

    def strategy(ids, min_support, **_):
        jax.block_until_ready(fresh(jnp.asarray(ids)))
        return CindTable.empty()

    monkeypatch.setitem(driver.STRATEGIES, 0, strategy)
    cfg = driver.Config(input_paths=[_write_planted(tmp_path)],
                        min_support=2, traversal_strategy=0)
    first = driver.run(cfg).counters
    mine = [p for p in first["compile-programs"]
            if p.startswith("jit(compile_probe)@")]
    assert len(mine) == 1, first["compile-programs"]
    program, seconds, verdict = mine[0].rsplit(":", 2)
    assert program == "jit(compile_probe)@discover"
    assert float(seconds) > 0
    assert verdict == "uncached"  # the tests keep the persistent cache off
    assert first["compiles"] == len(first["compile-programs"]) >= 1
    assert first["compile-misses"] >= 1
    assert first["compile-s"] >= float(seconds)

    second = driver.run(cfg).counters
    assert not any(p.startswith("jit(compile_probe)@")
                   for p in second["compile-programs"])


@pytest.mark.parametrize("strategy,pulls", [
    (0, {"pull-prepare-counts", "pull-cind-count", "pull-cind-ids"}),
    (1, {"pull-prepare-counts", "pull-capture-table", "pull-lattice-stats"}),
])
def test_discover_pull_spans(tmp_path, monkeypatch, strategy, pulls):
    """A traced run's pulls and postprocessing have spans of their own
    under the discover stage, with no span closed out of order; each
    compile is an instant on the trace."""
    mismatched = []
    close = tracer.Tracer.close

    def recording_close(self):
        mismatched.append(self.n_mismatched)
        close(self)

    monkeypatch.setattr(tracer.Tracer, "close", recording_close)
    trace_dir = str(tmp_path / "trace")
    cfg = driver.Config(input_paths=[_write_planted(tmp_path)],
                        min_support=2, traversal_strategy=strategy,
                        clean_implied=True, trace_dir=trace_dir)
    res = driver.run(cfg)
    assert len(res.table) > 0
    assert mismatched == [0]

    events = report.load_events(os.path.join(trace_dir, "events-host0.jsonl"))
    roots, unclosed = report.build_span_tree(
        [e for e in events if e["ph"] in "BEi"])
    assert unclosed == []
    discover = [n for n, _ in report.walk_spans(roots)
                if n["name"] == "discover" and n["cat"] == tracer.CAT_STAGE]
    assert len(discover) == 1
    below = [n for n, _ in report.walk_spans(discover[0]["children"])]
    pull_names = {n["name"] for n in below if n["cat"] == tracer.CAT_PULL}
    assert pulls <= pull_names, pull_names
    assert all(name.startswith("pull-") for name in pull_names)
    assert any(n["name"] == "postprocess" and n["cat"] == tracer.CAT_STAGE
               for n in below)
    compiled = [e for e in events if e["name"] == "compile"]
    assert len(compiled) == res.counters["compiles"]
    for e in compiled:
        assert e["cat"] == tracer.CAT_COMPILE
        assert {"program", "seconds", "cache", "stage"} <= set(e["args"])
    assert np.isclose(sum(e["args"]["seconds"] for e in compiled),
                      res.counters["compile-s"], atol=1e-3)


def _op_paths(text, op_filter=lambda line: True):
    return [line.split('op_name="', 1)[1].split('"', 1)[0].split("/")
            for line in text.splitlines()
            if 'op_name="' in line and op_filter(line)]


def _sharded_programs():
    """The sharded dense stages compiled on four CPU devices, tiny shapes."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from rdfind_tpu.models import sharded_dense
    from rdfind_tpu.parallel.mesh import AXIS, make_mesh

    mesh = make_mesh(4)

    def arg(shape, spec):
        return jax.ShapeDtypeStruct(shape, jnp.int32,
                                    sharding=NamedSharding(mesh, spec))

    emit = sharded_dense._emit_step.lower(
        arg((4 * N, 3), P(AXIS, None)), arg((4,), P(AXIS)), arg((), P()),
        mesh=mesh, projections="spo", use_ars=True,
        offer_cap=sharded_dense.offer_capacity(N, 4, 2))
    cooc = sharded_dense._cooc_step.lower(
        arg((4 * N, 3), P(AXIS, None)), arg((9, 4 * N), P(None, AXIS)),
        mesh=mesh, projections="spo", cap=2 * N, l_pad=256, c_pad=128,
        dtype="bf16")
    return {"_emit_step": emit.compile().as_text(),
            "_cooc_step": cooc.compile().as_text()}


_COLLECTIVES = (" all-to-all(", " all-gather(", " all-reduce(",
                " all-gather-start(", " all-reduce-start(")


def test_sharded_collectives_carry_exchange():
    """The join-line exchange of the sharded dense stages, every collective
    among it, runs under `exchange`; their other work reads under the names
    the single-device build uses."""
    names = set(tracer.DEVICE_SCOPES) | {
        tracer.SCOPE_MEMBERSHIP, tracer.SCOPE_EXCHANGE}
    want = {"_emit_step": {tracer.SCOPE_FREQUENT_CONDITIONS,
                           tracer.SCOPE_INTERN_CAPTURES,
                           tracer.SCOPE_EXCHANGE},
            "_cooc_step": {tracer.SCOPE_MEMBERSHIP, tracer.SCOPE_EXCHANGE}}
    for stage, text in _sharded_programs().items():
        coll = _op_paths(text, lambda line: any(c in line
                                                for c in _COLLECTIVES))
        assert coll, stage
        assert all(tracer.SCOPE_EXCHANGE in p for p in coll), stage
        scoped = {n for p in _op_paths(text) for n in p if n in names}
        assert scoped == want[stage], stage


@pytest.mark.parametrize("stage,scopes", [
    ("_stage_prepare", set(tracer.DEVICE_SCOPES)),
    ("_stage_membership", {tracer.SCOPE_MEMBERSHIP}),
])
def test_single_device_stages_keep_their_scopes(stage, scopes):
    """The one-chip join-line build and membership carry exactly the scopes
    they had before the sharded stages had theirs: no `exchange`."""
    text = STAGES[stage][0]().compile().as_text()
    names = set(tracer.DEVICE_SCOPES) | {
        tracer.SCOPE_MEMBERSHIP, tracer.SCOPE_MINIMALITY,
        tracer.SCOPE_EXCHANGE}
    assert {n for p in _op_paths(text) for n in p if n in names} == scopes
