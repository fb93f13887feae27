"""The plain reference against the repository's brute-force oracle, the
table parser, and the control: the reference on narrower value ids must
come out not correct."""

from __future__ import annotations

import numpy as np
import pytest

from benchmark import check, gen, reference
from benchmark.control import FOLD_BITS, table_text


def _graphs():
    rng = np.random.default_rng(0)
    yield "random", rng.integers(0, 12, size=(300, 3)), 3
    t = gen.inject_cind_structure(
        gen.generate_triples(3000, seed=1, n_predicates=8, n_entities=200),
        n_rules=6, ref_size=15, dep_size=12)
    yield "overlay", t, 12
    yield "lubm", gen.generate_lubm(1, seed=3, departments=1), 20


@pytest.mark.parametrize("name,triples,support", list(_graphs()))
def test_reference_equals_the_brute_force_oracle(name, triples, support):
    oracle = pytest.importorskip("rdfind_tpu.oracle")
    rows = [tuple(map(int, r)) for r in triples]
    raw = oracle.discover_cinds_definitional(rows, support)
    caps = reference.captures(triples, support)
    assert reference.raw_cinds(caps, support) == raw, name
    assert reference.minimize(raw) == oracle.minimize_cinds(raw), name


def test_parse_table_round_trip():
    t = gen.inject_cind_structure(
        gen.generate_triples(3000, seed=1, n_predicates=8, n_entities=200),
        n_rules=6, ref_size=15, dep_size=12)
    want, _ = reference.minimal_cinds(t, 12)
    assert {reference.is_binary(c[3]) for c in want} == {False, True}
    text = table_text(want)
    assert check.parse_table(text) == want
    checks = check.compare([text, text], want)
    assert check.passed(checks)
    wrong = text.replace("(support=12)", "(support=13)", 1)
    checks = check.compare([text, wrong], want)
    assert not check.passed(checks)
    assert checks["tables_wrong"]["value"] == 1
    assert checks["missing_rows"]["value"] == 1
    assert checks["extra_rows"]["value"] == 1


def test_control_is_not_correct():
    """The control: the reference with the projected value ids cut to
    16 bits (sketch-like containment) in the program's place.  The graph
    has more than 2^16 values, as every cell's has."""
    g = gen.inject_cind_structure(
        gen.generate_triples(200000, seed=2, n_predicates=16,
                             n_entities=60000), n_rules=8)
    t = gen.relabel(g, 2 ** 31 + 5)
    assert t.max() >= 1 << 16
    want, _ = reference.minimal_cinds(t, 100)
    ctrl, _ = reference.minimal_cinds(t, 100, FOLD_BITS)
    checks = check.compare([table_text(ctrl)], want)
    assert not check.passed(checks)


def test_reference_takes_ids_past_21_bits():
    """The cells' ids need 22 bits and more: the reference keys conditions
    by 32-bit halves, so spreading the ids over [2^30, 2^31) changes
    nothing but the values."""
    t = gen.generate_lubm(1, seed=3, departments=2)
    assert t.max() < 1 << 18

    def wide(v):
        return np.where(v == reference.NO_VALUE, v, (1 << 30) + 4099 * v)

    want, _ = reference.minimal_cinds(t, 20)
    got, _ = reference.minimal_cinds(wide(t), 20)
    assert len(want) > 100
    assert got == {(c[0], *wide(np.array(c[1:3])).tolist(), c[3],
                    *wide(np.array(c[4:6])).tolist(), c[6]) for c in want}
