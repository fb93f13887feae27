"""Sharded SmallToLarge on the dense path (models/sharded_dense.py), on
four of the suite's fake CPU devices: LUBM(1) through the CLI's main with
--dop 4 against the benchmark's plain reference, a whole benchmark run of
the four-chip configuration at one university, parity with the
single-device strategy on small graphs, and a planted stage failure."""

import json
import os

import jax
import numpy as np
import pytest

from benchmark import check, gen, reference
from benchmark import run as bench_run
from rdfind_tpu.models import sharded, sharded_dense, small_to_large
from rdfind_tpu.parallel.mesh import make_mesh
from rdfind_tpu.programs import rdfind
from rdfind_tpu.utils import synth

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LUBM1 = {"kind": "lubm", "universities": 1, "structure_seed": 0}
SUPPORT = 25  # lubm480x4's 12000 is 25 per university


@pytest.fixture(scope="module")
def lubm1():
    return gen.build_graph(LUBM1)


@pytest.fixture(scope="module")
def mesh4():
    assert len(jax.devices()) >= 4, "conftest should provide 8 CPU devices"
    return make_mesh(4)


def _cli(nt, out, *extra):
    got = []
    rc = rdfind.main([str(nt), "--support", str(SUPPORT), "--projection",
                      "spo", "--use-fis", "--clean-implied", "--dop", "4",
                      "--traversal-strategy", "1", "--output", str(out),
                      *extra], on_result=got.append)
    return rc, got


@pytest.mark.parametrize("seed", [7, 2 ** 31 + 11])
def test_cli_dop4_matches_reference(tmp_path, lubm1, seed, capsys):
    """The written table equals the reference's minimal set row by row,
    supports included; the sharded dense stages did the work, their
    exchanges reach the run's counters, and nothing degraded."""
    triples = gen.relabel(lubm1, seed)
    nt, out = tmp_path / "g.nt", tmp_path / "cinds.tsv"
    gen.write_nt(str(nt), triples)
    rc, got = _cli(nt, out)
    assert rc == 0
    want, _ = reference.minimal_cinds(triples, SUPPORT)
    checks = check.compare([out.read_text()], want)
    assert check.passed(checks), checks
    assert checks["reference_cinds"] == len(got[0].table) > 0
    counters = got[0].counters
    assert counters["stat-pair_backend"] == "matmul"
    sites = counters["stat-exchange_sites"]
    for site in ("freq", "exchange_a"):
        assert sites[site]["calls"] == 1 and sites[site]["ici_bytes"] > 0
    assert not counters.get("stat-degradations")
    assert "degrading" not in capsys.readouterr().err


def test_benchmark_run_of_the_four_chip_config(lubm1):
    """benchmark/run.py's whole run, without the look for a chip, with
    lubm480x4's flags on one university: correct on every table."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "lubm480x4.json")) as f:
        config = json.load(f)
    config = dict(config, name="lubm480x4-one", generator=LUBM1)
    cell = {"name": "lubm480x4-one.s2l", "config": config["name"],
            "traffic": "s2l", "chips": 4}
    bench = {"workloads": [cell], "per_layer": [],
             "end_to_end": [{"name": n, "unit": u} for n, u in (
                 ("triples_per_s", "triples/s"), ("peak_hbm_gib", "GiB"),
                 ("setup_s", "s"))]}
    args = bench_run.parse_args(["--workload", cell["name"], "--seed",
                                 str(2 ** 31 + 5), "--seconds", "0.1"])
    out = bench_run.run_cell(
        args, require_tpu=False, bench=bench, config=config,
        traffic={"support": SUPPORT, "flags": ["--traversal-strategy", "1"]})
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 2 and out["failed"] == 0
    assert out["device"]["count"] >= 4


def test_four_chip_config_runs_its_stated_dop():
    """lubm480x4.json's `dop` is the --dop its flags pass, and every cell
    of the configuration asks for that many chips."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "lubm480x4.json")) as f:
        config = json.load(f)
    flags = config["flags"]
    assert int(flags[flags.index("--dop") + 1]) == config["dop"]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = [c for c in bench["workloads"] if c["config"] == config["name"]]
    assert cells and all(c["chips"] == config["dop"] for c in cells)


@pytest.mark.parametrize("use_ars,clean_implied", [(False, True),
                                                    (True, False)])
def test_matches_single_device(mesh4, use_ars, clean_implied):
    """Same table as the single-device strategy, association rules and raw
    output included."""
    triples = synth.inject_cind_structure(
        synth.generate_triples(600, seed=4, n_predicates=8, n_entities=48),
        n_rules=6, ref_size=16, dep_size=9)
    stats = {}
    got = sharded.discover_sharded_s2l(triples, 3, mesh=mesh4,
                                       use_ars=use_ars,
                                       clean_implied=clean_implied,
                                       stats=stats)
    want = small_to_large.discover(triples, 3, use_association_rules=use_ars,
                                   clean_implied=clean_implied)
    assert stats["pair_backend"] == "matmul"
    assert len(want) > 0
    assert got.to_rows() == want.to_rows()


def test_pigeonhole_offers_find_every_frequent_condition(mesh4):
    """A condition at the support exactly, spread evenly so that no device
    holds more than its share, still counts: it is offered where it reaches
    ceil(support / D)."""
    rows = [(1000 + i, 7, 2000 + i) for i in range(8)]  # p=7: 8 rows
    rows += [(3000 + i, 9, 4000) for i in range(4)]     # p=9, o=4000: 4
    triples = np.asarray(rows, np.int32)
    rng = np.random.default_rng(0)
    triples = triples[rng.permutation(len(triples))]
    for support in (4, 8):
        got = sharded.discover_sharded_s2l(triples, support, mesh=mesh4,
                                           clean_implied=True)
        want = small_to_large.discover(triples, support, clean_implied=True)
        assert got.to_rows() == want.to_rows()
    assert sharded_dense.offer_capacity(1 << 24, 4, 12000) == 8192


def test_planted_stage_failure_fails_the_run(tmp_path, lubm1, monkeypatch,
                                             capsys):
    """A sharded stage that fails makes the run fail; the single-device
    twin never stands in for it."""
    calls = []

    def planted(*a, **k):
        raise RuntimeError("planted failure in the incidence exchange")

    def twin(*a, **k):
        calls.append(1)
        return small_to_large.discover(*a, **k)

    monkeypatch.setattr(sharded_dense, "_cooc_step", planted)
    monkeypatch.setattr(small_to_large, "discover", twin)
    nt, out = tmp_path / "g.nt", tmp_path / "cinds.tsv"
    gen.write_nt(str(nt), gen.relabel(lubm1, 3))
    with pytest.raises(RuntimeError, match="planted"):
        _cli(nt, out)
    assert calls == []
    assert "degrading" not in capsys.readouterr().err
    assert not out.exists()
