"""End-to-end CLI tests on a small N-Triples fixture."""

import gzip

import pytest

from rdfind_tpu import oracle
from rdfind_tpu.programs import (check_hash_collisions, count_conditions,
                                 count_distinct_values, count_triples, rdfind)

FIXTURE = """\
# people fixture
<alice> <bornIn> <berlin> .
<bob> <bornIn> <berlin> .
<carol> <bornIn> <paris> .
<alice> <livesIn> <berlin> .
<bob> <livesIn> <berlin> .
<carol> <livesIn> <paris> .
<dave> <livesIn> <rome> .
"""


@pytest.fixture()
def fixture_file(tmp_path):
    f = tmp_path / "people.nt"
    f.write_text(FIXTURE)
    return str(f)


def test_rdfind_cli_end_to_end(fixture_file, tmp_path, capsys):
    out = tmp_path / "cinds.txt"
    rc = rdfind.main([fixture_file, "--support", "2", "--clean-implied",
                      "--output", str(out), "--counters", "1"])
    assert rc == 0
    lines = out.read_text().splitlines()
    assert "s[p=<bornIn>] < s[p=<livesIn>] (support=3)" in lines
    # Golden parity with the oracle on the same file.
    triples = [tuple(t) for t in [
        ("<alice>", "<bornIn>", "<berlin>"), ("<bob>", "<bornIn>", "<berlin>"),
        ("<carol>", "<bornIn>", "<paris>"), ("<alice>", "<livesIn>", "<berlin>"),
        ("<bob>", "<livesIn>", "<berlin>"), ("<carol>", "<livesIn>", "<paris>"),
        ("<dave>", "<livesIn>", "<rome>")]]
    want = oracle.minimize_cinds(oracle.discover_cinds_definitional(triples, 2))
    assert len(lines) == len(want)
    err = capsys.readouterr().err
    assert "cind-counter" in err and "csv:" in err


def test_rdfind_cli_count_only(fixture_file, capsys):
    rc = rdfind.main([fixture_file, "--support", "2"])
    assert rc == 0
    assert "Detected" in capsys.readouterr().out


def test_rdfind_cli_half_approximate_flags(fixture_file, tmp_path, capsys):
    # --explicit-threshold/--sbf-bytes select the half-approximate 1/1 round
    # of the default strategy; output must equal the exact run, and the
    # half-approximate counters must show the mode actually engaged.
    out_a = tmp_path / "exact.txt"
    out_b = tmp_path / "ha.txt"
    assert rdfind.main([fixture_file, "--support", "2",
                        "--output", str(out_a)]) == 0
    assert rdfind.main([fixture_file, "--support", "2",
                        "--explicit-threshold", "1", "--sbf-bytes", "8",
                        "--output", str(out_b), "--counters", "1"]) == 0
    assert out_a.read_text() == out_b.read_text()
    assert "stat-ha_explicit_pairs" in capsys.readouterr().err


def test_rdfind_cli_gz_and_strategy(fixture_file, tmp_path, capsys):
    gz = tmp_path / "people.nt.gz"
    with gzip.open(gz, "wt") as f:
        f.write(FIXTURE)
    rc = rdfind.main([str(gz), "--support", "2", "--traversal-strategy", "0",
                      "--use-fis", "--clean-implied"])
    assert rc == 0
    out_a = capsys.readouterr().out
    # Under --clean-implied all strategies emit the identical minimal CIND set
    # (raw outputs differ: S2L keeps only minimal 2/1 and 1/2-pruned 2/2 CINDs,
    # cf. models/small_to_large.py docstring) — and gz input must not matter.
    rc = rdfind.main([fixture_file, "--support", "2", "--clean-implied"])
    assert rc == 0
    assert capsys.readouterr().out == out_a


def test_rdfind_only_read(fixture_file, capsys):
    rc = rdfind.main([fixture_file, "--only-read", "--counters", "1"])
    assert rc == 0
    assert "input-triples: 7" in capsys.readouterr().err


def test_count_triples(fixture_file, capsys):
    count_triples.main([fixture_file])
    assert "Counted 7 triples." in capsys.readouterr().out


def test_count_distinct_values(fixture_file, capsys):
    count_distinct_values.main([fixture_file])
    out = capsys.readouterr().out
    assert "Distinct URLs: 9" in out  # 4 people + 2 predicates + 3 places
    assert "Distinct literals: 0" in out


def test_count_conditions(fixture_file, capsys):
    count_conditions.main([fixture_file])
    out = capsys.readouterr().out
    assert "capture code" in out and "unary" in out and "binary" in out


def test_check_hash_collisions(fixture_file, capsys):
    check_hash_collisions.main([fixture_file])
    out = capsys.readouterr().out
    assert "Colliding values: 0" in out


def test_rdfind_empty_input(tmp_path, capsys):
    f = tmp_path / "empty.nt"
    f.write_text("# only a comment\n")
    rc = rdfind.main([str(f), "--support", "2"])
    assert rc == 0
    assert "Detected 0 CINDs." in capsys.readouterr().out


def test_rdfind_ar_output(tmp_path, capsys):
    f = tmp_path / "ar.nt"
    f.write_text("<a> <p1> <x> .\n<b> <p1> <x> .\n<c> <p2> <x> .\n<c> <p2> <y> .\n")
    out = tmp_path / "ars.txt"
    rc = rdfind.main([str(f), "--support", "2", "--use-fis", "--use-ars",
                      "--ar-output", str(out)])
    assert rc == 0
    lines = out.read_text().splitlines()
    assert "[p=<p1>] -> [o=<x>] (support=2,confidence=100.00%)" in lines


def test_rdfind_print_plan_and_sanity(fixture_file, capsys):
    import json
    rc = rdfind.main([fixture_file, "--support", "1", "--print-plan",
                      "--debug-level", "2", "--counters", "1"])
    assert rc == 0
    out, err = capsys.readouterr()
    plan = json.loads(out[:out.index("\n}") + 2])
    assert plan["strategy"] == 1
    assert "overlap-1/1" in plan["stages"]["discover"]
    assert plan["stages"]["ingest"][0] == "read+parse"
    # DEBUG_LEVEL_SANITY: trivial-CIND count reported, and it is zero.
    assert "sanity-trivial-cinds: 0" in err


def test_rdfind_file_filter_and_encoding(tmp_path, capsys):
    (tmp_path / "a.nt").write_bytes(
        '<s1> <p> "é" .\n<s2> <p> "é" .\n'.encode("utf-16"))
    (tmp_path / "ignore.txt").write_text("not rdf\n")
    rc = rdfind.main([str(tmp_path), "--file-filter", r"\.nt$",
                      "--encoding", "auto", "--support", "1",
                      "--counters", "1"])
    assert rc == 0
    _, err = capsys.readouterr()
    assert "input-triples: 2" in err


def test_rdfind_cli_skew_flags(fixture_file, capsys):
    """--rebalance-* and ablation flags reach the sharded pipeline and keep
    the output identical."""
    base = rdfind.main([fixture_file, "--support", "1", "--collect-result"])
    assert base == 0
    want, _ = capsys.readouterr()
    rc = rdfind.main([fixture_file, "--support", "1", "--collect-result",
                      "--dop", "2", "--rebalance-strategy", "2",
                      "--rebalance-max-load", "20",
                      "--rebalance-threshold", "0.5",
                      "--no-combinable-join"])
    assert rc == 0
    got, _ = capsys.readouterr()
    assert sorted(got.splitlines()) == sorted(want.splitlines())


def test_rdfind_find_only_fcs(fixture_file, capsys):
    """--find-only-fcs stops after frequent-condition mining with counts."""
    # Level 1 = unary only; level 2 adds binary (RDFind.scala:298-306).
    rc = rdfind.main([fixture_file, "--support", "2", "--find-only-fcs", "1",
                      "--counters", "1"])
    assert rc == 0
    _, err = capsys.readouterr()
    # Fixture: bornIn(3), livesIn(4), berlin-subj... count by hand:
    # unary frequent (>=2): p=bornIn(3), p=livesIn(4), o=berlin(4), o=paris(2),
    # s=alice(2), s=bob(2), s=carol(2) -> 7
    assert "frequent-single-conditions: 7" in err
    assert "frequent-double-conditions" not in err
    assert "cind-counter" not in err
    rc = rdfind.main([fixture_file, "--support", "2", "--find-only-fcs", "2",
                      "--counters", "1"])
    _, err = capsys.readouterr()
    assert "frequent-single-conditions: 7" in err
    assert "frequent-double-conditions:" in err


def test_rdfind_join_histogram(fixture_file, capsys):
    """--create-join-histogram prints the reference's 'Join size N encountered
    Mx' lines, consistent with the joinline oracle's line sizes."""
    rc = rdfind.main([fixture_file, "--support", "1",
                      "--create-join-histogram"])
    assert rc == 0
    out, _ = capsys.readouterr()
    lines = [l for l in out.splitlines() if l.startswith("Join size")]
    assert lines, out
    # Cross-check against a hand-rolled dict-of-sets join construction.
    import collections
    import re

    from rdfind_tpu.io import ntriples, reader
    triples = [ntriples.parse_line(l)
               for _, l in reader.iter_lines([fixture_file])]
    triples = [t for t in triples if t is not None]
    jls = collections.defaultdict(set)
    for t in triples:
        for pi in range(3):  # projections = "spo"
            a, b = [i for i in range(3) if i != pi]
            jls[t[pi]].add(("u", pi, a, t[a]))
            jls[t[pi]].add(("u", pi, b, t[b]))
            jls[t[pi]].add(("b", pi, t[a], t[b]))
    want = collections.Counter(len(v) for v in jls.values())
    got = {}
    for l in lines:
        m = re.match(r"Join size (\d+) encountered (\d+)x", l)
        got[int(m.group(1))] = int(m.group(2))
    assert got == dict(want)


def test_rdfind_rejects_empty_projection(fixture_file, capsys):
    with pytest.raises(SystemExit):
        rdfind.main([fixture_file, "--projection", "sp9"])
    _, err = capsys.readouterr()
    assert "subset of 'spo'" in err


def test_rdfind_histogram_with_only_join(fixture_file, capsys):
    """Histogram runs before the --do-only-join early return (ref order)."""
    rc = rdfind.main([fixture_file, "--support", "1", "--do-only-join",
                      "--create-join-histogram"])
    assert rc == 0
    out, _ = capsys.readouterr()
    assert any(l.startswith("Join size") for l in out.splitlines())


def test_package_discover_api():
    import numpy as np

    import rdfind_tpu
    ids = np.asarray([[0, 10, 20], [1, 10, 20], [0, 11, 20], [1, 11, 20]],
                     np.int32)
    for strat in (0, 1, 2, 3):
        t = rdfind_tpu.discover(ids, 2, strategy=strat)
        assert len(t) > 0
    with pytest.raises(ValueError, match="unknown traversal strategy"):
        rdfind_tpu.discover(ids, 2, strategy=9)


def test_rdfind_family_counts_debug(fixture_file, capsys):
    rc = rdfind.main([fixture_file, "--support", "2", "--debug-level", "1",
                      "--counters", "1"])
    assert rc == 0
    _, err = capsys.readouterr()
    assert "CIND families: 1/1:" in err
    assert "cinds-11:" in err


def test_rdfind_sharded_ingest_single_process(tmp_path, capsys):
    """--sharded-ingest works single-process too (one host owns all files)
    and matches the replicated-ingest output."""
    files = []
    for i, content in enumerate([
            "<a> <p> <x> .\n<b> <p> <x> .\n",
            "<a> <q> <x> .\n<b> <q> <x> .\n<c> <q> <y> .\n"]):
        f = tmp_path / f"s{i}.nt"
        f.write_text(content)
        files.append(str(f))
    rc = rdfind.main([*files, "--support", "1", "--traversal-strategy", "0",
                      "--output", str(tmp_path / "a.txt")])
    assert rc == 0
    rc = rdfind.main([*files, "--support", "1", "--traversal-strategy", "0",
                      "--sharded-ingest", "--dop", "2",
                      "--output", str(tmp_path / "b.txt")])
    assert rc == 0
    assert (tmp_path / "a.txt").read_text() == (tmp_path / "b.txt").read_text()


def test_rdfind_sharded_ingest_probes(tmp_path, capsys):
    """Every flag the sharded-ingest path once rejected now runs: the
    read-only and join-only probes stop at the same milestones as the
    replicated path."""
    f = tmp_path / "x.nt"
    f.write_text("<a> <p> <x> .\n<b> <p> <x> .\n")
    for flag in ("--only-read", "--do-only-join"):
        assert rdfind.main([str(f), "--sharded-ingest", flag, "--counters",
                            "1", "--support", "1"]) == 0
        err = capsys.readouterr().err
        assert "input-triples: 2" in err
        assert "cind-counter" not in err  # discovery never ran


def test_rdfind_sharded_ingest_checkpoint_resume(tmp_path, capsys):
    """Second --sharded-ingest run resumes both the per-host ingest cache and
    the discover checkpoint, with identical output — including the mined AR
    table (non-scalar stats survive the checkpoint, so resume re-mines
    nothing)."""
    f = tmp_path / "c.nt"
    f.write_text("".join(f"<s{i % 3}> <p> <o{i % 2}> .\n" for i in range(12)))
    args = [str(f), "--support", "2", "--sharded-ingest", "--counters", "1",
            "--use-fis", "--use-ars",
            "--checkpoint-dir", str(tmp_path / "ck"),
            "--ar-output", str(tmp_path / "{}.ars"),
            "--output", str(tmp_path / "{}.tsv")]
    assert rdfind.main([a.format("first") for a in args]) == 0
    first_err = capsys.readouterr().err
    assert "resumed-ingest" not in first_err
    assert rdfind.main([a.format("second") for a in args]) == 0
    second_err = capsys.readouterr().err
    assert "resumed-ingest: 1" in second_err
    assert "resumed-discover: 1" in second_err
    assert "phase mine-ars" not in second_err  # rules rode the checkpoint
    assert ((tmp_path / "first.tsv").read_text()
            == (tmp_path / "second.tsv").read_text())
    assert ((tmp_path / "first.ars").read_text()
            == (tmp_path / "second.ars").read_text())


def test_rdfind_sharded_ingest_use_ars(tmp_path):
    """--sharded-ingest --use-ars mines rules distributed and suppresses the
    same AR-implied CINDs as the replicated path."""
    f = tmp_path / "ar.nt"
    rows = [f"<s{i}> <born> <town{i % 2}> .\n<s{i}> <lives> <town{i % 2}> .\n"
            for i in range(4)]
    f.write_text("".join(rows))
    args = [str(f), "--support", "2", "--use-fis", "--use-ars",
            "--traversal-strategy", "0",
            "--output", str(tmp_path / "{}.tsv")]
    assert rdfind.main([a.format("rep") for a in args]) == 0
    assert rdfind.main([a.format("sh") for a in args] + ["--sharded-ingest"]) == 0
    rep = sorted((tmp_path / "rep.tsv").read_text().splitlines())
    sh = sorted((tmp_path / "sh.tsv").read_text().splitlines())
    assert rep == sh and len(rep) > 0


def test_rdfind_profile_dir(tmp_path):
    """--profile-dir writes an XLA profiler trace of the run."""
    f = tmp_path / "p.nt"
    f.write_text("<a> <p> <x> .\n<b> <p> <x> .\n")
    prof = tmp_path / "trace"
    assert rdfind.main([str(f), "--support", "1",
                        "--profile-dir", str(prof)]) == 0
    dumped = list(prof.rglob("*.xplane.pb")) + list(prof.rglob("*.json.gz"))
    assert dumped, f"no trace artifacts under {prof}"

