"""The benchmark's generator copies and its refusal to run without a chip.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q
"""

from __future__ import annotations

import hashlib
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import gen

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def digest(a: np.ndarray) -> str:
    a = np.ascontiguousarray(a, np.int32)
    return hashlib.sha256(a.tobytes()).hexdigest()[:16]


# Digests of each copy's output at a small size and a fixed seed.  A
# change here changes every cell's input: it belongs to a benchmark PR.
@pytest.mark.parametrize("name,make,want", [
    ("generate_triples",
     lambda: gen.generate_triples(5000, seed=3, n_predicates=16,
                                  n_entities=400), "2d14e95667bcc86e"),
    ("inject_cind_structure",
     lambda: gen.inject_cind_structure(
         gen.generate_triples(5000, seed=3, n_predicates=16,
                              n_entities=400), n_rules=4, ref_size=15,
         dep_size=12), "8602a8c94affb1c2"),
    ("generate_lubm",
     lambda: gen.generate_lubm(2, seed=4, departments=3), "1b082bbfea949e55"),
    ("relabel",
     lambda: gen.relabel(gen.generate_lubm(2, seed=4, departments=3),
                         2 ** 31 + 9), "d676f57d8188deac"),
])
def test_generator_digest(name, make, want):
    assert digest(make()) == want, name


def test_relabel_keeps_every_size():
    g = gen.generate_lubm(1, seed=4, departments=2)
    r = gen.relabel(g, 12345)

    def sizes(t):
        return ([np.sort(np.unique(t[:, f], return_counts=True)[1]).tolist()
                 for f in range(3)]
                + [np.sort(np.unique(t, return_counts=True)[1]).tolist(),
                   np.sort(np.unique(t, axis=0, return_counts=True)[1])
                   .tolist()])

    assert sizes(g) == sizes(r)
    assert not np.array_equal(gen.relabel(g, 1), gen.relabel(g, 2))
    assert np.array_equal(gen.relabel(g, 7), gen.relabel(g, 7))


def test_write_nt_spelling(tmp_path):
    t = np.array([[0, 1, 999999999], [12, 3400, 5]], np.int32)
    path = tmp_path / "x.nt"
    gen.write_nt(path, t)
    assert path.read_text() == (
        "<v000000000> <v000000001> <v999999999> .\n"
        "<v000000012> <v000003400> <v000000005> .\n")
    assert gen.token_id("<v000003400>") == 3400


def test_command_refuses_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "lubm120.aao-s3000", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode == 2
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr
