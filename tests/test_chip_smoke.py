"""CPU tests of chip_smoke.py's plumbing and of the compile-cache placement.

The smoke itself only runs on the chip; here its comparison and check
functions run at tiny size, and its main() must refuse the CPU.
"""

import importlib.util
import os

import jax
import numpy as np
import pytest

from rdfind_tpu import conditions as cc
from rdfind_tpu.data import Cind
from rdfind_tpu.runtime import compile_cache
from rdfind_tpu.utils import synth

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("strategy", [0, 1, 2, 3])
def test_compare_with_oracle_tiny(smoke, tmp_path, strategy):
    triples, _ = synth.generate_planted_cinds(2, 4, seed=3)
    res = smoke.compare_with_oracle(str(tmp_path), triples, 4,
                                    strategies=(strategy,))
    row = res["strategies"][strategy]
    assert res["oracle_cinds"] > 0
    assert row["equal"], row
    assert row["cinds"] == res["oracle_cinds"]


def test_main_refuses_without_tpu(smoke, capsys):
    assert jax.devices()[0].platform == "cpu"
    assert smoke.main([]) != 0
    out, err = capsys.readouterr()
    assert "no TPU" in err and "'cpu'" in err
    assert '"ok"' not in out


def test_sample_check_catches_a_wrong_cind(smoke):
    tok = lambda v: f"<v{v:09d}>"
    # s[p=10] = {1, 2} and s[p=11] = {1, 2, 3}: included, support 2.
    triples = np.array([[1, 10, 5], [2, 10, 5], [1, 11, 6], [2, 11, 6],
                        [3, 11, 6]], np.int64)
    code = cc.create(cc.PREDICATE, secondary_condition=cc.SUBJECT)
    good = Cind(code, tok(10), None, code, tok(11), None, 2)
    wrong_dir = Cind(code, tok(11), None, code, tok(10), None, 3)
    wrong_support = Cind(code, tok(10), None, code, tok(11), None, 5)
    res = smoke.sample_check(triples, [good, wrong_dir, wrong_support], 3,
                             seed=0)
    assert res["checked"] == 3
    assert sorted(res["bad"]) == sorted([wrong_dir.pretty(),
                                         wrong_support.pretty()])


def _report(**over):
    rep = {"device_kind": "TPU v5 lite", "cooc_dtype": "int8",
           "plane_bits": 4, "kernel_dtype": "int4", "plane_elem": "int4",
           "emit_pipeline": False, "fuse_verdict": True,
           "knobs": {"RDFIND_FUSE_VERDICT": "auto"},
           "kernels_used": {"fused_verdict": 3, "packed_contains": 1}}
    rep.update(over)
    return rep


@pytest.mark.parametrize("over,fault", [
    ({}, None),
    ({"kernels_used": {"fused_verdict": 1, "packed_contains": 1,
                       "materialized_verdict": 2}}, "materialized"),
    ({"kernels_used": {"packed_contains": 1}}, "fused verdict"),
    ({"kernels_used": {"fused_verdict": 1}}, "packed containment"),
    ({"emit_pipeline": True}, "emit_pipeline selected"),
    ({"kernels_used": {"fused_verdict": 1, "packed_contains_emit": 1}},
     "emit_pipeline kernel ran"),
    ({"plane_elem": "int8"}, "plane_elem"),
    ({"knobs": {"RDFIND_FUSE_VERDICT": "0"}}, "RDFIND_FUSE_VERDICT"),
])
def test_check_kernels(smoke, over, fault):
    faults = smoke.check_kernels(_report(**over), fused=True, contains=True)
    if fault is None:
        assert faults == []
    else:
        assert any(fault in f for f in faults), faults


def test_compile_cache_follows_env(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.place() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # set nothing


def test_compile_cache_defaults_to_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        assert compile_cache.place() == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == \
            os.path.join(REPO, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert not jax.config.jax_enable_compilation_cache  # off in tests
