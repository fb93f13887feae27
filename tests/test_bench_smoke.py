"""End-to-end smoke of the bench harness at tiny size on CPU (slow tier).

bench.py measures on the chip; between chip runs nothing exercised its
measurement machinery, so a refactor could silently rot it until the next
chip call burned time on a crash.  This runs the whole harness in a
subprocess on a tiny workload with JAX_PLATFORMS=cpu pinned (the bench's
only way onto the CPU), asserts the ONE JSON line parses and names its
device, and pins the occupancy/dtype fields the round-6 roofline
accounting added.
"""

import json
import os
import subprocess
import sys

import pytest


@pytest.mark.slow
def test_bench_end_to_end_smoke():
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(
        BENCH_TRIPLES="400",
        BENCH_MIN_SUPPORT="2",
        BENCH_PIPELINE_TRIPLES="400",
        JAX_PLATFORMS="cpu",
    )
    r = subprocess.run([sys.executable, os.path.join(repo, "bench.py")],
                       env=env, cwd=repo, capture_output=True, text=True,
                       timeout=1200)
    assert r.returncode == 0, r.stderr[-2000:]
    line = r.stdout.strip().splitlines()[-1]
    result = json.loads(line)

    assert result["metric"] == "cind_pairs_checked_per_sec_per_chip"
    assert result["device"] == {"platform": "cpu", "device_kind": "cpu",
                                "count": 1}
    assert result["value"] > 0, result
    detail = result["detail"]
    assert "error" not in detail, detail
    # The round-6 fields: resolved dtype + the dense plan's occupancy record.
    assert detail["cooc_dtype"] in ("int8", "bf16")
    plan = detail["dense_plan"]
    assert plan["policy"] in ("tile", "pow2")
    assert 0 < plan["occupancy"] <= 1
    assert plan["issued_flops"] >= plan["real_flops"] > 0
    # The MFU section reports the plan + occupancy on every backend (the
    # fraction-of-peak ratios need a real chip and are absent on CPU).
    mfu = detail["mfu"]
    assert "error" not in mfu, mfu
    assert mfu["occupancy"] == plan["occupancy"]
    assert "achieved_tflops" in mfu
    # int8 row: the sweep either ran or recorded why the backend refused.
    assert "int8_achieved_tops" in mfu or "int8_error" in mfu
    # The kernel selfcheck must still report parity in interpret mode.
    assert detail["pallas_vs_jnp"].get("parity") is True, \
        detail["pallas_vs_jnp"]
