"""bench_micro.py harness smoke: every section must produce numeric
results (parity with the reference's harness-only Go benchmarks —
values are machine-dependent and never asserted)."""

import json
import os
import subprocess
import sys

import pytest


@pytest.mark.parametrize(
    "section",
    [
        "challenges", "coldboot", "ed25519", "validator_set", "light", "mempool",
        "routing", "scheduler", "telemetry", "wal",
    ],
)
def test_section_produces_numbers(section):
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [sys.executable, os.path.join(repo, "bench_micro.py"), section],
        capture_output=True,
        text=True,
        timeout=240,
        cwd=repo,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert r.returncode == 0, r.stderr[-400:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["section"] == section
    assert "error" not in out, out
    numeric = [
        v for k, v in out.items() if isinstance(v, (int, float)) and k != "section"
    ]
    assert numeric and all(v > 0 for v in numeric), out
