"""Launch-layer regression: a real dry-run (512 forced host devices,
production 16×16 mesh) must lower, compile and produce a coherent
roofline record.  Runs the fastest (arch × shape) combos in a
subprocess because the device-count flag must precede jax init.
"""
import json
import os
import pathlib
import subprocess
import sys

ROOT = pathlib.Path(__file__).parents[1]


def _run_dryrun(tmp_path, arch, shape):
    out = tmp_path / "rec.json"
    env = dict(os.environ)
    env["PYTHONPATH"] = str(ROOT / "src")
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
         "--shape", shape, "--out", str(out)],
        env=env, capture_output=True, text=True, timeout=120,
        cwd=str(ROOT))
    assert proc.returncode == 0, proc.stdout + proc.stderr
    return json.load(open(out))


class TestDryrunLaunch:
    def test_decode_combo_produces_roofline_record(self, tmp_path):
        recs = _run_dryrun(tmp_path, "mamba2-780m", "decode_32k")
        (rec,) = recs
        assert rec["status"] == "ok"
        assert rec["mesh"] == "16x16" and rec["n_devices"] == 256
        # three roofline terms present and positive
        assert rec["analytic_compute_s"] > 0
        assert rec["analytic_memory_s"] > 0
        assert rec["collective_s"] >= 0
        assert rec["bottleneck"] in ("compute", "memory", "collective")
        # loop-aware collective accounting ran
        assert isinstance(rec["collectives"]["counts"], dict)

    def test_encoder_only_decode_is_skipped(self, tmp_path):
        recs = _run_dryrun(tmp_path, "hubert-xlarge", "decode_32k")
        (rec,) = recs
        assert rec["status"] == "skipped"
        assert "encoder-only" in rec["note"]
