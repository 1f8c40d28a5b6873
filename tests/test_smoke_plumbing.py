"""``chip_smoke.py --plumbing``: the whole bring-up smoke, end to end, on one
CPU device. One subprocess and about a minute, so it sits in a file of its
own that sorts late: a time-limited run spends its budget on unit tests
first."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_smoke_plumbing_switch_passes_at_tiny_size():
    """The explicit switch runs the same file on one CPU device: tiny-bert,
    interpreted kernels, every leg but dist (one device), and a report that
    says it is plumbing only."""
    flags = " ".join(f for f in os.environ.get("XLA_FLAGS", "").split()
                     if "xla_force_host_platform_device_count" not in f)
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), "--plumbing"],
        cwd=REPO, env={**os.environ, "XLA_FLAGS": flags},
        capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    *_, full, last = out.stdout.splitlines()
    # the last line is the result and carries exactly these keys; the full
    # report is the line before it
    assert json.loads(last) == {"ok": True, "device": {
        "platform": "cpu", "kind": "cpu", "count": 1}}
    tag = "[chip_smoke] report: "
    assert full.startswith(tag)
    report = json.loads(full[len(tag):])
    assert report["ok"] is True and report["plumbing_only"] is True
    assert report["device"] == json.loads(last)["device"]
    legs = report["legs"]
    assert [legs[k]["status"] for k in (
        "device", "main_server", "main_serverless", "kernels", "dist")] == [
        "ok", "ok", "ok", "ok", "skipped: one chip"]
    for name in ("main_server", "main_serverless"):
        checks = legs[name]["checks"]
        assert checks["train_loss"][-1] < checks["train_loss"][0]
        assert checks["chain_ok"] == 1.0 and checks["interpret_mode"] is True
    assert legs["main_server"]["checks"]["compression_ratio"] > 1
    with open(os.path.join(REPO, "chiprun_out", "chip_smoke.json")) as f:
        assert json.load(f) == report
