"""``chip_smoke.py`` off the chip: the toy-shape rehearsal runs every phase
through the code the chip run uses, and nothing but a TPU run ever prints
``"ok": true``."""

import json
import os
import re
import sys

import pytest

from veles_tpu.config import root

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import chip_smoke  # noqa: E402


@pytest.fixture(autouse=True)
def _keep_cache_dir(monkeypatch):
    # main() points the autotune DB at the checkout; put it back after
    monkeypatch.setattr(root.common, "cache_dir", root.common.cache_dir)


def test_rehearsal_runs_every_phase_and_never_says_ok(capsys):
    assert chip_smoke.main(["--rehearse", "--seed", "3"]) == 0
    out = capsys.readouterr().out
    lines = out.strip().splitlines()
    phases = [ln for ln in lines if ln.startswith("phase ")]
    assert [ln.split(":")[0] for ln in phases] == [
        "phase device", "phase kernels", "phase train", "phase serve"]
    for ln in phases:
        assert re.search(r": ok wall_s=[\d.]+ compile_s=[\d.]+ "
                         r"cache_hits=\d+ cache_misses=\d+ \| ", ln), ln
    assert "interpreted" in phases[1]           # no TPU: interpret mode
    assert "recompiles=0" in phases[2] and "on_device=True" in phases[2]
    assert "bitwise=5/5 ties=[]" in phases[3] and "recompiles=0" in phases[3]
    last = json.loads(lines[-1])
    assert last["ok"] is False and last["rehearsal"] == "passed"
    assert last["device"]["platform"] == "cpu"
    assert '"ok": true' not in out


def test_default_run_needs_a_tpu_and_prints_no_result(capsys):
    assert chip_smoke.main([]) != 0
    cap = capsys.readouterr()
    assert cap.out == "" and "TPU" in cap.err


def test_failed_phase_fails_the_run(capsys, monkeypatch):
    def broken(size, seed, on_tpu):
        chip_smoke.check(False, "kernel out of tolerance")

    monkeypatch.setattr(chip_smoke, "phase_kernels", broken)
    with pytest.raises(AssertionError, match="out of tolerance"):
        chip_smoke.main(["--rehearse"])
    out = capsys.readouterr().out
    assert "phase kernels" not in out and '"ok"' not in out
