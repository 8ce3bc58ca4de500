"""--curriculum: snapshot-phased chained training (productized
configs/induction_lm64_curriculum.sh; closest reference machinery is
rollback-to-best, manualrst_veles_algorithms.rst:164)."""
import json
import os
import subprocess
import sys

import pytest

from tests.test_cli import CONFIG_PY, REPO, run_cli


@pytest.fixture
def config_file(tmp_path):
    p = tmp_path / "wf.py"
    p.write_text(CONFIG_PY)
    return str(p)


def write_spec(tmp_path, **kw):
    spec = {
        "common": [],
        "phases": [
            {"overrides": ["my.lr=0.05"], "random_seed": 1},
            {"overrides": ["my.lr={1+i}e-2"], "random_seed": "{i}"},
        ],
    }
    spec.update(kw)
    p = tmp_path / "spec.json"
    p.write_text(json.dumps(spec))
    return str(p)


def test_curriculum_runs_phases_and_chains_best(tmp_path, config_file):
    spec = write_spec(tmp_path)
    out = tmp_path / "cur"
    res = tmp_path / "cres.json"
    r = run_cli(tmp_path, config_file, "--curriculum", spec,
                "--curriculum-out", str(out), "--result-file", str(res))
    assert r.returncode == 0, r.stderr
    summary = json.loads(res.read_text())
    assert summary["phases_run"] == 2
    assert summary["value"] is not None and summary["value"] < 50.0
    assert summary["best_snapshot"] and \
        os.path.exists(summary["best_snapshot"])
    # per-phase dirs + persisted summary (a phase that never improves
    # writes no snapshot, so only p1 is guaranteed a directory)
    assert (out / "p1").is_dir()
    disk = json.loads((out / "curriculum.json").read_text())
    assert disk["phases"][0]["phase"] == 1
    # last line of stdout is the summary JSON (without the phase list)
    tail = json.loads(r.stdout.strip().splitlines()[-1])
    assert tail["metric"] == "curriculum_best_value"


def test_curriculum_bar_stops_early(tmp_path, config_file):
    spec = write_spec(tmp_path, bar=100.0)  # any result clears it
    out = tmp_path / "cur2"
    res = tmp_path / "cres2.json"
    r = run_cli(tmp_path, config_file, "--curriculum", spec,
                "--curriculum-out", str(out), "--result-file", str(res))
    assert r.returncode == 0, r.stderr
    summary = json.loads(res.read_text())
    assert summary["phases_run"] == 1  # stopped after phase 1


def test_curriculum_parent_initialises_no_backend(tmp_path, config_file):
    """One process per chip: each phase is a child that needs the device,
    so the parent may pin a platform but must touch no device itself."""
    spec = write_spec(tmp_path, bar=100.0)  # one phase is enough
    r = subprocess.run(
        [sys.executable, "-c",
         "import sys; from jax._src import xla_bridge;"
         "from veles_tpu.__main__ import main;"
         "rc = main(sys.argv[1:]);"
         "print('BACKENDS', xla_bridge.backends_are_initialized());"
         "sys.exit(rc)", config_file, "--curriculum", spec,
         "--curriculum-out", str(tmp_path / "cur"), "--platform", "cpu"],
        capture_output=True, text=True, cwd=str(tmp_path), timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO))
    assert r.returncode == 0, r.stderr
    assert "BACKENDS False" in r.stdout, r.stdout


def test_curriculum_placeholder_expansion():
    from veles_tpu.runtime.curriculum import CurriculumError, expand_phases
    spec = {"phases": [
        {"overrides": ["workflow.max_epochs=10"], "random_seed": 1},
        {"repeat": 3, "epochs_increment": 5,
         "overrides": ["workflow.max_epochs={budget}", "x.seed={100+i}"],
         "random_seed": "{i}"}]}
    ph = expand_phases(spec)
    assert [p["index"] for p in ph] == [1, 2, 3, 4]
    assert "workflow.max_epochs=15" in ph[1]["overrides"]
    assert "workflow.max_epochs=25" in ph[3]["overrides"]
    assert "x.seed=104" in ph[3]["overrides"]
    assert ph[3]["random_seed"] == 4
    with pytest.raises(CurriculumError):
        expand_phases({"phases": [{"overrides": ["a={nope}"]}]})


def test_curriculum_warm_start_and_seed_forwarding(tmp_path, config_file):
    """--snapshot seeds phase 1; --random-seed reaches phases whose spec
    sets none; conflicting single-run flags error clearly."""
    # make a warm snapshot with a plain run
    res0 = tmp_path / "r0.json"
    r = run_cli(tmp_path, config_file, "--snapshot-dir",
                str(tmp_path / "warm"), "--result-file", str(res0))
    assert r.returncode == 0, r.stderr
    import glob
    warm = glob.glob(str(tmp_path / "warm" / "*_best.json"))[0]

    spec = tmp_path / "s.json"
    spec.write_text(json.dumps(
        {"phases": [{"overrides": ["my.lr=0.01"]}]}))  # no random_seed
    res = tmp_path / "r1.json"
    r = run_cli(tmp_path, config_file, "--curriculum", str(spec),
                "--curriculum-out", str(tmp_path / "c3"),
                "--snapshot", warm, "--random-seed", "7",
                "--result-file", str(res))
    assert r.returncode == 0, r.stderr
    assert f"restore {warm}" in (r.stdout + r.stderr)
    # the runner logs each phase's full argv; the forwarded seed must be
    # in it (the spec sets none, so it comes from --random-seed 7)
    assert "--random-seed 7" in (r.stdout + r.stderr)

    # conflicting flags rejected up front
    r2 = run_cli(tmp_path, config_file, "--curriculum", str(spec),
                 "--dry-run", "build")
    assert r2.returncode != 0
    assert "--curriculum is a training meta-mode" in r2.stderr
