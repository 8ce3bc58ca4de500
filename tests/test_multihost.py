"""Two real processes, one shared coordinator, Gloo collectives over
loopback — the way the reference tested master+slave in one process
against 127.0.0.1 (veles/tests/test_network.py:111-137,
test_launcher.py:91-118). Validates the full multi-host path: process
group init, global mesh, per-host sharded-index loading, global-batch
stitching, psum-equivalent gradient aggregation, host-0-only snapshots."""

import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

SCRIPT = os.path.join(os.path.dirname(__file__), "_multihost_train.py")
PP_SCRIPT = os.path.join(os.path.dirname(__file__), "_multihost_pp.py")


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


@pytest.mark.slow
def test_two_process_training_loopback(tmp_path):
    port = _free_port()
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    procs = [subprocess.Popen(
        [sys.executable, SCRIPT, str(tmp_path), str(i), "2", str(port)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, env=env)
        for i in range(2)]
    outs = [p.communicate(timeout=420)[0] for p in procs]
    for p, out in zip(procs, outs):
        assert p.returncode == 0, out.decode()

    w0 = np.load(tmp_path / "w_host0.npy")
    w1 = np.load(tmp_path / "w_host1.npy")
    # SPMD: both hosts hold identical replicated parameters.
    np.testing.assert_array_equal(w0, w1)

    r0 = json.load(open(tmp_path / "results_host0.json"))
    assert r0["epochs"] == 3
    assert r0["best_value"] < 50.0  # better than chance on a 2-class blob

    # Only host 0 snapshots (reference: slaves never snapshot,
    # veles/snapshotter.py:160).
    snaps = os.listdir(tmp_path / "snaps")
    assert any(s.endswith(".json") for s in snaps)
    manifests = [s for s in snaps if s.startswith("mh_ep")]
    assert manifests, snaps

    # phase 3: dp(cross-host) x sp(intra-host) attention training kept the
    # replicated projections identical on both hosts
    q0 = np.load(tmp_path / "wq_host0.npy")
    q1 = np.load(tmp_path / "wq_host1.npy")
    np.testing.assert_array_equal(q0, q1)


@pytest.mark.slow
def test_two_process_pp2_fused_1f1b_matches_single(tmp_path):
    """The fused-1F1B shard_map schedule SPANS the two-process Gloo
    boundary (VERDICT #2): stage 0 on host 0's only device, stage 1 on
    host 1's, ppermute activation transports + cross-shard gradient
    psums over loopback DCN.  Each worker asserts the two-process step
    is exact vs its LOCAL single-device AD reference (loss + every
    updated param leaf); here we additionally pin that both hosts
    agree bitwise — the collective rendezvous across processes is
    precisely where a schedule that works single-process deadlocks or
    diverges."""
    port = _free_port()
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    procs = [subprocess.Popen(
        [sys.executable, PP_SCRIPT, str(tmp_path), str(i), "2",
         str(port)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, env=env)
        for i in range(2)]
    outs = [p.communicate(timeout=420)[0] for p in procs]
    for p, out in zip(procs, outs):
        assert p.returncode == 0, out.decode()

    r0 = json.load(open(tmp_path / "pp_host0.json"))
    r1 = json.load(open(tmp_path / "pp_host1.json"))
    assert r0 == r1, (r0, r1)  # SPMD: identical losses on both hosts
    np.testing.assert_array_equal(
        np.load(tmp_path / "pp_emb_host0.npy"),
        np.load(tmp_path / "pp_emb_host1.npy"))
