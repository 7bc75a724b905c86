"""Launcher env construction: flag gating, -x parsing, pod detection."""
import pytest

from bluefog_tpu.run import launcher
from bluefog_tpu.utils.config import looks_like_tpu_environment


def _env(argv, base=None, monkeypatch=None):
    args = launcher.build_parser().parse_args(argv + ["python", "x.py"])
    return launcher._child_env(args)


def test_x_env_parsing(monkeypatch):
    monkeypatch.delenv("XLA_FLAGS", raising=False)
    env = _env(["-x", "FOO=bar", "-x", "BAZ=1"])
    assert env["FOO"] == "bar" and env["BAZ"] == "1"
    with pytest.raises(SystemExit):
        _env(["-x", "MALFORMED"])


def test_timeline_flag(monkeypatch):
    env = _env(["--timeline-filename", "/tmp/tl"])
    assert env["BLUEFOG_TIMELINE"] == "/tmp/tl"


def test_tpu_flags_gated_on_tpu_env(monkeypatch):
    """The overlap flags reach libtpu through LIBTPU_INIT_ARGS, only when
    the child will run on a TPU, and never through XLA_FLAGS (this jaxlib
    aborts on --xla_tpu_* there)."""
    for k in ("XLA_FLAGS", "LIBTPU_INIT_ARGS", "TPU_WORKER_HOSTNAMES",
              "TPU_ACCELERATOR_TYPE", "MEGASCALE_COORDINATOR_ADDRESS"):
        monkeypatch.delenv(k, raising=False)
    flag = "xla_tpu_enable_async_collective_fusion"

    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    env = _env([])
    assert flag not in env.get("LIBTPU_INIT_ARGS", "")
    assert "xla_tpu" not in env.get("XLA_FLAGS", "")

    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    env = _env([])
    assert flag in env["LIBTPU_INIT_ARGS"]
    assert "xla_tpu" not in env.get("XLA_FLAGS", "")

    # a value the user chose is kept, and the rest are appended after it
    monkeypatch.setenv("LIBTPU_INIT_ARGS", f"--{flag}=false --foo=1")
    args = _env([])["LIBTPU_INIT_ARGS"]
    assert args.startswith(f"--{flag}=false --foo=1 ")
    assert f"--{flag}=true" not in args
    assert "--xla_tpu_overlap_compute_collective_tc=true" in args


def test_looks_like_tpu_environment():
    # JAX_PLATFORMS decides when it is set ...
    assert looks_like_tpu_environment({"JAX_PLATFORMS": "tpu,cpu"})
    assert looks_like_tpu_environment({"JAX_PLATFORMS": "tpu"})
    assert not looks_like_tpu_environment(
        {"JAX_PLATFORMS": "cpu", "TPU_WORKER_HOSTNAMES": "a,b"})
    # ... unset, a single host with the TPU runtime's variables counts
    assert not looks_like_tpu_environment({})
    assert looks_like_tpu_environment({"TPU_WORKER_HOSTNAMES": "localhost"})
    assert looks_like_tpu_environment({"TPU_ACCELERATOR_TYPE": "v5litepod-4"})
    assert looks_like_tpu_environment({"MEGASCALE_COORDINATOR_ADDRESS": "x:1"})


def test_coordinator_requires_process_id():
    with pytest.raises(SystemExit):
        launcher.main(["--coordinator", "h:1", "--num-processes", "2",
                       "true"])


def test_parse_hosts():
    assert launcher.parse_hosts("h1,h2:2, h3:4") == [
        ("h1", 1), ("h2", 2), ("h3", 4)]
    with pytest.raises(SystemExit):
        launcher.parse_hosts(" , ")


def test_multihost_plan_command_lines_and_env(monkeypatch):
    """-H fan-out: one remote argv per rank, dense process ids in host
    order, coordinator defaulting to the first host, namespaced env +
    -x extras forwarded, cwd preserved (reference: run.py:133-198)."""
    plans = launcher.build_multihost_plan(
        [("h1", 1), ("h2", 2)], ["python", "train.py", "--lr", "0.1"],
        cwd="/work dir", base_env={"JAX_PLATFORMS": "tpu", "HOME": "/root",
                                   "BLUEFOG_PROCESS_ID": "9"},
        extra_env=["FOO=a b"], remote_shell="ssh", ssh_port=2222)
    assert [(h, p) for h, p, _ in plans] == [("h1", 0), ("h2", 1), ("h2", 2)]
    for i, (host, pid, argv) in enumerate(plans):
        assert argv[:3] == ["ssh", "-p", "2222"]
        assert argv[3] == host
        remote = argv[4]
        assert remote.startswith("cd '/work dir' && exec env ")
        assert f"BLUEFOG_PROCESS_ID={i}" in remote
        assert "BLUEFOG_NUM_PROCESSES=3" in remote
        assert "BLUEFOG_COORDINATOR=h1:48292" in remote
        assert "JAX_PLATFORMS=tpu" in remote
        assert "FOO='a b'" in remote
        assert "HOME=" not in remote              # only namespaced env
        assert "BLUEFOG_PROCESS_ID=9" not in remote   # bootstrap wins
        assert remote.endswith("python train.py --lr 0.1")
    # explicit coordinator overrides the first-host default
    plans = launcher.build_multihost_plan(
        [("h1", 1)], ["true"], cwd="/", coordinator="c0:7777")
    assert "BLUEFOG_COORDINATOR=c0:7777" in plans[0][2][-1]
    # a user@ ssh login prefix is not part of the dialable coordinator
    # address, and the default port is configurable (round-4 advisor item)
    plans = launcher.build_multihost_plan(
        [("alice@h1", 2)], ["true"], cwd="/", coordinator_port=50101)
    remote = plans[0][2][-1]
    assert "BLUEFOG_COORDINATOR=h1:50101" in remote
    assert "BLUEFOG_COORDINATOR=alice@" not in remote


def test_multihost_fanout_e2e_with_stub_shell(tmp_path):
    """main() with -H drives the full fan-out through a stub remote shell
    (records '<host> <remote command>' then runs it locally via sh), so the
    spawned 'remote' ranks really execute with the bootstrap env."""
    import os
    import subprocess
    import sys
    stub = tmp_path / "fake_ssh"
    log = tmp_path / "calls.log"
    stub.write_text(
        "#!/bin/sh\n"
        f'echo "$@" >> {log}\n'
        'host="$1"; shift\n'
        'exec sh -c "$@"\n')
    stub.chmod(0o755)
    out = tmp_path / "ranks"
    code = launcher.main(
        ["-H", "hostA,hostB", "--remote-shell", str(stub), "--",
         sys.executable, "-c",
         "import os,pathlib; pathlib.Path("
         f"'{out}' + os.environ['BLUEFOG_PROCESS_ID']).write_text("
         "os.environ['BLUEFOG_NUM_PROCESSES'] + ' ' + "
         "os.environ['BLUEFOG_COORDINATOR'])"])
    assert code == 0
    calls = log.read_text().splitlines()
    # ranks launch concurrently; the stub's log order is nondeterministic
    assert sorted(c.split()[0] for c in calls) == ["hostA", "hostB"]
    assert (out.parent / "ranks0").read_text() == "2 hostA:48292"
    assert (out.parent / "ranks1").read_text() == "2 hostA:48292"


def test_multihost_fanout_propagates_failure(tmp_path):
    import sys
    stub = tmp_path / "fake_ssh"
    stub.write_text('#!/bin/sh\nshift\nexec sh -c "$@"\n')
    stub.chmod(0o755)
    code = launcher.main(
        ["-H", "h1,h2", "--remote-shell", str(stub), "--",
         sys.executable, "-c",
         "import os,sys; sys.exit(3 if os.environ['BLUEFOG_PROCESS_ID'] "
         "== '1' else 0)"])
    assert code == 3


def test_multihost_fanout_kills_survivors_on_failure(tmp_path):
    """mpirun semantics: when one rank dies the others (blocked in
    collectives forever in real launches) are terminated, not awaited."""
    import sys
    import time
    stub = tmp_path / "fake_ssh"
    stub.write_text('#!/bin/sh\nshift\nexec sh -c "$@"\n')
    stub.chmod(0o755)
    t0 = time.perf_counter()
    code = launcher.main(
        ["-H", "h1,h2", "--remote-shell", str(stub), "--",
         sys.executable, "-c",
         "import os,sys,time\n"
         "sys.exit(2) if os.environ['BLUEFOG_PROCESS_ID'] == '0' "
         "else time.sleep(600)"])
    assert code == 2
    assert time.perf_counter() - t0 < 60      # did not wait out the sleeper


def test_multihost_plan_never_embeds_session_token(monkeypatch):
    """The ssh argv is visible in `ps` on both ends — the interactive
    session token must never ride the -H env forwarding."""
    plans = launcher.build_multihost_plan(
        [("h1", 1)], ["true"], cwd="/",
        base_env={"BLUEFOG_SESSION_TOKEN": "s3cret",
                  "BLUEFOG_LOG_LEVEL": "debug"})
    remote = plans[0][2][-1]
    assert "s3cret" not in remote and "BLUEFOG_SESSION_TOKEN" not in remote
    assert "BLUEFOG_LOG_LEVEL=debug" in remote


def test_version_flag(capsys):
    assert launcher.main(["--version"]) == 0
    assert "bluefog_tpu 0." in capsys.readouterr().out


def test_parse_hostfile(tmp_path):
    hf = tmp_path / "hosts"
    hf.write_text("# cluster\nh1 slots=4\n\nh2   # default one slot\n"
                  "h3 slots=2\n")
    assert launcher.parse_hostfile(str(hf)) == [
        ("h1", 4), ("h2", 1), ("h3", 2)]
    hf.write_text("h1 gpus=4\n")
    with pytest.raises(SystemExit, match="unsupported hostfile field"):
        launcher.parse_hostfile(str(hf))
    hf.write_text("# nothing\n")
    with pytest.raises(SystemExit, match="no hosts"):
        launcher.parse_hostfile(str(hf))
    # bad slots values fail with the file:line diagnostic, never launch 0
    for bad in ("h1 slots=abc", "h1 slots=0", "h1 slots=-2"):
        hf.write_text(bad + "\n")
        with pytest.raises(SystemExit, match="positive integer"):
            launcher.parse_hostfile(str(hf))


def test_hostfile_fanout_e2e(tmp_path):
    """--hostfile drives the same fan-out as -H, slots expanded per host."""
    import sys
    stub = tmp_path / "fake_ssh"
    stub.write_text('#!/bin/sh\nshift\nexec sh -c "$@"\n')
    stub.chmod(0o755)
    hf = tmp_path / "hosts"
    hf.write_text("hA slots=2\nhB slots=1\n")
    out = tmp_path / "r"
    code = launcher.main(
        ["--hostfile", str(hf), "--remote-shell", str(stub), "--verbose",
         "--", sys.executable, "-c",
         "import os,pathlib; pathlib.Path("
         f"'{out}' + os.environ['BLUEFOG_PROCESS_ID']).write_text("
         "os.environ['BLUEFOG_NUM_PROCESSES'])"])
    assert code == 0
    for i in range(3):
        assert (out.parent / f"r{i}").read_text() == "3"
    # argparse-level mutual exclusion: rejected on EVERY path, even
    # without a command
    with pytest.raises(SystemExit):
        launcher.main(["-H", "x", "--hostfile", str(hf)])


def test_check_environment(capsys):
    """--check prints a full diagnosis and exits 0 when devices resolve
    (CPU mesh here); the device probe comes LAST so everything else is
    already printed if the backend fails to come up."""
    assert launcher.check_environment() == 0
    out = capsys.readouterr().out
    assert "bluefog_tpu 0." in out
    assert "jax " in out and "jax_platforms config" in out
    assert "native (C++) components" in out
    assert "compile cache" in out
    lines = out.strip().splitlines()
    assert lines[-2].startswith("probing devices")     # probe is last
    assert lines[-1].startswith("devices: ")


# ---------------------------------------------------------------------------
# Supervisor: failure diagnosis + elastic restart
# ---------------------------------------------------------------------------

def test_local_failure_names_rank_and_code(tmp_path, capsys):
    """mpirun teardown loses WHICH rank died with WHICH code; ours must
    say both, terminate the sleeper instead of awaiting it, and name the
    failing rank again in the final error line."""
    import sys
    import time
    t0 = time.perf_counter()
    code = launcher.main(
        ["-np", "2", "--",
         sys.executable, "-c",
         "import os,sys,time\n"
         "sys.exit(5) if os.environ['BLUEFOG_PROCESS_ID'] == '0' "
         "else time.sleep(600)"])
    assert code == 5
    assert time.perf_counter() - t0 < 60
    err = capsys.readouterr().err
    assert "rank 0 exited with code 5" in err
    assert "job failed: rank 0 exited with code 5" in err


def test_restart_limit_respawns_dead_rank(capsys):
    """--restart-limit: a rank exiting non-zero is respawned (with
    BLUEFOG_RESTART_COUNT set) instead of killing the job; the respawn is
    counted in bluefog_rank_restarts_total."""
    import sys

    from bluefog_tpu.utils import metrics as bfm
    bfm.reset_metrics()
    code = launcher.main(
        ["-np", "1", "--restart-limit", "2", "--restart-backoff", "0.01",
         "--", sys.executable, "-c",
         "import os,sys; sys.exit(0 if os.environ.get("
         "'BLUEFOG_RESTART_COUNT') else 9)"])
    assert code == 0
    err = capsys.readouterr().err
    assert "rank 0 exited with code 9" in err
    assert "restarting rank 0 (attempt 1/2)" in err
    assert bfm.counter("bluefog_rank_restarts_total").total() == 1
    bfm.reset_metrics()


def test_restart_limit_exhausted_fails_with_count(capsys):
    import sys
    code = launcher.main(
        ["-np", "1", "--restart-limit", "1", "--restart-backoff", "0.01",
         "--", sys.executable, "-c", "import sys; sys.exit(7)"])
    assert code == 7
    err = capsys.readouterr().err
    assert "job failed: rank 0 exited with code 7 after 1 restart(s)" in err


def test_restart_backoff_schedule_pinned(monkeypatch, capsys):
    """The restart backoff is exponential with deterministic seeded
    jitter: attempt a sleeps ``backoff * 2**(a-1)`` scaled by +0..25 %
    from ``random.Random(f"bfrun:{rank}:{a}")`` — pin the exact schedule
    (reported to 2 decimals in the restart line) and the exhaustion
    message naming the rank and exit code."""
    import random
    import sys
    import time

    base_backoff = 0.5
    slept = []
    real_sleep = time.sleep
    monkeypatch.setattr(
        time, "sleep",
        lambda d: slept.append(d) if d >= base_backoff else real_sleep(d))
    code = launcher.main(
        ["-np", "1", "--restart-limit", "3",
         "--restart-backoff", str(base_backoff),
         "--", sys.executable, "-c", "import sys; sys.exit(7)"])
    assert code == 7
    err = capsys.readouterr().err
    expected = []
    for attempt in (1, 2, 3):
        base = base_backoff * (2 ** (attempt - 1))
        delay = base * (
            1.0 + 0.25 * random.Random(f"bfrun:0:{attempt}").random())
        assert base <= delay <= base * 1.25
        expected.append(delay)
        assert (f"restarting rank 0 (attempt {attempt}/3) "
                f"after {delay:.2f} s backoff") in err
    assert slept == pytest.approx(expected)
    assert "job failed: rank 0 exited with code 7 after 3 restart(s)" in err


def test_read_scale_warns_once_on_malformed(tmp_path, capsys):
    """A malformed scale file silently disables elastic scaling unless we
    tell the operator — warn exactly once per offending content, naming
    the path and what was found."""
    launcher._warned_scale.clear()
    scale = tmp_path / "scale"
    scale.write_text("six\n")
    assert launcher._read_scale(str(scale)) is None
    assert launcher._read_scale(str(scale)) is None
    err = capsys.readouterr().err
    assert err.count("malformed scale file") == 1
    assert str(scale) in err
    assert "'six'" in err
    # new offending content warns again (it is a different mistake)
    scale.write_text("7.5")
    assert launcher._read_scale(str(scale)) is None
    assert "'7.5'" in capsys.readouterr().err
    # a missing file is the normal idle state: silent
    assert launcher._read_scale(str(tmp_path / "absent")) is None
    assert capsys.readouterr().err == ""
    launcher._warned_scale.clear()


def test_read_scale_warns_once_below_minimum(tmp_path, capsys):
    launcher._warned_scale.clear()
    scale = tmp_path / "scale"
    scale.write_text("0")
    assert launcher._read_scale(str(scale), min_world=1) is None
    assert launcher._read_scale(str(scale), min_world=1) is None
    err = capsys.readouterr().err
    assert err.count(
        "target 0 is below the minimum world size 1") == 1
    assert str(scale) in err
    # a valid target reads clean, no warning
    scale.write_text("3")
    assert launcher._read_scale(str(scale), min_world=1) == 3
    assert capsys.readouterr().err == ""
    launcher._warned_scale.clear()


def test_multihost_restart_respawns_remote_argv(tmp_path, capsys):
    """-H fan-out honors --restart-limit too: the dead rank's ssh argv is
    respawned verbatim while the survivor keeps running."""
    import sys
    stub = tmp_path / "fake_ssh"
    stub.write_text('#!/bin/sh\nshift\nexec sh -c "$@"\n')
    stub.chmod(0o755)
    marker = tmp_path / "died_once"
    code = launcher.main(
        ["-H", "h1,h2", "--remote-shell", str(stub),
         "--restart-limit", "1", "--restart-backoff", "0.01", "--",
         sys.executable, "-c",
         "import os,sys,pathlib\n"
         f"m = pathlib.Path('{marker}')\n"
         "if os.environ['BLUEFOG_PROCESS_ID'] == '1' and not m.exists():\n"
         "    m.write_text('x'); sys.exit(11)\n"
         "sys.exit(0)"])
    assert code == 0
    err = capsys.readouterr().err
    assert "rank 1 on h2 exited with code 11" in err
    assert "restarting rank 1 on h2" in err


@pytest.mark.slow
def test_restart_resumes_from_latest_complete_checkpoint(tmp_path, capsys):
    """Acceptance (c): the killed rank's respawn resumes from the latest
    COMPLETE checkpoint — falling past the torn step_3 directory its
    predecessor died writing — and the job exits 0 within the budget."""
    import os
    import sys

    import bluefog_tpu
    repo = os.path.dirname(os.path.dirname(bluefog_tpu.__file__))
    ckdir = tmp_path / "ckpts"
    script = tmp_path / "train_stub.py"
    script.write_text(
        "import os, sys\n"
        "import jax.numpy as jnp\n"
        "from bluefog_tpu import checkpoint as ckpt\n"
        "d = sys.argv[1]\n"
        "if os.environ.get('BLUEFOG_RESTART_COUNT'):\n"
        "    out, at = ckpt.restore_latest(d)\n"
        "    assert at == 2, (at, ckpt.all_steps(d, True))\n"
        "    assert int(out['s']) == 2\n"
        "    sys.exit(0)\n"
        "ckpt.save(d, {'s': jnp.asarray(1)}, step=1)\n"
        "ckpt.save(d, {'s': jnp.asarray(2)}, step=2)\n"
        "os.makedirs(os.path.join(d, 'step_3'))\n"
        "with open(os.path.join(d, 'step_3', 'arrays'), 'w') as f:\n"
        "    f.write('torn mid-write')\n"
        "sys.exit(9)\n")
    code = launcher.main(
        ["-np", "1", "--restart-limit", "1", "--restart-backoff", "0.01",
         "-x", f"PYTHONPATH={repo}",
         "--", sys.executable, str(script), str(ckdir)])
    assert code == 0
    err = capsys.readouterr().err
    assert "rank 0 exited with code 9" in err
    assert "restarting rank 0 (attempt 1/1)" in err


def test_scale_signalling_mode(tmp_path, capsys):
    """`bfrun-tpu --scale N` (no command) writes the scale file a running
    --elastic supervisor watches, and exits 0."""
    scale = tmp_path / "scale"
    code = launcher.main(["--scale", "3", "--scale-file", str(scale)])
    assert code == 0
    assert scale.read_text().strip() == "3"
    out = capsys.readouterr().out
    assert f"scale target 3 written to {scale}" in out
    with pytest.raises(SystemExit, match="positive"):
        launcher.main(["--scale", "0", "--scale-file", str(scale)])


def test_elastic_join_spawns_fresh_rank(tmp_path, capsys):
    """--elastic: a scale target above the slot count spawns a fresh rank
    with a never-used id, BLUEFOG_JOIN_COUNT set, and the grown world
    size — the in-process signal that it must bootstrap by neighbor pull,
    not checkpoint."""
    import sys
    scale = tmp_path / "scale"
    marker = tmp_path / "marker"
    prog = (
        "import os, sys, time\n"
        "rank = os.environ['BLUEFOG_PROCESS_ID']\n"
        "jc = os.environ.get('BLUEFOG_JOIN_COUNT')\n"
        "if jc:\n"
        "    open(%r, 'w').write('JOIN_COUNT=%%s PROCESS_ID=%%s "
        "NUM_PROCESSES=%%s' %% (jc, rank, "
        "os.environ['BLUEFOG_NUM_PROCESSES']))\n"
        "    sys.exit(0)\n"
        "if rank == '0':\n"
        "    open(%r, 'w').write('3')\n"
        "    for _ in range(600):\n"
        "        if os.path.exists(%r): sys.exit(0)\n"
        "        time.sleep(0.05)\n"
        "    sys.exit(1)\n"
        "sys.exit(0)\n" % (str(marker), str(scale), str(marker)))
    code = launcher.main(
        ["-np", "2", "--elastic", "--scale-file", str(scale),
         "--", sys.executable, "-c", prog])
    assert code == 0
    err = capsys.readouterr().err
    assert "elastic join: starting rank 2 (target 3)" in err
    got = marker.read_text()
    assert "JOIN_COUNT=1" in got
    assert "PROCESS_ID=2" in got
    assert "NUM_PROCESSES=3" in got


def test_elastic_retire_sigterms_highest_ranks(tmp_path, capsys):
    """--elastic: a scale target below the slot count SIGTERMs the
    highest-numbered live ranks (graceful retire); any exit code counts
    as a clean retirement, so the job still ends 0."""
    import sys
    import time
    scale = tmp_path / "scale"
    prog = (
        "import os, sys, time\n"
        "rank = os.environ['BLUEFOG_PROCESS_ID']\n"
        "if rank == '0':\n"
        "    time.sleep(0.3)\n"
        "    open(%r, 'w').write('1')\n"
        "    sys.exit(0)\n"
        "if rank == '1':\n"
        "    sys.exit(0)\n"
        "time.sleep(600)\n" % str(scale))
    t0 = time.perf_counter()
    code = launcher.main(
        ["-np", "3", "--elastic", "--scale-file", str(scale),
         "--", sys.executable, "-c", prog])
    assert code == 0
    assert time.perf_counter() - t0 < 60
    err = capsys.readouterr().err
    assert "elastic retire: stopping rank 2 (target 1)" in err
    assert "rank 2 retired (exit code" in err
