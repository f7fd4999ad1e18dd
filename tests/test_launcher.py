"""Launcher layer tests (reference: tests/unit/launcher/test_multinode_runner.py
and test_runner.py — pure command/parse tests, no cluster needed)."""
import argparse
import os
import subprocess
import sys
import textwrap

import pytest

from deepspeed_tpu.launcher import (
    PDSHRunner, OpenMPIRunner, MPICHRunner, IMPIRunner, SlurmRunner,
    GcloudTPURunner)
from deepspeed_tpu.launcher import launch as launch_mod
from deepspeed_tpu.launcher import runner as runner_mod

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def runner_args():
    return argparse.Namespace(
        user_script="train.py", user_args=["--epochs", "2"],
        master_port=29500, hostfile="/tmp/hostfile", comment="",
        tpu_name="mytpu", zone="us-central2-b")


WORLD = {"worker-0": 1, "worker-1": 1}


def test_pdsh_cmd(runner_args):
    r = PDSHRunner(runner_args, WORLD)
    r.add_export("JAX_PLATFORMS", "tpu")
    cmd = r.get_cmd({}, {})
    assert cmd[0] == "pdsh"
    assert "worker-0,worker-1" in cmd
    joined = " ".join(cmd)
    assert "deepspeed_tpu.launcher.launch" in joined
    assert "--coordinator_address=worker-0:29500" in joined
    assert "--nnodes=2" in joined
    assert "export JAX_PLATFORMS=tpu" in joined
    assert "train.py --epochs 2" in joined


def test_pdsh_respects_master_addr_and_quotes_args(runner_args):
    runner_args.master_addr = "10.1.2.3"
    runner_args.user_args = ["--prompt", "hello world"]
    joined = " ".join(PDSHRunner(runner_args, WORLD).get_cmd({}, {}))
    assert "--coordinator_address=10.1.2.3:29500" in joined
    # argument with a space must survive the remote shell as ONE word
    assert "'hello world'" in joined


def test_openmpi_cmd(runner_args):
    r = OpenMPIRunner(runner_args, WORLD)
    r.add_export("XLA_FLAGS", "--xla_a --xla_b")
    cmd = r.get_cmd({}, {})
    assert cmd[:3] == ["mpirun", "-n", "2"]
    assert "--npernode" in cmd and "1" in cmd
    # filtered host list, not the raw hostfile (honours --include/--exclude)
    assert "--host" in cmd
    assert cmd[cmd.index("--host") + 1] == "worker-0:1,worker-1:1"
    assert "--hostfile" not in cmd
    # exec-style runner: env value must NOT be shell-quoted
    assert "XLA_FLAGS=--xla_a --xla_b" in cmd
    # routes through launch.py so the coordination env reaches workers
    assert "deepspeed_tpu.launcher.launch" in cmd
    assert "--node_rank=auto" in cmd
    assert "train.py" in cmd


def test_mpich_impi_slurm_cmds(runner_args):
    for cls, exe in ((MPICHRunner, "mpirun"), (IMPIRunner, "mpirun"),
                     (SlurmRunner, "srun")):
        cmd = cls(runner_args, WORLD).get_cmd({}, {})
        assert cmd[0] == exe
        assert "train.py" in cmd
        assert "deepspeed_tpu.launcher.launch" in cmd, cls
    # MPICH must convey the host list or every rank lands on the launch host
    mpich = MPICHRunner(runner_args, WORLD).get_cmd({}, {})
    assert "-hosts" in mpich
    assert mpich[mpich.index("-hosts") + 1] == "worker-0,worker-1"


def test_module_flag_forwarded(runner_args):
    runner_args.module = True
    for cls in (PDSHRunner, OpenMPIRunner, MPICHRunner, IMPIRunner,
                SlurmRunner):
        joined = " ".join(cls(runner_args, WORLD).get_cmd({}, {}))
        assert "--module" in joined, cls
    # gcloud builds a raw shell command: module mode = `python -m`
    joined = " ".join(GcloudTPURunner(runner_args, WORLD).get_cmd({}, {}))
    assert "-m train.py" in joined


def test_slurm_exports_via_environment(runner_args):
    r = SlurmRunner(runner_args, WORLD)
    r.add_export("XLA_FLAGS", "--xla_a --xla_b")
    env = {}
    cmd = r.get_cmd(env, {})
    # values with spaces cannot ride the comma-separated --export list;
    # they go through the inherited environment instead
    assert "--export=ALL" in cmd
    assert env["XLA_FLAGS"] == "--xla_a --xla_b"
    assert not any("--xla_a" in c for c in cmd)


def test_gcloud_cmd(runner_args):
    cmd = GcloudTPURunner(runner_args, WORLD).get_cmd({}, {})
    assert cmd[:5] == ["gcloud", "compute", "tpus", "tpu-vm", "ssh"]
    assert "mytpu" in cmd
    assert "--worker=all" in cmd
    assert "--zone" in cmd


# ---------------------------------------------------------------- hostfile parse

def test_parse_hostfile():
    pool = runner_mod._parse_hostfile(
        ["# comment", "", "worker-0 slots=4", "worker-1 slots=2"])
    assert pool == {"worker-0": 4, "worker-1": 2}


def test_parse_hostfile_bad_entry():
    with pytest.raises(ValueError, match="bad entry"):
        runner_mod._parse_hostfile(["worker-0 slots=four"])


def test_parse_hostfile_duplicate():
    with pytest.raises(ValueError, match="multiple entries"):
        runner_mod._parse_hostfile(["w slots=1", "w slots=2"])


def test_parse_hostfile_empty():
    with pytest.raises(ValueError):
        runner_mod._parse_hostfile(["# nothing"])


# ------------------------------------------------------------ include / exclude

HOSTS = {"worker-0": 4, "worker-1": 4}


def test_include_whole_host():
    out = runner_mod.parse_resource_filter(HOSTS, include_str="worker-1")
    assert out == {"worker-1": [0, 1, 2, 3]}


def test_include_slots():
    out = runner_mod.parse_resource_filter(HOSTS,
                                           include_str="worker-0:0,2")
    assert out == {"worker-0": [0, 2]}


def test_exclude_host():
    out = runner_mod.parse_resource_filter(HOSTS, exclude_str="worker-0")
    assert out == {"worker-1": [0, 1, 2, 3]}


def test_exclude_slot():
    out = runner_mod.parse_resource_filter(HOSTS, exclude_str="worker-1:0")
    assert out["worker-1"] == [1, 2, 3]


def test_include_exclude_mutually_exclusive():
    with pytest.raises(ValueError):
        runner_mod.parse_resource_filter(HOSTS, include_str="worker-0",
                                         exclude_str="worker-1")


def test_filter_unknown_host():
    with pytest.raises(ValueError):
        runner_mod.parse_resource_filter(HOSTS, include_str="nope")


def test_world_info_roundtrip():
    info = {"worker-0": 1, "worker-1": 1}
    assert runner_mod.decode_world_info(
        runner_mod.encode_world_info(info)) == info


# --------------------------------------------------------------------- launch.py

def test_launch_worker_env():
    args = launch_mod.parse_args([
        "--coordinator_address=10.0.0.1:29501", "--nnodes=4", "--node_rank=2",
        "train.py", "--lr", "0.1"])
    env = launch_mod.build_worker_env(args, base_env={})
    assert env["COORDINATOR_ADDRESS"] == "10.0.0.1:29501"
    assert env["NPROC"] == "4"
    assert env["PROCESS_ID"] == "2"
    assert env["RANK"] == "2" and env["WORLD_SIZE"] == "4"
    assert env["MASTER_ADDR"] == "10.0.0.1"
    assert env["MASTER_PORT"] == "29501"
    cmd = launch_mod.build_worker_cmd(args)
    assert cmd == [sys.executable, "-u", "train.py", "--lr", "0.1"]


def test_launch_module_mode():
    args = launch_mod.parse_args([
        "--coordinator_address=h:1", "--module", "pkg.train"])
    assert launch_mod.build_worker_cmd(args) == \
        [sys.executable, "-u", "-m", "pkg.train"]


# ------------------------------------------------------------------- end-to-end

TRAIN_SCRIPT = textwrap.dedent("""
    import os
    os.environ["JAX_PLATFORMS"] = "cpu"
    # drop any inherited virtual-device flags (the outer pytest process
    # forces an 8-device mesh): one CPU device — this test exercises the
    # LAUNCHER, not the mesh
    os.environ["XLA_FLAGS"] = ""
    assert os.environ["COORDINATOR_ADDRESS"].startswith("127.0.0.1")
    assert os.environ["NPROC"] == "1" and os.environ["PROCESS_ID"] == "0"
    import numpy as np
    import deepspeed_tpu
    from deepspeed_tpu.models.gpt2 import gpt2_model
    model = gpt2_model(size="custom", vocab_size=64, max_seq_len=16,
                       num_layers=2, num_heads=2, d_model=32,
                       dtype="float32", attention_impl="xla")
    config = {"train_micro_batch_size_per_gpu": 4,
              "gradient_accumulation_steps": 2,
              "optimizer": {"type": "Adam", "params": {"lr": 1e-3}}}
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=config)
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(0, 64, (4, 16), dtype=np.int32)}
    data = [batch] * 8
    for _ in range(2):
        loss = engine.train_batch(data_iter=iter(data * 10))
    print(f"E2E_OK loss={float(loss):.4f}")
""")


@pytest.mark.slow
def test_cli_single_host_smoke(tmp_path):
    """deepspeed-CLI end-to-end: launch a 2-step training run on one host
    (VERDICT round-1 item 4 'Done =' criterion)."""
    script = tmp_path / "train_smoke.py"
    script.write_text(TRAIN_SCRIPT)
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("JAX_PLATFORMS", None)
    proc = subprocess.run(
        [sys.executable, "-m", "deepspeed_tpu.launcher.runner",
         "--hostfile", str(tmp_path / "missing_hostfile"),
         str(script)],
        env=env, capture_output=True, text=True, timeout=240)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "E2E_OK" in proc.stdout


def test_ds_report_runs(capsys):
    from deepspeed_tpu.launcher import ds_report
    assert ds_report.main() == 0
    out = capsys.readouterr().out
    assert "deepspeed_tpu version" in out
    assert "jax version" in out


# ------------------------------------------------------------ new bin tools

import json
import pathlib

REPO_BIN = pathlib.Path(REPO) / "bin"


def test_ds_elastic_cli(tmp_path, capsys):
    import runpy
    cfg = {"elasticity": {"enabled": True, "max_train_batch_size": 64,
                          "micro_batch_sizes": [2, 4],
                          "min_gpus": 1, "max_gpus": 8}}
    p = tmp_path / "cfg.json"
    p.write_text(json.dumps(cfg))
    import sys as _sys
    argv = _sys.argv
    _sys.argv = ["ds_elastic", "-c", str(p), "-w", "4"]
    try:
        with pytest.raises(SystemExit) as e:
            runpy.run_path(str(REPO_BIN / "ds_elastic"), run_name="__main__")
        assert e.value.code == 0
    finally:
        _sys.argv = argv
    out = capsys.readouterr().out
    assert "final batch size" in out and "micro batch @ world=4" in out


def test_ds_ssh_local_fallback(tmp_path, capsys):
    import runpy
    import sys as _sys
    argv = _sys.argv
    _sys.argv = ["ds_ssh", "-f", str(tmp_path / "nope"), "echo", "DS_SSH_OK"]
    try:
        with pytest.raises(SystemExit) as e:
            runpy.run_path(str(REPO_BIN / "ds_ssh"), run_name="__main__")
        assert e.value.code == 0
    finally:
        _sys.argv = argv


def test_ds_bench_runs_on_virtual_mesh():
    import subprocess
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    code = (
        "import os, runpy, sys\n"
        "import jax; jax.config.update('jax_platforms', 'cpu')\n"
        f"sys.argv = ['ds_bench', '--max-bytes', str(1 << 20),"
        " '--trials', '1', '--warmup', '1', '--ops', 'all_reduce']\n"
        f"runpy.run_path({str(REPO + '/bin/ds_bench')!r},"
        " run_name='__main__')\n")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=120)
    assert "all_reduce" in r.stdout, r.stderr[-1500:]


def test_ds_migrate_cli(tmp_path, capsys):
    """Round-5 migration CLI: merges a reference-layout dir to npz,
    torch-free at read time (the fixture is written by real torch)."""
    import runpy
    torch = pytest.importorskip("torch")
    import collections
    d = tmp_path / "ck" / "global_step3"
    d.mkdir(parents=True)
    (tmp_path / "ck" / "latest").write_text("global_step3")
    sd = collections.OrderedDict([("w", torch.arange(6.).reshape(2, 3))])
    torch.save({"module": sd, "iteration": 3,
                "param_shapes": [collections.OrderedDict(
                    (k, v.shape) for k, v in sd.items())]},
               d / "mp_rank_00_model_states.pt")
    out = tmp_path / "m.npz"
    import sys as _sys
    argv = _sys.argv
    _sys.argv = ["ds_migrate", str(tmp_path / "ck"), "-o", str(out)]
    try:
        runpy.run_path(str(REPO_BIN / "ds_migrate"), run_name="__main__")
    except SystemExit as e:
        assert not e.code
    finally:
        _sys.argv = argv
    import numpy as np
    z = np.load(out)
    np.testing.assert_array_equal(z["w"],
                                  np.arange(6, dtype=np.float32).reshape(2, 3))
    assert "wrote" in capsys.readouterr().out
