"""``bench/run.py`` end to end: a CPU rehearsal of a cell added as a file,
and of a model that is not dense added as files, and the refusals
without a chip or without the program."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from tiny import BENCH, REPO, copy_with_tiny_cell

KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def _run(cwd, bench, *args, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, os.path.join(bench, "run.py"),
                           *args], cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=timeout)


@pytest.mark.parametrize("cell, trace", [("tiny", "0"), ("tiny", "1"),
                                         ("tiny_open", "0"),
                                         ("tiny_mesh4", "0"),
                                         ("tiny_moe", "0"),
                                         ("tiny_moe", "1")])
def test_rehearsal_of_an_added_cell(tmp_path, cell, trace):
    bench = copy_with_tiny_cell(str(tmp_path))
    r = _run(tmp_path, bench, "--workload", cell, "--seed", str(2**31 + 7),
             "--seconds", "2", "--trace", trace, "--rehearse")
    assert r.returncode == 0, r.stderr[-3000:]
    last = json.loads(r.stdout.strip().splitlines()[-1])
    assert list(last) == ["rehearsal"]          # never a result line
    res = last["rehearsal"]
    assert list(res)[:len(KEYS)] == KEYS and list(res)[-1] == "checks"
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    assert res["device"]["platform"] == "cpu"
    # warm-up compiled every shape the window meets, refills of any count
    assert "compiles_in_window=0 " in r.stderr
    if trace == "0":
        assert set(res["metrics"]) == {"setup_s", "tokens_per_s"}
    else:
        assert res["metrics"] == {}             # no TPU in a CPU trace
    tail = r.stderr.strip().splitlines()[-4:]
    assert [t.split("=")[0] for t in tail] == [
        "check max_logit_gap", "check logit_rel_err", "check k_mismatch",
        "check not_ok"]


def test_refuses_without_a_tpu():
    r = _run(REPO, BENCH, "--workload", "qwen3_4b.offline", "--seed", "1",
             "--seconds", "1", timeout=300)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "no TPU" in r.stderr


def test_refuses_without_the_program(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    r = _run(tmp_path, str(tmp_path / "bench"), "--workload",
             "qwen3_4b.offline", "--seed", "1", "--seconds", "1",
             timeout=300)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "no program" in r.stderr
