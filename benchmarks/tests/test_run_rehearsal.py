"""run.py end to end on the CPU at a toy size: the control flow of a
run, the look for a chip skipped. A rehearsal ends in a line that names
``platform: cpu`` and is not a result; with a token altered where it is
produced, the comparison comes out false; with no accelerator and no
``--rehearse``, run.py prints no result and exits non-zero."""

import json
import os
import subprocess
import sys

import pytest

from conftest import HERE, REPO

REHEARSAL = os.path.join(HERE, "rehearsal")


def run(workload, *extra, env=None, seed=2 ** 31 + 9):
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks", "run.py"),
         "--workload", workload, "--seed", str(seed), "--seconds", "3",
         *extra], capture_output=True, text=True, timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu", **(env or {})})
    lines = [l for l in proc.stdout.splitlines() if l.strip()]
    return proc, lines


@pytest.mark.parametrize("workload,trace", [
    ("smollm2-1.7b.chat", "0"), ("smollm2-1.7b.long-prompt", "1"),
    ("mistral-7b-16l.batch", "0")])
def test_rehearsal_is_not_a_result(workload, trace):
    proc, lines = run(workload, "--trace", trace, "--rehearse", REHEARSAL)
    assert proc.returncode == 0, proc.stderr[-2000:]
    last = json.loads(lines[-1])
    assert last["rehearsal"] is True and "correct" not in last
    assert last["device"]["platform"] == "cpu"
    assert last["attempted"] > 0 and last["failed"] == 0
    assert last["would_be_correct"] is True
    facts = json.loads(lines[-2])
    assert facts["device"]["platform"] == "cpu"
    assert facts["recompiles_in_window"] == 0
    assert facts["requests_completed"] == last["attempted"]
    assert "compared served_gap_mean" in proc.stderr
    assert proc.stderr.rstrip().splitlines()[-1] == "correct: True"


def test_a_token_altered_where_it_is_produced_is_not_correct():
    # the program's own fault site: every collected token is replaced at
    # the emit boundary (token ^ 1), lengths kept, nothing crashes
    proc, lines = run("smollm2-1.7b.chat", "--trace", "0", "--rehearse",
                      REHEARSAL,
                      env={"GOFR_FAULTS": "logit_corrupt:at=1,times=1000000"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    last = json.loads(lines[-1])
    assert last["would_be_correct"] is False
    assert last["failed"] == 0          # every answer came, whole: wrong
    assert proc.stderr.rstrip().splitlines()[-1] == "correct: False"


def test_no_accelerator_no_result():
    proc, lines = run("smollm2-1.7b.chat", "--trace", "0")
    assert proc.returncode != 0
    assert lines == []
