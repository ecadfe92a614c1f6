"""The controls: runs that MUST come out `correct: false`.

Each breaks one guarantee the configuration states, underneath the
timed path, and drives the rest of a run with the harness's look for a
chip skipped:

  approximate_answers  every answer of more than 100 rows loses one row
                       where it is produced ("every answer is the exact
                       set") — every cell (the mesh cell's control is
                       tests/test_mesh_cell.py's)
  drop_commits         commits are acknowledged and never applied ("an
                       acknowledged commit is visible to the next query")
                       — the cell with writes

As tests they run on the CPU at scale 0.002.  On the chip, at the cell's
own size (PERF.md has the readings):

    python3 benchmark/tests/test_control.py <workload> <control|sound> <seconds> <seed> [<seed> ..]
"""

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import pytest  # noqa: E402

CASES = [
    ("mem-uniform-closed", "approximate_answers"),
    ("mem-zipf-open", "approximate_answers"),
    ("wal-mixed95-closed", "approximate_answers"),
    ("wal-mixed95-closed", "drop_commits"),
]


def run(workload, control, seed, seconds, scale=None, require_chip=False):
    from benchmark.harness import cell

    return cell.run_cell(workload, seed, seconds, False, time.monotonic(),
                         require_chip=require_chip, scale=scale,
                         sabotage=control)


@pytest.mark.parametrize("workload,control", CASES)
def test_a_broken_guarantee_is_not_correct(workload, control, capfd):
    result, code = run(workload, control, 2**31 + 17, 3.0, scale=0.002)
    out = capfd.readouterr().out
    assert code == 0 and result["correct"] is False
    failed = [json.loads(line) for line in out.splitlines()
              if '"log": "compare"' in line and '"ok": false' in line]
    names = {f["name"] for f in failed}
    if control == "approximate_answers":
        assert "wrong_answers" in names and result["failed"] > 0
    else:
        assert names & {"read_your_write_misses", "wrong_answers", "count_rpc"}


@pytest.mark.parametrize("workload", sorted({w for w, _ in CASES}))
def test_the_sound_run_is_correct(workload, capfd):
    result, code = run(workload, None, 2**31 + 18, 3.0, scale=0.002)
    capfd.readouterr()
    assert code == 0 and result["correct"] is True and result["failed"] == 0


if __name__ == "__main__":
    workload, control, seconds = sys.argv[1], sys.argv[2], float(sys.argv[3])
    control = None if control == "sound" else control
    # one process per seed: each run builds its own server and store
    import subprocess

    if len(sys.argv) > 5:
        for seed in sys.argv[4:]:
            subprocess.run([sys.executable, os.path.abspath(__file__),
                            workload, sys.argv[2], sys.argv[3], seed],
                           cwd=ROOT, check=False)
        sys.exit(0)
    result, code = run(workload, control, int(sys.argv[4]), seconds,
                       require_chip=True)
    print(json.dumps({"control": sys.argv[2], "workload": workload,
                      "seed": int(sys.argv[4]), "exit": code,
                      "result": result}), flush=True)
