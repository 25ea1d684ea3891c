"""The control of each mix, at a size a test holds: the reference in the
program's place in bfloat16 comes out not correct by the run's own
decision, the program correct.  The faults a multicut solution can have,
planted in the reference put in the program's place, fail their numbers."""

import json

import pytest

from benchmark.harness import cell as cell_mod


def calibrate_lines(capsys, argv):
    from benchmark import calibrate

    assert calibrate.main(argv) == 0
    return [json.loads(x) for x in capsys.readouterr().out.splitlines()
            if x.startswith("{")]


@pytest.mark.parametrize("workload", ["tiny.ws", "tiny.cc", "tiny.mc"])
def test_control_fails_and_program_passes(rehearsal, capsys, workload):
    lines = calibrate_lines(capsys, [
        "--workload", workload, "--seeds", "11,12,13", "--jobs", "1",
        "--program", "--control"])
    assert len(lines) == 3
    cell = cell_mod.load(workload, rehearsal.BENCH_FILE)
    limits = {k: v for e in cell.traffic["checks"]
              for k, v in e["limits"].items()}
    for line in lines:
        assert line["program"]["correct"] is True, line
        assert all(line["program"][k] <= limits[k] for k in limits), line
        assert line["control"]["correct"] is False, line
        assert any(line["control"][k] > limits[k] for k in limits), line


@pytest.mark.parametrize("fault, number", [
    ("merge_all", "mc_objective_gap"),
    ("split_all", "mc_attractive_pairs"),
    ("split_all", "mc_objective_gap"),
    ("drop_edges", "mc_graph_mismatch"),
])
def test_planted_multicut_fault_fails_its_number(rehearsal, capsys, fault,
                                                 number):
    lines = calibrate_lines(capsys, [
        "--workload", "tiny.mc", "--seeds", "11", "--jobs", "1",
        "--faults"])
    cell = cell_mod.load("tiny.mc", rehearsal.BENCH_FILE)
    limit = {k: v for e in cell.traffic["checks"]
             for k, v in e["limits"].items()}[number]
    got = lines[0]["faults"][fault]
    assert got["correct"] is False, got
    assert got[number] > limit, got
