"""A run with the timed path broken underneath comes out not correct.

Each fault is planted in the program as the window drives it: an answer
altered where it is produced, and half of the blocks left out.  (The
mixes train nothing and exchange nothing between chips.)"""

import numpy as np
import pytest

from conftest import last_json


def alter_labels(monkeypatch):
    """Merge every label of the lower half of each block into one."""
    from cluster_tools_tpu.tasks.thresholded_components import (
        BlockComponentsTask,
    )
    from cluster_tools_tpu.tasks.watershed import WatershedTask

    for cls in (WatershedTask, BlockComponentsTask):
        real = cls.compute_batch

        def compute(self, payload, blocking, config, real=real):
            batch, labels = real(self, payload, blocking, config)
            labels = np.array(labels)
            half = labels[:, : labels.shape[1] // 2]
            half[half > 0] = half.max()
            return batch, labels

        monkeypatch.setattr(cls, "compute_batch", compute)


def leave_out_half(monkeypatch):
    """The upper half of every block computed and then left out: zeros
    reach the store in its place."""
    from cluster_tools_tpu.tasks.thresholded_components import (
        BlockComponentsTask,
    )
    from cluster_tools_tpu.tasks.watershed import WatershedTask

    for cls in (WatershedTask, BlockComponentsTask):
        real = cls.compute_batch

        def compute(self, payload, blocking, config, real=real):
            batch, labels = real(self, payload, blocking, config)
            labels = np.array(labels)
            labels[:, labels.shape[1] // 2:] = 0
            return batch, labels

        monkeypatch.setattr(cls, "compute_batch", compute)


@pytest.mark.parametrize("workload", ["tiny.ws", "tiny.cc", "tiny.mc"])
@pytest.mark.parametrize("fault", [alter_labels, leave_out_half])
def test_fault_is_not_correct(rehearsal, capsys, monkeypatch, workload,
                              fault):
    fault(monkeypatch)
    assert rehearsal.main(["--workload", workload, "--seed", "2", "--seconds",
                           "0.05"]) == 0
    result = last_json(capsys.readouterr().out)
    assert result["correct"] is False
    number = {"tiny.cc": "cc_mismatch_share"}.get(workload,
                                                   "ws_mismatch_share")
    got = result["checks"][number]
    assert got["value"] > got["limit"], result["checks"]


@pytest.mark.parametrize("workload", ["tiny.ws", "tiny.cc", "tiny.mc"])
def test_sound_run_is_correct(rehearsal, capsys, workload):
    assert rehearsal.main(["--workload", workload, "--seed", "2", "--seconds",
                           "0.05"]) == 0
    result = last_json(capsys.readouterr().out)
    assert result["correct"] is True, result["checks"]
    assert list(result)[-1] == "checks"
    assert set(result["metrics"]) == {"mvox_s", "setup_s"}


def alter_costs(monkeypatch):
    """Edge costs shifted where they are computed."""
    from cluster_tools_tpu.tasks import costs

    real = costs.transform_probabilities_to_costs
    monkeypatch.setattr(costs, "transform_probabilities_to_costs",
                        lambda *a, **k: real(*a, **k) + 0.5)


def split_solution(monkeypatch):
    """The multicut solver returns every node as its own segment."""
    from cluster_tools_tpu.tasks import multicut

    monkeypatch.setattr(multicut, "solve_multicut",
                        lambda n, uv, c, **k: np.arange(n, dtype=np.int64))


def merge_solution(monkeypatch):
    """The multicut solver merges every node into one segment."""
    from cluster_tools_tpu.tasks import multicut

    monkeypatch.setattr(multicut, "solve_multicut",
                        lambda n, uv, c, **k: np.zeros(n, dtype=np.int64))


def drop_edges(monkeypatch):
    """Every third edge of each block's sub-graph is lost."""
    from cluster_tools_tpu.tasks import graph

    real = graph.block_edges
    monkeypatch.setattr(graph, "block_edges",
                        lambda seg, *a, **k: real(seg, *a, **k)[::3])


@pytest.mark.parametrize("fault, number", [
    (alter_costs, "mc_cost_gap"),
    (split_solution, "mc_attractive_pairs"),
    (merge_solution, "mc_objective_gap"),
    (drop_edges, "mc_graph_mismatch"),
])
def test_multicut_fault_is_not_correct(rehearsal, capsys, monkeypatch, fault,
                                       number):
    fault(monkeypatch)
    assert rehearsal.main(["--workload", "tiny.mc", "--seed", "2",
                           "--seconds", "0.05"]) == 0
    result = last_json(capsys.readouterr().out)
    assert result["correct"] is False
    got = result["checks"][number]
    assert got["value"] > got["limit"], result["checks"]
