"""The program's ``ThresholdedComponentsWorkflow`` behind one guard, for
the cells whose jobs label one ROI each through the collective task
(``sharded=True``).

Such a cell rests on the task labelling the global config's ROI alone.  A
program whose collective task labels the whole volume under an ROI cannot
run the cell: each job would label every voxel, for minutes, and write
what the checks then call wrong.  So the first sharded workflow of a
process first runs the task once over a tiny all-foreground volume, with
an ROI that covers one chunk of it, on the job's devices and settings, and
refuses when anything is labelled outside the ROI or the ROI is not one
component.  The refusal comes before the job's own work: the warm-up job
fails within seconds and the run exits without a result.  Other than that
the workflow is the program's, unchanged.
"""

from __future__ import annotations

import os

import numpy as np

from benchmark.harness import n5
from cluster_tools_tpu.workflows import \
    ThresholdedComponentsWorkflow as _ProgramWorkflow

PROBE_SHAPE = (8, 16, 16)
PROBE_CHUNKS = (4, 8, 8)
PROBE_ROI = ((0, 0, 0), (4, 8, 8))  # one chunk

# None until probed; then the probe's verdict for this process
_VERDICT = None


def probe_roi(root: str, config_dir: str):
    """Run the program's collective task over ``PROBE_ROI`` of a volume of
    ones thresholded at 0.5, with the job's global and task settings
    otherwise.  None when it labels the ROI as one component and writes
    nothing outside it, else what went wrong."""
    from cluster_tools_tpu.runtime import build
    from cluster_tools_tpu.runtime import config as cfg

    path = os.path.join(root, "probe.n5")
    n5.write(path, "raw", np.ones(PROBE_SHAPE, np.float32), PROBE_CHUNKS)
    probe_configs = os.path.join(root, "configs")
    gconf = dict(cfg.global_config(config_dir), block_shape=list(PROBE_CHUNKS),
                 roi_begin=list(PROBE_ROI[0]), roi_end=list(PROBE_ROI[1]))
    cfg.write_global_config(probe_configs, gconf)
    task = "sharded_components"
    cfg.write_config(probe_configs, task,
                     dict(cfg.read_config(config_dir, task), threshold=0.5,
                          threshold_mode="greater", sigma=0.0))
    wf = _ProgramWorkflow(os.path.join(root, "tmp"), probe_configs,
                          input_path=path, input_key="raw", output_path=path,
                          output_key="labels", sharded=True)
    if not build([wf]):
        return "the collective task failed on the probe volume"
    got = n5.read(path, "labels", (0, 0, 0), PROBE_SHAPE)
    inside = tuple(slice(b, e) for b, e in zip(*PROBE_ROI))
    roi_ids = np.unique(got[inside])
    outside = int(np.count_nonzero(got)) - int(np.count_nonzero(got[inside]))
    if outside:
        return (f"{outside} voxels outside the ROI {PROBE_ROI} of a "
                f"{PROBE_SHAPE} volume were labelled: the collective task "
                "labels the whole volume, not the job's ROI")
    if len(roi_ids) != 1 or roi_ids[0] == 0:
        return f"the all-foreground ROI read labels {roi_ids.tolist()}"
    return None


class ThresholdedComponentsWorkflow(_ProgramWorkflow):
    """The program's workflow; with ``sharded=True`` the first instance of
    the process probes the collective task (``probe_roi``) and every
    instance refuses to build when the probe failed."""

    def __init__(self, tmp_folder, config_dir=None, *args, **kwargs):
        global _VERDICT
        super().__init__(tmp_folder, config_dir, *args, **kwargs)
        if self.sharded and _VERDICT is None:
            _VERDICT = probe_roi(
                os.path.join(os.path.dirname(tmp_folder), "roi_probe"),
                config_dir) or ""

    def requires(self):
        if self.sharded and _VERDICT:
            raise RuntimeError(f"the program cannot run this cell: {_VERDICT}")
        return super().requires()
