"""``kda_step_kernel_share.decode`` (PR 45): the share of the decode segment
that is the KDA state's one-token step as a kernel, ``dcp_kda_step``: the
counter that says the kernel engaged. A data file and an appended manifest
entry in the form of ``kda_scan_kernel_share.admit``'s (PR 43), read by the
reader that was there; a program without the kernel (the parent) reports
nothing under the name."""

import json
import pathlib

from perfbench import run
from perfbench.readers import trace_share

HERE = pathlib.Path(__file__).resolve().parent.parent
ROOT = HERE.parent
NAME = "kda_step_kernel_share.decode"
CELLS = ["glm53flash_longctx_backlog", "solaropen2_longgen_backlog"]


def test_the_manifest_lists_the_metric_on_both_kda_cells_after_what_was_there():
    m = run.load_json(ROOT / "BENCHMARK.json")
    names = [p["name"] for p in m["per_layer"]]
    assert names.index(NAME) > names.index("state_rows_per_tick")
    entry = m["per_layer"][names.index(NAME)]
    like = m["per_layer"][names.index("kda_scan_kernel_share.admit")]
    assert entry == dict(like, name=NAME) and entry["workloads"] == CELLS
    e2e = {e["name"]: e for e in m["end_to_end"]}
    assert set(CELLS) <= set(e2e["serve_tokens_per_s"]["workloads"])


def test_its_file_names_the_kernel_the_program_calls():
    spec = json.load(open(HERE / "layer_metrics" / f"{NAME}.json"))
    assert spec == {"reader": "trace_share", "ops": "dcp_kda_step",
                    "of_module": "_segment_impl"}
    src = (ROOT / "distributed_compute_pytorch_tpu" / "ops" / "pallas"
           / "kda_step.py").read_text()
    assert f'name="{spec["ops"]}"' in src


class _Trace:
    """A trace summary with a decode segment and, maybe, the kernel; the
    admission kernel's name is not the step's."""

    def __init__(self, kernel_s):
        self.kernel_s, self.window_s = kernel_s, 4.0

    def op_time_s(self, pattern):
        return {"dcp_kda_step": self.kernel_s,
                "dcp_kda_chunk_scan": 0.3}.get(pattern, 0.0)

    def module_time_s(self, pattern):
        return (3.2, 12) if pattern == "_segment_impl" else (0.0, 0)


def test_a_program_without_the_kernel_reports_nothing():
    spec = json.load(open(HERE / "layer_metrics" / f"{NAME}.json"))
    assert trace_share.read(spec, {"trace": _Trace(0.0)}) is None
    assert trace_share.read(spec, {"trace": None}) is None
    assert trace_share.read(spec, {"trace": _Trace(0.96)}) == 30.0
