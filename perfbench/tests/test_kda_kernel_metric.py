"""``kda_scan_kernel_share.admit`` (PR 43): the share of admission that is
the KDA recurrence's kernel, ``dcp_kda_chunk_scan``: the counter that says
the kernel engaged. A data file and an appended manifest entry in the form
of ``paged_attn_share.decode``'s, read by the reader that was there; a
program without the kernel (the parent) reports nothing under the name.
(The case lives here because ``test_glm_family.py`` is a file the benchmark
already had.)"""

import json
import pathlib

from perfbench import run
from perfbench.readers import trace_share

HERE = pathlib.Path(__file__).resolve().parent.parent
ROOT = HERE.parent
NAME = "kda_scan_kernel_share.admit"
CELL = "glm53flash_longctx_backlog"


def test_the_manifest_lists_the_metric_on_the_glm_cell_after_what_was_there():
    m = run.load_json(ROOT / "BENCHMARK.json")
    names = [p["name"] for p in m["per_layer"]]
    assert names.index(NAME) > names.index("sparse_selected_share")
    entry = m["per_layer"][names.index(NAME)]
    like = dict(m["per_layer"][names.index("paged_attn_share.decode")])
    assert CELL in entry.pop("workloads")
    like.pop("workloads")
    assert entry == dict(like, name=NAME, moves="serve_tokens_per_s")
    e2e = {e["name"]: e for e in m["end_to_end"]}
    assert CELL in e2e["serve_tokens_per_s"]["workloads"]


def test_its_file_names_the_kernel_the_program_calls():
    spec = json.load(open(HERE / "layer_metrics" / f"{NAME}.json"))
    assert spec == {"reader": "trace_share", "ops": "dcp_kda_chunk_scan",
                    "of_module": "_admit_impl"}
    src = (ROOT / "distributed_compute_pytorch_tpu" / "ops" / "pallas"
           / "kda_scan.py").read_text()
    assert f'name="{spec["ops"]}"' in src


class _Trace:
    """A trace summary with an admission module and, maybe, the kernel."""

    def __init__(self, kernel_s):
        self.kernel_s, self.window_s = kernel_s, 4.0

    def op_time_s(self, pattern):
        return self.kernel_s if pattern == "dcp_kda_chunk_scan" else 0.0

    def module_time_s(self, pattern):
        return (2.5, 5) if pattern == "_admit_impl" else (0.0, 0)


def test_a_program_without_the_kernel_reports_nothing():
    spec = json.load(open(HERE / "layer_metrics" / f"{NAME}.json"))
    assert trace_share.read(spec, {"trace": _Trace(0.0)}) is None
    assert trace_share.read(spec, {"trace": None}) is None
    assert trace_share.read(spec, {"trace": _Trace(0.5)}) == 20.0
