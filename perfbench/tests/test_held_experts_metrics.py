"""``experts_chosen_share.decode`` and ``held_experts_kernel_share.decode``
(PR 46): the share of the held experts that a tick's rows in the plan chose
(what the decode form needs of their weights: what ``dcp_held_experts``
reads where it runs, what is left to win where the dense form runs), and the
share of the decode segment that is that kernel: the counter that says it
engaged. Two data files and two appended manifest entries, read by readers
that were there; a program without the counters or the kernel (the parent)
reports nothing under either name."""

import json
import pathlib

import pytest

from perfbench import run
from perfbench.readers import counter_ratio, trace_share

HERE = pathlib.Path(__file__).resolve().parent.parent
ROOT = HERE.parent
CHOSEN = "experts_chosen_share.decode"
KERNEL = "held_experts_kernel_share.decode"
CELLS = {CHOSEN: ["glm53flash_longctx_backlog", "kexaone_reason_backlog",
                  "solaropen2_longgen_backlog"],
         KERNEL: ["glm53flash_longctx_backlog"]}
# pinned with == by the benchmark's own tests of the cells that brought them
# (test_zaya_family.py, test_joyai_family.py): not a later metric's to list
PINNED = {"zaya1_longprompt_backlog", "joyai_longdoc_backlog"}


def spec_of(name):
    return json.load(open(HERE / "layer_metrics" / f"{name}.json"))


@pytest.mark.parametrize("name,layer,source", [
    (CHOSEN, "Models", "program_counter"),
    (KERNEL, "Pallas kernels", "device_trace")])
def test_the_manifest_lists_the_metric_after_what_was_there(name, layer,
                                                            source):
    m = run.load_json(ROOT / "BENCHMARK.json")
    names = [p["name"] for p in m["per_layer"]]
    assert names.index(name) > names.index("kda_step_kernel_share.decode")
    assert m["per_layer"][names.index(name)] == {
        "name": name, "unit": "%", "better": "lower", "source": source,
        "layer": layer, "moves": "serve_tokens_per_s",
        "workloads": CELLS[name]}
    e2e = {e["name"]: e for e in m["end_to_end"]}
    assert set(CELLS[name]) <= set(e2e["serve_tokens_per_s"]["workloads"])
    assert not PINNED & set(CELLS[name])


@pytest.mark.parametrize("name", [CHOSEN, KERNEL])
def test_its_reader_was_there(name):
    assert (HERE / "readers" / f"{spec_of(name)['reader']}.py").exists()


def test_the_counters_file_names_what_the_program_counts():
    spec = spec_of(CHOSEN)
    assert spec == {"reader": "counter_ratio", "numerator": ["experts_chosen"],
                    "denominator": ["experts_held_ticks"], "scale": 100.0}
    src = (ROOT / "distributed_compute_pytorch_tpu" / "serve.py").read_text()
    assert '"experts_chosen", "experts_held_ticks"' in src


def test_the_kernels_file_names_the_kernel_the_program_calls():
    spec = spec_of(KERNEL)
    assert spec == {"reader": "trace_share", "ops": "dcp_held_experts",
                    "of_module": "_segment_impl"}
    src = (ROOT / "distributed_compute_pytorch_tpu" / "ops" / "pallas"
           / "held_experts.py").read_text()
    assert f'name="{spec["ops"]}"' in src


@pytest.mark.parametrize("counters,want", [
    ({"experts_chosen": 52, "experts_held_ticks": 100}, 52.0),
    ({"experts_chosen": 640, "experts_held_ticks": 640}, 100.0),
    ({"experts_held_ticks": 0, "experts_chosen": 0}, None),
    ({"expert_assignments": 9}, None)])           # the parent's counters
def test_the_chosen_share_reads_the_two_counters(counters, want):
    assert counter_ratio.read(spec_of(CHOSEN), {"counters": counters}) == want


class _Trace:
    """A trace summary with a decode segment and, maybe, the kernel."""

    def __init__(self, kernel_s):
        self.kernel_s, self.window_s = kernel_s, 4.0

    def op_time_s(self, pattern):
        return {"dcp_held_experts": self.kernel_s,
                "dcp_kda_step": 0.3}.get(pattern, 0.0)

    def module_time_s(self, pattern):
        return (3.2, 12) if pattern == "_segment_impl" else (0.0, 0)


@pytest.mark.parametrize("trace,want", [
    (_Trace(0.0), None), (None, None), (_Trace(1.28), 40.0)])
def test_a_program_without_the_kernel_reports_nothing(trace, want):
    assert trace_share.read(spec_of(KERNEL), {"trace": trace}) == want
