"""Two proofs that ``correct`` can come out false, at a size a test run
holds (the cells' ``rehearse`` sizes, on the CPU):

- the CONTROL: the reference computed in a lower precision than the
  configuration states fails at least one of the cell's limits. At the
  cell's own size the control is int8, read on the chip beside the program
  (PERF.md section 2). At the test's size (rows of 64 elements) int8's 7
  bits a row are no coarser than bfloat16's 8 bits an element and nothing
  could hold it out, so the test's control is fp8, against the limits the
  rehearsal size has;
- a BROKEN timed path: the harness's look for a chip is skipped
  (``--rehearse``) and the rest of a run is driven with the program broken
  underneath -- a train step that returns its state unchanged, a served
  token altered where it is produced -- and the run's checks fail.
"""

import json
import pathlib

import pytest

from perfbench import control, run

HERE = pathlib.Path(__file__).resolve().parent.parent


def cell_files(name):
    cell = run.load_json(HERE / "workloads" / f"{name}.json")
    cfg = run.load_json(HERE / "configs" / f"{cell['config']}.json")
    traffic = run.load_json(HERE / "traffic" / f"{cell['traffic']}.json")
    return (run.overlay(cell, cell["rehearse"]),
            run.overlay(cfg, cfg["rehearse"]),
            run.overlay(traffic, traffic["rehearse"]))


def test_train_control_is_over_a_limit():
    cell, cfg, traffic = cell_files("gpt2m_train_seq1024")
    r = control.train_control(cell, cfg, traffic, seed=11, chips=1)
    lim = cell["limits"]
    over = [k for k in ("grad_norm_gap", "delta_norm_gap", "grad_elem_diff",
                        "grad_vector_pooled") if r["fp8"][k] > lim[k]]
    assert "grad_vector_pooled" in over
    assert over, (r["fp8"], lim)


def rehearse(workload, seconds="3"):
    return run.main(["--workload", workload, "--seed", "9", "--seconds",
                     seconds, "--trace", "0", "--rehearse"])


def test_sound_train_run_is_correct_and_unchanged_state_is_not(monkeypatch):
    assert rehearse("gpt2m_train_seq1024") == 0
    from distributed_compute_pytorch_tpu.train import trainer as tr
    real = tr.make_step_fns

    def broken(*a, **kw):
        init_fn, train_step, eval_step = real(*a, **kw)

        def unchanged(state, x, y):
            new_state, metrics = train_step(state, x, y)
            del new_state
            return state, metrics
        return init_fn, unchanged, eval_step

    monkeypatch.setattr(tr, "make_step_fns",
                        lambda *a, **kw: broken(*a, **dict(kw, donate=False)))
    assert rehearse("gpt2m_train_seq1024") == 1


def test_altered_served_token_is_not_correct(monkeypatch):
    from distributed_compute_pytorch_tpu import serve
    real = serve.ContinuousBatcher.serve_detailed

    def altered(self, requests, **kw):
        results = real(self, requests, **kw)
        for r in results:
            if len(r.tokens) > 2:
                r.tokens[1] = (r.tokens[1] + 17) % 500 + 1
        return results

    monkeypatch.setattr(serve.ContinuousBatcher, "serve_detailed", altered)
    assert rehearse("mistral7b_chat_steady") == 1
