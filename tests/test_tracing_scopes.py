"""The program's names on the profiler's timeline (ISSUE 24).

Device side: the lowered programs carry the declared scopes of
``obs.tracing.SCOPES`` in their ``op_name`` locations, forward and under
``transpose(jvp(``. Host side: a ``span`` lands on the host plane of
whatever profile is running, with its arguments, with and without a
``Tracer``. And every scope or span a benchmark metric reads is one the
program emits."""

import glob
import json
import pathlib
import re

import jax
import jax.numpy as jnp
import optax
import pytest

from distributed_compute_pytorch_tpu.core.mesh import make_mesh
from distributed_compute_pytorch_tpu.models.registry import build_model
from distributed_compute_pytorch_tpu.obs import tracing
from distributed_compute_pytorch_tpu.serve import ContinuousBatcher
from distributed_compute_pytorch_tpu.train.step import make_step_fns

ROOT = pathlib.Path(__file__).resolve().parent.parent
PKG = ROOT / "distributed_compute_pytorch_tpu"


def _locations(lowered) -> set:
    """Every ``loc("...")`` name of a lowered program: the name stacks
    XLA turns into ``op_name``."""
    return set(re.findall(r'loc\("([^"]+)"',
                          lowered.as_text(debug_info=True)))


def _has(locs, *path) -> bool:
    """Some location holds ``path`` as consecutive ``/`` components."""
    want = "/".join(path)
    return any(re.search(rf"(^|/){re.escape(want)}(/|$)", n) for n in locs)


def test_scope_refuses_an_undeclared_name():
    with pytest.raises(ValueError, match="not declared"):
        tracing.scope("attention")
    with tracing.scope("attn"):      # a declared one is a plain context
        pass


TRAIN_SCOPES = {
    # gpt2 tiny has no dropout of its own: the test turns it on
    "gpt2": ("embed", "attn", "mlp", "head", "loss", "dropout"),
    "llama": ("embed", "attn", "mlp", "head", "loss"),
}


def _train_step_locations(name, **kw) -> set:
    """The locations of the tiny model's lowered train step."""
    model = build_model(name, preset="tiny", **kw)
    mesh = make_mesh("data=1", devices=jax.devices()[:1])
    init_fn, train_step, _ = make_step_fns(
        model, optax.adamw(1e-3), mesh, donate=False)
    state = init_fn(jax.random.key(0))
    x = jnp.zeros((2, 16), jnp.int32)
    return _locations(train_step.lower(state, x, x))


@pytest.mark.parametrize("name", sorted(TRAIN_SCOPES))
def test_train_step_carries_every_training_scope(name):
    kw = {"dropout_rate": 0.1} if name == "gpt2" else {}
    locs = _train_step_locations(name, **kw)
    for s in TRAIN_SCOPES[name]:
        if s == "dropout":
            # inside the one helper, so it nests under its caller's scope
            for outer in ("embed", "attn", "mlp"):
                assert _has(locs, f"jvp({outer})", "dropout"), (outer, s)
                assert _has(locs, f"transpose(jvp({outer}))", "dropout")
            continue
        assert _has(locs, f"jvp({s})"), s
        assert _has(locs, f"transpose(jvp({s}))"), s
    assert _has(locs, "optimizer")
    assert not any("jvp(optimizer)" in n for n in locs)


def test_dropout_scope_holds_the_bit_generator_in_both_passes():
    """The masks' bits are XLA's ``RngBitGenerator``, an operation of its
    own that no consumer fuses away, drawn under ``dropout`` in the forward
    and again in the backward; the key derivations above the helper
    (``fold_in``, ``split``) stay threefry and stay outside the scope."""
    locs = _train_step_locations("gpt2", dropout_rate=0.1)
    for outer in ("embed", "attn", "mlp"):
        for way in (f"jvp({outer})", f"transpose(jvp({outer}))"):
            assert _has(locs, way, "dropout", "rng_bit_generator"), way
    under = [n for n in locs if _has({n}, "dropout")]
    assert under and not any(
        w in n for n in under for w in ("threefry", "random_bits", "uniform"))


def test_zero1_update_is_one_optimizer_scope(devices8):
    model = build_model("gpt2", preset="tiny")
    mesh = make_mesh("data=8", devices=devices8)
    init_fn, train_step, _ = make_step_fns(
        model, optax.adamw(1e-3), mesh, donate=False, shard_update=True)
    state = init_fn(jax.random.key(0))
    x = jnp.zeros((8, 16), jnp.int32)
    locs = _locations(train_step.lower(state, x, x))
    assert _has(locs, "optimizer")
    assert not _has(locs, "optimizer", "optimizer")


@pytest.fixture(scope="module")
def tiny_llama_cb():
    model = build_model("llama", preset="tiny")
    params, _ = model.init(jax.random.key(0))
    return ContinuousBatcher(model, params, slots=2, t_max=32,
                             prompt_buf=8, segment=4)


def test_serve_programs_carry_the_serving_scopes(tiny_llama_cb):
    from distributed_compute_pytorch_tpu.serve import Request
    cb = tiny_llama_cb
    out = cb.serve([Request(tokens=[5, 9, 12], max_new=3)])
    assert len(out[0]) == 3
    programs = cb._program_sigs      # each program's first dispatch
    fn, args, kwargs = programs["segment"]
    seg = _locations(fn.lower(*args, **kwargs))
    for path in (("decode",), ("decode", "embed"), ("decode", "attn"),
                 ("attn", "kv_gather"), ("attn", "kv_write"),
                 ("decode", "mlp"), ("decode", "head"),
                 ("decode", "sample")):
        assert _has(seg, *path), path
    fn, args, kwargs = programs["admit"]
    adm = _locations(fn.lower(*args, **kwargs))
    for path in (("admit",), ("admit", "embed"), ("admit", "attn"),
                 ("admit", "mlp"), ("admit", "kv_write")):
        assert _has(adm, *path), path
    assert not _has(adm, "decode") and not _has(seg, "admit")


def test_layer_kinds_carry_the_expert_and_window_scopes():
    """A decoder of layer kinds (``models/hybrid.py``): router, experts
    and the shared expert inside ``mlp``, the window layers' attention
    (banded prefill, ring read) as ``attn_local`` inside ``attn``, in both
    serve programs."""
    from distributed_compute_pytorch_tpu.serve import Request
    model = build_model("hybrid", preset="tiny")
    params, _ = model.init(jax.random.key(0))
    cb = ContinuousBatcher(model, params, slots=2, t_max=32, prompt_buf=16,
                           segment=4)
    out = cb.serve([Request(tokens=list(range(1, 13)), max_new=3)])
    assert len(out[0]) == 3
    for program, outer in (("segment", "decode"), ("admit", "admit")):
        fn, args, kwargs = cb._program_sigs[program]
        locs = _locations(fn.lower(*args, **kwargs))
        for path in ((outer, "mlp", "router"), (outer, "mlp", "experts"),
                     (outer, "mlp", "shared_expert"),
                     (outer, "attn", "attn_local"), (outer, "attn")):
            assert _has(locs, *path), (program, path)
    seg = _locations(cb._program_sigs["segment"][0].lower(
        *cb._program_sigs["segment"][1], **cb._program_sigs["segment"][2]))
    assert _has(seg, "attn_local", "kv_write")       # the ring's write
    assert _has(seg, "attn", "kv_write")             # the pool's


def test_a_block_models_pass_carries_its_scopes():
    """A model that generates by block diffusion: its segment is the block
    program, wrapped in ``block_pass`` (never ``decode``), with the rule's
    work under ``unmask`` and the pool's span write under ``attn``."""
    from distributed_compute_pytorch_tpu.serve import Request
    model = build_model(
        "hybrid", vocab_size=256, max_seq_len=32,
        layer_types=("full_attention",) * 2, mlp_layer_types=("sparse",) * 2,
        num_heads=4, num_kv_heads=2, head_dim=16, d_model=64, d_ff=128,
        rope_sliding_only=False, norm_placement="pre", num_experts=8,
        top_k=2, moe_d_ff=32, shared_d_ff=0, router="softmax",
        block_length=4, denoising_steps=2, mask_token_id=200)
    params, _ = model.init(jax.random.key(0))
    cb = ContinuousBatcher(model, params, slots=2, t_max=32, prompt_buf=16,
                           segment=4)
    out = cb.serve([Request(tokens=list(range(1, 11)), max_new=5)])
    assert len(out[0]) == 5
    fn, args, kwargs = cb._program_sigs["segment"]
    seg = _locations(fn.lower(*args, **kwargs))
    for path in (("block_pass",), ("block_pass", "embed"),
                 ("block_pass", "attn"), ("attn", "kv_write"),
                 ("attn", "kv_gather"), ("block_pass", "mlp", "router"),
                 ("block_pass", "mlp", "experts"), ("block_pass", "head"),
                 ("block_pass", "unmask")):
        assert _has(seg, *path), path
    assert not _has(seg, "decode") and not _has(seg, "sample")
    fn, args, kwargs = cb._program_sigs["admit"]
    adm = _locations(fn.lower(*args, **kwargs))
    assert _has(adm, "admit", "attn") and not _has(adm, "block_pass")


def test_latent_layers_carry_the_latent_scopes():
    """A latent-attention layer (``models/hybrid.py``): everything its
    mixer does as ``attn_latent`` inside ``attn``, the products with the
    up-projection of the compressed K/V (its expansion in the admission
    program, its absorption into query and output in a tick) as
    ``latent_absorb`` inside that, and the pool's write inside it too, in
    both serve programs (three layers: admission needs no feed-forward
    after the last layer's K/V, so the last layer's is not in its
    program)."""
    from distributed_compute_pytorch_tpu.serve import Request
    model = build_model(
        "hybrid", vocab_size=256, max_seq_len=64,
        layer_types=("latent_attention",) * 3,
        mlp_layer_types=("dense", "sparse", "sparse"), num_heads=4,
        d_model=64,
        d_ff=128, q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, norm_placement="pre",
        qk_norm=False, num_experts=8, experts_held=(0, 4), top_k=2,
        moe_d_ff=32, shared_d_ff=32)
    params, _ = model.init(jax.random.key(0))
    cb = ContinuousBatcher(model, params, slots=2, t_max=64, prompt_buf=32,
                           segment=4)
    out = cb.serve([Request(tokens=list(range(1, 13)), max_new=3)])
    assert len(out[0]) == 3
    for program, outer in (("segment", "decode"), ("admit", "admit")):
        fn, args, kwargs = cb._program_sigs[program]
        locs = _locations(fn.lower(*args, **kwargs))
        for path in ((outer, "attn", "attn_latent"),
                     ("attn_latent", "latent_absorb"),
                     (outer, "mlp", "experts")):
            assert _has(locs, *path), (program, path)
        assert not _has(locs, "attn_local")
        assert not _has(locs, "mlp", "attn_latent")
    seg = _locations(cb._program_sigs["segment"][0].lower(
        *cb._program_sigs["segment"][1], **cb._program_sigs["segment"][2]))
    assert _has(seg, "attn_latent", "kv_write")      # the tick's one vector
    adm = _locations(cb._program_sigs["admit"][0].lower(
        *cb._program_sigs["admit"][1], **cb._program_sigs["admit"][2]))
    assert _has(adm, "admit", "kv_write")            # the whole-block write


def test_cca_layers_carry_the_cca_scopes():
    """A compressed-convolutional-attention layer (``models/hybrid.py``):
    everything its mixer does as ``attn_cca`` inside ``attn``; what it adds
    round the projections and the attention product (the convolutions, the
    mean, the norms, the rotation, the value shift, the tail's read and
    write) as ``cca_mix`` inside that; the MLP router under ``router`` and
    the experts under ``experts`` inside ``mlp``; in both serve programs."""
    from distributed_compute_pytorch_tpu.serve import Request
    model = build_model(
        "hybrid", vocab_size=256, max_seq_len=64,
        layer_types=("cca_attention",) * 2,
        mlp_layer_types=("sparse_top1",) * 2, num_heads=4, num_kv_heads=2,
        head_dim=16, d_model=64, norm_placement="pre", qk_norm=False,
        partial_rotary_factor=0.5, num_experts=5, experts_held=(0, 4),
        top_k=1, moe_d_ff=32, shared_d_ff=0, router_hidden=16,
        scale_residual_merge=True, tie_embeddings=True)
    params, _ = model.init(jax.random.key(0))
    cb = ContinuousBatcher(model, params, slots=2, t_max=64, prompt_buf=32,
                           segment=4)
    out = cb.serve([Request(tokens=list(range(1, 13)), max_new=3)])
    assert len(out[0]) == 3
    for program, outer in (("segment", "decode"), ("admit", "admit")):
        fn, args, kwargs = cb._program_sigs[program]
        locs = _locations(fn.lower(*args, **kwargs))
        for path in ((outer, "attn", "attn_cca"), ("attn_cca", "cca_mix"),
                     (outer, "mlp", "router"), (outer, "mlp", "experts")):
            assert _has(locs, *path), (program, path)
        assert not _has(locs, "attn_latent") and not _has(locs, "attn_local")
        assert not _has(locs, "mlp", "attn_cca")
        assert not _has(locs, "cca_mix", "experts")
    seg = _locations(cb._program_sigs["segment"][0].lower(
        *cb._program_sigs["segment"][1], **cb._program_sigs["segment"][2]))
    assert _has(seg, "attn_cca", "kv_write")         # the tick's pool write


def test_linear_and_sparse_latent_layers_carry_their_scopes():
    """A KDA linear-attention layer (``models/hybrid.py``): everything its
    mixer does as ``attn_linear`` inside ``attn``, the convolutions, decays
    and recurrence as ``linear_scan`` inside that. A sparse latent layer:
    ``attn_sparse`` inside ``attn``, the indexer, its pooled keys and the
    top-k as ``index_select`` inside that, the up-projection's products as
    ``latent_absorb`` where a latent layer has them, the pools' writes and
    the selected read under ``kv_write`` / ``kv_gather``. The
    hyper-connections as ``hyper_mix`` under ``attn`` and ``mlp`` alike. In
    both serve programs."""
    from distributed_compute_pytorch_tpu.serve import Request
    model = build_model(
        "hybrid", vocab_size=256, max_seq_len=64,
        layer_types=("linear_attention", "sparse_latent_attention",
                     "linear_attention"),
        mlp_layer_types=("dense", "sparse", "sparse"), num_heads=4,
        d_model=64, d_ff=128, q_lora_rank=48, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=0, v_head_dim=16,
        norm_placement="pre", qk_norm=False, kda_heads=4, kda_head_dim=16,
        kda_gate_rank=8, index_heads=2, index_head_dim=16, index_topk=8,
        index_rope_dim=8, hc_mult=4, swiglu_limit=10.0, num_experts=8,
        experts_held=(0, 4), top_k=2, moe_d_ff=32, shared_d_ff=32)
    params, _ = model.init(jax.random.key(0))
    cb = ContinuousBatcher(model, params, slots=2, t_max=64, prompt_buf=32,
                           segment=4)
    out = cb.serve([Request(tokens=list(range(1, 21)), max_new=3)])
    assert len(out[0]) == 3
    for program, outer in (("segment", "decode"), ("admit", "admit")):
        fn, args, kwargs = cb._program_sigs[program]
        locs = _locations(fn.lower(*args, **kwargs))
        for path in ((outer, "attn", "attn_linear"),
                     ("attn_linear", "linear_scan"),
                     (outer, "attn", "attn_sparse"),
                     ("attn_sparse", "index_select"),
                     ("attn_sparse", "latent_absorb"),
                     (outer, "attn", "hyper_mix"), (outer, "mlp", "hyper_mix"),
                     (outer, "mlp", "experts")):
            assert _has(locs, *path), (program, path)
        assert not _has(locs, "attn_latent") and not _has(locs, "attn_cca")
        assert not _has(locs, "mlp", "attn_linear")
        assert not _has(locs, "attn_linear", "index_select")
        assert not _has(locs, "linear_scan", "hyper_mix")
    seg = _locations(cb._program_sigs["segment"][0].lower(
        *cb._program_sigs["segment"][1], **cb._program_sigs["segment"][2]))
    assert _has(seg, "attn_sparse", "kv_write")      # the tick's one vector
    assert _has(seg, "index_select", "kv_write")     # and its pooled key
    assert _has(seg, "index_select", "kv_gather")    # the pooled keys' read
    assert _has(seg, "attn_sparse", "kv_gather")     # the chosen tokens'
    adm = _locations(cb._program_sigs["admit"][0].lower(
        *cb._program_sigs["admit"][1], **cb._program_sigs["admit"][2]))
    assert _has(adm, "admit", "kv_write")            # blocks, state, tails


def test_admission_prefix_gather_is_a_kv_gather():
    """With the prefix cache on, a second request sharing a block-aligned
    prefix attaches it: the admission program gathers the cached K/V."""
    from distributed_compute_pytorch_tpu.serve import Request
    model = build_model("llama", preset="tiny")
    params, _ = model.init(jax.random.key(0))
    cb = ContinuousBatcher(model, params, slots=2, t_max=64, prompt_buf=24,
                           segment=4, prefix_cache=True)
    seen = []
    noted = cb._note_program
    cb._note_program = lambda kind, fn, args, kwargs: (
        seen.append((kind, fn, args, kwargs)),
        noted(kind, fn, args, kwargs))[1]
    prompt = list(range(1, 20))
    cb.serve([Request(tokens=prompt, max_new=2)])
    cb.serve([Request(tokens=prompt + [7], max_new=2)])
    cb._note_program = noted
    found = False
    for kind, fn, args, kwargs in seen:
        if kind != "admit":
            continue
        found |= _has(_locations(fn.lower(*args, **kwargs)),
                      "admit", "kv_gather")
    assert found


def _emitted_span_names() -> set:
    names = set()
    for f in PKG.rglob("*.py"):
        names |= set(re.findall(r'\bspan\(\s*"([a-z_]+)"', f.read_text()))
    return names


def test_every_name_a_benchmark_metric_reads_is_emitted():
    spans = _emitted_span_names()
    assert {"data_wait", "train_step", "epoch_fence", "log_read",
            "admit_wave", "dispatch_segment", "harvest",
            "await_arrival"} <= spans
    read = 0
    unscoped = {}
    for f in sorted((ROOT / "perfbench" / "layer_metrics").glob("*.json")):
        spec = json.loads(f.read_text())
        if spec["reader"] == "trace_scope_share":
            for s in spec.get("scope", []) + spec.get("except", []):
                assert s in tracing.SCOPES, (f.name, s)
                read += 1
            if not spec.get("scope"):
                unscoped[f.name] = spec
        if spec["reader"] == "idle_owner_share":
            assert spec["span"] in spec["owners"]
            for s in spec["owners"]:
                assert s in spans, (f.name, s)
                read += 1
        if spec["reader"] == "span_share":
            for s in [spec["numerator"], *spec["denominator"]]:
                assert s in spans, (f.name, s)
    assert read >= 17
    # the scopes ISSUE 41 declared are each read by a metric's file (and a
    # file that reads none of its own leaves them out with the rest of the
    # vocabulary, as below)
    new = {"attn_linear", "linear_scan", "attn_sparse", "index_select",
           "hyper_mix"}
    assert new <= set(tracing.SCOPES)
    named = set()
    for f in (ROOT / "perfbench" / "layer_metrics").glob("*.json"):
        named |= set(json.loads(f.read_text()).get("scope", []))
    assert new <= named
    # an "unscoped" share leaves out the program's whole vocabulary but
    # the scopes that wrap its program (``wraps``): the reader takes
    # ``obs.tracing.SCOPES`` from the run (``perfbench/readers/
    # trace_scope_share.py``), so a scope declared later (``router``,
    # ``experts``, ``shared_expert``, ``attn_local`` since PR 28) is left
    # out without an edit of the file, whose own ``except`` list need not
    # name it
    from perfbench.readers import trace_scope_share
    assert unscoped
    for name, spec in unscoped.items():
        assert set(spec["wraps"]) <= {"admit", "decode"}, name
        assert set(spec["wraps"]) <= set(tracing.SCOPES), name
        lines = {0: {"modules": [], "ops": [
            {"start": 0, "dur": 4, "stats": {"tf_op": "jit(f)/decode/add:"}},
            *({"start": 10 * (i + 1), "dur": 4, "stats": {
                "tf_op": f"jit(f)/decode/{sc}/dot:"}}
              for i, sc in enumerate(tracing.SCOPES)
              if sc not in spec["wraps"])]}}
        share = trace_scope_share.share(
            lines, {k: v for k, v in spec.items() if k != "of_module"},
            tracing.SCOPES)
        n_ops = 1 + len(set(tracing.SCOPES) - set(spec["wraps"]))
        assert share == pytest.approx(100.0 / n_ops), name


def _host_events(trace_dir) -> list:
    files = sorted(glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(files[-1])
    out = []
    for plane in data.planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            for ev in line.events:
                out.append((ev.name, dict(ev.stats), ev.duration_ns))
    return out


@pytest.mark.parametrize("with_tracer", [False, True])
def test_span_reaches_the_profile_with_its_arguments(tmp_path, with_tracer):
    tr = tracing.Tracer() if with_tracer else None
    prev = tracing.configure_tracer(tr)
    try:
        with jax.profiler.trace(str(tmp_path)):
            with tracing.span("x", rids="a,b", rows=2) as sp:
                with tracing.span("inner"):
                    pass
                sp.note(done="a")
            tracing.instant("marker", n=3)
    finally:
        tracing.configure_tracer(prev)
    events = {name: stats for name, stats, _ in _host_events(tmp_path)}
    # TraceMe's own packing would cut "a,b" at the comma
    assert events["x"] == {"rids": "a;b", "rows": 2, "done": "a"}
    assert "inner" in events and events["marker"] == {"n": 3}
    if with_tracer:
        evs = tr.events()
        assert tracing.validate_chrome_trace(evs) == []
        begin = next(e for e in evs if e["name"] == "x" and e["ph"] == "B")
        end = next(e for e in evs if e["name"] == "x" and e["ph"] == "E")
        assert begin["args"] == {"rids": "a,b", "rows": 2}
        assert end["args"] == {"done": "a"}


def test_span_with_neither_sink_allocates_nothing():
    assert tracing.current_tracer() is None
    assert not jax.profiler.TraceAnnotation.is_enabled()
    s = tracing.span("x", rids="a b")
    assert s is tracing.span("y") and type(s).__slots__ == ()
    with s as inside:
        inside.note(done="a")          # accepted and dropped
    tracing.instant("x")


def test_tracer_dump_carries_its_wall_clock_epoch(tmp_path):
    import time
    before = time.time_ns()
    tr = tracing.Tracer()
    after = time.time_ns()
    with tr.span("a", k=1):
        pass
    out = tmp_path / "trace.json"
    tr.dump(str(out))
    doc = json.loads(out.read_text())
    assert before <= doc["epoch_unix_ns"] <= after
    assert tracing.validate_chrome_trace(doc["traceEvents"]) == []
    assert [e["ph"] for e in doc["traceEvents"]] == ["B", "E"]
    assert not hasattr(tr, "close")
    with pytest.raises(TypeError):
        tracing.Tracer(jsonl_path=str(tmp_path / "spans.jsonl"))


def test_request_spans_share_the_requests_id(tiny_llama_cb):
    """admit_wave, dispatch_segment and harvest name the requests they
    handle by ``Request.request_id`` (else the positional default)."""
    from distributed_compute_pytorch_tpu.serve import Request
    tr = tracing.Tracer()
    prev = tracing.configure_tracer(tr)
    try:
        tiny_llama_cb.serve([
            Request(tokens=[5, 9], max_new=2, request_id="alpha"),
            Request(tokens=[7], max_new=6)])
    finally:
        tracing.configure_tracer(prev)
    evs = tr.events()
    args = lambda name, ph: [e.get("args", {}) for e in evs
                             if e["name"] == name and e["ph"] == ph]
    assert args("admit_wave", "B")[0]["rids"] == "alpha req-1"
    assert all(a["rids"] for a in args("dispatch_segment", "B"))
    ends = args("harvest", "E")
    assert [a["first"] for a in ends][0] == "alpha req-1"
    assert {i for a in ends for i in a["done"].split()} >= {"alpha",
                                                            "req-1"}
