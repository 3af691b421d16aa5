"""``dcp-generate`` — sample tokens from a trained causal-LM checkpoint.

The inference-side companion of ``dcp-train`` (the reference repo trains
only; ``/root/reference/main.py`` has no generation path). Prompts and
outputs are token-id sequences — the contract every tokenizer-owning
caller can script against:

    dcp-generate --ckpt_path ck.npz --model gpt2 --model_preset tiny \\
        --prompt 12,7,90 --max_new_tokens 16 --temperature 0.8

Several prompts separated by ``;`` form a LEFT-padded batch (each prompt
decodes exactly as it would alone). ``--mesh`` runs SHARDED generation —
params restored into the training layout (``parallel.api.pick_strategy``),
batch over ``data``/``fsdp``, KV cache heads over ``tensor`` — so a
checkpoint that needed FSDP/TP to train also generates.

Prints one JSON line per prompt: {"prompt": [...], "tokens": [...],
"new": [...]}.
"""

from __future__ import annotations

import argparse
import json
import sys


def _parse_prompts(s: str) -> list[list[int]]:
    out = []
    for part in s.split(";"):
        try:
            ids = [int(t) for t in part.replace(",", " ").split()]
        except ValueError:
            raise SystemExit(f"--prompt must be token ids, got {part!r}")
        if not ids:
            raise SystemExit("--prompt has an empty prompt "
                             "(check for stray ';')")
        out.append(ids)
    return out


def load_model_and_params(model_name: str, preset, vocab_size, max_seq_len,
                          ckpt_path: str, mesh_spec=None, quantize=None):
    """Shared ``dcp-generate``/``dcp-serve`` checkpoint loader: build the
    model from its knobs, restore the params subtree (straight into the
    mesh layout when ``mesh_spec`` is given — no host-side full copy,
    which is what lets a bigger-than-one-chip checkpoint load at all),
    and optionally apply weight-only int8. The parameters keep the dtype
    the checkpoint was saved in. Returns ``(model, params, mesh)``. One
    implementation so the two CLIs cannot drift."""
    import jax

    from distributed_compute_pytorch_tpu.models.registry import build_model
    from distributed_compute_pytorch_tpu.train.checkpoint import (
        restore_params, saved_param_dtype)

    kw = {k: v for k, v in (("preset", preset),
                            ("vocab_size", vocab_size),
                            ("max_seq_len", max_seq_len),
                            # serve the weights in the dtype they were
                            # trained in (dcp-train --param_dtype): a
                            # bfloat16 checkpoint gives bfloat16 weights,
                            # activations and KV cache
                            ("param_dtype", saved_param_dtype(ckpt_path)))
          if v is not None}
    model = build_model(model_name, **kw)
    # ABSTRACT template: structure/shapes/dtypes only — a concrete init
    # would materialise the full unsharded model on one device
    template = jax.eval_shape(lambda k: model.init(k)[0],
                              jax.random.key(0))
    mesh = None
    if mesh_spec is not None:
        from distributed_compute_pytorch_tpu.core.mesh import make_mesh
        from distributed_compute_pytorch_tpu.parallel.api import (
            pick_strategy, tree_shardings)
        mesh = make_mesh(mesh_spec)
        shardings = tree_shardings(pick_strategy(mesh, model),
                                   template, mesh)
        params = restore_params(ckpt_path, template, shardings)
    else:
        params = restore_params(ckpt_path, template)
    if quantize in ("int8", "int8-kv"):
        # quantize AFTER the (possibly sharded) restore: the jitted
        # transform's outputs inherit the restored layout via SPMD, so
        # q/scale stay sharded exactly where the float kernels were and
        # the mixed-dtype dots partition like any other dot — sharded
        # int8 serving composes (pinned by tests/test_quantize.py's mesh
        # case, bit-equal to the single-device quantized run)
        from distributed_compute_pytorch_tpu.utils.quantize import (
            quantize_params_int8)
        params = jax.jit(quantize_params_int8)(params)
    return model, params, mesh


def check_tokenizer_vocab(tok, model) -> None:
    """The trainer sizes the model vocab EXACTLY to the tokenizer
    (``--dataset text``); any mismatch means this is not the training
    tokenizer and the ids would silently mean different tokens (e.g.
    forgetting ``--tokenizer`` falls back to 'byte', vocab 259)."""
    if tok.vocab_size != model.config.vocab_size:
        raise SystemExit(
            f"tokenizer vocab ({tok.vocab_size}) != model vocab "
            f"({model.config.vocab_size}) — pass the --tokenizer "
            f"the model was trained with")


def check_eos(eos_id, vocab: int) -> None:
    if eos_id is not None and not 0 <= eos_id < vocab:
        # an unreachable eos would silently never stop anything
        raise SystemExit(f"--eos_id {eos_id} outside vocab [0, {vocab})")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--ckpt_path", required=True,
                   help="checkpoint written by dcp-train (v1 file or "
                        "sharded v2 directory)")
    p.add_argument("--model", default="gpt2",
                   choices=("gpt2", "llama", "moe"),
                   help="causal families only (BERT is bidirectional); "
                        "'moe' decodes with per-token argmax routing "
                        "(models/moe.py::MoEBlock)")
    p.add_argument("--model_preset", default=None)
    p.add_argument("--vocab_size", type=int, default=None)
    p.add_argument("--max_seq_len", type=int, default=None)
    p.add_argument("--prompt", default=None,
                   help="comma/space-separated token ids; several prompts "
                        "separated by ';' decode as one left-padded batch")
    p.add_argument("--text_prompt", action="append", default=None,
                   help="TEXT prompt, encoded with --tokenizer and decoded "
                        "back to text (repeat the flag for a batch); "
                        "mutually exclusive with --prompt")
    p.add_argument("--tokenizer", default="byte",
                   help="'byte' or a tokenizer .json — must match the one "
                        "the corpus was tokenized with (--dataset text)")
    p.add_argument("--mesh", default=None,
                   help="mesh spec for SHARDED generation (e.g. "
                        "'data=2,tensor=4'); params restore into the "
                        "training strategy's layout")
    p.add_argument("--max_new_tokens", type=int, default=32)
    p.add_argument("--temperature", type=float, default=0.0,
                   help="0 = greedy")
    p.add_argument("--top_k", type=int, default=None,
                   help="sample only among the k highest-probability "
                        "tokens (temperature > 0)")
    p.add_argument("--top_p", type=float, default=None,
                   help="nucleus sampling: smallest token set with "
                        "cumulative probability >= p (temperature > 0)")
    p.add_argument("--eos_id", type=int, default=None,
                   help="stop a row at this token id (output is trimmed "
                        "at the first occurrence)")
    p.add_argument("--quantize", default=None, choices=("int8", "int8-kv"),
                   help="int8 inference: 'int8' quantizes the weights "
                        "(halves the decode tick's weight stream — "
                        "measured faster), 'int8-kv' additionally "
                        "stores the KV cache as int8 with per-row "
                        "scales — halves cache MEMORY (longer contexts "
                        "per chip) but measured SLOWER per tick on "
                        "v5e (ops/attention.py::cached_attention_q8). "
                        "Both compose with --mesh")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--force-cpu", action="store_true", dest="force_cpu")
    args = p.parse_args(argv)

    import jax
    if args.force_cpu:
        jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np

    from distributed_compute_pytorch_tpu.infer import generate
    from distributed_compute_pytorch_tpu.utils.compilation_cache import (
        enable as enable_compile_cache)
    from distributed_compute_pytorch_tpu.utils.logging import device_banner
    enable_compile_cache()
    # stderr: stdout carries the result lines
    print(f"dcp-generate | {device_banner()}", file=sys.stderr, flush=True)

    model, params, mesh = load_model_and_params(
        args.model, args.model_preset, args.vocab_size, args.max_seq_len,
        args.ckpt_path, mesh_spec=args.mesh, quantize=args.quantize)

    tok = None
    if args.text_prompt is not None:
        if args.prompt is not None:
            raise SystemExit("--prompt and --text_prompt are mutually "
                             "exclusive")
        from distributed_compute_pytorch_tpu.data.tokenizer import (
            build_tokenizer)
        tok = build_tokenizer(args.tokenizer)
        check_tokenizer_vocab(tok, model)
        prompts = [tok.encode(t) for t in args.text_prompt]
        if any(not p for p in prompts):
            raise SystemExit("--text_prompt encodes to zero tokens")
        if args.eos_id is None:
            args.eos_id = tok.eos_id   # text mode: stop at the text eos
    elif args.prompt is not None:
        prompts = _parse_prompts(args.prompt)
    else:
        raise SystemExit("one of --prompt / --text_prompt is required")
    vocab = model.config.vocab_size
    bad = [t for ids in prompts for t in ids if not 0 <= t < vocab]
    if bad:
        # the embedding gather would CLAMP out-of-range ids silently
        raise SystemExit(f"prompt ids {bad} outside vocab [0, {vocab})")
    check_eos(args.eos_id, vocab)
    if args.temperature == 0.0 and (args.top_k is not None
                                    or args.top_p is not None):
        # greedy ignores truncation; silence here would mislead
        raise SystemExit("--top_k/--top_p need --temperature > 0 "
                         "(sampling); temperature 0 is greedy")

    # LEFT-padded batch (pads excluded from attention; each row decodes
    # exactly as it would alone — pinned by tests/test_generate.py)
    T0 = max(len(ids) for ids in prompts)
    batch = np.zeros((len(prompts), T0), np.int32)
    mask = np.zeros((len(prompts), T0), np.int32)
    for i, ids in enumerate(prompts):
        batch[i, T0 - len(ids):] = ids
        mask[i, T0 - len(ids):] = 1
    if mesh is not None:
        # the batch axes need a divisible leading dim: pad with copies of
        # the last row (dropped again before printing)
        from distributed_compute_pytorch_tpu.core.mesh import (
            batch_sharding, dp_world_size)
        ws = dp_world_size(mesh)
        extra = (-len(prompts)) % ws
        if extra:
            batch = np.concatenate([batch] + [batch[-1:]] * extra)
            mask = np.concatenate([mask] + [mask[-1:]] * extra)
    prompt = jnp.asarray(batch)
    prompt_mask = jnp.asarray(mask) if len(prompts) > 1 else None
    if mesh is not None:
        prompt = jax.device_put(prompt, batch_sharding(mesh, 2))
        if prompt_mask is not None:
            prompt_mask = jax.device_put(prompt_mask,
                                         batch_sharding(mesh, 2))

    out = generate(model, params, prompt, args.max_new_tokens,
                   temperature=args.temperature, eos_id=args.eos_id,
                   top_k=args.top_k, top_p=args.top_p,
                   rng=jax.random.key(args.seed), prompt_mask=prompt_mask,
                   mesh=mesh, kv_quant=args.quantize == "int8-kv")
    out = np.asarray(out)
    for i, ids in enumerate(prompts):
        toks = [int(t) for t in out[i, T0 - len(ids):]]
        new = toks[len(ids):]
        if args.eos_id is not None and args.eos_id in new:
            new = new[:new.index(args.eos_id) + 1]
        rec = {"prompt": ids, "tokens": toks[:len(ids)] + new, "new": new}
        if tok is not None:
            rec["text"] = args.text_prompt[i] + tok.decode(new)
        print(json.dumps(rec))
    return 0


if __name__ == "__main__":
    sys.exit(main())
