"""Speculative-decoding proposers and configuration (serve-side).

Decode is HBM-bound: every tick streams the full weight set to emit one
token per row (llama bf16 0.541 ms/tick at ~0.73 hbm_efficiency in a
pre-round record removed in PR 21). Speculation verifies ``k`` DRAFTED tokens per weight
stream instead — the model layers grew a ``verify_step`` that scores a
whole draft window in one forward pass (``models/*.py``,
``ops/attention.py::cache_verify_and_attend``), and
``serve.ContinuousBatcher`` applies an EXACT accept/reject rule, so
output correctness never depends on draft quality. This module holds the
host-side half: where drafts come from.

Two proposers ship:

- :class:`NGramProposer` (the default): self-drafting by suffix lookup
  over the row's own token history (prompt + generated). When the recent
  suffix has occurred before, propose its historical continuation —
  free, no second model, and strong exactly where speculation pays most
  (repetitive spans: code, JSON, quoted context, chat boilerplate).
- :class:`DraftModelProposer`: greedy continuations from a small draft
  model via ``infer.generate`` over a fixed context window (one compile
  total). Worth it when a distilled sibling of the target exists.

Any object with ``propose(context: list[int], k: int) -> list[int]`` is
a valid proposer (``SpecConfig.proposer`` duck-types) — tests use this
to force rejection paths.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any


@dataclass(frozen=True)
class SpecConfig:
    """Speculation settings for ``serve.ContinuousBatcher(speculate=…)``.

    ``k``: drafted tokens per verify step — each verify segment scores
    ``k + 1`` positions (the row's current token plus ``k`` drafts) in
    one forward pass and emits 1..k+1 tokens.

    ``proposer``: ``"ngram"`` (self-drafting, default), ``"draft"``
    (needs ``draft_model`` + ``draft_params``), or any object with a
    ``propose(context, k)`` method.

    Auto-disable: speculation that isn't accepted is pure waste (every
    verify still streams the weights once, same as a plain tick, but
    scores k+1 positions). Over each window of ``autodisable_window``
    proposed tokens, an acceptance rate below ``autodisable_below``
    flips the batcher back to plain segment decode for the rest of the
    run (sticky until ``reset()``); outputs are unaffected either way —
    the accept rule is exact, this is purely a throughput guard.
    """

    k: int = 4
    proposer: Any = "ngram"
    ngram_max: int = 4
    ngram_min: int = 1
    draft_model: Any = None
    draft_params: Any = None
    draft_window: int = 32
    autodisable_window: int = 64
    autodisable_below: float = 0.10

    def __post_init__(self):
        if self.k < 1:
            raise ValueError(f"speculate k must be >= 1, got {self.k}")
        if self.ngram_min < 1 or self.ngram_max < self.ngram_min:
            raise ValueError("need 1 <= ngram_min <= ngram_max")


class NGramProposer:
    """Self-drafting by longest-suffix n-gram lookup.

    For the row's token history ``ctx``, find the most recent earlier
    occurrence of the longest matching recent suffix (length
    ``ngram_max`` down to ``ngram_min``) and propose the ``k`` tokens
    that followed it. History repeats itself often enough in real
    decodes (lists, code idioms, retrieved context being quoted) that
    this wins HBM streams with zero extra model cost; when it's wrong,
    the exact verify rule wastes only the speculated columns of one
    forward pass, and the batcher's auto-disable stops even that.
    """

    def __init__(self, ngram_max: int = 4, ngram_min: int = 1):
        if ngram_min < 1 or ngram_max < ngram_min:
            raise ValueError("need 1 <= ngram_min <= ngram_max")
        self.ngram_max = ngram_max
        self.ngram_min = ngram_min

    def propose(self, context: list[int], k: int) -> list[int]:
        n_ctx = len(context)
        for n in range(min(self.ngram_max, n_ctx - 1), self.ngram_min - 1,
                       -1):
            pat = context[-n:]
            # most recent earlier occurrence wins (locality: recent
            # continuations predict the immediate future best)
            for s in range(n_ctx - n - 1, -1, -1):
                if context[s:s + n] == pat:
                    cont = context[s + n:s + n + k]
                    if cont:
                        # pad short continuations by repeating the tail:
                        # extra columns are verified like any other draft
                        while len(cont) < k:
                            cont.append(cont[-1])
                        return cont
        # no suffix recurs: still propose SOMETHING — repeating the last
        # token is free to verify and right surprisingly often (runs of
        # pad/eos/whitespace), and never wrong in a way that costs
        # correctness
        return [context[-1]] * k if context else [0] * k


class DraftModelProposer:
    """Drafts from a small model's greedy continuation.

    Uses ``infer.generate`` over a FIXED context window (left-padded by
    repeating the first token) so the draft forward compiles once per
    ``(window, k)`` and is reused for every row and request. The draft
    model's quality only moves the acceptance rate — never the output
    (the verify rule is exact).
    """

    def __init__(self, model, params, window: int = 32):
        self.model = model
        self.params = params
        self.window = int(window)
        self._gen = None
        self._gen_k = None

    def propose(self, context: list[int], k: int) -> list[int]:
        from distributed_compute_pytorch_tpu import infer
        ctx = list(context[-self.window:])
        if not ctx:
            return [0] * k
        pad = self.window - len(ctx)
        ctx = [ctx[0]] * pad + ctx
        if self._gen is None or self._gen_k != k:
            self._gen = infer.make_generate_fn(self.model, k)
            self._gen_k = k
        import jax
        import jax.numpy as jnp
        toks = self._gen(self.params, jnp.asarray([ctx], jnp.int32),
                         jax.random.key(0))
        return [int(t) for t in toks[0, self.window:self.window + k]]


def make_proposer(cfg: SpecConfig):
    """Resolve ``cfg.proposer`` to an object with ``propose(ctx, k)``."""
    if cfg.proposer == "ngram":
        return NGramProposer(cfg.ngram_max, cfg.ngram_min)
    if cfg.proposer == "draft":
        if cfg.draft_model is None or cfg.draft_params is None:
            raise ValueError(
                "proposer='draft' needs draft_model and draft_params")
        return DraftModelProposer(cfg.draft_model, cfg.draft_params,
                                  cfg.draft_window)
    if hasattr(cfg.proposer, "propose"):
        return cfg.proposer
    raise ValueError(f"unknown proposer {cfg.proposer!r}")
