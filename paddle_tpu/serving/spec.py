"""Speculative decoding proposers: draft cheap, verify in one pass.

The engine's decode tick emits exactly one token per slot per
dispatch, so tokens/sec is dispatch-bound long before the hardware is.
Speculative decoding breaks the one-dispatch-one-token coupling: a
PROPOSER guesses ``k`` draft tokens per slot from information the
engine already has, and ONE windowed target-model dispatch
(``GPTModel._compiled_fused_spec_verify_fn``) scores all k+1
positions —
the engine then accepts the longest prefix where the target's argmax
equals the draft, plus the one "bonus" token the target produced at
the first mismatch.  Greedy acceptance is LOSSLESS: every emitted
token is the target model's own pick given its true prefix, so
drafts only decide how many tokens each dispatch yields (1..k+1) and
speculative greedy outputs are token-identical to the non-speculative
engine (tests/test_serving.py asserts it).  Wrong drafts cost nothing
beyond the fixed window compute — the engine's write cursor simply
does not advance over rejected lanes.

The verify dispatch ALSO picks each lane's token and counts the
accepted prefix on device, so a verify tick downloads picks
``[B, W]`` + accept counts ``[B]``, never the full ``[B, W, V]``
logits.  Proposers only ever see the host-side token history.

Two proposers ship here:

* ``PromptLookupProposer`` — n-gram match against the slot's own
  prompt + emitted history (prompt-lookup decoding): zero extra
  model, pure numpy on the host, ideal for the summarization / code /
  chat regime where output n-grams repeat.  This is the production
  CPU-side default.
* ``DraftModelProposer`` — a smaller GPT drafts autoregressively.
  The draft model must share the target's tokenizer/vocabulary (the
  engine cross-checks ``vocab_size`` at construction).  Reference
  implementation: it re-runs the history through ``generate()`` per
  proposal, which is simple and correct but O(history) per tick —
  production drafting would keep per-slot draft K/V hot.

A proposer is a plain strategy object — stateless across requests —
so one instance can serve every slot of an engine.

Robustness contract: a proposer that RAISES mid-draft degrades, it
does not kill the tick — the engine catches the exception, counts
``serving.proposer_failures``, and runs the verify window with zero
drafts (plain one-token decode speed) so no in-flight request is
evicted over a drafting hiccup.  The deterministic chaos harness
(serving/faults.py, ``spec_draft`` site) exercises exactly this path.
"""
from __future__ import annotations

import numpy as np


class Proposer:
    """Draft-token source for speculative decoding.

    ``propose(history, k)`` receives one slot's full token history
    (prompt + everything emitted so far, the last entry being the
    token whose K/V the next dispatch will write) and returns up to
    ``k`` int draft tokens predicted to FOLLOW it.  Returning fewer
    than ``k`` (or none) is always safe: the engine pads the window by
    repeating the current token, but pad lanes are pure FILLER for the
    static window shape — they are never counted as proposed lanes,
    can never be accepted, and their garbage K/V is rewritten before
    any query can see it, so a shortfall costs nothing and corrupts no
    metric.

    ``vocab_size`` (optional): when not None, the engine asserts it
    matches the target model's vocabulary at construction — a draft
    from a different tokenizer would never match and only burn the
    window compute.
    """

    vocab_size = None

    def propose(self, history, k):
        raise NotImplementedError


class PromptLookupProposer(Proposer):
    """Prompt-lookup drafting: find the most recent earlier occurrence
    of the history's trailing ``ngram`` tokens and propose the tokens
    that followed it.  The host-side twin of
    ``generate(compiled='speculative')``'s on-device draft_row —
    free of any draft model, which keeps the whole speculative
    subsystem runnable on the CPU tier-1 suite."""

    def __init__(self, ngram=3, max_window=1024):
        ngram = int(ngram)
        if ngram < 1:
            raise ValueError(f"ngram must be >= 1, got {ngram}")
        max_window = int(max_window)
        if max_window < ngram + 1:
            raise ValueError(
                f"max_window ({max_window}) must exceed ngram "
                f"({ngram}) or no match could ever land")
        self.ngram = ngram
        # bound the host-side scan: propose() runs per slot per
        # decode tick, and hits are overwhelmingly recent — a fixed
        # lookback keeps the drafting cost O(max_window), independent
        # of how long the sequence grows
        self.max_window = max_window

    def propose(self, history, k):
        h = np.asarray(history, np.int64).reshape(-1)[-self.max_window:]
        n = self.ngram
        if len(h) < n + 1:
            return h[:0]
        pat = h[-n:]
        # candidate windows must end strictly before the history's
        # last position (the trailing pattern itself never matches)
        wins = np.lib.stride_tricks.sliding_window_view(h[:-1], n)
        hits = np.nonzero((wins == pat[None, :]).all(axis=1))[0]
        if len(hits) == 0:
            return h[:0]
        j = int(hits[-1])          # most recent occurrence wins
        return h[j + n:j + n + k]


class DraftModelProposer(Proposer):
    """Draft with a smaller GPT sharing the target's tokenizer/vocab:
    greedy-decode ``k`` continuation tokens of the slot's history.

    The draft runs EAGER (uncompiled) on purpose: history length grows
    every tick, and a compiled prefill per distinct length would
    thrash the program cache; eager drafting is correct at any length
    with zero compiles.  Histories longer than the draft model's
    position table are tail-truncated — a draft from a clipped context
    is still just a guess, and verification keeps it honest.

    ``weight_dtype="int8"`` relayouts the draft's transformer blocks
    through weight-only int8 (serving/quant.py) before first use —
    drafts are pure guesses that verification keeps honest, so the
    draft model is the SAFEST place to quantize aggressively: a
    rounding-flipped draft token costs at most one accepted lane,
    never output correctness."""

    def __init__(self, draft_model, weight_dtype=None):
        if weight_dtype not in (None, "int8"):
            raise ValueError(
                f"DraftModelProposer: unsupported weight_dtype "
                f"{weight_dtype!r} (only 'int8')")
        if getattr(draft_model, "scan_layers", False):
            draft_model = draft_model._sync_decode_twin()
        draft_model.eval()
        if weight_dtype == "int8":
            from .quant import relayout_weights_int8
            relayout_weights_int8(draft_model)
        self.model = draft_model
        sspec = draft_model.serving_spec()
        self.vocab_size = sspec.vocab_size
        self._max_position = sspec.max_positions

    def propose(self, history, k):
        h = np.asarray(history, np.int32).reshape(-1)
        keep = self._max_position - int(k)
        if keep < 1:
            return h[:0]
        if len(h) > keep:
            h = h[-keep:]
        out = self.model.generate(h[None, :], max_new_tokens=int(k))
        return np.asarray(out.numpy()[0][len(h):], np.int32)
