"""Draft sources for batched speculative serving (``serving.ContinuousBatcher``).

Speculative decoding splits a decode step into PROPOSE (cheap, per-slot, k tokens)
and VERIFY (one fused target forward over ``[B, k+1]``, ``models.llama.forward_slots``).
This module owns the propose side: one small interface, three shipped implementations —

- :class:`NgramDrafter` — model-free prompt-lookup drafting (the "self-drafting" /
  prompt-lookup-decoding trick): propose the continuation of the longest recent n-gram
  match inside the request's own prompt + generated context. Zero extra programs, zero
  extra memory, CPU-trivial — this is what makes the whole speculative feature
  tier-1-testable without a second model. Acceptance is workload-dependent (great on
  extraction/repetition-heavy traffic, ~0 on incompressible text) but NEVER changes
  outputs: the verify step emits exactly what plain decode would.
- :class:`ModuleDrafter` — the target's OWN prediction module (DeepSeek's multi-token
  prediction, ``models/deepseek_v32.py``): no weights or cache of its own — the module's
  are the engine's — and a propose that exists only inside the fused decode scan, where
  the verify's hidden states are.
- :class:`ModelDrafter` — a real draft model (llama- or gpt-family config via
  ``models.common.cached_decode_family``; cross-family draft/target pairs work whenever
  the vocabularies match) with its own per-slot KV cache mirroring the engine's lane
  layout. Per engine step it runs k+1 cheap batched decode steps (k proposals + one
  coverage catch-up write) so its cache always covers exactly the slots the target
  wrote — acceptance bookkeeping is then a shared position advance, with no per-slot
  control flow on device.

The draft NEVER affects output tokens (greedy slots accept by exact token match;
sampled slots replay the target's own sampler or run the vectorized Leviathan
accept/reject — see ``docs/speculative_serving.md``), only how many target forwards a
sequence costs. A useless drafter degrades throughput toward ~1 token/step, not
correctness.
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp

from .models.common import cached_decode_family

__all__ = ["DraftSource", "NgramDrafter", "ModelDrafter", "ModuleDrafter",
           "ngram_propose_resident"]


class DraftSource:
    """Interface the serving engine drives (one instance per engine; ``bind`` first).

    Lifecycle: ``bind(engine)`` once at engine construction; ``admit(slot, prompt,
    plan)`` whenever a request enters a lane (``plan`` is the engine's
    ``_plan_prefill`` result — the draft must reproduce the SAME left-padded cache
    layout so engine decode positions index both caches); ``propose(lanes, pending,
    positions, k)`` once per spec step, BEFORE the engine's verify — so
    ``engine.positions`` still addresses the pre-verify layout; ``warm_programs``
    enumerates any compiled programs into the AOT cache for warmup manifests.

    Proposals must be DETERMINISTIC given the lane context: the engine builds the
    residual-mode draft distribution as a point mass on the proposal (a stochastic
    drafter would need to surface its q rows; neither shipped drafter samples).

    ``resident = True`` marks a drafter whose propose step has a device-resident
    counterpart the engine may run INSIDE the fused multi-round decode scan
    (``serving.spec_multi``) instead of calling :meth:`propose` on the host. The
    fused path never calls ``propose`` — losslessness (replay/greedy emissions
    do not depend on proposals) is what licenses the swap, so a resident device
    proposer need not match its host twin token-for-token, only be deterministic.
    """

    resident = False  # host-loop only unless a subclass opts in

    def bind(self, engine) -> None:  # noqa: B027 - optional hook
        pass

    def admit(self, slot: int, prompt: np.ndarray, plan) -> None:  # noqa: B027
        pass

    def propose(self, lanes: Sequence, pending: np.ndarray, positions: np.ndarray,
                k: int) -> np.ndarray:
        """→ proposals int32 [len(lanes), k]; rows of idle lanes (``lanes[i] is
        None``) are don't-care (the verify computes them, the engine ignores them)."""
        raise NotImplementedError

    def warm_programs(self, engine, max_new_tokens: int = 32) -> list:
        return []


class ModuleDrafter(DraftSource):
    """The model's own prediction module as the drafter: ``cfg.mtp_draft`` tokens a
    round from the module that the config carries (``models.deepseek._module_draft``).
    Each round the module runs over the rows the verify wrote, fed with the verify's
    hidden states and the ACCEPTED tokens, and leaves each lane's next draft in the
    engine's cache; prefill writes the module's prompt rows and the first draft. So
    everything happens on the device, inside the engine's fused scan
    (``serving.spec_module``), at every ``decode_steps``; there is no host propose.
    Needs the paged cache and replay acceptance (the residual test runs in the host
    loop, which has no hidden states)."""

    resident = True

    def bind(self, engine) -> None:
        k = getattr(engine.cfg, "mtp_draft", 0)
        if not k:
            raise ValueError(f"{type(engine.cfg).__name__} has no prediction module "
                             "(mtp_draft = 0): nothing to draft with")
        if engine.spec_k != k:
            raise ValueError(f"the prediction module drafts {k} token(s) a round; "
                             f"spec_k={engine.spec_k}")
        if not engine.paged or engine.spec_accept != "replay":
            raise ValueError("the prediction module drafts inside the fused scan over the "
                             "paged cache: page_size >= 1 and spec_accept='replay'")

    def propose(self, lanes, pending, positions, k):
        raise NotImplementedError("the prediction module drafts inside the fused decode "
                                  "scan only (it reads the verify's hidden states)")


class NgramDrafter(DraftSource):
    """Prompt-lookup self-drafting: the context IS the draft model.

    For each active lane, find the most recent earlier occurrence of the longest
    suffix n-gram (n down from ``max_ngram`` to 1) of ``prompt + generated`` and
    propose the tokens that followed it; when the copied continuation runs short,
    re-match against the hypothetically-extended context; when nothing matches,
    repeat the last token (a deterministic throwaway — the verify's correction
    token keeps decode moving at ≥1 token/step regardless).

    Entirely host-side numpy over contexts the engine already holds: no params, no
    cache, no compiled programs, works with prefix-cached engines — and makes
    speculative serving exercisable in CI on CPU.
    """

    resident = True  # device twin: ngram_propose_resident (zero extra programs)

    def __init__(self, max_ngram: int = 3):
        if max_ngram < 1:
            raise ValueError(f"max_ngram={max_ngram} must be >= 1")
        self.max_ngram = max_ngram

    def propose(self, lanes, pending, positions, k):
        out = np.zeros((len(lanes), k), np.int32)
        for i, req in enumerate(lanes):
            if req is None:
                continue
            ctx = np.concatenate(
                [np.asarray(req.prompt, np.int32),
                 np.asarray(req.tokens, np.int32)]
            )
            out[i] = self._propose_one(ctx, k)
        return out

    def _propose_one(self, ctx: np.ndarray, k: int) -> np.ndarray:
        out = np.empty((k,), np.int32)
        filled = 0
        while filled < k:
            cont = self._lookup(ctx, k - filled)
            if cont is None:
                out[filled:] = ctx[-1]  # deterministic fallback: repeat last token
                break
            take = min(len(cont), k - filled)
            out[filled:filled + take] = cont[:take]
            ctx = np.concatenate([ctx, cont[:take]])
            filled += take
        return out

    def _lookup(self, ctx: np.ndarray, want: int) -> Optional[np.ndarray]:
        """Continuation after the most recent earlier match of the longest suffix
        n-gram, or None. Longest n wins; among equal n the LATEST occurrence wins
        (recent repetition predicts the immediate future best). Vectorized window
        compare — this runs per active slot per decode step, so a Python scan here
        would bill host milliseconds against a sub-millisecond verify dispatch."""
        L = len(ctx)
        if L < 2:
            return None
        from numpy.lib.stride_tricks import sliding_window_view

        for n in range(min(self.max_ngram, L - 1), 0, -1):
            pat = ctx[L - n:]
            # Windows over ctx[:L-1]: starts 0..L-1-n, so the suffix itself (start
            # L-n) is never its own match.
            win = sliding_window_view(ctx[:L - 1], n)
            hits = np.flatnonzero((win == pat[None, :]).all(axis=1))
            if hits.size:
                h = int(hits[-1])
                cont = ctx[h + n:h + n + want]
                if cont.size:
                    return cont
        return None


def ngram_propose_resident(history: jax.Array, lengths: jax.Array, k: int,
                           max_ngram: int) -> jax.Array:
    """Device-resident prompt-lookup drafting: :class:`NgramDrafter`'s propose
    step as pure vectorized gathers, runnable INSIDE the fused decode scan
    (``serving.spec_multi``) with zero extra programs and zero host round-trips.

    ``history`` [B, S] int32 — each lane's prompt + generated tokens packed from
    column 0 (the scan body appends accepted emissions in-carry); ``lengths``
    [B] int32 — valid token count per lane; ``k``/``max_ngram`` static. Returns
    proposals [B, k] int32.

    Per lane: the longest suffix n-gram (n from ``max_ngram`` down to 1) is
    matched against every earlier window of ``history[:length-1]`` (the suffix
    never matches itself); the LATEST hit wins, and the k tokens following it
    are proposed, clamped at the context end (positions past the last valid
    token repeat it). No hit → repeat the last token. This is a deliberate
    simplification of the host drafter's re-match-on-exhaustion refill loop:
    emissions in replay/greedy acceptance do not depend on proposals, so the
    two proposers may disagree token-for-token without affecting output — only
    the accept rate. Deterministic given (history, lengths), as the DraftSource
    contract requires.
    """
    B, S = history.shape
    lengths = lengths.astype(jnp.int32)
    starts = jnp.arange(S, dtype=jnp.int32)[None, :]
    best_n = jnp.zeros((B,), jnp.int32)
    best_h = jnp.zeros((B,), jnp.int32)
    for n in range(max_ngram, 0, -1):
        # Suffix pattern: the last n valid tokens (clip keeps short lanes in
        # bounds; the validity mask below kills their matches anyway).
        pat_idx = jnp.clip(
            lengths[:, None] - n + jnp.arange(n, dtype=jnp.int32)[None, :], 0, S - 1
        )
        pat = jnp.take_along_axis(history, pat_idx, axis=1)
        match = jnp.ones((B, S), bool)
        for j in range(n):
            shifted = jnp.concatenate(
                [history[:, j:], jnp.zeros((B, j), history.dtype)], axis=1
            )
            match &= shifted == pat[:, j:j + 1]
        # Host semantics: windows over ctx[:L-1] with starts 0..L-1-n, so the
        # suffix itself is never its own match and n > L-1 finds nothing.
        valid = (starts + n <= lengths[:, None] - 1) & (lengths[:, None] - 1 >= n)
        h = jnp.max(jnp.where(match & valid, starts, -1), axis=1)
        take = (h >= 0) & (best_n == 0)  # largest n wins; latest start within n
        best_n = jnp.where(take, n, best_n)
        best_h = jnp.where(take, h, best_h)
    hit = best_n > 0
    src = jnp.where(hit, best_h + best_n, lengths - 1)
    step = hit.astype(jnp.int32)
    idx = src[:, None] + jnp.arange(k, dtype=jnp.int32)[None, :] * step[:, None]
    idx = jnp.clip(jnp.minimum(idx, lengths[:, None] - 1), 0, S - 1)
    return jnp.take_along_axis(history, idx, axis=1).astype(jnp.int32)


@partial(jax.jit, static_argnames=("cfg",), donate_argnums=(1,))
def _draft_decode_step(params, cache, tokens, positions, cfg):
    """One batched draft decode over every lane: (greedy proposals [B] int32, cache).
    The same per-slot ``forward_slots`` contract the engine's decode/verify use, so
    draft positions are exactly engine positions."""
    fam = cached_decode_family(cfg)
    logits, cache = fam.forward_slots(params, tokens[:, None], cache, positions, cfg)
    return jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32), cache


@partial(jax.jit, static_argnames=("cfg", "max_len"))
def _draft_prefill_jit(params, row, mask, cfg, max_len: int):
    """Fresh single-row draft prefill (no logits — the pending token comes from the
    TARGET's prefill; the draft only needs the K/V state)."""
    fam = cached_decode_family(cfg)
    cache = fam.init_cache(cfg, 1, max_len)
    _, cache = fam.forward_cached(
        params, row, cache, cfg, token_mask=mask, last_only=True
    )
    return cache


@partial(jax.jit, static_argnames=("cfg",), donate_argnums=(3,))
def _draft_chunk_jit(params, row, mask, cache, cfg):
    """Chunk-append continuation for long draft prompts (one shared executable)."""
    fam = cached_decode_family(cfg)
    _, cache = fam.forward_cached(
        params, row, cache, cfg, token_mask=mask, last_only=True
    )
    return cache


class ModelDrafter(DraftSource):
    """A small draft model with its own per-slot cache, lane-aligned with the engine.

    Layout invariant: the draft cache row for slot s holds EXACTLY the token positions
    the engine cache row holds (same left-padded prefill width from the engine's
    ``_plan_prefill``, same per-step advance), so ``engine.positions`` drives both —
    the drafter needs no position bookkeeping of its own, and acceptance/rewind is
    free (the next step's writes overwrite rejected-draft garbage; the causal mask
    hides it meanwhile, exactly as in the target cache).

    Per spec step this runs k+1 batched T=1 decode steps: k greedy proposals plus one
    catch-up step writing the last proposal, so draft coverage always equals target
    coverage (p .. p+k) with no full-acceptance special case. The catch-up forward's
    logits are discarded — one wasted draft step per round buys the absence of any
    per-slot device control flow.
    """

    def __init__(self, params: dict, cfg):
        self.params = params
        self.cfg = cfg
        cached_decode_family(cfg)  # raises early for families without decode
        self._engine = None
        self.cache = None
        self._decode_fn = _draft_decode_step
        self._prefill_fn = _draft_prefill_jit
        self._chunk_fn = _draft_chunk_jit

    def bind(self, engine) -> None:
        if self.cfg.vocab_size != engine.cfg.vocab_size:
            raise ValueError(
                f"draft vocab_size={self.cfg.vocab_size} != target "
                f"vocab_size={engine.cfg.vocab_size}: speculative acceptance needs "
                "one shared token space"
            )
        if engine.prefix_cache_size:
            raise ValueError(
                "ModelDrafter does not support prefix-cached engines (the registry's "
                "right-aligned layout has no draft-side counterpart); use NgramDrafter"
            )
        from .compile_cache import as_cached

        self._engine = engine
        fam = cached_decode_family(self.cfg)
        self.cache = fam.init_cache(self.cfg, engine.max_slots, engine.max_len)
        cc = engine.compile_cache
        self._decode_fn = as_cached(
            _draft_decode_step, cc, "serving.draft.decode", ("cfg",))
        self._prefill_fn = as_cached(
            _draft_prefill_jit, cc, "serving.draft.prefill", ("cfg", "max_len"))
        self._chunk_fn = as_cached(
            _draft_chunk_jit, cc, "serving.draft.prefill_chunk", ("cfg",))
        from .serving import _insert_row

        self._insert_fn = as_cached(
            _insert_row, cc, "serving.draft.insert_row", ("slot", "scan_layers"))

    def admit(self, slot: int, prompt: np.ndarray, plan) -> None:
        """Prefill ``prompt`` into draft lane ``slot`` with the ENGINE's padded
        layout (``plan`` = the engine's ``("bucket", width)`` / ``("chunk", total)``
        decision, replayed chunk-for-chunk so the program surface mirrors the
        engine's: one prefill per bucket width plus one shared chunk-append)."""
        mode, total = plan
        pad = total - len(prompt)
        row = np.zeros((1, total), np.int32)
        row[0, pad:] = prompt
        mask = np.zeros((1, total), bool)
        mask[0, pad:] = True
        if mode == "bucket":
            cache = self._prefill_fn(
                self.params, jnp.asarray(row), jnp.asarray(mask),
                cfg=self.cfg, max_len=self._engine.max_len,
            )
        else:
            bucket = self._engine.prompt_bucket
            cache = self._prefill_fn(
                self.params, jnp.asarray(row[:, :bucket]),
                jnp.asarray(mask[:, :bucket]),
                cfg=self.cfg, max_len=self._engine.max_len,
            )
            for c in range(1, total // bucket):
                sl = slice(c * bucket, (c + 1) * bucket)
                cache = self._chunk_fn(
                    self.params, jnp.asarray(row[:, sl]), jnp.asarray(mask[:, sl]),
                    cache, cfg=self.cfg,
                )
        # graftlint: disable=recompile-hazard(slot indexes a compile-time cache row; at most max_slots variants, admission-time only)
        self.cache = self._insert_fn(self.cache, cache, slot=slot, scan_layers=self.cfg.scan_layers)

    def propose(self, lanes, pending, positions, k):
        B = len(lanes)
        proposals = np.zeros((B, k), np.int32)
        tok = np.asarray(pending, np.int32)
        pos = np.asarray(positions, np.int32).copy()
        for j in range(k + 1):
            greedy, self.cache = self._decode_fn(
                self.params, self.cache, jnp.asarray(tok), jnp.asarray(pos),
                cfg=self.cfg,
            )
            if j < k:
                tok = np.asarray(greedy)
                proposals[:, j] = tok
            # else: catch-up step — wrote proposals[:, -1]; its output is discarded
            pos += 1  # per-row writes past max_len drop out of bounds (never read)
        return proposals

    def warm_programs(self, engine, max_new_tokens: int = 32) -> list:
        """Mirror ``ContinuousBatcher.warm_programs`` for the draft surface: decode,
        one prefill per reachable bucket width (+ the chunked pair), per-slot row
        inserts. Returns warmup-manifest entries; empty without an AOT cache."""
        if engine.compile_cache is None:
            return []
        fam = cached_decode_family(self.cfg)
        entries = []
        lanes = jnp.zeros((engine.max_slots,), jnp.int32)
        entries.append(self._decode_fn.warm(
            self.params, self.cache, lanes, lanes, cfg=self.cfg
        ))
        widths = []
        if engine.prompt_buckets is not None:
            widths = [b for b in engine.prompt_buckets
                      if b + max_new_tokens <= engine.max_len]
        for width in widths:
            row = jnp.zeros((1, width), jnp.int32)
            mask = jnp.zeros((1, width), bool)
            entries.append(self._prefill_fn.warm(
                self.params, row, mask, cfg=self.cfg, max_len=engine.max_len
            ))
        row_cache = fam.init_cache(self.cfg, 1, engine.max_len)
        if engine.prompt_bucket + max_new_tokens <= engine.max_len:
            row = jnp.zeros((1, engine.prompt_bucket), jnp.int32)
            mask = jnp.zeros((1, engine.prompt_bucket), bool)
            entries.append(self._prefill_fn.warm(
                self.params, row, mask, cfg=self.cfg, max_len=engine.max_len
            ))
            entries.append(self._chunk_fn.warm(
                self.params, row, mask, row_cache, cfg=self.cfg
            ))
        for slot in range(engine.max_slots):
            entries.append(self._insert_fn.warm(
                self.cache, row_cache, slot=slot, scan_layers=self.cfg.scan_layers
            ))
        return entries
