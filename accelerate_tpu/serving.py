"""Continuous-batching inference engine (slot-based KV cache, per-slot positions).

The reference's generation story is hook-dispatched ``model.generate`` on one request at a
time (``benchmarks/big_model_inference``); throughput-oriented serving — admitting new
requests into a running decode batch the moment a slot frees — has no reference
counterpart. On TPU it is the natural shape: ONE compiled decode program advances every
active slot one token per call, so arrival/completion churn never recompiles anything.

Design (static shapes throughout):
- ``max_slots`` decode lanes share one cache pytree ``[max_slots, max_len, ...]``; each
  slot has its own write position (``positions`` [B] int32) — unlike the training/prefill
  cache (``models/llama.init_cache``) whose single scalar index advances all rows together.
- Prefill runs the existing single-row compiled path (``llama.forward_cached`` with the
  prompt left-padded to a bucketed width — one executable per bucket) and the resulting
  cache ROW is scattered into the engine cache at the freed slot (one compiled insert).
- Decode is ONE loop, the scan: ``_decode_multi_step`` runs ``decode_steps = N >= 1``
  steps as one dispatched ``lax.scan`` (sampling, EOS and budget masking on the device;
  one token per slot per call at the default N = 1) — or, with ``spec_k > 0``,
  the batched SPECULATIVE step: a ``spec_decode.DraftSource`` proposes k tokens per
  active slot, ONE fused target forward over ``[B, k+1]`` (``_spec_verify_step``, the
  per-slot ``llama.forward_slots``) verifies them, and each slot accepts a
  variable-length prefix (1..k+1 tokens per step). Greedy slots accept by exact token
  match against the fused argmax; sampled slots either REPLAY the target's own sampler
  over the shared filtered-softmax path with the request's per-step key schedule
  (default — emitted tokens are then BITWISE what ``spec_k=0`` would have drawn) or run
  the vectorized Leviathan accept/reject (``spec_accept="residual"``,
  ``generation.speculative_accept_batch`` — lossless in distribution, higher
  acceptance). Rejected drafts leave garbage K/V above each slot's rewound position;
  the per-slot ``positions``/``valid`` causal masking makes it unreachable until the
  next step's writes overwrite it. The draft NEVER changes outputs, only how many
  target forwards a sequence costs (``stats()["tokens_per_step"]``).

Paged KV cache (``page_size > 0``, docs/paged_kv.md): the dense per-lane rows are replaced
by a shared pool of fixed-size pages + per-lane block tables (``paged_kv.BlockManager`` on
the host, ``models.*.forward_slots_paged`` + the Pallas ``ops/paged_attention`` kernel on
the device) — KV memory then costs what admitted requests ACTUALLY occupy, admission
defers (FIFO) on pool pressure instead of overcommitting, and the prefix cache becomes
refcounted page lists with copy-on-write at divergence instead of whole row-cache
snapshots. ``kv_demand`` prices requests page-granularly for the gateway.

Disaggregated roles (``role="prefill"|"decode"``, docs/disaggregated_serving.md): a
prefill-role engine admits + prefills on TRANSIENT lanes and exports each request's KV
as a refcounted page-list :class:`KVHandoff` instead of decoding; a decode-role engine
never prefills — it adopts transferred handoffs (read-only full pages, COW at the write
boundary — the prefix-cache adoption path generalized across engines) and runs
decode-only lanes. ``serving_gateway.disagg.DisaggRouter`` routes between them.

Correctness contract (tested): with requests submitted at staggered times, every finished
sequence equals ``llama.generate``'s greedy output for that prompt alone (for MoE configs,
for that prompt left-padded to the engine's bucket width — capacity-pooled MoE routing is
shape-sensitive, so parity is defined at matching padded shapes) — with ``spec_k > 0``
token-for-token identical to ``spec_k = 0``, greedy and sampled alike
(docs/speculative_serving.md), and with ``page_size > 0`` token-for-token identical to
the dense layout (tests/test_serving_paged.py).
"""

from __future__ import annotations

import dataclasses
import sys
import time
from collections import OrderedDict, deque
from functools import partial
from typing import Callable, Optional

import numpy as np
import jax
import jax.numpy as jnp

from .compile_cache import AotCache, as_cached, pick_bucket
from .generation import (
    GenerationConfig,
    filtered_logits,
    sampling_core,
    sampling_core_dyn_k,
    speculative_accept_batch,
)
from .paged_kv import BlockManager, KVBudgetError, pages_for
from .resilience.faults import EngineCrashed, StepWatchdog
from .telemetry.compile_monitor import compile_label
from .telemetry.schemas import (
    FAULT_SCHEMA,
    RECOVERY_SCHEMA,
    SERVING_KV_SCHEMA,
    SERVING_SCHEMA,
    SERVING_SPEC_SCHEMA,
    SERVING_THROUGHPUT_SCHEMA,
)
from .telemetry.slo import latency_summary
from .telemetry.tracing import PHASES, EnginePhase, phase
from .utils.dataclasses import CompileCacheConfig

__all__ = ["ContinuousBatcher", "KVBudgetError", "KVHandoff", "Request",
           "normalize_submit"]

#: Replica roles (docs/disaggregated_serving.md): ``mixed`` is the historical
#: engine (prefill AND decode on the same lanes); ``prefill`` chunk-prefills
#: admitted requests and EXPORTS their KV as page-list handoffs instead of
#: decoding (lanes are transient — freed the same step they prefill); ``decode``
#: never prefills — work arrives as handoffs whose pages it adopts read-only
#: (COW at the write boundary, the prefix-cache adoption semantics generalized
#: across engines) and runs decode-only lanes at high occupancy.
ENGINE_ROLES = ("mixed", "prefill", "decode")


def _model(cfg):
    """The model module the engine runs: the one that defines the config's class
    (``models/llama.py`` for a ``LlamaConfig``, ``models/deepseek.py`` for a
    ``DeepseekConfig``). The ONE seam between the engine and a decoder: the jitted
    programs below call it at trace time (``cfg`` is static), ``__init__`` resolves it
    once and refuses a module that lacks what the requested paths call."""
    return sys.modules[type(cfg).__module__]


#: What each engine path calls on the model module (``__init__`` checks them by name).
_PATH_CALLS = {
    "prefill": ("init_cache", "forward_cached"),
    "prefix_cache": ("forward_cached_logits",),
    "decode": ("forward_slots_multi",),
    # the one-token body of the scan over dense rows
    "dense rows (page_size=0)": ("forward_slots",),
    "paged": ("init_paged_cache", "paged_walk_shape"),
    # the host loop's verify, dense and paged, and the fused rounds
    "spec_k": ("forward_slots", "forward_slots_paged", "forward_slots_spec_multi"),
}


@partial(jax.jit, static_argnames=("top_k",))
def _draw(logits_row, key, temperature, top_p, top_k: int):
    """One sampled draw over ``generation.sampling_core`` — the SAME code path
    ``sample_logits`` uses, so batcher output can never drift from generate(). Only
    ``top_k`` is static (it shapes lax.top_k); temperature/top_p trace as scalars so
    arbitrary user values share one executable."""
    return sampling_core(logits_row[None], key, temperature, top_p, top_k)[0]


def normalize_submit(prompt, max_new_tokens=None, eos_token_id=None, gen=None,
                     rng=None):
    """Validate and normalize one submit() call's request arguments →
    ``(prompt int32 [L], GenerationConfig)``.

    The ONE copy of the argument contract shared by ``ContinuousBatcher.submit``
    and the gateway's admission path (``serving_gateway``), so the two can never
    drift: either ``max_new_tokens``/``eos_token_id`` or a full ``gen`` (not
    both), rng only with temperature sampling, an integral positive generation
    budget (a fractional/bool budget would slip past range checks, overrun its
    validated cache window and silently truncate at the decode position clamp),
    and a non-empty prompt. All violations raise — they are caller bugs, unlike
    engine-geometry overflow which each caller handles itself
    (``_plan_prefill``)."""
    prompt = np.asarray(prompt, np.int32).ravel()
    if prompt.size == 0:
        raise ValueError("empty prompt: prefill needs at least one token")
    if gen is not None and (max_new_tokens is not None or eos_token_id is not None):
        raise ValueError(
            "pass either gen= or max_new_tokens/eos_token_id, not both"
        )
    if rng is not None and (gen is None or gen.temperature <= 0.0):
        raise ValueError(
            "rng was given but the request is greedy (no gen / temperature<=0): the "
            "key would be silently ignored — pass gen=GenerationConfig(temperature=...)"
        )
    if gen is None:
        gen = GenerationConfig(
            max_new_tokens=32 if max_new_tokens is None else max_new_tokens,
            temperature=0.0, eos_token_id=eos_token_id,
        )
    mnt = gen.max_new_tokens
    if isinstance(mnt, bool) or not isinstance(mnt, (int, np.integer)):
        raise TypeError(
            f"max_new_tokens must be an int, got {type(mnt).__name__} ({mnt!r}): "
            "a fractional budget would overrun the validated cache window and "
            "silently truncate at the slot boundary"
        )
    if mnt < 1:
        raise ValueError(
            f"max_new_tokens={mnt} must be >= 1 (the prefill emits the first token)"
        )
    if gen.temperature > 0.0 and rng is None:
        raise ValueError("temperature sampling needs a per-request rng key")
    return prompt, gen


@dataclasses.dataclass
class _PagedPrefix:
    """One paged prefix-registry entry: the physical pages covering the registered
    prefix (full shared pages, plus — when the boundary cuts a page — an immutable
    registry-owned copy of the partial page). The prefix length itself is derived
    from the registry key at lookup; the entry holds one refcount on every id in
    ``pages``, and eviction releases them."""
    pages: np.ndarray  # [n] int32 physical page ids


@dataclasses.dataclass
class KVHandoff:
    """One prefilled request's transferable KV state (docs/disaggregated_serving.md).

    Built by a prefill-role engine the step a request's prefill lands: the lane
    is freed immediately, but its pages covering the prefill context
    ``[0, prefill_len)`` move INTO this record (``BlockManager.detach_slot`` —
    refcounts conserved, the handoff now owns them). A decode-role engine adopts
    them via :meth:`ContinuousBatcher.adopt_handoff` after the page payload
    crosses engines through ``ops.collectives.kv_page_transfer``. The record
    stays alive (pages refcounted on the SOURCE pool) until the request reaches
    a terminal state, so a dead decode replica can re-adopt from the
    still-refcounted pages instead of re-prefilling; the router releases it via
    :meth:`ContinuousBatcher.release_handoff`."""

    uid: int                      # source-engine request uid (router bookkeeping)
    prompt: np.ndarray
    gen: GenerationConfig
    rng: Optional[jax.Array]      # per-request key schedule (sampled requests)
    tokens: list                  # already emitted (the prefill's first token)
    pages: np.ndarray             # [n] int32 SOURCE-pool page ids covering the context
    prefill_len: int              # next write position (= adopted context length)
    valid_range: tuple            # (v0, v1): positions [v0, v1) hold real tokens


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray
    gen: GenerationConfig
    rng: Optional[jax.Array] = None      # per-request key schedule (None → greedy-determined)
    #: Streaming hook: called as ``on_token(token_id)`` the moment each token is
    #: appended (prefill's first token included) — tokens arrive in exactly the order
    #: ``tokens`` records them, so a streamed transcript equals the final list.
    on_token: Optional[Callable[[int], None]] = None
    # filled by the engine
    tokens: list = dataclasses.field(default_factory=list)
    done: bool = False
    enqueued_at: float = 0.0             # time.monotonic() at submit (queue-wait metrics)
    #: Machine-readable failure reason when the fault boundary quarantined this
    #: request (``step_fault:<kind>`` / ``prefill_fault:<kind>`` /
    #: ``recovery_unservable:<detail>``); None = never failed. A failed request
    #: is ``done`` (terminal) with the tokens it got before the fault.
    failed: Optional[str] = None
    #: Times this request was re-admitted by crash recovery (each re-admission
    #: replays prefill over prompt + already-emitted tokens).
    recoveries: int = 0
    #: Recovery context: prompt + already-emitted tokens, set when a rebuild
    #: requeues this request; the next admission prefills THIS instead of the
    #: prompt (and clears it), so generation resumes at the exact next token.
    _recover_ctx: Optional[np.ndarray] = dataclasses.field(default=None, repr=False)

    def __post_init__(self):
        if self.rng is not None and self.gen.temperature > 0.0:
            # Exactly generate_loop's schedule (generation.py): split(rng, max_new_tokens),
            # draw i consumes key i — so a sampled request reproduces generate() exactly.
            self._step_keys = jax.random.split(self.rng, self.gen.max_new_tokens)
        else:
            self._step_keys = None

    def _sample(self, logits_row):
        """Draw this request's next token from an ON-DEVICE logits row: the prefill's
        first token of a sampled request (greedy ones take the fused argmax; every later
        token is drawn inside the decode program). Only the drawn int crosses to host."""
        if self.gen.temperature <= 0.0:
            return int(np.asarray(jnp.argmax(logits_row)))
        key = self._step_keys[len(self.tokens)]
        return int(np.asarray(_draw(
            logits_row, key, self.gen.temperature, self.gen.top_p, top_k=self.gen.top_k
        )))


@partial(jax.jit, static_argnames=("cfg",), donate_argnums=(1,))
def _spec_verify_step(params, cache, tokens, positions, cfg):
    """Batched speculative VERIFY: score ``tokens`` [B, k+1] (each lane's pending token
    + k draft proposals) in ONE fused target forward → (greedy [B, k+1] int32, logits
    [B, k+1, V] fp32, new cache).

    Column j of the output is the target's next-token distribution AFTER input j given
    that lane's accepted context — exactly what j sequential one-token decode steps
    would have produced (same rope positions, same masking, dense MoE routing), which
    is what makes prefix acceptance lossless. Rejected proposals leave garbage K/V
    above the lane's rewound position; the causal mask hides it until the next step's
    writes land on those very slots."""
    logits, cache = _model(cfg).forward_slots(params, tokens, cache, positions, cfg)
    return jnp.argmax(logits, axis=-1).astype(jnp.int32), logits, cache


@partial(jax.jit, static_argnames=("top_k",))
def _replay_draws(logits_rows, keys, temperature, top_p, top_k: int):
    """Replay the plain sampler at every verify position of ONE sampled slot in one
    dispatch: ``logits_rows`` [T, V] + per-emission keys [T] → the tokens [T] that
    ``spec_k = 0`` decode would draw at each position (``generation.sampling_core`` —
    the same filtered-softmax path, so replay-mode speculative output is BITWISE the
    plain sampled output). Only the drawn int32 vector crosses to host."""
    return jax.vmap(
        lambda row, key: sampling_core(row[None], key, temperature, top_p, top_k)[0]
    )(logits_rows, keys)


@partial(jax.jit, static_argnames=("top_k",))
def _spec_residual_jit(logits_rows, drafts, keys, temperature, top_p, top_k: int):
    """Leviathan accept/reject for ONE sampled slot's round, fully on device →
    (emitted [k+1] int32, count int32): ``emitted[:count]`` = accepted draft prefix +
    the correction (residual re-draw at the first rejection) or the bonus draw on full
    acceptance.

    Target probs come from the SAME ``filtered_logits`` path ``generate()`` samples
    from; all k accept tests run at once through the vectorized
    ``speculative_accept_batch`` (the deterministic drafter's q is a point mass on its
    proposal, under which min(1, p/q) reduces to accept-with-prob p(draft) and the
    residual to p minus the draft's mass, renormalized). Tests after the first
    rejection are computed and discarded — their keys are never consumed by a retained
    draw, so the sequential accept-chain distribution (exactly the target's own
    sampling distribution, per ``generation.speculative_accept``) is unchanged."""
    k = drafts.shape[0]
    p = jax.nn.softmax(filtered_logits(logits_rows, temperature, top_p, top_k), axis=-1)
    q = jax.nn.one_hot(drafts, logits_rows.shape[-1], dtype=jnp.float32)
    acc, toks = speculative_accept_batch(p[:-1], q, drafts, keys[:-1])
    n = jnp.sum(jnp.cumprod(acc.astype(jnp.int32)))  # leading accepts
    bonus = jax.random.categorical(
        keys[-1], jnp.log(jnp.maximum(p[-1], 1e-30))
    ).astype(jnp.int32)
    correction = jnp.where(n == k, bonus, toks[jnp.minimum(n, k - 1)])
    emitted = jnp.where(
        jnp.arange(k + 1) < n, jnp.concatenate([drafts, jnp.zeros((1,), jnp.int32)]), 0
    )
    emitted = emitted.at[n].set(correction)
    return emitted, n + 1


@partial(jax.jit, static_argnames=("slot", "scan_layers"), donate_argnums=(0,))
def _insert_row(cache, row_cache, slot: int, scan_layers: bool):
    """Scatter a single-row prefill cache into engine cache slot ``slot``.

    Layer kv leaves are [B, C, K, hd] per layer (lists), or [L, B, C, K, hd] stacked when
    ``scan_layers`` — the batch axis moves to position 1, so the slot index must too.
    """
    if scan_layers:
        put = lambda full, row: full.at[:, slot].set(row[:, 0])  # noqa: E731
    else:
        put = lambda full, row: full.at[slot].set(row[0])  # noqa: E731

    return {
        "layers": jax.tree_util.tree_map(put, cache["layers"], row_cache["layers"]),
        "valid": cache["valid"].at[slot].set(row_cache["valid"][0]),
        "index": cache["index"],
    }


@partial(jax.jit, static_argnames=("cfg", "page_size"), donate_argnums=(1,))
def _spec_verify_step_paged(params, cache, tables, tokens, positions, cfg,
                            page_size: int):
    """:func:`_spec_verify_step` over the paged cache — ONE fused [B, k+1] verify
    whose K/V lives in pool pages. Draft writes past a lane's allocated pages route
    through the SENTINEL table entry and drop (the paged spelling of the dense
    path's out-of-bounds-scatter contract for non-load-bearing draft tails)."""
    logits, cache = _model(cfg).forward_slots_paged(
        params, tokens, cache, tables, positions, cfg, page_size
    )
    return jnp.argmax(logits, axis=-1).astype(jnp.int32), logits, cache


def _multi_select(sample: bool, keys, temps, top_ps, top_ks):
    """(select_token, xs) for the multi-step scan body.

    ``sample=False`` (every live lane greedy) is the fused argmax. ``sample=True``
    folds the host-built per-lane EMISSION-INDEXED key windows in as scan xs
    (``keys`` [B, N, 2] → [N, B, 2]: step j consumes each lane's key for emission
    ``len(tokens)+j``, exactly the key :meth:`Request._sample` hands ``_draw`` for
    the prefill's first token) and draws every sampled lane via the vmapped
    ``sampling_core_dyn_k`` — the same row[None]-shaped draw ``_draw`` /
    ``_replay_draws`` dispatch, so sampled output is bitwise ``generate()``'s at
    every N. Greedy lanes ride along with a safe temperature of 1.0 and their draw
    DISCARDED in favor of the argmax (a divide-by-zero guard, not a semantic: the
    where picks the argmax)."""
    if not sample:
        return (lambda logits, _: jnp.argmax(logits, axis=-1).astype(jnp.int32)), None
    safe_temps = jnp.where(temps > 0.0, temps, 1.0)

    def select_token(logits, step_keys):
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        drawn = jax.vmap(
            lambda row, key, t, p, k: sampling_core_dyn_k(row[None], key, t, p, k)[0]
        )(logits, step_keys, safe_temps, top_ps, top_ks)
        return jnp.where(temps > 0.0, drawn, greedy)

    return select_token, jnp.moveaxis(keys, 1, 0)


@partial(jax.jit, static_argnames=("cfg", "n_steps", "sample"), donate_argnums=(1,))
def _decode_multi_step(params, cache, tokens, positions, active, budgets, eos_ids,
                       keys, temps, top_ps, top_ks, cfg, n_steps: int, sample: bool):
    """``n_steps`` decode steps as ONE dispatched program (tok_buf [N, B] int32,
    counts [B] int32, new cache) — the device-resident super-step
    (docs/multistep_decode.md). Sampling, EOS/budget masking and lane freezing
    all happen in-scan (``llama.forward_slots_multi``); the host drains the
    token buffer once per super-step instead of once per token."""
    select_token, xs = _multi_select(sample, keys, temps, top_ps, top_ks)
    cache, tok_buf, counts, *model_counts = _model(cfg).forward_slots_multi(
        params, cache, tokens, positions, active, budgets, eos_ids,
        select_token, xs, n_steps, cfg,
    )
    return (tok_buf, counts, cache, *model_counts)


@partial(jax.jit, static_argnames=("cfg", "n_steps", "sample", "page_size"),
         donate_argnums=(1,))
def _decode_multi_step_paged(params, cache, tables, tokens, positions, active,
                             budgets, eos_ids, keys, temps, top_ps, top_ks, cfg,
                             n_steps: int, sample: bool, page_size: int):
    """:func:`_decode_multi_step` over the PAGED cache: every scan step's K/V
    writes route through the DEVICE-RESIDENT block tables uploaded once per
    super-step (admission reserves each lane's full residual budget up front —
    ``BlockManager.admit`` — so no table entry can appear mid-scan; frozen/past-
    budget positions route to the sentinel and drop)."""
    select_token, xs = _multi_select(sample, keys, temps, top_ps, top_ks)
    cache, tok_buf, counts, *model_counts = _model(cfg).forward_slots_multi(
        params, cache, tokens, positions, active, budgets, eos_ids,
        select_token, xs, n_steps, cfg, tables=tables, page_size=page_size,
    )
    return (tok_buf, counts, cache, *model_counts)


def _spec_multi_select(sample: bool, temps, top_ps, top_ks):
    """``select_ref(logits [B, k+1, V], keys [B, k+1, 2]) -> ref [B, k+1]`` for
    the fused speculative scan body: the reference tokens the accept walk
    compares proposals against at every verify position.

    ``sample=False`` is the fused argmax — the exact op ``_spec_verify_step``
    returns. ``sample=True`` draws every (lane, position) via the same
    row[None]-shaped vmapped ``sampling_core_dyn_k`` the multi-step scan uses
    (bitwise ``sampling_core``, hence bitwise ``_replay_draws``' per-position
    replay); the keys arrive CURSOR-indexed from the scan body, so position j
    consumes lane b's key for emission ``count[b]+j`` — exactly the key the
    host loop's ``_replay_round`` window would hand it. Greedy lanes ride along
    with a safe temperature and their draw discarded in favor of the argmax."""
    if not sample:
        return lambda logits, _: jnp.argmax(logits, axis=-1).astype(jnp.int32)
    safe_temps = jnp.where(temps > 0.0, temps, 1.0)

    def select_ref(logits, keys):
        B, T, V = logits.shape
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        drawn = jax.vmap(
            lambda row, key, t, p, k: sampling_core_dyn_k(row[None], key, t, p, k)[0]
        )(
            logits.reshape(B * T, V), keys.reshape(B * T, 2),
            jnp.repeat(safe_temps, T), jnp.repeat(top_ps, T),
            jnp.repeat(top_ks, T),
        ).reshape(B, T)
        return jnp.where(temps[:, None] > 0.0, drawn, greedy)

    return select_ref


@partial(jax.jit,
         static_argnames=("cfg", "n_steps", "spec_k", "max_ngram", "sample"),
         donate_argnums=(1,))
def _spec_multi_step(params, cache, tokens, positions, active, budgets, eos_ids,
                     key_tab, temps, top_ps, top_ks, history, hist_lens, cfg,
                     n_steps: int, spec_k: int, max_ngram: int, sample: bool):
    """``n_steps`` speculative draft→verify→accept rounds as ONE dispatched
    program (tok_buf [N, B, spec_k+1], emits [N, B], counts [B], proposed [B],
    accepted [B], new cache) — the device-resident speculative super-step
    (docs/speculative_serving.md). Drafting is the resident n-gram gather over
    the carried ``history``/``hist_lens`` context; verify/accept/key-cursor
    semantics live in ``models.common.spec_multi_step_decode``."""
    from .spec_decode import ngram_propose_resident

    propose = lambda hist, lens: ngram_propose_resident(  # noqa: E731
        hist, lens, spec_k, max_ngram)
    select_ref = _spec_multi_select(sample, temps, top_ps, top_ks)
    cache, tok_buf, emits, counts, proposed, accepted = (
        _model(cfg).forward_slots_spec_multi(
            params, cache, tokens, positions, active, budgets, eos_ids,
            propose, select_ref, key_tab, history, hist_lens, n_steps, spec_k,
            cfg,
        )
    )
    return tok_buf, emits, counts, proposed, accepted, cache


@partial(jax.jit,
         static_argnames=("cfg", "n_steps", "spec_k", "max_ngram", "sample",
                          "page_size"),
         donate_argnums=(1,))
def _spec_multi_step_paged(params, cache, tables, tokens, positions, active,
                           budgets, eos_ids, key_tab, temps, top_ps, top_ks,
                           history, hist_lens, cfg, n_steps: int, spec_k: int,
                           max_ngram: int, sample: bool, page_size: int):
    """:func:`_spec_multi_step` over the PAGED cache: every round's [B, spec_k+1]
    verify writes route through the device-resident block tables (admission
    reserves the full residual budget up front, so no entry appears mid-scan);
    rejected-draft and frozen-lane positions route to the sentinel and DROP —
    the paged spelling of the per-round garbage-above-rewind contract."""
    from .spec_decode import ngram_propose_resident

    propose = lambda hist, lens: ngram_propose_resident(  # noqa: E731
        hist, lens, spec_k, max_ngram)
    select_ref = _spec_multi_select(sample, temps, top_ps, top_ks)
    cache, tok_buf, emits, counts, proposed, accepted = (
        _model(cfg).forward_slots_spec_multi(
            params, cache, tokens, positions, active, budgets, eos_ids,
            propose, select_ref, key_tab, history, hist_lens, n_steps, spec_k,
            cfg, tables=tables, page_size=page_size,
        )
    )
    return tok_buf, emits, counts, proposed, accepted, cache


@partial(jax.jit, static_argnames=("page_size", "scan_layers"), donate_argnums=(0,))
def _insert_row_paged(cache, row_cache, write_ids, slot, page_size: int,
                      scan_layers: bool):
    """Scatter a single-row prefill cache into pool pages.

    ``write_ids`` [MP] maps the row's logical pages to physical pool pages; SENTINEL
    entries (adopted shared-prefix pages, or pages past the row) are out of bounds
    and the scatter drops them — a lane can never write a page it doesn't own. One
    compiled program serves every slot and row width (``slot`` is a traced scalar —
    unlike the dense ``_insert_row``'s per-slot static scatter, the paged layout
    makes the lane index data). A leaf named ``ring`` is the second kind of cache
    state: a per-lane ring of pages outside the block tables, which takes the row's
    last pages (the prompt's last window) whatever ``write_ids`` says."""
    MP = write_ids.shape[0]
    lanes = cache["valid"].shape[0]

    def pages_of(r):                                             # [C, ...] → [MP, ps, ...]
        pad = MP * page_size - r.shape[0]
        r = jnp.pad(r, ((0, pad),) + ((0, 0),) * (r.ndim - 1))
        return r.reshape(MP, page_size, *r.shape[1:])

    def put(path, pool, row):
        if scan_layers:
            r = row[:, 0]                                        # [L, C, ...]
            pad = MP * page_size - r.shape[1]
            r = jnp.pad(r, ((0, 0), (0, pad)) + ((0, 0),) * (r.ndim - 2))
            r = r.reshape(r.shape[0], MP, page_size, *r.shape[2:])
            return pool.at[:, write_ids].set(r.astype(pool.dtype))
        if getattr(path[-1], "key", None) == "ring":
            # A sliding layer's per-lane ring [lanes * R, ps, ...] (models.common.
            # ring_tables): land the row's last R logical pages, page j at slot * R +
            # j mod R; pages before the row's start drop through the sentinel.
            R = pool.shape[0] // lanes
            j = (row_cache["index"] - 1) // page_size - (R - 1) + jnp.arange(R)
            dst = jnp.where(j >= 0, slot * R + j % R, pool.shape[0])
            return pool.at[dst].set(
                pages_of(row[0])[jnp.clip(j, 0, MP - 1)].astype(pool.dtype))
        # a pool page is the row-major view of the row's page (an index-key plane lays two
        # 64-value keys in one 128-lane row: ops.sparse_attention.index_pool_shape)
        return pool.at[write_ids].set(
            pages_of(row[0]).astype(pool.dtype).reshape(MP, *pool.shape[1:]))

    layers = jax.tree_util.tree_map_with_path(put, cache["layers"], row_cache["layers"])
    valid = jax.lax.dynamic_update_slice(
        cache["valid"], row_cache["valid"], (slot, 0)
    )
    return {"layers": layers, "valid": valid}


@partial(jax.jit, static_argnames=("page_size", "scan_layers"))
def _gather_row_paged(cache, read_ids, prefix_len, page_size: int, scan_layers: bool):
    """Reassemble a single-row DENSE cache from pool pages (paged prefix-cache
    resume): gather ``read_ids`` [MP] (sentinel entries clamp; slots past
    ``prefix_len`` are marked invalid) into the ``[1, max_len]`` row layout the
    chunked-prefill programs consume, with the row's write index at ``prefix_len``.
    Does NOT donate the pool — the registered pages stay live for other adopters."""
    MP = read_ids.shape[0]
    max_len = cache["valid"].shape[1]

    def get(pool):
        P = pool.shape[1] if scan_layers else pool.shape[0]
        ids = jnp.minimum(read_ids, P - 1)
        if scan_layers:
            pages = pool[:, ids]                                 # [L, MP, ps, ...]
            r = pages.reshape(pool.shape[0], MP * page_size, *pages.shape[3:])
            return r[:, :max_len][:, None]                       # [L, 1, C, ...]
        pages = pool[ids]                                        # [MP, ps, ...]
        r = pages.reshape(MP * page_size, *pages.shape[2:])
        return r[:max_len][None]                                 # [1, C, ...]

    return {
        "layers": jax.tree_util.tree_map(get, cache["layers"]),
        "valid": (jnp.arange(max_len) < prefix_len)[None, :],
        "index": jnp.asarray(prefix_len, jnp.int32),
    }


@partial(jax.jit, static_argnames=("scan_layers",), donate_argnums=(0,))
def _copy_page(cache, src, dst, scan_layers: bool):
    """Copy pool page ``src`` → ``dst`` (the registry-side COW: an immutable snapshot
    of a partial boundary page whose owning lane keeps writing its own copy)."""
    axis = 1 if scan_layers else 0

    def cp(pool):
        page = jax.lax.dynamic_index_in_dim(pool, src, axis=axis)
        return jax.lax.dynamic_update_slice_in_dim(pool, page, dst, axis=axis)

    return {
        "layers": jax.tree_util.tree_map(cp, cache["layers"]),
        "valid": cache["valid"],
    }


@partial(jax.jit, static_argnames=("scan_layers",))
def _export_pages(cache, read_ids, scan_layers: bool):
    """Gather pool pages ``read_ids`` [MP] into a transferable page BLOCK
    (``[MP, ps, ...]`` per leaf, ``[L, MP, ps, ...]`` stacked): the device-side
    half of a prefill→decode KV handoff. Sentinel/padding ids clamp — their
    content is never imported (the destination scatter drops them through its
    own sentinel entries). Does NOT donate: the source pages stay live in the
    handoff record until the request is terminal (a dead decode replica
    re-adopts from them)."""
    def get(pool):
        P = pool.shape[1] if scan_layers else pool.shape[0]
        ids = jnp.minimum(read_ids, P - 1)
        return pool[:, ids] if scan_layers else pool[ids]

    return jax.tree_util.tree_map(get, cache["layers"])


@partial(jax.jit, static_argnames=("scan_layers",), donate_argnums=(0,))
def _import_pages(cache, block, write_ids, scan_layers: bool):
    """Scatter a transferred page block into THIS pool's pages ``write_ids``
    [MP] — the destination half of a KV handoff. SENTINEL entries (padding past
    the handoff's real pages) are out of bounds and drop, exactly the
    ``_insert_row_paged`` contract: an import can never write a page it wasn't
    given."""
    def put(pool, b):
        if scan_layers:
            return pool.at[:, write_ids].set(b.astype(pool.dtype))
        return pool.at[write_ids].set(b.astype(pool.dtype))

    return {
        "layers": jax.tree_util.tree_map(put, cache["layers"], block),
        "valid": cache["valid"],
    }


@partial(jax.jit, donate_argnums=(0,))
def _set_lane_valid(cache, slot, valid_row):
    """Install one lane's valid mask (adoption-time lane setup: a handoff
    admission has no prefill row to carry the mask, so the host computes it
    from the handoff's layout and writes it directly). ``slot`` is traced —
    one program serves every lane."""
    valid = jax.lax.dynamic_update_slice(cache["valid"], valid_row[None], (slot, 0))
    return {"layers": cache["layers"], "valid": valid}


@partial(jax.jit, static_argnames=("cfg", "max_len"))
def _prefill_jit(params, row, mask, cfg, max_len: int):
    cache = _model(cfg).init_cache(cfg, 1, max_len)
    logits, cache = _model(cfg).forward_cached(
        params, row, cache, cfg, token_mask=mask, last_only=True
    )
    last = logits[:, -1, :]
    return jnp.argmax(last, axis=-1).astype(jnp.int32), last, cache


@partial(jax.jit, static_argnames=("cfg",), donate_argnums=(3,))
def _prefill_chunk_jit(params, row, mask, cache, cfg):
    """Chunked prefill continuation: append one bucket-width chunk to an existing row
    cache. One compiled executable serves every chunk of every long prompt."""
    logits, cache = _model(cfg).forward_cached(
        params, row, cache, cfg, token_mask=mask, last_only=True
    )
    last = logits[:, -1, :]
    return jnp.argmax(last, axis=-1).astype(jnp.int32), last, cache


@partial(jax.jit, static_argnames=("cfg", "max_len"))
def _prefill_full_logits_jit(params, row, mask, cfg, max_len: int):
    """Right-aligned prefill (prefix-cache layout): fresh cache + one chunk, returning
    per-position logits (the caller indexes the real last token, which may sit before
    trailing pads)."""
    cache = _model(cfg).init_cache(cfg, 1, max_len)
    logits, cache = _model(cfg).forward_cached_logits(params, row, cache, cfg, token_mask=mask)
    return logits, cache


@partial(jax.jit, static_argnames=("cfg",))
def _prefill_chunk_keep_jit(params, row, mask, cache, cfg):
    """Chunk append WITHOUT donating the input cache — the prefix registry keeps the
    input state alive for reuse by later prompts sharing this prefix."""
    logits, cache = _model(cfg).forward_cached_logits(params, row, cache, cfg, token_mask=mask)
    return logits, cache


class ContinuousBatcher:
    """Continuous-batching decode over ``max_slots`` shared lanes (greedy or sampled
    per request).

    ``submit()`` queues requests; ``step()`` admits queued requests into free slots
    (compiled prefill + row insert), advances every active slot one token with ONE
    compiled decode call, and returns the requests finished this step. ``run()`` drains
    everything and reports tokens/s.
    """

    def __init__(self, params, cfg, max_slots: int = 8, max_len: int = 512,
                 prompt_bucket: int = 64, prefix_cache: int = 0, telemetry=None,
                 compile_cache=None, prompt_buckets=None, spec_k: int = 0,
                 drafter=None, spec_accept: str = "replay", page_size: int = 0,
                 kv_pages: Optional[int] = None, tracer=None, faults=None,
                 step_timeout_s: Optional[float] = None,
                 recover: Optional[bool] = None, role: str = "mixed",
                 decode_steps: int = 1):
        self.params = params
        self.cfg = cfg
        #: The model module (the seam: :func:`_model`), and what this engine's paths
        #: call on it, checked by name before any program is built.
        self.model = _model(cfg)
        paths = ["prefill", "paged" if page_size else "dense rows (page_size=0)"]
        paths += ["decode"] * (role != "prefill") + ["spec_k"] * bool(spec_k)
        paths += ["prefix_cache"] * bool(prefix_cache)
        for path in paths:
            for fn in _PATH_CALLS[path]:
                if not hasattr(self.model, fn):
                    raise NotImplementedError(
                        f"{self.model.__name__} has no {fn}: the engine's {path} path "
                        f"calls it, so this model cannot be served that way")
        # Cache state a lane keeps OUTSIDE the block tables (a sliding layer's ring of
        # pages): a page list does not describe such a lane's cache.
        lane_state = getattr(self.model, "lane_state_in_cache", None)
        if role != "mixed" and lane_state is not None and lane_state(cfg):
            raise NotImplementedError(
                f"{self.model.__name__} keeps cache state per lane, outside the block "
                f"tables (lane_state_in_cache): the engine's prefill/decode hand-off path "
                f"(role={role!r}) moves a cache as a list of pages, so this model cannot "
                "be served that way")
        self.max_slots = max_slots
        self.max_len = max_len
        self.prompt_bucket = prompt_bucket
        # Paged KV cache: ``page_size > 0`` replaces the dense per-lane
        # ``[max_slots, max_len]`` cache with a shared pool of ``kv_pages`` fixed-size
        # pages and per-lane block tables (``paged_kv.BlockManager``) — KV memory then
        # costs what admitted requests ACTUALLY occupy, the prefix cache shares pages
        # by refcount instead of snapshotting whole rows, and max concurrency at a
        # fixed KV budget becomes a function of real sequence lengths (docs/
        # paged_kv.md). ``kv_pages`` defaults to dense-equivalent capacity
        # (max_slots × pages-per-row); size it smaller to cap KV memory — admission
        # then DEFERS when the pool is exhausted and resumes as pages free.
        if not isinstance(page_size, (int, np.integer)) or isinstance(page_size, bool):
            raise TypeError(f"page_size must be an int, got {type(page_size).__name__}")
        if page_size < 0:
            raise ValueError(f"page_size={page_size} must be >= 0 (0 = dense cache)")
        self.page_size = int(page_size)
        self.paged = self.page_size > 0
        # Disaggregated serving roles (docs/disaggregated_serving.md): the
        # handoff unit is the KV page, so the prefill/decode roles require the
        # paged layout; a prefill-role engine never decodes (spec_k would warm
        # dead programs) and a decode-role engine never prefills (a prefix
        # registry could never be filled). One deliberate exception to
        # "never prefills": a decode-role engine with IN-ENGINE recovery armed
        # (non-crash faults/watchdog) rebuilds survivors through the normal
        # re-prefill admission — correctness-preserving (bitwise, like any
        # recovery re-admission) but it compiles prefill programs outside the
        # warmed decode-only slice; crash-kind faults instead escalate to the
        # router, whose failover RE-ADOPTS without prefilling.
        if role not in ENGINE_ROLES:
            raise ValueError(f"role={role!r} must be one of {ENGINE_ROLES}")
        if role != "mixed" and not self.paged:
            raise ValueError(
                f"role={role!r} needs the paged KV cache (page_size >= 1): the "
                "cross-engine handoff unit is the page"
            )
        if role == "prefill" and spec_k:
            raise ValueError(
                "spec_k was given on a prefill-role engine: it never dispatches "
                "decode, so the verify/draft programs would be dead weight"
            )
        # The decode loop (docs/multistep_decode.md): ``decode_steps=N`` runs N
        # decode steps as ONE dispatched lax.scan super-step — sampling,
        # EOS/budget masking and lane freezing happen on-device, and the host
        # drains a [N, B] token buffer once per super-step (the same tokens at
        # every N >= 1, greedy and sampled, dense and paged). Coexists with spec_k:
        # speculation wins while ``spec_enabled``; the super-step is the decode
        # path speculation degrades INTO when the gateway disables it (safe —
        # both paths consume the same emission-indexed key schedule).
        if not isinstance(decode_steps, (int, np.integer)) or isinstance(
                decode_steps, bool):
            raise TypeError(
                f"decode_steps must be an int, got {type(decode_steps).__name__}")
        if decode_steps < 1:
            raise ValueError(
                f"decode_steps={decode_steps} must be >= 1 (1 = one token a "
                "dispatch)")
        if role == "prefill" and decode_steps > 1:
            raise ValueError(
                "decode_steps>1 was given on a prefill-role engine: it never "
                "dispatches decode, so the super-step program would be dead weight"
            )
        self.multi_step = int(decode_steps)
        if role == "decode" and prefix_cache:
            raise ValueError(
                "prefix_cache was given on a decode-role engine: it never runs "
                "prefill, so the registry could never be populated"
            )
        self.role = role
        #: Prefill-role export queue: KVHandoff records built the step their
        #: request's prefill landed, drained by the router (``take_handoffs``).
        self.handoffs: deque = deque()
        self.handoffs_exported = 0
        self.handoffs_adopted = 0
        #: Per-lane (v0, v1) valid ranges recorded at paged admission — the
        #: layout fact a handoff must carry (the dense row's mask is gone once
        #: the lane is freed).
        self._lane_valid: list = [(0, 0)] * max_slots
        if kv_pages is not None and not self.paged:
            raise ValueError(
                "kv_pages was given but page_size=0: the pool size would be silently "
                "ignored — pass page_size>=1 to enable the paged KV cache"
            )
        # Batched speculative decoding: ``spec_k`` draft proposals per active slot per
        # step, verified by ONE fused [B, spec_k+1] target forward; each slot accepts a
        # variable-length prefix. 0 (default) = no speculation: the scan at this
        # engine's ``decode_steps``. ``drafter`` is a
        # ``spec_decode.DraftSource`` (default: the model-free NgramDrafter).
        # ``spec_accept`` picks the sampled-slot acceptance test: "replay" (bitwise
        # parity with spec_k=0 under a fixed key schedule) or "residual" (vectorized
        # Leviathan accept/reject — lossless in distribution, higher acceptance).
        if not isinstance(spec_k, (int, np.integer)) or isinstance(spec_k, bool):
            raise TypeError(f"spec_k must be an int, got {type(spec_k).__name__}")
        if spec_k < 0:
            raise ValueError(f"spec_k={spec_k} must be >= 0 (0 disables speculation)")
        if spec_accept not in ("replay", "residual"):
            raise ValueError(
                f"spec_accept={spec_accept!r}: expected 'replay' or 'residual'"
            )
        self.spec_k = int(spec_k)
        self.spec_accept = spec_accept
        if drafter is not None and not self.spec_k:
            raise ValueError(
                "a drafter was given but spec_k=0: it would be silently ignored — "
                "pass spec_k>=1 to enable speculative decoding"
            )
        if self.spec_k and drafter is None:
            from .spec_decode import NgramDrafter

            drafter = NgramDrafter()
        self.drafter = drafter
        # Persistent AOT executable cache (``accelerate_tpu.compile_cache``): accepts
        # a shared AotCache (e.g. ``accelerator.compile_cache``) or a
        # CompileCacheConfig. Disabled/None leaves every program on the plain
        # module-level jits — identical behavior and dispatch cost.
        if isinstance(compile_cache, CompileCacheConfig):
            compile_cache = AotCache(compile_cache)
        self.compile_cache = compile_cache if (
            compile_cache is not None and compile_cache.enabled
        ) else None
        cc = self.compile_cache
        self._prefill_fn = as_cached(
            _prefill_jit, cc, "serving.prefill", ("cfg", "max_len"))
        self._prefill_chunk_fn = as_cached(
            _prefill_chunk_jit, cc, "serving.prefill_chunk", ("cfg",))
        self._prefill_full_logits_fn = as_cached(
            _prefill_full_logits_jit, cc, "serving.prefill_full_logits",
            ("cfg", "max_len"))
        self._prefill_chunk_keep_fn = as_cached(
            _prefill_chunk_keep_jit, cc, "serving.prefill_chunk_keep", ("cfg",))
        self._insert_row_fn = as_cached(
            _insert_row, cc, "serving.insert_row", ("slot", "scan_layers"))
        #: The decode programs, by name, in THIS engine's KV layout: ``(compile label,
        #: program)``. Each is a pair of twin jits that differ by the block tables
        #: (``_tables``) and the static ``page_size`` (``_layout_statics``) alone;
        #: ``_dispatch`` is their one call site.
        self._decode_programs = {}
        self._layout_statics = {"page_size": self.page_size} if self.paged else {}
        for name, dense, paged, statics in (
            ("decode_multi", _decode_multi_step, _decode_multi_step_paged,
             ("cfg", "n_steps", "sample")),
            ("spec_verify", _spec_verify_step, _spec_verify_step_paged, ("cfg",)),
            ("spec_multi", _spec_multi_step, _spec_multi_step_paged,
             ("cfg", "n_steps", "spec_k", "max_ngram", "sample")),
        ):
            label, fn = (f"serving.{name}_paged", paged) if self.paged else (
                f"serving.{name}", dense)
            self._decode_programs[name] = (label, as_cached(
                fn, cc, label, statics + ("page_size",) * self.paged))
        self._insert_paged_fn = as_cached(
            _insert_row_paged, cc, "serving.insert_paged",
            ("page_size", "scan_layers"))
        self._gather_row_fn = as_cached(
            _gather_row_paged, cc, "serving.gather_row_paged",
            ("page_size", "scan_layers"))
        self._copy_page_fn = as_cached(
            _copy_page, cc, "serving.copy_page", ("scan_layers",))
        self._export_pages_fn = as_cached(
            _export_pages, cc, "serving.export_pages", ("scan_layers",))
        self._import_pages_fn = as_cached(
            _import_pages, cc, "serving.import_pages", ("scan_layers",))
        self._lane_valid_fn = as_cached(
            _set_lane_valid, cc, "serving.lane_valid", ())
        # Shape-bucketed prefill: pad each prompt to the smallest rung of a geometric
        # ladder so prefill compiles once per BUCKET instead of once per chunk count
        # (and the warmup manifest can enumerate the whole compile surface). Explicit
        # ``prompt_buckets`` wins; else the compile-cache config's ladder; else the
        # historical chunked prefill. The ladder is capped so a bucket always fits the
        # engine cache. Prefix caching keeps its right-aligned chunk layout (snapshots
        # must align across prompt lengths), so it takes precedence over bucketing.
        if prompt_buckets is not None:
            self.prompt_buckets = tuple(sorted({int(b) for b in prompt_buckets}))
        elif cc is not None and cc.config.bucket_serving:
            # An empty ladder (bucket_min >= max_len) means bucketing is off.
            self.prompt_buckets = cc.config.ladder(max_len) or None
        else:
            self.prompt_buckets = None
        if self.prompt_buckets is not None and any(
            b < 1 or b > max_len for b in self.prompt_buckets
        ):
            raise ValueError(
                f"prompt_buckets={self.prompt_buckets} must lie in [1, max_len={max_len}]"
            )
        self.bucket_hits = 0    # prompt admitted into an already-compiled bucket
        self.bucket_misses = 0  # first prompt of a bucket (compiles/loads its program)
        self._buckets_seen: set = set()
        if self.paged:
            if kv_pages is None:
                kv_pages = max_slots * pages_for(max_len, self.page_size)
            self.block_mgr = BlockManager(
                int(kv_pages), self.page_size, max_slots, max_len
            )
            self.cache = self.model.init_paged_cache(
                cfg, max_slots, max_len, int(kv_pages), self.page_size
            )
            self.kv_page_bytes = self.cache_bytes() // int(kv_pages)
        else:
            self.block_mgr = None
            self.kv_page_bytes = 0
            self.cache = self.model.init_cache(cfg, max_slots, max_len)
        self.tokens = np.zeros((max_slots,), np.int32)  # host-side; uploaded per decode
        self.positions = np.zeros((max_slots,), np.int32)  # next write slot per lane
        self.slot_req: list[Optional[Request]] = [None] * max_slots
        self.queue: deque[Request] = deque()
        self._uid = 0
        # Prefix caching (opt-in): keep up to ``prefix_cache`` row-cache snapshots keyed
        # by full-chunk prompt prefixes; a new request sharing a registered prefix skips
        # recomputing it (the classic shared-system-prompt win). Uses a RIGHT-aligned
        # prompt layout (prefix always at positions 0..P, so snapshots align for every
        # prompt length); rotary attention only sees position differences, so outputs
        # still equal the standalone greedy decode (tested).
        self.prefix_cache_size = prefix_cache
        self._prefix_reg: "OrderedDict[bytes, object]" = OrderedDict()
        self.prefix_hits = 0
        self.prefix_misses = 0
        # Prefix-eviction observability: LRU drops used to be silent, making
        # "cache too small" indistinguishable from "cold key" in production stats.
        # ``prefix_evictions`` counts drops; misses split into capacity misses (the
        # key WAS registered and got evicted — remembered in a bounded key set) vs
        # key misses (never seen). In paged mode eviction also releases the entry's
        # page references (pages free when their refcount reaches zero).
        self.prefix_evictions = 0
        self.prefix_capacity_misses = 0
        self.prefix_key_misses = 0
        self._evicted_keys: "OrderedDict[bytes, bool]" = OrderedDict()
        self._evicted_keys_cap = max(64, 8 * prefix_cache)
        self.peak_active_slots = 0  # high-water concurrent lanes (bench: max
        #                             concurrency actually reached at this KV budget)
        # Admission/eviction counters + the step-level telemetry pipeline
        # (``accelerate_tpu.telemetry.Telemetry``): when attached, every decode step
        # emits a serving record through the SAME sinks the train step uses —
        # stats() stops being fire-and-forget.
        self.telemetry = telemetry
        # Request-scoped tracing (``telemetry.tracing.Tracer``): when attached AND
        # enabled, admission emits admit/prefill spans and every decode dispatch
        # emits one span per active traced lane — disabled, the hot path pays the
        # same two attribute reads as the telemetry check (tests/test_tracing.py).
        self.tracer = tracer
        # Per-request queue wait measured AT admission (submit → slot), so the
        # bare-engine path reports the same latency percentiles the gateway does
        # (bounded window; ``queue_wait_s`` keeps the oldest-queued age).
        self.queue_waits: deque[float] = deque(maxlen=1024)
        self.admitted = 0   # requests that entered a slot (prefill ran)
        self.prefill_chunks = 0  # prefill programs dispatched (chunked: one a chunk)
        self.evicted = 0    # slot frees: finished (EOS/max_new_tokens) requests
        self.evicted_external = 0  # slot frees forced by evict() (deadline/cancel/preempt)
        # Decode-throughput accounting: tokens emitted per decode dispatch is THE
        # speculative-decoding headline metric (TPOT ∝ 1/tokens_per_step when decode
        # dominates); proposed/accepted drive the acceptance rate.
        self.decode_steps = 0    # decode/verify dispatches (admission prefills excluded)
        self.decode_tokens = 0   # tokens emitted by those dispatches
        #: End of the previous decode dispatch (tracer clock), for the measured
        #: ``host_s`` inter-dispatch gap every decode span carries — the host
        #: dead time multi-step decode exists to amortize. None until the first
        #: dispatch of a trace-enabled run (and only maintained while tracing).
        self._last_dispatch_end: Optional[float] = None
        self.spec_proposed = 0   # draft tokens proposed (spec_k × active lanes per step)
        self.spec_accepted = 0   # proposed tokens that were emitted (match/accept)
        if self.drafter is not None:
            self.drafter.bind(self)
        # Fault boundary (docs/resilience.md): ``faults`` is a
        # ``resilience.FaultPlan`` injecting deterministic failures at the
        # serving sites; ``step_timeout_s`` arms a StepWatchdog that converts
        # an overlong dispatch (hang) into the same failure path.
        # ``recover`` turns the boundary ON: a failed dispatch quarantines the
        # poison request (terminal ``failed:<reason>``, bisection when
        # attribution is ambiguous), releases its lane/pages, and rebuilds the
        # survivors' engine state from prompt + already-emitted tokens so
        # serving continues. Default: recovery is armed exactly when faults or
        # a watchdog are (the undisturbed engine stays byte-identical — an
        # unexpected exception then propagates as before).
        self.faults = faults
        self._watchdog = (
            StepWatchdog(step_timeout_s) if step_timeout_s else None
        )
        self.recover = bool(
            recover if recover is not None
            else (faults is not None or self._watchdog is not None)
        )
        #: Pool size remembered for recovery rebuilds (paged engines).
        self._kv_pages_total = int(kv_pages) if self.paged else 0
        #: Speculative decoding master switch: the gateway's degradation rungs
        #: flip it under pressure. Disabling mid-run is always output-safe
        #: (verification guarantees correctness; a stale draft cache only
        #: lowers acceptance), it just reverts decode to the scan.
        self.spec_enabled = True
        #: Set when an injected ``crash`` killed this engine (EngineCrashed
        #: escaped a dispatch): the object must not serve again — the fleet
        #: router replaces it via its restart path.
        self.crashed = False
        self.step_failures = 0        # dispatches the fault boundary caught
        self.quarantined = 0          # requests terminally failed by recovery
        self.recovered_admissions = 0  # survivor re-admissions (prefill replays)
        self.bisect_rounds = 0        # ambiguous-attribution probe rounds
        self.recovered_uids: set = set()   # engine uids that survived ≥1 rebuild
        self._suspects: Optional[set] = None  # narrowed poison candidates (uids)
        self._bisect_hold: list[Request] = []  # suspects held out of admission

    # ------------------------------------------------------------------ user API
    def stats(self) -> dict:
        """Engine observability snapshot: queue depth, busy lanes, admission/eviction
        totals, prefix-cache counters, decode-throughput counters. ``queue_wait_s`` is
        the age of the OLDEST queued request (0.0 when the queue is empty) — queue
        latency stays observable even without the gateway tier (``serving_gateway``)
        on top. ``tokens_per_step`` (emitted tokens per decode dispatch — >1 only with
        speculation accepting drafts) and ``spec_accept_rate`` (accepted/proposed
        drafts) are the speculative headline numbers serve-bench and bench rows
        stamp; both are None before any decode step / proposal.

        Paged engines (``page_size > 0``) additionally report the page pool:
        occupancy, ``kv_bytes_in_use``/``kv_bytes_total``, prefix-share refcounts
        (``kv_shared_pages``) and alloc/free/COW/adopt/defer counters — the same
        fields the ``serving.kv/v1`` telemetry record carries per step. Prefix-cache
        eviction is observable in both layouts: ``prefix_evictions`` plus the
        capacity-vs-key miss split.

        ``phases`` is what the process's phase ledger
        (``telemetry.tracing.PHASES``) has counted under ``engine.*`` since the
        process began, with or without a profiler: per phase of docs/telemetry.md's
        table its ``count``, ``total_ns``, ``self_ns``, longest (``max_ns``,
        ``max_t0_ns``, ``max_attrs``) and ``sums`` of every numeric attribute
        (``tokens``, ``chunks``, ``pages_live``, the model's ``DECODE_COUNTERS``…).
        The ledger is the process's, so the engines of one process share it; the
        table is a copy as of the call (``PhaseLedger.totals``: a few microseconds
        for the engine's dozen names)."""
        active = sum(r is not None for r in self.slot_req)
        queue_wait_s = 0.0
        if self.queue:
            now = time.monotonic()
            queue_wait_s = max(0.0, now - min(r.enqueued_at for r in self.queue))
        kv = {"paged": self.paged}
        if self.paged:
            ms = self.block_mgr.stats()
            kv.update({
                "page_size": self.page_size,
                "pages_total": ms["pages_total"],
                "pages_free": ms["pages_free"],
                "pages_in_use": ms["pages_in_use"],
                "page_occupancy": ms["page_occupancy"],
                "kv_page_bytes": self.kv_page_bytes,
                "kv_bytes_in_use": ms["pages_in_use"] * self.kv_page_bytes,
                "kv_bytes_total": ms["pages_total"] * self.kv_page_bytes,
                "kv_shared_pages": ms["shared_pages"],
                "kv_alloc_count": ms["alloc_count"],
                "kv_free_count": ms["free_count"],
                "kv_cow_count": ms["cow_count"],
                "kv_adopt_count": ms["adopt_count"],
                "kv_defer_count": ms["defer_count"],
            })
        return {
            **kv,
            "role": self.role,
            "handoffs_pending": len(self.handoffs),
            "handoffs_exported": self.handoffs_exported,
            "handoffs_adopted": self.handoffs_adopted,
            "peak_active_slots": self.peak_active_slots,
            "prefix_evictions": self.prefix_evictions,
            "prefix_capacity_misses": self.prefix_capacity_misses,
            "prefix_key_misses": self.prefix_key_misses,
            "queued": len(self.queue),
            "queue_wait_s": queue_wait_s,
            "queue_wait": latency_summary(self.queue_waits),
            "active_slots": active,
            "max_slots": self.max_slots,
            "slot_occupancy": active / self.max_slots,
            "admitted": self.admitted,
            "prefill_chunks": self.prefill_chunks,
            "evicted": self.evicted,
            "evicted_external": self.evicted_external,
            "prefix_entries": len(self._prefix_reg),
            "prefix_hits": self.prefix_hits,
            "prefix_misses": self.prefix_misses,
            "bucket_hits": self.bucket_hits,
            "bucket_misses": self.bucket_misses,
            "spec_k": self.spec_k,
            "multi_step": self.multi_step,
            "decode_steps": self.decode_steps,
            "decode_tokens": self.decode_tokens,
            "tokens_per_step": (
                round(self.decode_tokens / self.decode_steps, 4)
                if self.decode_steps else None
            ),
            "spec_proposed": self.spec_proposed,
            "spec_accepted": self.spec_accepted,
            "spec_accept_rate": (
                round(self.spec_accepted / self.spec_proposed, 4)
                if self.spec_proposed else None
            ),
            "spec_enabled": self.spec_enabled,
            "step_failures": self.step_failures,
            "quarantined": self.quarantined,
            "recovered_admissions": self.recovered_admissions,
            "bisect_rounds": self.bisect_rounds,
            "bisect_held": len(self._bisect_hold),
            "watchdog_timeouts": (
                self._watchdog.timeouts if self._watchdog is not None else 0
            ),
            "phases": PHASES.totals("engine."),
        }

    def _emit_telemetry(self, extra: Optional[dict] = None) -> None:
        """Push a serving counter record through the telemetry pipeline (no-op when
        no enabled Telemetry is attached — the hot loop pays one attribute check)."""
        tel = self.telemetry
        if tel is None or not tel.enabled:
            return
        from .telemetry import TELEMETRY_REV

        record = {
            "schema": SERVING_SCHEMA,
            "telemetry_rev": TELEMETRY_REV,
            **self.stats(),
        }
        del record["phases"]    # the program's own memory, not a stream of records
        if self.compile_cache is not None:
            record["compile_cache"] = self.compile_cache.stats()
        if extra:
            record.update(extra)
        tel.emit(record)
        if self.paged:
            # Dedicated page-pool record: the serving-memory story as a first-class
            # stream (pool occupancy, bytes, sharing, churn) — dashboards watch this
            # without parsing the full engine counter record.
            ms = self.block_mgr.stats()
            tel.emit({
                "schema": SERVING_KV_SCHEMA,
                "telemetry_rev": TELEMETRY_REV,
                # Causality key: trace.span/v1 decode spans of the same request
                # carry this step index, so a span joins to the pool state that
                # step saw (same contract as serving.spec/v1 below).
                "step": self.decode_steps,
                "page_size": self.page_size,
                "pages_total": ms["pages_total"],
                "pages_in_use": ms["pages_in_use"],
                "page_occupancy": ms["page_occupancy"],
                "kv_bytes_in_use": ms["pages_in_use"] * self.kv_page_bytes,
                "kv_bytes_total": ms["pages_total"] * self.kv_page_bytes,
                "kv_shared_pages": ms["shared_pages"],
                "kv_alloc_count": ms["alloc_count"],
                "kv_free_count": ms["free_count"],
                "kv_cow_count": ms["cow_count"],
                "kv_adopt_count": ms["adopt_count"],
                "kv_defer_count": ms["defer_count"],
                "prefix_entries": len(self._prefix_reg),
                "prefix_evictions": self.prefix_evictions,
            })

    def submit(self, prompt, max_new_tokens: Optional[int] = None,
               eos_token_id: Optional[int] = None,
               gen: Optional[GenerationConfig] = None,
               rng: Optional[jax.Array] = None,
               on_token: Optional[Callable[[int], None]] = None) -> Request:
        """Queue a request. Either pass ``max_new_tokens``/``eos_token_id`` (greedy), or a
        full ``GenerationConfig`` via ``gen`` — not both (silently preferring one would
        drop the caller's limits). Temperature sampling needs ``rng``. ``on_token``
        streams each generated token id as it is produced."""
        if self.role == "decode":
            raise RuntimeError(
                "decode-role engine takes no direct submissions: work arrives "
                "as KV handoffs (adopt_handoff) from a prefill-role replica — "
                "route through the DisaggRouter (docs/disaggregated_serving.md)"
            )
        prompt, gen = normalize_submit(prompt, max_new_tokens, eos_token_id, gen, rng)
        # The prompt's padded prefill width + generation budget must fit the cache
        # (and, paged, the whole page pool): kv_demand runs _plan_prefill's layout
        # validation and raises KVBudgetError for a request the pool could NEVER
        # hold — deferring it would deadlock the FIFO queue forever.
        self.kv_demand(len(prompt), gen.max_new_tokens)
        req = Request(self._uid, prompt, gen, rng, on_token=on_token,
                      enqueued_at=time.monotonic())
        self._uid += 1
        self.queue.append(req)
        return req

    def kv_demand(self, prompt_len: int, max_new: int) -> int:
        """Cache-token cost of one request under THIS engine's layout — the number
        the gateway's admission budget accounts.

        Dense: the planned padded prefill width plus the generation budget (every
        admitted token reserves a dense slot whether or not it is ever reached).
        Paged: the PAGE-granular worst case — ``pages × page_size`` for the pages
        covering prompt + budget — so admission prices real memory, not padded
        maxima. Raises ``ValueError`` for unservable geometry (via
        ``_plan_prefill``) and :class:`KVBudgetError` when the demand exceeds the
        whole page pool.

        **Role-aware** (the disagg admission-cost fix, docs/
        disaggregated_serving.md): a prefill-role engine holds a request's
        PROMPT pages only (its lanes never decode — budget pages would
        double-count KV the decode replica charges, rejecting servable
        requests as ``kv_budget``); a decode-role engine prices the adoption —
        the adopted context pages plus the generation budget, with one extra
        page for the transient COW import of a partial boundary page."""
        _, total = self._plan_prefill(prompt_len, max_new)
        if self.paged:
            if self.role == "prefill":
                return self.block_mgr.demand(total) * self.page_size
            if self.role == "decode":
                need = self.block_mgr.demand(total + max_new) + 1
                if need > self.block_mgr.num_pages:
                    raise KVBudgetError(
                        f"adoption needs {need} pages ({total + max_new} cache "
                        f"tokens + the transient boundary-page import at "
                        f"page_size={self.page_size}) but the pool only has "
                        f"{self.block_mgr.num_pages} — it can never be adopted"
                    )
                return need * self.page_size
            return self.block_mgr.demand(total + max_new) * self.page_size
        return total + max_new

    def kv_capacity_tokens(self) -> int:
        """Total cache-token capacity of this engine's KV layout (the denominator
        for ``kv_demand``-priced admission): pool pages × page_size when paged,
        max_slots × max_len dense."""
        if self.paged:
            return self.block_mgr.num_pages * self.page_size
        return self.max_slots * self.max_len

    def cache_bytes(self) -> int:
        """Total bytes of the KV cache planes (page pool or dense rows, scale
        planes included) — the ONE byte accounting behind ``kv_page_bytes``,
        ``stats()``'s kv_bytes columns, and serve-bench's budget math, so they can
        never disagree on what 'KV bytes' means."""
        return sum(
            leaf.size * leaf.dtype.itemsize
            for leaf in jax.tree_util.tree_leaves(self.cache["layers"])
        )

    def cancel(self, uid: int) -> bool:
        """Cooperatively withdraw a request by uid, wherever it is.

        Queued: removed before it ever touches a slot. In flight: its lane is freed
        immediately — the next ``step()`` admits into it and the stale cache row is
        simply overwritten (idle lanes keep computing ignored output, so no compiled
        program changes shape). Returns False when the uid is unknown or already
        finished; the request object is left exactly as far as it got (``tokens``
        keeps the prefix generated so far, ``done`` stays False)."""
        for req in self.queue:
            if req.uid == uid:
                self.queue.remove(req)
                return True
        for req in self._bisect_hold:
            if req.uid == uid:
                self._bisect_hold.remove(req)
                self._suspects = None if not self._bisect_hold else self._suspects
                return True
        return self.evict_slot(uid)

    def set_spec_enabled(self, enabled: bool) -> None:
        """Toggle speculative decoding at runtime (the gateway degradation
        rung). Always output-safe: speculation never changes emitted tokens,
        only how many a dispatch produces — disabling lands on the scan at this
        engine's ``decode_steps`` (``serving.decode_multi[_paged]``, warmed
        alongside the verify/fused-spec programs, so the toggle costs no
        compiles); re-enabling resumes proposals (a ModelDrafter's stale lane
        cache only lowers acceptance until its lanes cycle)."""
        if self.spec_k:
            self.spec_enabled = bool(enabled)

    def _spec_fused(self) -> bool:
        """Whether speculative decode dispatches as the FUSED multi-round scan
        (``serving.spec_multi[_paged]``) instead of the host loop: needs
        ``decode_steps > 1`` (the super-step geometry), replay acceptance (the
        residual accept consumes keys data-dependently on device draws the scan
        cannot replay), and a drafter with a device-resident propose
        (``DraftSource.resident`` — the shipped NgramDrafter). Everything else
        keeps the PR-6 host loop, bitwise-identically."""
        return (self.multi_step > 1 and self.spec_accept == "replay"
                and getattr(self.drafter, "resident", False))

    def evict_slot(self, uid: int) -> bool:
        """Free the decode lane holding request ``uid`` (deadline enforcement /
        preemption / cancellation). The slot is reusable by the very next ``step()``;
        the evicted request is NOT marked done and keeps its partial ``tokens``."""
        for slot, req in enumerate(self.slot_req):
            if req is not None and req.uid == uid:
                self.slot_req[slot] = None
                self._release_lane(slot)
                self.evicted_external += 1
                return True
        return False

    def _release_lane(self, slot: int) -> None:
        """Return a freed lane's page references to the pool (paged mode; pages a
        prefix entry still references survive). Dense lanes have nothing to do —
        their cache row is overwritten at the next admit."""
        if self.paged:
            self.block_mgr.release_slot(slot)

    # ------------------------------------------------------------ fault boundary
    def _pre_dispatch(self, site: str, active: list[int]) -> float:
        """Guard hook before a decode/verify dispatch: opens the watchdog
        window and fires any injected fault due at ``site``. Disabled
        (no faults, no watchdog) this is two attribute reads."""
        wd = self._watchdog
        t0 = wd.open() if wd is not None else 0.0
        fp = self.faults
        if fp is not None:
            uids = [self.slot_req[i].uid for i in active
                    if self.slot_req[i] is not None]
            spec = fp.draw(site, uids=uids)
            if spec is not None:
                if spec.kind == "hang":
                    # The stall the watchdog exists to catch: dispatch still
                    # runs, the post-dispatch check converts the overrun into
                    # the step-failure path before any token is emitted.
                    time.sleep(spec.hang_s)
                elif spec.kind == "crash":
                    # Whole-engine death: marks this engine unusable and
                    # escapes the recovery boundary — there is no in-engine
                    # recovery from a dead process; the fleet router owns it.
                    self.crashed = True
                    raise EngineCrashed(site)
                else:
                    raise fp.fault_for(spec, site)
        return t0

    def _post_dispatch(self, t0: float, site: str = "serving.decode") -> None:
        if self._watchdog is not None:
            self._watchdog.check(t0, site)

    def _emit_fault(self, site: str, kind: str, uid, reason: str) -> None:
        tel = self.telemetry
        if tel is not None and tel.enabled:
            tel.emit({
                "schema": FAULT_SCHEMA, "site": site, "kind": kind,
                "uid": uid, "reason": reason, "step": self.decode_steps,
            })

    def _emit_recovery(self, action: str, **cols) -> None:
        tel = self.telemetry
        if tel is not None and tel.enabled:
            tel.emit({
                "schema": RECOVERY_SCHEMA, "action": action,
                "step": self.decode_steps, **cols,
            })

    def _quarantine(self, req: Request, reason: str) -> Request:
        """Terminally fail one request at the boundary: machine-readable
        ``failed`` reason, lane/pages released, partial tokens kept (they were
        already streamed). Returned to the caller like any finished request."""
        req.failed = reason
        req.done = True
        self.quarantined += 1
        for slot, r in enumerate(self.slot_req):
            if r is req:
                self.slot_req[slot] = None
                self._release_lane(slot)
                break
        tracer = self.tracer
        if tracer is not None and tracer.enabled:
            tracer.event(tracer.handle_for(req.uid), "fault",
                         step=self.decode_steps, reason=reason)
        self._emit_recovery("quarantine", uid=req.uid, reason=reason)
        return req

    def _detach_for_requeue(self, req: Request) -> None:
        """Pull a live request off its lane (if any) and arm its recovery
        context — the next admission prefills prompt + emitted tokens."""
        for slot, r in enumerate(self.slot_req):
            if r is req:
                self.slot_req[slot] = None
                self._release_lane(slot)
                break
        req._recover_ctx = (
            np.concatenate([req.prompt, np.asarray(req.tokens, np.int32)])
            if req.tokens else req.prompt
        )

    def _rebuild_survivors(self) -> None:
        """Reset the device-side engine state (a failed donated dispatch may
        have left the cache garbage) and requeue every surviving lane at the
        FRONT of the queue for recovery re-admission. Zero new programs: the
        fresh cache has the warmed shapes, and re-admission rides the same
        prefill/insert executables as any admission."""
        survivors = [r for r in self.slot_req if r is not None]
        if self.paged:
            # Drain the prefix registry against the OLD manager FIRST: its
            # entries hold old-pool page ids, and releasing them against a
            # fresh manager would drive refcounts negative. The keys land in
            # the evicted set so re-registration classifies honestly.
            while self._evict_prefix_lru():
                pass
            self.block_mgr = BlockManager(
                self._kv_pages_total, self.page_size, self.max_slots,
                self.max_len,
            )
            self.cache = self.model.init_paged_cache(
                self.cfg, self.max_slots, self.max_len, self._kv_pages_total,
                self.page_size,
            )
        else:
            # Dense prefix snapshots are independent row caches (the keep-alive
            # chunk program never donates) — they survive a cache rebuild.
            self.cache = self.model.init_cache(self.cfg, self.max_slots, self.max_len)
        self.slot_req = [None] * self.max_slots
        self.positions[:] = 0
        self.tokens[:] = 0
        for req in sorted(survivors, key=lambda r: r.uid, reverse=True):
            self._detach_for_requeue(req)
            self.queue.appendleft(req)
        self._emit_recovery("rebuild", survivors=len(survivors))

    def _recover_step_failure(self, error: Exception,
                              active_reqs: list[Request]) -> list[Request]:
        """The decode-dispatch failure path: attribute the poison (directly
        when the error names a uid, else by bisection over the active set),
        quarantine it, and rebuild the survivors so the next step continues.

        Bisection contract: a data-poison request is assumed to fail every
        dispatch it participates in (deterministic reproduction). The probe
        half keeps running; the held half waits out one clean dispatch and is
        then requeued as the sole suspect set — the candidate set halves per
        failing round until one request remains."""
        self.step_failures += 1
        site = getattr(error, "site", "serving.decode")
        kind = getattr(error, "kind", type(error).__name__)
        uid = getattr(error, "uid", None)
        self._emit_fault(site, kind, uid, reason=str(error))
        live = [r for r in active_reqs if not r.done]
        failed: list[Request] = []
        if uid is not None and any(r.uid == uid for r in live):
            victim = next(r for r in live if r.uid == uid)
            failed.append(self._quarantine(victim, f"step_fault:{kind}"))
            self._suspects = None
        else:
            cands = [r for r in live
                     if self._suspects is None or r.uid in self._suspects]
            if not cands:
                cands = live
            if len(cands) == 1:
                failed.append(self._quarantine(cands[0], f"step_fault:{kind}"))
                self._suspects = None
            elif cands:
                half = max(1, len(cands) // 2)
                probe, hold = cands[:half], cands[half:]
                for req in hold:
                    self._detach_for_requeue(req)
                    self._bisect_hold.append(req)
                self._suspects = {r.uid for r in probe}
                self.bisect_rounds += 1
                self._emit_recovery("bisect", candidates=len(cands),
                                    probing=len(probe), held=len(hold))
        if not getattr(error, "pre_dispatch", False):
            self._rebuild_survivors()
        return failed

    def _release_bisect_hold(self) -> None:
        """Requeue the held suspects (FRONT, uid order) as the sole remaining
        candidates — they carry their recovery context from the detach."""
        self._suspects = {r.uid for r in self._bisect_hold}
        for req in sorted(self._bisect_hold, key=lambda r: r.uid,
                          reverse=True):
            self.queue.appendleft(req)
        self._bisect_hold = []

    def _after_clean_step(self, active_reqs: list[Request]) -> None:
        """Bisection bookkeeping after a clean decode dispatch: a clean probe
        clears its half — the held suspects requeue as the remaining
        candidates; a clean dispatch covering EVERY suspect clears the
        suspicion entirely (the fault was transient, nobody is poisoned)."""
        if self._bisect_hold:
            self._release_bisect_hold()
        elif self._suspects is not None:
            active_uids = {r.uid for r in active_reqs if not r.done}
            done_uids = {r.uid for r in active_reqs if r.done}
            if self._suspects <= (active_uids | done_uids):
                self._suspects = None

    def step(self) -> list[Request]:
        """Admit queued requests, then advance every active slot: up to
        ``decode_steps`` tokens each in one device-resident super-step (one token
        at the default; admission, eviction and deadline checks act at SUPER-STEP
        boundaries; docs/multistep_decode.md), or — speculative — a verified
        1..spec_k+1-token prefix each a round.

        With recovery armed (``faults``/``step_timeout_s``/``recover=True``) a
        failed dispatch no longer kills the process: the poison request is
        quarantined (terminal ``failed:<reason>``, returned like any finished
        request), its lane/pages are released, and the survivors' state is
        rebuilt from prompt + already-emitted tokens so the next ``step()``
        continues the workload (docs/resilience.md)."""
        lanes = self.max_slots - self.slot_req.count(None)
        with phase("engine.step", queued=len(self.queue), lanes=lanes):
            if self.role == "prefill":
                return self._prefill_role_step(lanes)
            with phase("engine.admit", lanes=lanes):
                finished_at_admit = self._admit()
            active = [i for i, r in enumerate(self.slot_req) if r is not None]
            self.peak_active_slots = max(self.peak_active_slots, len(active))
            if not active:
                if self._bisect_hold:
                    # No probe can run (every lane drained — e.g. the whole probe
                    # half was quarantined or finished): the held suspects are the
                    # only remaining work, and nothing else can exonerate them.
                    # Release them or they would be stranded forever — run()'s
                    # drain would exit (queue and lanes empty) with live requests
                    # parked in the hold, a silent loss.
                    self._release_bisect_hold()
                if finished_at_admit:
                    self._emit_telemetry()  # admissions alone still move the counters
                return finished_at_admit
            # Decode-path routing, three targets: speculation wins while enabled (it
            # already emits multiple tokens per dispatch) — fused into one scan of N
            # draft→verify→accept rounds where it can be (``_spec_fused``; docs/
            # speculative_serving.md), else one round a dispatch in the host loop;
            # the scan at this engine's N is BOTH the standalone path and what
            # speculation degrades into when the gateway's pressure rungs flip
            # ``spec_enabled`` off — safe mid-request, because every path consumes
            # the same emission-indexed key schedule.
            use_spec = self.spec_k and self.spec_enabled
            n_steps = self.multi_step
            if use_spec and self._spec_fused():
                decode = self._spec_multi
            elif use_spec:
                decode, n_steps = self._spec_step, 1
            else:
                decode = self._multi_step
            # The decode path chosen, all of it: one phase, and the tracer-clock t0
            # every path's per-lane "decode" span records share.
            ph = EnginePhase(self.tracer, "engine.decode", lanes=len(active),
                             n_steps=n_steps)
            if not self.recover:
                with ph:
                    finished = decode(active, ph)
            else:
                active_reqs = [self.slot_req[i] for i in active]
                try:
                    with ph:
                        finished = decode(active, ph)
                except EngineCrashed:
                    # A crash is the death of the whole engine, not a step fault:
                    # no in-engine quarantine/rebuild is possible — it propagates
                    # to the replica's owner (the fleet router's failover path).
                    raise
                except Exception as e:  # the fault boundary: quarantine + rebuild
                    finished = self._recover_step_failure(e, active_reqs)
                else:
                    self._after_clean_step(active_reqs)
            self.evicted += len(finished)
            self._emit_telemetry()
            # Report in submission order (uid is the admission counter), not slot order —
            # slot assignment is an engine detail a client should never observe.
            return sorted(finished_at_admit + finished, key=lambda r: r.uid)

    # ------------------------------------------------------- disaggregated roles
    def _prefill_role_step(self, lanes: int) -> list[Request]:
        """Prefill-role ``step()``: admit queued requests (compiled prefill —
        the normal admission path, fault boundary included), then EXPORT every
        admitted lane as a :class:`KVHandoff` and free it. Lanes are transient:
        one step can prefill up to ``max_slots`` requests, and the next step's
        lanes are empty again — the replica is a prefill pump, never a decode
        host. Returns only requests that finished AT admission (EOS or a
        1-token budget — those never need a handoff)."""
        with phase("engine.admit", lanes=lanes):
            finished = self._admit()
        active = [i for i, r in enumerate(self.slot_req) if r is not None]
        self.peak_active_slots = max(self.peak_active_slots, len(active))
        for slot in active:
            self._export_lane(slot)
        if finished or active:
            self._emit_telemetry()
        return sorted(finished, key=lambda r: r.uid)

    def _export_lane(self, slot: int) -> None:
        """Detach lane ``slot`` into a handoff record: pages covering the
        prefill context keep their refcounts (ownership moves to the record —
        ``release_handoff`` drops them at the request's terminal state), pages
        past the context (a prefix-layout row's invalid tail) release now, and
        the lane frees for the next admission."""
        req = self.slot_req[slot]
        pages = self.block_mgr.detach_slot(slot)
        n_ctx = int(self.positions[slot])
        keep = pages_for(n_ctx, self.page_size)
        if len(pages) > keep:
            self.block_mgr.release(pages[keep:])
        self.handoffs.append(KVHandoff(
            uid=req.uid, prompt=req.prompt, gen=req.gen, rng=req.rng,
            tokens=list(req.tokens), pages=pages[:keep], prefill_len=n_ctx,
            valid_range=self._lane_valid[slot],
        ))
        self.handoffs_exported += 1
        self.slot_req[slot] = None

    def take_handoffs(self) -> list:
        """Drain the export queue (router-facing): every handoff built since
        the last call, in admission order."""
        out = list(self.handoffs)
        self.handoffs.clear()
        return out

    def export_page_block(self, h: KVHandoff):
        """Gather one handoff's source pages into the transferable page block
        the destination engine scatters (``adopt_handoff``). The block is
        table-width (``max_pages`` — ONE compiled gather for every handoff
        size); entries past the handoff's real pages are clamped padding the
        import drops, so only ``h.pages`` ever lands anywhere."""
        mgr = self.block_mgr
        read_ids = np.zeros((mgr.max_pages,), np.int32)
        read_ids[: len(h.pages)] = h.pages
        return self._export_pages_fn(
            self.cache, jnp.asarray(read_ids), scan_layers=self.cfg.scan_layers
        )

    def release_handoff(self, h: KVHandoff) -> int:
        """Drop a handoff record's page references on THIS (source) engine;
        pages free when nothing else holds them. Returns pages freed."""
        return self.block_mgr.release(h.pages)

    def can_adopt_handoff(self, h: KVHandoff) -> bool:
        """Would :meth:`adopt_handoff` land right now? (A free lane AND the
        pool covering the transient import peak.) The router checks this
        BEFORE gathering/transferring the page block — a deferred adoption
        must not pay (or telemeter) a device copy it then throws away."""
        if self.role == "prefill" or not self.paged:
            return False
        if not any(r is None for r in self.slot_req):
            return False
        mgr = self.block_mgr
        n_full = h.prefill_len // self.page_size
        remaining = h.gen.max_new_tokens - len(h.tokens)
        n_lane_pages = mgr.demand(h.prefill_len + remaining + 1)
        return len(h.pages) + (n_lane_pages - n_full) <= mgr.free_pages

    def adopt_handoff(self, h: KVHandoff, block, on_token=None,
                      replay_tokens: bool = False):
        """Decode-side handoff admission: land a transferred page block in this
        engine's pool and start a decode lane EXACTLY where the prefill replica
        left off — no prefill runs here, ever.

        The adoption is the prefix-cache adoption path generalized across
        engines: the block is staged into import-owned pages, the lane ADOPTS
        the fully-covered context pages read-only (refcount++, never written —
        decode writes start at ``prefill_len``), a partial boundary page is
        re-materialized as an owned COPY (COW at the divergence point, the
        ``_PagedPrefix`` semantics), and the import's references drop — full
        pages then belong to the lane, the boundary original frees. Budget
        pages are allocated fresh.

        Returns the engine :class:`Request` occupying the lane, or ``None``
        when the admission must DEFER (no free lane, or pool pressure — the
        defer counter moves; nothing is consumed either way).
        ``replay_tokens`` re-delivers the handoff's already-emitted tokens
        through ``on_token`` (re-adoption after a decode-replica death, after
        the router's ``on_retry`` stream reset)."""
        if self.role == "prefill":
            raise RuntimeError("a prefill-role engine cannot adopt handoffs")
        if not self.paged:
            raise RuntimeError("handoff adoption needs the paged KV cache")
        slot = next(
            (i for i, r in enumerate(self.slot_req) if r is None), None)
        if slot is None:
            return None
        mgr = self.block_mgr
        ps = self.page_size
        n_src = len(h.pages)
        n_full = h.prefill_len // ps
        partial = h.prefill_len % ps != 0
        remaining = h.gen.max_new_tokens - len(h.tokens)
        if remaining <= 0 or not h.tokens:
            raise ValueError(
                f"handoff uid={h.uid} has no decode work (emitted "
                f"{len(h.tokens)}/{h.gen.max_new_tokens}) — it should have "
                "finished on the prefill replica"
            )
        # The lane's page reservation mirrors the mixed engine's worst case
        # (context + full residual budget, so there is NO mid-decode OOM path);
        # the transient import peak is the lane demand plus the boundary page's
        # short-lived original (released right after its COW copy).
        n_lane_tokens = h.prefill_len + remaining + 1
        n_lane_pages = mgr.demand(n_lane_tokens)
        if n_src + (n_lane_pages - n_full) > mgr.free_pages:
            mgr.defer_count += 1
            return None
        import_ids = mgr.import_pages(n_src)
        write_ids = np.full((mgr.max_pages,), mgr.SENTINEL, np.int32)
        write_ids[:n_src] = import_ids
        self.cache = self._import_pages_fn(
            self.cache, block, jnp.asarray(write_ids),
            scan_layers=self.cfg.scan_layers,
        )
        lane_ids = mgr.admit(slot, n_lane_tokens, adopted=import_ids[:n_full],
                             cow_partial=partial)
        if partial:
            # COW: the lane's first writable page starts as a copy of the
            # shared boundary page (context above it, fresh slots below).
            self.cache = self._copy_page_fn(
                self.cache, int(import_ids[n_full]), int(lane_ids[n_full]),
                scan_layers=self.cfg.scan_layers,
            )
        # Import stage complete: drop the importer's references — full pages
        # now belong solely to the lane, the boundary original frees.
        mgr.release(import_ids)
        v0, v1 = h.valid_range
        valid_row = np.zeros((self.max_len,), bool)
        valid_row[v0:v1] = True
        self.cache = self._lane_valid_fn(self.cache, slot, jnp.asarray(valid_row))
        req = Request(self._uid, h.prompt, h.gen, h.rng, on_token=on_token)
        self._uid += 1
        req.tokens = list(h.tokens)
        self.slot_req[slot] = req
        self.positions[slot] = h.prefill_len
        self.tokens[slot] = int(h.tokens[-1])
        self.admitted += 1
        self.handoffs_adopted += 1
        self._lane_valid[slot] = (v0, v1)
        if self.drafter is not None:
            # Mirror the engine lane's layout on the draft cache. Every
            # handoff layout is "context left-padded to width prefill_len"
            # (bucket/chunk: pad = total - len(prompt); prefix: pad = 0), so
            # ONE synthesized bucket plan reproduces it exactly — the draft
            # row's positions then index both caches, like any admission.
            # The pending token (h.tokens[-1]) is written by the first draft
            # decode step, exactly as after a normal admission.
            self.drafter.admit(slot, np.asarray(h.prompt, np.int32),
                               ("bucket", h.prefill_len))
        if replay_tokens and on_token is not None:
            # Re-adoption after a decode-replica death: the router already
            # fired the on_retry stream reset, so the handoff's tokens (the
            # prefill's first emission) re-deliver from position zero and the
            # final transcript stays byte-identical.
            for tok in h.tokens:
                on_token(int(tok))
        return req

    def _decode_spans(self, ph: EnginePhase, lanes, **shared) -> None:
        """The Tracer's "decode" span records of one dispatch: one per traced
        lane ``(request, its own attributes)``, all sharing ``ph``'s [t0, now],
        this dispatch's step index and the measured inter-dispatch gap. Only
        called while tracing."""
        t1 = ph.now()
        host_s = self._host_gap(ph.t0, t1)
        for req, own in lanes:
            ph.span(req.uid, "decode", t1, step=self.decode_steps, **shared,
                    **own, host_s=host_s)

    def _host_gap(self, t0: float, t1: float) -> float:
        """Measured inter-dispatch gap for this decode dispatch's spans: previous
        dispatch's end → this dispatch's start, on the tracer clock. 0.0 for the
        first dispatch of a trace (no previous end to measure from) and clamped
        at 0 (a virtual clock may not advance between steps). Only called while
        tracing — the disabled hot path keeps its two-attribute-read contract."""
        prev = self._last_dispatch_end
        self._last_dispatch_end = t1
        return round(max(0.0, t0 - prev), 9) if prev is not None else 0.0

    def _paged_walk(self, active: list[int]) -> dict:
        """What the paged-attention kernel walks at the first decode step of a
        dispatch, one layer's worth summed over the active lanes: ``pages_walked``
        table entries fetched, of which ``pages_live`` hold a key the lane's query
        may see (``ops.paged_attention.walk_range``, the rule both paged kernels
        share; the block and the window are the model's ``paged_walk_shape``). On a
        model that alternates banded and full layers this is a banded layer's."""
        from .ops.paged_attention import walk_range

        pool_dtype = jax.tree_util.tree_leaves(self.cache["layers"])[0].dtype
        block, window = self.model.paged_walk_shape(
            self.cfg, self.page_size, pool_dtype.itemsize, self.block_mgr.max_pages)
        _, blocks, pages = walk_range(
            self.positions[active],
            np.array([self._lane_valid[i][0] for i in active], np.int32),
            T=1, window=window, page_size=self.page_size, block=block)
        return {"pages_live": int(pages.sum()), "pages_walked": int(blocks.sum()) * block}

    # ---------------------------------------------------------------- decode loops
    # step() chooses among three: ``_multi_step`` (the scan, every cell's path),
    # ``_spec_multi`` (speculation fused into a scan) and ``_spec_step`` (speculation,
    # one round a dispatch). Each keeps what is its own — how it turns the program's
    # buffers into per-lane emissions; the lane arguments, the dense-or-paged
    # dispatch, the landing and the speculative record are built once, below.
    def _lane_args(self, active: list[int], key_window: int):
        """The per-lane arguments of a scan dispatch → ``(sampled, args)``.

        ``args`` are on the device and in the programs' order after the cache (and,
        paged, the block tables): each lane's pending token and write position, whether
        it is live, its remaining budget, its EOS id (−1: none), its next ``key_window``
        emission keys, its temperature, top-p and top-k. Scan step j of a sampled lane
        consumes the key of emission ``len(tokens)+j`` — the key ``generate()`` would
        draw that token with (the window is clamped at the final key: draws past the
        budget are frozen). ``sampled`` (any live lane samples) is the programs' static
        ``sample``; without it the keys are zeros nobody reads.

        With no lane active this is what :meth:`warm_programs` lowers each scan from:
        the shapes and dtypes of a dispatch are stated here and nowhere else."""
        B = self.max_slots
        active_mask = np.zeros((B,), bool)
        budgets = np.ones((B,), np.int32)   # idle lanes: frozen at step 0, never read
        eos_ids = np.full((B,), -1, np.int32)
        temps = np.zeros((B,), np.float32)
        top_ps = np.ones((B,), np.float32)
        top_ks = np.zeros((B,), np.int32)
        sampled = False
        key_rows: list = [None] * B
        for i in active:
            req = self.slot_req[i]
            active_mask[i] = True
            budgets[i] = req.gen.max_new_tokens - len(req.tokens)
            if req.gen.eos_token_id is not None:
                eos_ids[i] = req.gen.eos_token_id
            if req.gen.temperature > 0.0:
                sampled = True
                temps[i] = req.gen.temperature
                top_ps[i] = req.gen.top_p
                top_ks[i] = req.gen.top_k
                key_rows[i] = self._step_keys_window(req, len(req.tokens), key_window)
        if sampled:
            filler = jnp.zeros_like(
                next(k for k in key_rows if k is not None)
            )  # greedy/idle lanes: key bits are never consumed (temp 0 → argmax)
            keys = jnp.stack([k if k is not None else filler for k in key_rows])
        else:
            keys = jnp.zeros((B, key_window, 2), jnp.uint32)
        return sampled, tuple(jnp.asarray(a) for a in (
            self.tokens, self.positions, active_mask, budgets, eos_ids, keys,
            temps, top_ps, top_ks))

    def _tables(self) -> tuple:
        """What a paged program takes ahead of its lane arguments: the block tables,
        uploaded once a dispatch (host-side page allocation never rebuilds device
        state). Nothing over dense rows."""
        return (jnp.asarray(self.block_mgr.tables),) if self.paged else ()

    def _dispatch(self, name: str, active: list[int], tables: tuple, args: tuple,
                  walk=None, **statics):
        """Dispatch decode program ``name`` in this engine's KV layout — the ONE call
        site of a dense/paged pair — under its compile label, inside the
        ``engine.decode.dispatch`` phase (``walk``: its attributes) and behind the
        fault guard. → ``(t_guard, the program's outputs)``; the caller fetches, then
        checks the watchdog (``_post_dispatch(t_guard)``) before any token lands."""
        label, fn = self._decode_programs[name]
        t_guard = self._pre_dispatch("serving.decode", active)
        with compile_label(label), phase("engine.decode.dispatch", **(walk or {})):
            return t_guard, fn(self.params, self.cache, *tables, *args, cfg=self.cfg,
                               **self._layout_statics, **statics)

    def _land(self, active: list[int], emissions, **counted):
        """Land one dispatch's tokens → ``(finished requests, [(lane, its request,
        tokens it landed)] over the active lanes)``.

        ``emissions`` yields ``(lane, tokens)`` in generation order (step-major or
        round-major, lane-minor): each token is appended and streamed in that order,
        clamped to the lane's remaining budget (belt and braces over the programs' own
        masks: a gateway deadline acts only between dispatches, so nothing past the
        budget may surface). Then, per lane: the last token becomes the pending one
        (emitted, not yet written), the position advances by what landed, and EOS or an
        exhausted budget finishes the request and frees its lane and pages (a dense row
        is overwritten at the next admit). ``counted`` joins ``tokens`` on the
        ``engine.decode.drain`` phase."""
        landed = [0] * self.max_slots
        with phase("engine.decode.drain") as drain:
            for i, toks in emissions:
                req = self.slot_req[i]
                for tok in toks:
                    if len(req.tokens) >= req.gen.max_new_tokens:
                        break
                    req.tokens.append(tok)
                    landed[i] += 1
                    if req.on_token is not None:
                        req.on_token(tok)
            finished, lanes = [], []
            for i in active:
                req = self.slot_req[i]
                lanes.append((i, req, landed[i]))
                if landed[i]:
                    self.tokens[i] = req.tokens[-1]
                    self.positions[i] += landed[i]
                eos = req.gen.eos_token_id
                if ((eos is not None and req.tokens[-1] == eos)
                        or len(req.tokens) >= req.gen.max_new_tokens):
                    req.done = True
                    finished.append(req)
                    self.slot_req[i] = None
                    self._release_lane(i)
            # A lane at the end of its window stays inside the cache (its writes drop).
            self.positions = np.minimum(self.positions, self.max_len - 1)
            step_tokens = sum(landed)
            drain.set_metadata(tokens=step_tokens, **counted)
        self.decode_steps += 1
        self.decode_tokens += step_tokens
        return finished, lanes

    def _count_spec(self, rounds: int, lanes: int, proposed: int, accepted: int,
                    tokens: int) -> None:
        """Add one speculative dispatch to the acceptance counters and emit its
        ``serving.spec/v1`` record (after :meth:`_land`, so ``step`` is the causality
        key the dispatch's ``trace.span/v1`` decode spans and ``serving.kv/v1`` record
        carry)."""
        self.spec_proposed += proposed
        self.spec_accepted += accepted
        tel = self.telemetry
        if tel is None or not tel.enabled:
            return
        from .telemetry import TELEMETRY_REV

        tel.emit({
            "schema": SERVING_SPEC_SCHEMA,
            "telemetry_rev": TELEMETRY_REV,
            "step": self.decode_steps,
            "spec_k": self.spec_k,
            "rounds": rounds,  # the host loop is one round per dispatch
            "active_slots": lanes,
            "step_proposed": proposed,
            "step_accepted": accepted,
            "step_tokens": tokens,
            "proposed_total": self.spec_proposed,
            "accepted_total": self.spec_accepted,
            "spec_accept_rate": (
                round(self.spec_accepted / self.spec_proposed, 4)
                if self.spec_proposed else None
            ),
            "tokens_per_step": (
                round(self.decode_tokens / self.decode_steps, 4)
                if self.decode_steps else None
            ),
        })

    def _multi_step(self, active: list[int], ph: EnginePhase) -> list[Request]:
        """The decode loop: ``decode_steps = N >= 1`` decode steps in ONE dispatched
        scan (``serving.decode_multi``/``decode_multi_paged``), then ONE drain of
        the [N, B] token buffer.

        The program freezes finishing lanes in-scan (EOS / remaining-budget
        masking — a frozen lane's writes drop out of bounds, so the final
        emitted token is never written: the pending-token pattern), which is
        what makes the emitted streams the same at every N: greedy lanes ride
        the fused argmax, sampled lanes consume their emission-indexed key
        windows through the ``sampling_core`` filter ops (see ``_multi_select``).
        The drain is step-major, lane-minor — exact generation order, so
        ``on_token`` streaming transcripts equal the final token lists.
        Admission/eviction/deadlines act between super-steps; the fault boundary
        + watchdog wrap the whole dispatch, so fault attribution and bisection
        run at super-step granularity (docs/multistep_decode.md)."""
        N = self.multi_step
        with phase("engine.decode.prepare"):
            sampled, lane_args = self._lane_args(active, N)
            tables = self._tables()
            walk = self._paged_walk(active) if self.paged else None
        t_guard, (tok_buf, counts, self.cache, *model_counts) = self._dispatch(
            "decode_multi", active, tables, lane_args, walk,
            n_steps=N, sample=sampled)
        with phase("engine.decode.fetch"):
            tok_host = np.asarray(tok_buf).tolist()     # [N, B]
            counts_host = np.asarray(counts).tolist()   # [B]
            # what the model counted beside its tokens (its DECODE_COUNTERS), if anything
            model_counts = [np.asarray(c) for c in model_counts]
        self._post_dispatch(t_guard)  # watchdog check BEFORE any token lands
        finished, lanes = self._land(
            active,
            ((i, tok_host[j][i:i + 1]) for j in range(N) for i in active
             if j < counts_host[i]),
            **{name: int(v) for c in model_counts
               for name, v in zip(self.model.DECODE_COUNTERS, c)})
        if ph.tracer is not None:
            # One span per traced lane for the whole super-step: ``tokens`` is
            # that lane's real emission count, ``n_steps`` the fused depth, and
            # ``host_s`` the measured inter-dispatch gap — N tokens share ONE gap.
            self._decode_spans(
                ph, ((req, {"tokens": n}) for _, req, n in lanes),
                occupancy=len(active), n_steps=N)
        return finished

    def _spec_multi(self, active: list[int], ph: EnginePhase) -> list[Request]:
        """Fused speculative super-step: ``decode_steps=N`` draft→verify→accept
        rounds in ONE dispatched scan (``serving.spec_multi``/``spec_multi_paged``),
        then ONE drain of the [N, B, spec_k+1] token buffer — speculation with
        ZERO host involvement between rounds.

        Drafting runs in-scan (the resident n-gram gather over each lane's
        carried prompt+generated history), the verify is the fused
        [B, spec_k+1] forward as the scan body, and acceptance advances each
        lane's emission-key CURSOR by its own ``n_emit`` — so sampled lanes
        consume exactly the keys the host loop's ``_replay_round`` would, and
        emitted streams are BITWISE the host-loop spec path's (hence bitwise
        ``spec_k=0``; see docs/speculative_serving.md). The drain is
        round-major, lane-minor — the exact order N sequential ``_spec_step``
        calls would have appended. Admission/eviction/deadlines and the fault
        boundary + watchdog act at super-step granularity, exactly as in
        ``_multi_step``."""
        N = self.multi_step
        k = self.spec_k
        with phase("engine.decode.prepare"):
            # Per-lane key TABLE: the next N*(k+1) emission keys (the worst case —
            # N full acceptances); the scan's per-lane cursor (its emission count)
            # indexes into it.
            sampled, lane_args = self._lane_args(active, N * (k + 1))
            # Drafting history: prompt + generated so far, packed from column 0 —
            # compact token order, so it works unchanged with prefix-cached and
            # paged layouts (it is NOT the cache layout, just the token sequence).
            history = np.zeros((self.max_slots, self.max_len), np.int32)
            hist_lens = np.zeros((self.max_slots,), np.int32)
            for i in active:
                req = self.slot_req[i]
                ctx = np.concatenate(
                    [np.asarray(req.prompt, np.int32),
                     np.asarray(req.tokens, np.int32)]
                )[-self.max_len:]
                history[i, :len(ctx)] = ctx
                hist_lens[i] = len(ctx)
            lane_args += (jnp.asarray(history), jnp.asarray(hist_lens))
            tables = self._tables()
        t_guard, (tok_buf, emits, _, proposed, accepted, self.cache) = (
            self._dispatch("spec_multi", active, tables, lane_args,
                           n_steps=N, spec_k=k,
                           max_ngram=int(self.drafter.max_ngram), sample=sampled))
        with phase("engine.decode.fetch"):
            ref_host = np.asarray(tok_buf).tolist()    # [N, B, k+1]
            emits_host = np.asarray(emits).tolist()    # [N, B]
            prop_host = np.asarray(proposed)           # [B]
            acc_host = np.asarray(accepted)            # [B]
        self._post_dispatch(t_guard)  # watchdog check BEFORE any token lands
        finished, lanes = self._land(
            active, ((i, ref_host[r][i][:emits_host[r][i]])
                     for r in range(N) for i in active))
        if ph.tracer is not None:
            # As in ``_multi_step``, with the lane's ``proposed``/``accepted`` totals
            # over the N rounds.
            self._decode_spans(
                ph, ((req, {"tokens": n, "proposed": int(prop_host[i]),
                            "accepted": int(acc_host[i])}) for i, req, n in lanes),
                occupancy=len(active), n_steps=N)
        self._count_spec(N, len(active), int(prop_host.sum()), int(acc_host.sum()),
                         sum(n for _, _, n in lanes))
        return finished

    def _spec_step(self, active: list[int], ph: EnginePhase) -> list[Request]:
        """Speculative decode: propose → ONE fused verify → per-slot prefix acceptance.

        Per active slot the emitted tokens are exactly the first ``n_emit`` columns of
        that slot's reference row (fused argmax for greedy, sampler replay or Leviathan
        accept for sampled): accepted proposals EQUAL their reference tokens, and the
        first mismatch column already holds the correction — so emission is a single
        slice, with EOS truncation and the generation budget applied on top. The budget
        cap also bounds every load-bearing cache write to ``prefill + max_new - 2 <
        max_len``, so lanes near their window end can never depend on a dropped
        out-of-bounds draft write."""
        k = self.spec_k
        T = k + 1
        with phase("engine.decode.prepare"):
            proposals = np.asarray(
                self.drafter.propose(self.slot_req, self.tokens, self.positions, k),
                np.int32,
            )
            seq = np.zeros((self.max_slots, T), np.int32)
            seq[:, 0] = self.tokens  # pending token: emitted last step, not yet written
            seq[:, 1:] = proposals
            tables = self._tables()
        t_guard, (greedy, logits, self.cache) = self._dispatch(
            "spec_verify", active, tables,
            (jnp.asarray(seq), jnp.asarray(self.positions)))
        with phase("engine.decode.fetch"):
            greedy_host = np.asarray(greedy)  # [B, T]
        self._post_dispatch(t_guard)  # watchdog check BEFORE any token lands
        emissions = []
        accepted = {}
        for i in active:
            req = self.slot_req[i]
            # Budget cap: emitting more would overrun the validated cache window.
            limit = min(T, req.gen.max_new_tokens - len(req.tokens))
            if req.gen.temperature > 0.0 and self.spec_accept == "residual":
                emitted_vec, count = self._residual_round(req, logits[i], proposals[i])
                emitted = [int(t) for t in emitted_vec[: min(int(count), limit)]]
            else:
                ref = (greedy_host[i] if req.gen.temperature <= 0.0
                       else self._replay_round(req, logits[i]))
                n = 0
                while n < k and proposals[i, n] == ref[n]:
                    n += 1
                emitted = [int(t) for t in ref[: min(n + 1, limit)]]
            eos = req.gen.eos_token_id
            if eos is not None and eos in emitted:
                emitted = emitted[: emitted.index(eos) + 1]
            emissions.append((i, emitted))
            # Accepted = emitted tokens that were draft proposals (the trailing
            # correction/bonus is the target's own, never a proposal credit).
            accepted[i] = sum(
                1 for j, t in enumerate(emitted) if j < k and t == int(proposals[i, j])
            )
        finished, lanes = self._land(active, emissions)
        if ph.tracer is not None:
            self._decode_spans(
                ph, ((req, {"tokens": n, "proposed": k,
                            "accepted": accepted[i]}) for i, req, n in lanes),
                occupancy=len(active))
        self._count_spec(1, len(active), k * len(active), sum(accepted.values()),
                         sum(n for _, _, n in lanes))
        return finished

    def _step_keys_window(self, req: Request, start: int, T: int):
        """[T] slice of the request's per-emission key schedule beginning at emission
        ``start``, clamped at the final key — positions past the generation budget are
        verify-row surplus whose draws are computed and discarded (never emitted, and
        their keys are never consumed by a retained draw)."""
        ks = req._step_keys
        idx = np.minimum(start + np.arange(T), ks.shape[0] - 1)
        return ks[idx]

    def _replay_round(self, req: Request, logits_rows) -> np.ndarray:
        """Sampled-slot REPLAY reference row: the tokens plain ``spec_k=0`` decode
        would draw at each verify position, using the request's own key schedule
        (emission m consumes key m — the invariant that makes speculative sampled
        output bitwise identical to the plain engine's)."""
        keys = self._step_keys_window(req, len(req.tokens), self.spec_k + 1)
        return np.asarray(_replay_draws(
            logits_rows, keys, req.gen.temperature, req.gen.top_p, top_k=req.gen.top_k
        ))

    def _residual_round(self, req: Request, logits_rows, drafts):
        """Sampled-slot Leviathan accept/reject (``spec_accept="residual"``): one
        fused dispatch returns (emitted row, count). Lossless in DISTRIBUTION (each
        emitted token is marginally the target's own sampling distribution), not
        bitwise — emission m still consumes key m, but through accept/residual draws
        instead of a direct categorical."""
        keys = self._step_keys_window(req, len(req.tokens), self.spec_k + 1)
        emitted, count = _spec_residual_jit(
            logits_rows, jnp.asarray(drafts), keys,
            req.gen.temperature, req.gen.top_p, top_k=req.gen.top_k,
        )
        return np.asarray(emitted), int(count)

    def run(self, report_throughput: bool = False):
        """Drain queue + active slots; returns finished requests (and tokens/s).

        ``report_throughput`` routes the aggregate through the telemetry pipeline
        (a ``serving.throughput/v1`` record alongside the per-step counter records)
        when one is attached, instead of any caller-side printing — and still
        returns ``(requests, tokens_per_sec)`` for direct use.
        """
        out = []
        t0 = time.perf_counter()
        while (self.queue or self._bisect_hold
               or any(r is not None for r in self.slot_req)):
            out.extend(self.step())
        dt = time.perf_counter() - t0
        if report_throughput:
            n_tokens = sum(len(r.tokens) for r in out)  # every request drains in run()
            tokens_per_sec = n_tokens / dt if dt > 0 else float("inf")
            self._emit_telemetry(
                {
                    "schema": SERVING_THROUGHPUT_SCHEMA,
                    "wall_s": round(dt, 6),
                    "tokens_generated": n_tokens,
                    "requests_finished": len(out),
                    "tokens_per_sec": round(tokens_per_sec, 3)
                    if tokens_per_sec != float("inf")
                    else None,
                }
            )
            return out, tokens_per_sec
        return out

    def warm_programs(self, max_new_tokens: int = 32) -> list:
        """Pre-compile this engine's whole program surface into the AOT cache
        WITHOUT executing anything (``python -m accelerate_tpu warmup --serve``).

        Covers: the decode scan at this engine's ``decode_steps`` in both ``sample``
        variants (a mixed workload alternates greedy-only and sampled super-steps),
        with ``spec_k > 0`` the fused [B, spec_k+1] speculative verify plus the
        draft source's own programs (draft AND verify ride the same bucket ladder
        and warmup manifest, so a spec-enabled replica restart compiles nothing)
        and, where the drafter is resident, the FUSED speculative super-step pair
        (``serving.spec_multi[_paged]`` — the program such an engine actually
        dispatches; verify + decode_multi stay warm as its degradation
        targets), one prefill per bucket
        that ``_plan_prefill`` can actually route a ``max_new_tokens``-budget
        request to, the first-chunk + chunk-append pair (the fallback for
        prompts/budgets no bucket fits — always part of the live surface), and
        the row-insert programs — per-slot scatters dense, the single
        dynamic-slot page scatter (plus prefix gather/copy) paged. An engine
        warms ITS surface, nothing it cannot dispatch; the manifest's page
        geometry records which layout the cache directory is warm for. Returns
        warmup-manifest entries; empty when no enabled compile cache is attached."""
        if self.compile_cache is None:
            return []
        entries = []
        if self.role != "prefill":
            # The decode programs of this layout, lowered from the lane arguments a
            # dispatch with no live lane would upload. Role engines warm THEIR slice
            # of the surface: a prefill-role replica never dispatches decode.
            tables = self._tables()

            def warm(name, args, **statics):
                _, fn = self._decode_programs[name]
                entries.append(fn.warm(self.params, self.cache, *tables, *args,
                                       cfg=self.cfg, **self._layout_statics, **statics))

            # Both sample variants: the engine picks per super-step by whether any
            # live lane samples, so a mixed workload needs the pair warm.
            _, lane_args = self._lane_args([], self.multi_step)
            for sample in (False, True):
                warm("decode_multi", lane_args, n_steps=self.multi_step, sample=sample)
            if self.spec_k:
                warm("spec_verify", (
                    jnp.zeros((self.max_slots, self.spec_k + 1), jnp.int32),
                    jnp.asarray(self.positions)))
                if self._spec_fused():
                    # The fused spec super-step pair: what this engine dispatches
                    # while spec_enabled; the host-loop verify above stays warm as
                    # its degradation target alongside decode_multi.
                    _, lane_args = self._lane_args(
                        [], self.multi_step * (self.spec_k + 1))
                    lane_args += (jnp.zeros((self.max_slots, self.max_len), jnp.int32),
                                  jnp.zeros((self.max_slots,), jnp.int32))
                    for sample in (False, True):
                        warm("spec_multi", lane_args, n_steps=self.multi_step,
                             spec_k=self.spec_k,
                             max_ngram=int(self.drafter.max_ngram), sample=sample)
                entries.extend(self.drafter.warm_programs(self, max_new_tokens))
        if self.paged:
            # Paged surface: the dynamic-slot page scatter (ONE program for every
            # slot/row — the table made the lane index data) and, with prefix
            # caching, the page gather + partial-page copy. Prefill programs below
            # are layout-shared with dense. A decode-role replica has no
            # prefill/insert programs at all (the handoff import + COW copy +
            # lane-valid setup replace them), and a prefill-role replica warms the
            # page-export gather.
            write_ids = jnp.zeros((self.block_mgr.max_pages,), jnp.int32)
            if self.role == "decode":
                page_axis = 1 if self.cfg.scan_layers else 0
                block = jax.tree_util.tree_map(
                    lambda pool: jnp.zeros(
                        pool.shape[:page_axis]
                        + (self.block_mgr.max_pages,)
                        + pool.shape[page_axis + 1:],
                        pool.dtype,
                    ),
                    self.cache["layers"],
                )
                entries.append(self._import_pages_fn.warm(
                    self.cache, block, write_ids,
                    scan_layers=self.cfg.scan_layers,
                ))
                entries.append(self._copy_page_fn.warm(
                    self.cache, 0, 0, scan_layers=self.cfg.scan_layers,
                ))
                entries.append(self._lane_valid_fn.warm(
                    self.cache, 0, jnp.zeros((self.max_len,), bool),
                ))
                return entries  # no prefill surface, by construction
            row0 = self.model.init_cache(self.cfg, 1, self.max_len)
            entries.append(self._insert_paged_fn.warm(
                self.cache, row0, write_ids, 0,
                page_size=self.page_size, scan_layers=self.cfg.scan_layers,
            ))
            if self.role == "prefill":
                entries.append(self._export_pages_fn.warm(
                    self.cache, write_ids, scan_layers=self.cfg.scan_layers,
                ))
            if self.prefix_cache_size:
                entries.append(self._gather_row_fn.warm(
                    self.cache, write_ids, 0,
                    page_size=self.page_size, scan_layers=self.cfg.scan_layers,
                ))
                entries.append(self._copy_page_fn.warm(
                    self.cache, 0, 0, scan_layers=self.cfg.scan_layers,
                ))
        if self.prompt_buckets is not None and not self.prefix_cache_size:
            # Only buckets a request with this generation budget can land in —
            # a bucket with b + max_new > max_len is unreachable via _plan_prefill.
            widths = [b for b in self.prompt_buckets
                      if b + max_new_tokens <= self.max_len]
        else:
            widths = []
        row_cache = None
        if self.prefix_cache_size:
            row = jnp.zeros((1, self.prompt_bucket), jnp.int32)
            mask = jnp.zeros((1, self.prompt_bucket), bool)
            entries.append(self._prefill_full_logits_fn.warm(
                self.params, row, mask, cfg=self.cfg, max_len=self.max_len
            ))
            row_cache = self.model.init_cache(self.cfg, 1, self.max_len)
            entries.append(self._prefill_chunk_keep_fn.warm(
                self.params, row, mask, row_cache, cfg=self.cfg
            ))
        else:
            for width in widths:
                row = jnp.zeros((1, width), jnp.int32)
                mask = jnp.zeros((1, width), bool)
                entries.append(self._prefill_fn.warm(
                    self.params, row, mask, cfg=self.cfg, max_len=self.max_len
                ))
            if self.prompt_bucket + max_new_tokens <= self.max_len:
                # The chunked pair serves every prompt the ladder can't (and ALL
                # prompts when no ladder is configured). Skipped when even one
                # chunk + budget overflows the cache — _plan_prefill would reject
                # every such request, so the programs are unreachable.
                row = jnp.zeros((1, self.prompt_bucket), jnp.int32)
                mask = jnp.zeros((1, self.prompt_bucket), bool)
                entries.append(self._prefill_fn.warm(
                    self.params, row, mask, cfg=self.cfg, max_len=self.max_len
                ))
                row_cache = self.model.init_cache(self.cfg, 1, self.max_len)
                entries.append(self._prefill_chunk_fn.warm(
                    self.params, row, mask, row_cache, cfg=self.cfg
                ))
        if not self.paged:
            if row_cache is None:
                row_cache = self.model.init_cache(self.cfg, 1, self.max_len)
            for slot in range(self.max_slots):
                entries.append(self._insert_row_fn.warm(
                    self.cache, row_cache, slot=slot, scan_layers=self.cfg.scan_layers
                ))
        return entries

    # ------------------------------------------------------------------ internals
    def _plan_prefill(self, prompt_len: int, max_new: int):
        """Pick the prefill layout for one prompt: ``("bucket", width)`` when the
        bucket ladder is active and a rung fits prompt + generation budget,
        ``("chunk", total)`` for the chunked path; raises when neither fits.

        Prompts that overflow every bucket (or whose budget only fits under the
        tighter chunk padding) quietly fall back to chunked prefill — bucketing
        bounds the compile surface for the common case, it must never shrink the
        admissible request set.
        """
        if self.prompt_buckets is not None and not self.prefix_cache_size:
            bucket = pick_bucket(prompt_len, self.prompt_buckets)
            if bucket is not None and bucket + max_new <= self.max_len:
                return "bucket", bucket
        n_chunks = max(1, -(-prompt_len // self.prompt_bucket))
        total = n_chunks * self.prompt_bucket
        if total + max_new > self.max_len:
            raise ValueError(
                f"prompt ({prompt_len} tokens → {n_chunks} chunks of "
                f"{self.prompt_bucket}) + max_new_tokens={max_new} exceeds "
                f"max_len={self.max_len}"
            )
        return "chunk", total

    def _admit(self) -> list[Request]:
        finished = []
        for slot in range(self.max_slots):
            # A request can finish AT admission (its first token hits EOS or
            # max_new_tokens == 1), freeing the slot for the next queued request — hence
            # the inner loop per slot, and such requests are reported like any other.
            while self.slot_req[slot] is None and self.queue:
                # PEEK, don't pop: a paged admission can defer on pool pressure, and
                # the head request must keep its place (FIFO — later arrivals never
                # jump a request waiting for pages).
                req = self.queue[0]
                # Recovery re-admission: the context is prompt + already-emitted
                # tokens and the budget is what REMAINS — for a first admission
                # both reduce to the historical prompt/max_new values exactly.
                ctx = req._recover_ctx if req._recover_ctx is not None else req.prompt
                remaining = req.gen.max_new_tokens - len(req.tokens)
                # ONE plan decision per admission, threaded to the engine prefill AND
                # the drafter — the draft cache layout must mirror the engine row's,
                # so the two must never derive it independently.
                try:
                    if self.prefix_cache_size:
                        plan = None
                        if req._recover_ctx is not None:
                            # Prefix engines skip _plan_prefill; a recovery
                            # context that outgrew the cache must still fail
                            # machine-readably, not scribble past max_len.
                            chunks = max(1, -(-len(ctx) // self.prompt_bucket))
                            total = chunks * self.prompt_bucket
                            if total + remaining > self.max_len:
                                raise ValueError(
                                    f"recovery context ({len(ctx)} tokens → "
                                    f"{total} padded) + remaining budget "
                                    f"{remaining} exceeds max_len={self.max_len}"
                                )
                    else:
                        plan = self._plan_prefill(len(ctx), remaining)
                except ValueError as e:
                    if req._recover_ctx is None or not self.recover:
                        raise
                    # Recovery geometry can overflow where the original prompt
                    # fit (chunk padding of the grown context): fail THIS
                    # request machine-readably, keep serving the rest.
                    self.queue.popleft()
                    finished.append(
                        self._quarantine(req, f"recovery_unservable:{e}")
                    )
                    continue
                fp = self.faults
                if fp is not None and self.recover:
                    spec = fp.draw("serving.prefill", uid=req.uid)
                    if spec is not None and spec.kind == "crash":
                        self.crashed = True
                        raise EngineCrashed("serving.prefill", uid=req.uid)
                    if spec is not None:
                        # A prefill failure is ALWAYS attributable: the fault
                        # fired admitting exactly this request. Nothing was
                        # dispatched, so no rebuild — quarantine and continue.
                        self.queue.popleft()
                        self.step_failures += 1
                        self._emit_fault("serving.prefill", spec.kind, req.uid,
                                         reason=f"injected:{spec.kind}")
                        finished.append(
                            self._quarantine(req, f"prefill_fault:{spec.kind}")
                        )
                        continue
                reserved = None
                if self.paged:
                    try:
                        reserved = self._reserve_paged(plan, ctx, remaining)
                    except Exception as e:
                        return self._prefill_failed(req, e, finished)
                    if reserved is None:
                        # Page pool exhausted: every admission waits until lanes finish
                        # and free pages (the defer counter moved). Nothing was consumed.
                        with EnginePhase(self.tracer, "engine.defer", uid=req.uid) as ph:
                            if ph.tracer is not None:
                                ph.tracer.count_defer(req.uid)
                        return finished
                # plan is None on a prefix-cache engine (_plan_prefill is skipped):
                # its rows are whole chunks of the prompt bucket.
                width = plan[1] if plan is not None else (
                    max(1, -(-len(ctx) // self.prompt_bucket)) * self.prompt_bucket)
                with EnginePhase(self.tracer, "engine.prefill", uid=req.uid,
                                 prompt_len=len(ctx), width=int(width)) as ph:
                    tracing = ph.tracer is not None
                    hits0, chunks0 = self.prefix_hits, self.prefill_chunks
                    if tracing:
                        cow0 = self.block_mgr.cow_count if self.paged else 0
                        adopt0 = self.block_mgr.adopt_count if self.paged else 0
                    try:
                        greedy_dev, logits_dev, prefill_len = self._prefill_into_slot(
                            slot, req, plan, ctx, remaining, reserved)
                    except Exception as e:
                        return self._prefill_failed(req, e, finished)
                    self.queue.popleft()
                    hit = self.prefix_hits > hits0
                    # Without a plan the path actually run is a prefix-snapshot
                    # resume only when the registry hit — a cold prompt ran the
                    # right-aligned chunked prefill.
                    mode = plan[0] if plan is not None else (
                        "prefix" if hit else "chunk")
                    ph.set_metadata(chunks=self.prefill_chunks - chunks0, mode=mode)
                    if req._recover_ctx is None:
                        self.queue_waits.append(
                            max(0.0, time.monotonic() - req.enqueued_at)
                        )
                        ph.set_metadata(queue_wait_ms=1e3 * self.queue_waits[-1])
                    with phase("engine.prefill.fetch", uid=req.uid):
                        first = (
                            int(np.asarray(greedy_dev)[0])   # fused on-device argmax (4 bytes)
                            if req.gen.temperature <= 0.0
                            else req._sample(logits_dev[0])
                        )
                    if self.drafter is not None:
                        # Same lane, same padded layout: the draft cache row must mirror
                        # the engine row so engine positions index both.
                        self.drafter.admit(slot, ctx, plan)
                    self.admitted += 1
                    if req._recover_ctx is not None:
                        # Recovery re-admission succeeded: the prefill replayed
                        # prompt + emitted tokens and `first` IS the next emission.
                        req._recover_ctx = None
                        req.recoveries += 1
                        self.recovered_admissions += 1
                        self.recovered_uids.add(req.uid)
                        self._emit_recovery("readmit", uid=req.uid,
                                            tokens_kept=len(req.tokens))
                    self.slot_req[slot] = req
                    self.positions[slot] = prefill_len  # next write = first decode slot
                    self.tokens[slot] = first
                    req.tokens.append(int(first))
                    if req.on_token is not None:
                        req.on_token(int(first))
                    if tracing:
                        # Span closes AFTER the first token is extracted and streamed:
                        # the device sync that produces it is prefill cost the client
                        # waits on, so queue.dur + prefill.dur reconstructs TTFT.
                        tracer = ph.tracer
                        handle = tracer.handle_for(req.uid)
                        tracer.event(
                            handle, "admit", t=ph.t0, lane=slot,
                            kv_defer_retries=handle.kv_defers if handle else 0,
                        )
                        ph.span(
                            req.uid, "prefill", ph.now(),
                            mode=mode, width=int(width), prompt_len=len(ctx),
                            prefix_hit=hit,
                            cow=(self.block_mgr.cow_count - cow0) if self.paged else 0,
                            adopted_pages=(
                                (self.block_mgr.adopt_count - adopt0) if self.paged else 0
                            ),
                        )
                hit_eos = req.gen.eos_token_id is not None and int(first) == req.gen.eos_token_id
                if hit_eos or len(req.tokens) >= req.gen.max_new_tokens:
                    req.done = True
                    finished.append(req)
                    self.slot_req[slot] = None
                    self._release_lane(slot)
                    self.evicted += 1  # finished AT admission still cycled the slot
        return finished

    def _prefill_failed(self, req: Request, e: Exception,
                        finished: list[Request]) -> list[Request]:
        """Called while handling ``e``, raised admitting the head request
        ``req``: without recovery (or on whole-engine death, the fleet router's
        problem) it goes on up; else the request is quarantined — attribution
        is certain — and, since the row insert may have consumed the donated
        cache, the survivors are rebuilt. → ``finished``, for ``_admit`` to
        return."""
        if isinstance(e, EngineCrashed) or not self.recover:
            raise
        self.queue.popleft()
        self.step_failures += 1
        kind = getattr(e, "kind", type(e).__name__)
        self._emit_fault(getattr(e, "site", "serving.prefill"),
                         kind, req.uid, reason=str(e))
        finished.append(self._quarantine(req, f"prefill_fault:{kind}"))
        if not getattr(e, "pre_dispatch", False):
            self._rebuild_survivors()
        return finished

    def _prefill_into_slot(self, slot: int, req: Request, plan, ctx, remaining: int,
                           reserved=None):
        """Run one request's prefill and land its KV in lane ``slot`` →
        ``(greedy_dev, logits_dev, prefill_len)``. ``reserved`` is what
        :meth:`_reserve_paged` returned for this admission (paged engines).

        ``ctx``/``remaining`` are the admission context and generation budget —
        the request's prompt and full budget normally, prompt + emitted tokens
        and the residual budget on a recovery re-admission.

        Dense: the historical path — single-row prefill, compiled per-slot row
        scatter. Paged: allocate pages (adopting refcounted shared-prefix pages on a
        registry hit), prefill the SAME dense row (identical compute → identical
        tokens), scatter it into the owned pages through the write-id map, then
        register this prompt's prefixes as page lists."""
        if not self.paged:
            row_cache, greedy_dev, logits_dev, prefill_len = self._prefill(
                ctx, remaining, plan
            )
            # graftlint: disable=recompile-hazard(slot indexes a compile-time cache row; at most max_slots variants, admission-time only)
            self.cache = self._insert_row_fn(self.cache, row_cache, slot=slot, scan_layers=self.cfg.scan_layers)
            return greedy_dev, logits_dev, prefill_len
        return self._prefill_into_slot_paged(slot, req, plan, ctx, remaining,
                                             reserved)

    # ---------------------------------------------------------------- paged admission
    def _reserve_paged(self, plan, ctx, remaining: int):
        """The pool's half of a paged admission, before any device work: the
        prefix lookup, the pages the lane would adopt and own, and room for
        them. → None when the admission must defer (nothing consumed; the
        request stays queued), else ``(hit_len, entry, lookup_chunks, total,
        adopted, cow_partial, n_tokens)`` for :meth:`_prefill_into_slot_paged`."""
        mgr = self.block_mgr
        ps = self.page_size
        max_new = remaining
        hit_len, entry = 0, None
        lookup_chunks = 0
        if self.prefix_cache_size:
            bucket = self.prompt_bucket
            n_chunks = max(1, -(-len(ctx) // bucket))
            total = n_chunks * bucket
            hit_len, entry, lookup_chunks = self._lookup_prefix_paged(
                ctx, n_chunks
            )
        else:
            _, total = plan
        # Full pages of the shared prefix are ADOPTED (refcount++, read-only); a
        # prefix boundary cutting a page mid-way re-materializes that partial page
        # as an owned fresh one — copy-on-write at the divergence point (the row
        # scatter below fills it, so no device copy runs on this direction).
        adopted = [] if entry is None else list(entry.pages[: hit_len // ps])
        cow_partial = hit_len > 0 and hit_len % ps != 0
        # A prefill-role engine never decodes: its lanes hold the CONTEXT pages
        # only (the decode replica charges the budget pages at adoption —
        # reserving them here too would double-count KV, the disagg admission
        # fix in kv_demand).
        n_tokens = total if self.role == "prefill" else total + max_new
        # Pool pressure: the prefix registry is a CACHE and yields to live
        # traffic — evict LRU entries (releasing their page references) before
        # deferring. Without this, registry-held pages could starve admission
        # FOREVER once every lane drains (deferral waits on lanes to free pages,
        # and none are active). Last resort: the adopted entry itself yields and
        # the request retries as a cold miss — the submit-time KVBudgetError
        # bound guarantees the bare request fits an otherwise-empty pool.
        while not mgr.can_admit(n_tokens, n_adopted=len(adopted)):
            if self._evict_prefix_lru(keep=entry):
                continue
            if entry is not None:
                hit_len, entry, adopted, cow_partial = 0, None, [], False
                self._evict_prefix_lru()
                continue
            mgr.defer_count += 1
            return None
        return hit_len, entry, lookup_chunks, total, adopted, cow_partial, n_tokens

    def _prefill_into_slot_paged(self, slot: int, req: Request, plan, ctx,
                                 remaining: int, reserved):
        mgr = self.block_mgr
        ps = self.page_size
        max_new = remaining
        hit_len, entry, lookup_chunks, total, adopted, cow_partial, n_tokens = reserved
        # Count the prefix outcome only now, when this admission actually
        # proceeds: a deferred request re-runs the lookup every step() while it
        # waits, and counting there would inflate hits/misses N-fold under
        # exactly the pool-pressure conditions these stats exist to diagnose.
        # The count also reflects what was SERVED: an adoption dropped by the
        # pressure loop above lands as a miss, not the hit it briefly found.
        if lookup_chunks:
            if entry is not None:
                self.prefix_hits += 1
                self._prefix_reg.move_to_end(ctx[:hit_len].tobytes())
            else:
                self._classify_prefix_miss(ctx, lookup_chunks)
        if self.prefix_cache_size:
            # hit_len == 0 and entry is None on a miss — the same call covers both.
            row_cache, greedy_dev, logits_dev, prefill_len = self._prefill_prefix_paged(
                ctx, hit_len, entry, total // self.prompt_bucket, total
            )
        else:
            row_cache, greedy_dev, logits_dev, prefill_len = self._prefill(
                ctx, max_new, plan
            )
        fp = self.faults
        if fp is not None and self.recover:
            spec = fp.draw("serving.kv_admit", uid=req.uid)
            if spec is not None:
                # Injected page-pool allocation failure: raised BEFORE admit
                # touches the manager, so nothing leaks; the admission
                # boundary quarantines this request (always attributable).
                raise fp.fault_for(spec, "serving.kv_admit", uid=req.uid)
        ids = mgr.admit(slot, n_tokens, adopted=adopted, cow_partial=cow_partial)
        # The lane's valid layout (what a handoff must carry — the dense row's
        # mask is gone once a prefill-role lane exports): prefix layout is
        # LEFT-aligned ([0, len)), bucket/chunk layouts are left-PADDED
        # ([pad, total)).
        self._lane_valid[slot] = (
            (0, len(ctx)) if self.prefix_cache_size
            else (total - len(ctx), total)
        )
        # Row scatter: sentinel out the adopted pages (never written) and everything
        # past the row's own extent; decode writes continue directly into the
        # remaining allocated pages.
        n_adopted = len(adopted)
        n_row_pages = pages_for(total, ps)
        write_ids = np.full((mgr.max_pages,), mgr.SENTINEL, np.int32)
        write_ids[n_adopted:n_row_pages] = ids[n_adopted:n_row_pages]
        self.cache = self._insert_paged_fn(
            self.cache, row_cache, jnp.asarray(write_ids), slot,
            page_size=ps, scan_layers=self.cfg.scan_layers,
        )
        if self.prefix_cache_size:
            self._register_prefixes_paged(slot, ctx)
        return greedy_dev, logits_dev, prefill_len

    def _lookup_prefix_paged(self, prompt: np.ndarray, n_chunks: int):
        """Longest registered full-chunk prefix of ``prompt`` →
        ``(hit length, entry, lookup_chunks)``.

        Capped at ``n_chunks - 1`` chunks: the final chunk is always recomputed so
        its logits exist (the dense path replays it from the shorter snapshot —
        identical compute, without needing that shorter entry to still be live).
        Counter-free and LRU-neutral: a deferred admission repeats this lookup
        every step, so hit/miss accounting (and the LRU touch) happen at the ONE
        point the admission proceeds (``_prefill_into_slot_paged``);
        ``lookup_chunks`` > 0 tells the caller a countable lookup happened."""
        bucket = self.prompt_bucket
        full_chunks = min(len(prompt) // bucket, n_chunks - 1)
        for k in range(full_chunks, 0, -1):
            hit = self._prefix_reg.get(prompt[: k * bucket].tobytes())
            if hit is not None:
                return k * bucket, hit, full_chunks
        return 0, None, full_chunks

    def _classify_prefix_miss(self, prompt: np.ndarray, full_chunks: int) -> None:
        """Count one prefix miss, split capacity (key was evicted) vs cold key."""
        self.prefix_misses += 1
        bucket = self.prompt_bucket
        if any(
            prompt[: k * bucket].tobytes() in self._evicted_keys
            for k in range(full_chunks, 0, -1)
        ):
            self.prefix_capacity_misses += 1
        else:
            self.prefix_key_misses += 1

    def _prefill_prefix_paged(self, prompt: np.ndarray, hit_len: int, entry,
                              n_chunks: int, total: int):
        """Right-aligned chunked prefill resuming from a page-list prefix entry.

        On a hit, the entry's pages (full pages + the registry's immutable partial
        boundary copy, if any) are gathered back into the dense row layout — a
        bandwidth-only copy that skips the prefix's prefill FLOPs — and the
        remaining chunks run the ordinary keep-alive chunk program. The caller
        scatters the finished row into the lane's own pages."""
        bucket = self.prompt_bucket
        row = np.zeros((1, total), np.int32)
        row[0, : len(prompt)] = prompt
        mask = np.zeros((1, total), bool)
        mask[0, : len(prompt)] = True
        start = hit_len // bucket
        cache = None
        if entry is not None:
            read_ids = np.full((self.block_mgr.max_pages,), self.block_mgr.SENTINEL,
                               np.int32)
            read_ids[: len(entry.pages)] = entry.pages
            cache = self._gather_row_fn(
                self.cache, jnp.asarray(read_ids), hit_len,
                page_size=self.page_size, scan_layers=self.cfg.scan_layers,
            )
        logits = None
        self.prefill_chunks += n_chunks - start
        for c in range(start, n_chunks):
            sl = slice(c * bucket, (c + 1) * bucket)
            if cache is None:
                logits, cache = self._prefill_full_logits_fn(
                    self.params, jnp.asarray(row[:, sl]), jnp.asarray(mask[:, sl]),
                    cfg=self.cfg, max_len=self.max_len,
                )
            else:
                logits, cache = self._prefill_chunk_keep_fn(
                    self.params, jnp.asarray(row[:, sl]), jnp.asarray(mask[:, sl]),
                    cache, cfg=self.cfg,
                )
        last_col = (len(prompt) - 1) % bucket
        last = logits[:, last_col, :]
        greedy = jnp.argmax(last, axis=-1).astype(jnp.int32)
        return cache, greedy, last, len(prompt)

    def _register_prefixes_paged(self, slot: int, prompt: np.ndarray) -> None:
        """Register every full-chunk prefix of ``prompt`` as a refcounted page list.

        Unlike the dense registry (whole row-cache snapshots — max_len × layers
        bytes per ENTRY), a paged entry is the page ids covering the prefix: full
        pages are shared with the lane by refcount, and a boundary cutting a page
        mid-way gets an immutable device COPY of just that page (the lane keeps
        writing its own) — so N entries over one system prompt cost its pages once
        plus at most one partial page each."""
        mgr = self.block_mgr
        ps = self.page_size
        bucket = self.prompt_bucket
        lane_ids = mgr.lane_pages(slot)
        for c in range(1, len(prompt) // bucket + 1):
            key = prompt[: c * bucket].tobytes()
            if key in self._prefix_reg:
                self._prefix_reg.move_to_end(key)
                continue
            p_len = c * bucket
            n_full = p_len // ps
            pages = [int(p) for p in lane_ids[:n_full]]
            if p_len % ps:
                dst = mgr.take_copy_page()
                if dst is None:
                    continue  # pool too tight for a registry copy — skip, not fail
                self.cache = self._copy_page_fn(
                    self.cache, int(lane_ids[n_full]), dst,
                    scan_layers=self.cfg.scan_layers,
                )
                mgr.retain(pages)
                pages = pages + [dst]
            else:
                mgr.retain(pages)
            self._register_prefix(key, _PagedPrefix(np.asarray(pages, np.int32)))

    def _prefill(self, prompt: np.ndarray, max_new: int, plan=None):
        """Single-row prefill → (cache row, on-device greedy token [1], on-device
        logits row [1, V], decode start position).

        Layout comes from ``_plan_prefill`` (``plan`` passes a precomputed decision
        so admission computes it once and hands the SAME one to the drafter):
        **bucketed** (one executable per ladder rung — the prompt is left-padded to
        its bucket and prefilled in one dispatch) or **chunked** (one bucket-width
        executable plus one shared chunk-append executable — a 10-chunk prompt
        compiles nothing new). With ``prefix_cache`` enabled, prompts sharing
        registered full-chunk prefixes skip straight to the first uncached chunk."""
        if self.prefix_cache_size:
            return self._prefill_prefix_cached(prompt)
        mode, total = plan if plan is not None else self._plan_prefill(len(prompt), max_new)
        pad = total - len(prompt)
        row = np.zeros((1, total), np.int32)
        row[0, pad:] = prompt
        mask = np.zeros((1, total), bool)
        mask[0, pad:] = True
        if mode == "bucket":
            if total in self._buckets_seen:
                self.bucket_hits += 1
            else:
                self.bucket_misses += 1
                self._buckets_seen.add(total)
            self.prefill_chunks += 1
            greedy, logits, cache = self._prefill_fn(
                self.params, jnp.asarray(row), jnp.asarray(mask),
                cfg=self.cfg, max_len=self.max_len,
            )
            return cache, greedy, logits, total
        bucket = self.prompt_bucket
        n_chunks = total // bucket
        self.prefill_chunks += n_chunks
        greedy, logits, cache = self._prefill_fn(
            self.params, jnp.asarray(row[:, :bucket]), jnp.asarray(mask[:, :bucket]),
            cfg=self.cfg, max_len=self.max_len,
        )
        for c in range(1, n_chunks):
            sl = slice(c * bucket, (c + 1) * bucket)
            greedy, logits, cache = self._prefill_chunk_fn(
                self.params, jnp.asarray(row[:, sl]), jnp.asarray(mask[:, sl]), cache,
                cfg=self.cfg,
            )
        return cache, greedy, logits, total

    def _prefill_prefix_cached(self, prompt: np.ndarray):
        """RIGHT-aligned chunked prefill with prefix-snapshot reuse.

        The prompt occupies positions [0, len); trailing slots of the last chunk are
        invalid pads that the first decode writes simply overwrite (decode starts at
        position len). After each fully-real chunk the row cache is snapshotted into an
        LRU registry keyed by the prefix bytes; a later prompt starting with the same
        chunks resumes from the snapshot (the chunk-append executable does not donate its
        input, so snapshots stay alive)."""
        bucket = self.prompt_bucket
        n_chunks = max(1, -(-len(prompt) // bucket))
        total = n_chunks * bucket
        row = np.zeros((1, total), np.int32)
        row[0, :len(prompt)] = prompt
        mask = np.zeros((1, total), bool)
        mask[0, :len(prompt)] = True
        full_chunks = len(prompt) // bucket  # only fully-real chunks are cacheable

        # Longest registered prefix wins.
        cache = None
        start = 0
        for k in range(full_chunks, 0, -1):
            key = prompt[: k * bucket].tobytes()
            hit = self._prefix_reg.get(key)
            if hit is not None:
                self._prefix_reg.move_to_end(key)
                cache = hit
                start = k
                self.prefix_hits += 1
                break
        if cache is None and full_chunks:
            self._classify_prefix_miss(prompt, full_chunks)

        logits = None
        self.prefill_chunks += n_chunks - start
        for c in range(start, n_chunks):
            sl = slice(c * bucket, (c + 1) * bucket)
            if cache is None:
                logits, cache = self._prefill_full_logits_fn(
                    self.params, jnp.asarray(row[:, sl]), jnp.asarray(mask[:, sl]),
                    cfg=self.cfg, max_len=self.max_len,
                )
            else:
                logits, cache = self._prefill_chunk_keep_fn(
                    self.params, jnp.asarray(row[:, sl]), jnp.asarray(mask[:, sl]),
                    cache, cfg=self.cfg,
                )
            if c + 1 <= full_chunks:
                self._register_prefix(prompt[: (c + 1) * bucket].tobytes(), cache)
        if logits is None:
            # Whole prompt was a registered prefix with no partial tail: re-run the last
            # chunk to recover its logits (cache state is already correct; the rewrite is
            # idempotent — same tokens into the same slots).
            sl = slice((start - 1) * bucket, start * bucket)
            prev_key = prompt[: (start - 1) * bucket].tobytes() if start > 1 else None
            prev = self._prefix_reg.get(prev_key) if prev_key else None
            if prev is not None or start == 1:      # else _recompute_all counts its own
                self.prefill_chunks += 1
            if prev is not None:
                logits, cache = self._prefill_chunk_keep_fn(
                    self.params, jnp.asarray(row[:, sl]), jnp.asarray(mask[:, sl]),
                    prev, cfg=self.cfg,
                )
            else:
                logits, cache = self._prefill_full_logits_fn(
                    self.params, jnp.asarray(row[:, sl]), jnp.asarray(mask[:, sl]),
                    cfg=self.cfg, max_len=self.max_len,
                ) if start == 1 else self._recompute_all(row, mask, n_chunks)
        # The real last token may sit before trailing pads: index its logits column.
        last_col = (len(prompt) - 1) % bucket
        last = logits[:, last_col, :]
        greedy = jnp.argmax(last, axis=-1).astype(jnp.int32)
        return cache, greedy, last, len(prompt)

    def _recompute_all(self, row, mask, n_chunks):
        bucket = self.prompt_bucket
        self.prefill_chunks += n_chunks
        logits, cache = self._prefill_full_logits_fn(
            self.params, jnp.asarray(row[:, :bucket]), jnp.asarray(mask[:, :bucket]),
            cfg=self.cfg, max_len=self.max_len,
        )
        for c in range(1, n_chunks):
            sl = slice(c * bucket, (c + 1) * bucket)
            logits, cache = self._prefill_chunk_keep_fn(
                self.params, jnp.asarray(row[:, sl]), jnp.asarray(mask[:, sl]), cache,
                cfg=self.cfg,
            )
        return logits, cache

    def _register_prefix(self, key: bytes, value) -> None:
        """Insert/refresh one prefix entry (dense row-cache snapshot, or a
        ``_PagedPrefix`` page list) and enforce the LRU capacity — with the
        eviction OBSERVABLE: each drop counts in ``prefix_evictions`` and the key
        lands in a bounded evicted-key set so later misses on it report as
        capacity misses, not cold keys. Paged entries release their page
        references on eviction (pages free when nothing else holds them)."""
        self._prefix_reg[key] = value
        self._prefix_reg.move_to_end(key)
        while len(self._prefix_reg) > self.prefix_cache_size:
            self._evict_prefix_lru()

    def _evict_prefix_lru(self, keep=None) -> bool:
        """Evict the least-recently-used prefix entry (skipping ``keep``, the
        entry an in-progress admission is adopting), with the drop OBSERVABLE:
        counted in ``prefix_evictions`` and remembered in the bounded
        evicted-key set so later misses on it classify as capacity misses.
        Paged entries release their page references (pages free when nothing
        else holds them). Returns False when nothing evictable remains."""
        victim = None
        for key in self._prefix_reg:  # OrderedDict: oldest first
            if self._prefix_reg[key] is not keep:
                victim = key
                break
        if victim is None:
            return False
        old = self._prefix_reg.pop(victim)
        self.prefix_evictions += 1
        self._evicted_keys[victim] = True
        self._evicted_keys.move_to_end(victim)
        while len(self._evicted_keys) > self._evicted_keys_cap:
            self._evicted_keys.popitem(last=False)
        if isinstance(old, _PagedPrefix):
            self.block_mgr.release(old.pages)
        return True
