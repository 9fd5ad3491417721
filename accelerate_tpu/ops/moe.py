"""Mixture-of-Experts layer with expert parallelism over the mesh "ep" axis.

Reference delegation points this replaces (SURVEY.md §2.2 EP row): the reference only
*recognizes* DeepSpeed MoE modules (``transformer_moe_cls_names`` ``dataclasses.py:1105``) and
defers all routing/dispatch to DeepSpeed's CUDA all-to-all. Here MoE is first-class and
TPU-idiomatic: routing builds dense one-hot dispatch/combine tensors (the GSPMD MoE pattern —
einsums the MXU loves, no ragged scatter), expert weights carry an explicit PartitionSpec on
the "ep" axis, and a ``with_sharding_constraint`` on the dispatched activations makes XLA
insert the token all-to-all over ICI — the NCCL a2a analog is a compiler-inserted collective,
not a library call.

Components: top-k softmax router with capacity dropping, Switch/Mixtral-style load-balancing
auxiliary loss, batched expert FFN (SwiGLU, matching the dense MLP).

``moe_mlp_grouped`` is the serving-side layer of a model whose experts outnumber the
chip (DeepSeek-V3: 256 routed experts, 8 a token, one shared): a router over ALL the
published experts — a sigmoid one with a selection bias and group-limited top-k
(``router_sigmoid_grouped``), or a softmax one that keeps its ``top_k`` largest and
renormalises them (``router_softmax_topk``: no bias, no groups; 128 small experts whole on
one chip) — and ONE dropless grouped product a projection over the experts this chip
HOLDS (``expert_offset .. expert_offset + E_held``), beside a shared expert if the layer
has one; what the absent experts would add is left out — the exchange between
expert-parallel chips is not here.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..utils.constants import EXPERT_AXIS

__all__ = ["router_topk", "load_balancing_loss", "moe_mlp", "moe_mlp_dense",
           "router_sigmoid_grouped", "router_softmax_topk", "moe_mlp_grouped",
           "expert_partition_specs"]


def router_topk(
    x: jax.Array, w_router: jax.Array, top_k: int
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Top-k softmax routing.

    x [T, D], w_router [D, E] → (logits [T, E], gates [T, k] renormalized, idx [T, k]).
    Router math in fp32 regardless of compute dtype (routing is precision-sensitive).
    """
    logits = x.astype(jnp.float32) @ w_router.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, top_k)
    gates = gates / jnp.maximum(jnp.sum(gates, axis=-1, keepdims=True), 1e-9)
    return logits, gates, idx


def load_balancing_loss(
    logits: jax.Array, idx: jax.Array, num_experts: int,
    token_mask: Optional[jax.Array] = None,
) -> jax.Array:
    """Switch-Transformer auxiliary loss: E · Σ_e f_e · p_e.

    f_e = fraction of tokens whose top-1 lands on expert e; p_e = mean router probability of
    e. Minimized (=1) at uniform balance. ``token_mask`` [T] bool (sample packing: False on
    pad slots) restricts both means to REAL tokens — pads would otherwise bias the balance
    statistic toward whatever experts they happen to route to.
    """
    probs = jax.nn.softmax(logits, axis=-1)
    top1 = idx[..., 0]
    oh = jax.nn.one_hot(top1, num_experts, dtype=jnp.float32)
    if token_mask is not None:
        m = token_mask.astype(jnp.float32)[:, None]
        denom = jnp.maximum(m.sum(), 1.0)
        f = jnp.sum(oh * m, axis=0) / denom
        p = jnp.sum(probs * m, axis=0) / denom
    else:
        f = jnp.mean(oh, axis=0)
        p = jnp.mean(probs, axis=0)
    return num_experts * jnp.sum(f * p)


def _capacity(tokens: int, num_experts: int, top_k: int, capacity_factor: float) -> int:
    cap = int(tokens * top_k * capacity_factor / num_experts)
    return max(cap, 1)


def moe_mlp(
    x: jax.Array,
    experts: dict,
    w_router: jax.Array,
    top_k: int = 2,
    capacity_factor: float = 1.25,
    compute_dtype=jnp.bfloat16,
    shard: bool = True,
    token_mask: Optional[jax.Array] = None,
) -> tuple[jax.Array, jax.Array]:
    """MoE SwiGLU FFN. x [B, S, D]; experts {w_gate/w_up [E, D, F], w_down [E, F, D]}.

    Returns (y [B, S, D], aux_loss scalar). Tokens beyond an expert's capacity are dropped
    (contribute zero through that expert) — the standard fixed-shape TPU formulation; with
    ``capacity_factor ≥ top_k·E/…`` nothing drops.

    ``token_mask`` [B, S] bool (sample packing: False on pad slots): pad tokens neither
    claim expert-capacity slots (they would crowd out REAL tokens and increase dropping)
    nor enter the load-balancing statistic; their output rows are zero.
    """
    B, S, D = x.shape
    T = B * S
    E = experts["w_gate"].shape[0]
    C = _capacity(T, E, top_k, capacity_factor)

    flat = x.reshape(T, D)
    logits, gates, idx = router_topk(flat, w_router, top_k)
    live = None if token_mask is None else token_mask.reshape(T).astype(bool)
    aux = load_balancing_loss(logits, idx, E, token_mask=live)

    # Position of each (token, choice) in its expert's buffer, via cumulative count over the
    # flattened (k-major) assignment order; entries beyond capacity are dropped.
    onehot = jax.nn.one_hot(idx, E, dtype=jnp.int32)           # [T, k, E]
    if live is not None:
        # Pads claim no slots: zeroing their assignment BEFORE the cumsum removes them
        # from capacity competition entirely (and from dispatch/combine below).
        onehot = onehot * live[:, None, None].astype(jnp.int32)
    flat_oh = onehot.transpose(1, 0, 2).reshape(T * top_k, E)  # k-major: top-1s claim slots first
    pos_flat = jnp.cumsum(flat_oh, axis=0) - flat_oh           # [T*k, E]
    pos = pos_flat.reshape(top_k, T, E).transpose(1, 0, 2)     # [T, k, E]
    pos_tk = jnp.sum(pos * onehot, axis=-1)                    # [T, k] slot within chosen expert
    keep = pos_tk < C

    # Dense dispatch/combine tensors (GSPMD MoE): dispatch [T, E, C] bool, combine [T, E, C].
    slot_oh = jax.nn.one_hot(jnp.where(keep, pos_tk, C), C + 1, dtype=compute_dtype)[..., :C]
    dispatch = jnp.einsum("tke,tkc->tec", onehot.astype(compute_dtype), slot_oh)
    combine = jnp.einsum("tk,tke,tkc->tec", gates.astype(compute_dtype),
                         onehot.astype(compute_dtype), slot_oh)

    xin = jnp.einsum("td,tec->ecd", flat.astype(compute_dtype), dispatch)  # [E, C, D]
    if shard:
        xin = _maybe_shard(xin, P(EXPERT_AXIS, None, None))

    # Batched expert SwiGLU — expert dim sharded on "ep": XLA turns the dispatch einsum above
    # into the token all-to-all, and each device computes only its local experts.
    gate = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xin, experts["w_gate"].astype(compute_dtype)))
    up = jnp.einsum("ecd,edf->ecf", xin, experts["w_up"].astype(compute_dtype))
    out = jnp.einsum("ecf,efd->ecd", gate * up, experts["w_down"].astype(compute_dtype))
    if shard:
        out = _maybe_shard(out, P(EXPERT_AXIS, None, None))

    y = jnp.einsum("ecd,tec->td", out, combine)  # combine: weighted return all-to-all
    return y.reshape(B, S, D).astype(x.dtype), aux


def moe_mlp_dense(
    x: jax.Array,
    experts: dict,
    w_router: jax.Array,
    top_k: int = 2,
    compute_dtype=jnp.bfloat16,
) -> jax.Array:
    """Drop-free MoE FFN: every expert computed on every token, combined by top-k gates.

    Exact inference semantics — no capacity dropping (the training formulation's fixed-shape
    load-management artifact, ``moe_mlp``).  Cost is E× the FFN over the given tokens, which
    is the right trade only when T is tiny: single-token decode steps, where the FFN is
    HBM-bandwidth-bound anyway and a ragged per-expert gather would defeat jit.
    """
    B, S, D = x.shape
    T = B * S
    E = experts["w_gate"].shape[0]
    flat = x.reshape(T, D).astype(compute_dtype)
    _, gates, idx = router_topk(x.reshape(T, D), w_router, top_k)
    # [T, E] combine weights: renormalized gate mass on each chosen expert, 0 elsewhere.
    weights = jnp.sum(
        gates[..., None] * jax.nn.one_hot(idx, E, dtype=jnp.float32), axis=1
    ).astype(compute_dtype)
    gate = jax.nn.silu(jnp.einsum("td,edf->etf", flat, experts["w_gate"].astype(compute_dtype)))
    up = jnp.einsum("td,edf->etf", flat, experts["w_up"].astype(compute_dtype))
    out = jnp.einsum("etf,efd->etd", gate * up, experts["w_down"].astype(compute_dtype))
    y = jnp.einsum("etd,te->td", out, weights)
    return y.reshape(B, S, D).astype(x.dtype)


def router_sigmoid_grouped(x: jax.Array, w_router: jax.Array, bias: jax.Array, *,
                           top_k: int, n_group: int, topk_group: int,
                           scale: float, norm_topk: bool = True):
    """DeepSeek-V3's router: x [T, D], w_router [D, E], bias [E] → (gates [T, k] fp32,
    idx [T, k] int32). Scores ``s = sigmoid(x W)`` in fp32; SELECTION uses ``s + bias``
    (the bias steers load and never enters a gate): the E experts form ``n_group`` equal
    groups, a group scores the sum of its two largest ``s + bias``, the ``topk_group``
    best groups stay, and the ``top_k`` largest ``s + bias`` inside them are chosen.
    Gates are the chosen ``s``, divided by their sum (``norm_topk``), times ``scale``."""
    with jax.named_scope("router"):
        T, E = x.shape[0], w_router.shape[1]
        s = jax.nn.sigmoid(jnp.dot(x.astype(jnp.float32), w_router.astype(jnp.float32),
                                   precision=jax.lax.Precision.HIGHEST))
        pick = s + bias.astype(jnp.float32)
        grouped = pick.reshape(T, n_group, E // n_group)
        group_score = jax.lax.top_k(grouped, 2)[0].sum(-1)               # [T, n_group]
        kept = jax.lax.top_k(group_score, topk_group)[1]                 # [T, topk_group]
        keep = jnp.zeros((T, n_group), bool).at[jnp.arange(T)[:, None], kept].set(True)
        masked = jnp.where(keep[:, :, None], grouped, -jnp.inf).reshape(T, E)
        idx = jax.lax.top_k(masked, top_k)[1]
        gates = jnp.take_along_axis(s, idx, axis=1)
        if norm_topk:
            gates = gates / (gates.sum(-1, keepdims=True) + 1e-20)
        return gates * scale, idx.astype(jnp.int32)


def router_softmax_topk(x: jax.Array, w_router: jax.Array, *, top_k: int,
                        norm_topk: bool = True, scale: float = 1.0):
    """The softmax router of the small-expert models: x [T, D], w_router [D, E] → (gates
    [T, k] fp32, idx [T, k] int32). ``p = softmax(x W)`` over ALL experts in fp32, the
    ``top_k`` largest chosen (a tie to the lower index), their ``p`` divided by their sum
    (``norm_topk``), times ``scale``. No bias, no groups."""
    with jax.named_scope("router"):
        p = jax.nn.softmax(jnp.dot(x.astype(jnp.float32), w_router.astype(jnp.float32),
                                   precision=jax.lax.Precision.HIGHEST), axis=-1)
        gates, idx = jax.lax.top_k(p, top_k)
        if norm_topk:
            gates = gates / (gates.sum(-1, keepdims=True) + 1e-20)
        return gates * scale, idx.astype(jnp.int32)


def _swiglu(x, w: dict, dtype):
    gate = jax.nn.silu(x @ w["w_gate"].astype(dtype))
    return (gate * (x @ w["w_up"].astype(dtype))) @ w["w_down"].astype(dtype)


def _grouped_dot(rows: jax.Array, w: jax.Array, sizes: jax.Array) -> jax.Array:
    """``rows[group g] @ w[g]`` for rows sorted by group, ``sizes`` rows a group: the
    megablox grouped matmul that ships with jax (a Pallas kernel whose grid runs over
    the row tiles that hold a group's rows, so an empty expert costs nothing and its
    weights are never read). Measured against ``jax.lax.ragged_dot`` on a v5e at this
    layer's two shapes — 4096 sorted rows of a 512-token prefill chunk and 256 of a
    32-lane decode step, 16 experts of 7168 x 2048 — it took 2.34 against 4.65 ms and
    1.43 against 2.09 ms for the three projections (PERF.md, PR 28), at this tiling.
    Rows past ``sizes.sum()`` come back unwritten."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    from ._common import interpret_default

    m, k = rows.shape
    tm = min(128, m)
    rows = jnp.pad(rows, ((0, -m % tm), (0, 0)))
    out = gmm(rows, w, sizes, preferred_element_type=rows.dtype,
              tiling=(tm, min(512, k), min(2048, w.shape[-1])),
              interpret=interpret_default())
    return out[:m]


def moe_mlp_grouped(x: jax.Array, moe: dict, *, top_k: int, n_group: int = 1,
                    topk_group: int = 1, scale: float = 1.0, norm_topk: bool = True,
                    expert_offset: int = 0, compute_dtype=jnp.bfloat16,
                    router: str = "sigmoid_grouped"):
    """Dropless MoE SwiGLU over the experts HELD here, beside a shared expert.

    x [T, D]; ``moe`` = ``{"router" [D, E_published], "router_bias" [E_published],
    "shared" {w_gate/w_up [D, Fs], w_down [Fs, D]}, "experts" {w_gate/w_up
    [E_held, D, F], w_down [E_held, F, D]}}``: the router keeps its published width and
    the held experts are the published ones ``expert_offset .. expert_offset +
    E_held``. ``router`` names the rule in front of the product: ``"sigmoid_grouped"``
    (:func:`router_sigmoid_grouped`, reads ``router_bias``) or ``"softmax"``
    (:func:`router_softmax_topk`: no bias, no groups); a layer without ``"shared"`` has
    no shared expert. The (token, chosen expert) pairs whose expert is held are sorted by
    expert, each projection is ONE grouped product over the sorted rows
    (:func:`_grouped_dot`: a row meets only its own expert's weights, nothing is
    dropped whatever the load), the rows are un-sorted, weighted by their gates and
    summed per token, and the shared expert is added. Pairs whose expert lives on
    another chip contribute nothing.

    Returns ``(y [T, D], counts int32[3])``: the held pairs computed, the tokens that
    entered, the largest number of pairs on one expert."""
    T, D = x.shape
    E = moe["experts"]["w_gate"].shape[0]
    if router == "softmax":
        gates, idx = router_softmax_topk(x, moe["router"], top_k=top_k,
                                         norm_topk=norm_topk, scale=scale)
    elif router == "sigmoid_grouped":
        gates, idx = router_sigmoid_grouped(
            x, moe["router"], moe["router_bias"], top_k=top_k, n_group=n_group,
            topk_group=topk_group, scale=scale, norm_topk=norm_topk)
    else:
        raise ValueError(f"router={router!r}: expected 'sigmoid_grouped' or 'softmax'")
    xc = x.astype(compute_dtype)
    with jax.named_scope("experts"):
        local = idx.reshape(-1) - expert_offset                          # [T*k]
        key = jnp.where((local >= 0) & (local < E), local, E)            # E: not held here
        order = jnp.argsort(key, stable=True)
        sizes = jnp.bincount(key, length=E + 1)[:E].astype(jnp.int32)
        n_pairs = sizes.sum()
        rows = xc[order // top_k]                                        # [T*k, D] sorted
        w = {k: v.astype(compute_dtype) for k, v in moe["experts"].items()}
        h = (jax.nn.silu(_grouped_dot(rows, w["w_gate"], sizes))
             * _grouped_dot(rows, w["w_up"], sizes))
        out = _grouped_dot(h, w["w_down"], sizes)                        # [T*k, D]
        # rows past the held pairs belong to no group: the product left them unwritten
        live = (jnp.arange(T * top_k) < n_pairs)[:, None]
        out = jnp.where(live, out.astype(jnp.float32), 0.0) * gates.reshape(-1)[order][:, None]
        routed = jnp.zeros((T * top_k, D), jnp.float32).at[order].set(
            out, unique_indices=True).reshape(T, top_k, D).sum(1)
    if "shared" in moe:
        with jax.named_scope("shared"):
            routed = routed + _swiglu(xc, moe["shared"], compute_dtype).astype(jnp.float32)
    counts = jnp.stack([n_pairs, jnp.int32(T), sizes.max()]).astype(jnp.int32)
    return routed.astype(x.dtype), counts


def expert_partition_specs() -> dict:
    """PartitionSpecs for the expert weight dict: expert dim on "ep", ffn dim on "tp"."""
    from ..utils.constants import TENSOR_AXIS

    return {
        "w_gate": P(EXPERT_AXIS, None, TENSOR_AXIS),
        "w_up": P(EXPERT_AXIS, None, TENSOR_AXIS),
        "w_down": P(EXPERT_AXIS, TENSOR_AXIS, None),
        "w_router": P(),
    }


def _maybe_shard(x: jax.Array, spec: P) -> jax.Array:
    from .collectives import maybe_shard

    return maybe_shard(x, spec, require_axis=EXPERT_AXIS)
