"""chip_smoke.py — does the system still start on the chip?

    python chip_smoke.py                 # on a TPU host; anything else exits non-zero
    python chip_smoke.py --cpu-dry-run   # same code path at toy widths on the CPU

One process drives the two main paths through the entry points a user calls, at the
full width of ``llama.CONFIGS["mistral-7b"]`` (d_model 4096, 32 query / 8 KV heads of
128, d_ff 14336, vocab 32000, sliding window 4096) with only the depth cut:

1. **train** — ``Accelerator(mixed_precision="bf16")`` → ``create_train_state`` →
   ``build_train_step`` → a few AdamW steps on one repeated seeded batch. With four
   local devices the same global batch is also trained under ``MeshConfig(fsdp=4)``
   and must match the one-device first-step loss.
2. **serve** — the trained params in bfloat16 through ``ContinuousBatcher`` (paged KV,
   multi-step decode): two waves of seeded requests, more than there are slots, prompts
   from tens of tokens to beyond the window.
3. **kernels** — every ``pallas_call`` under ``accelerate_tpu/ops`` compiled by Mosaic
   at this model's shapes and compared with the ``jax.numpy`` reference beside it.

Nothing here catches an exception to carry on: any failed check raises, the traceback is
the report, and the exit code is non-zero. The per-phase seconds, compile counts and
peak bytes it prints are START-UP FACTS (is it compiling? does it fit?), not speed
metrics — no number printed here belongs in a benchmark table.

The last stdout line of a passing TPU run is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import re
import sys
import time

import numpy as np

import jax
import jax.numpy as jnp

from accelerate_tpu.utils.environment import place_compile_cache

SEED = 0

# ----------------------------------------------------------------------------- sizes
TRAIN_STEPS = 4
SLOTS = 4
PAGE_SIZE = 16
DECODE_STEPS = 4


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What is cut to fit, as found on a 16 GB v5e (CHANGES.md, PR 21): at 16 bytes a
    parameter (fp32 master, two Adam moments, gradients) one mistral-7b layer is 218 M
    parameters and embedding + head 262 M; ``depth`` layers plus the activations of a
    ``batch`` x ``seq`` step are what fits beside them (depth 3 needs 17.7 of 15.75 GB)."""

    depth: int = 2
    seq: int = 8192          # above the 4096 window: the flash kernels' band-skipping path
    batch: int = 4           # global; divisible by the four-chip fsdp axis, same on one chip
    max_len: int = 8192      # serving cache length (> window)
    prompt_bucket: int = 512
    # (prompt length, token budget) per request; two waves, each more requests than SLOTS.
    waves: tuple = (
        ((24, 8), (130, 12), (700, 16), (2100, 8), (4600, 12), (60, 16)),
        ((40, 12), (4600, 8), (300, 16), (2100, 12), (90, 8), (700, 16)),
    )


DRY = Sizes(seq=96, max_len=128, prompt_bucket=16,
            waves=(((5, 3), (20, 4), (70, 3), (9, 4), (33, 3), (12, 4)),
                   ((7, 4), (70, 3), (18, 4), (40, 3), (6, 4), (25, 3))))

_PREFIX = ""


def say(msg: str) -> None:
    print(f"{_PREFIX}{msg}", flush=True)


def check(ok: bool, what: str) -> None:
    """A failed check raises — no phase carries on past one."""
    if not ok:
        raise AssertionError(f"chip_smoke check failed: {what}")
    say(f"  ok: {what}")


# ------------------------------------------------------------- start-up fact counters
class Compiles:
    """Programs XLA handed back (compiled or read from the persistent cache), the
    seconds that took, and how many came from the cache — via ``jax.monitoring``."""

    def __init__(self):
        from accelerate_tpu.telemetry import CompileMonitor

        self.hits = 0
        jax.monitoring.register_event_listener(self._on_event)
        self.monitor = CompileMonitor().start()

    def _on_event(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def mark(self):
        return (self.monitor.count, self.monitor.seconds, self.hits)

    def since(self, mark) -> str:
        n, s, h = (a - b for a, b in zip(self.mark(), mark))
        return f"compilations={n} (persistent-cache hits={h}) compile_s={s:.1f}"


def peak_bytes() -> list:
    """``peak_bytes_in_use`` per device — the process's high-water mark SO FAR."""
    return [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in jax.devices()]


def facts(phase: str, compiles: Compiles, mark, first_s: float, later_s: list) -> None:
    later = ", ".join(f"{s:.2f}" for s in later_s)
    say(f"[{phase}] start-up facts (not speed metrics): first call {first_s:.1f}s "
        f"(compile included), later calls [{later}]s; {compiles.since(mark)}; "
        f"peak_bytes_in_use so far per device {peak_bytes()}")


# ------------------------------------------------------------------------------ train
def model_config(sizes: Sizes, dry: bool):
    from accelerate_tpu.models import llama

    cfg = dataclasses.replace(
        llama.CONFIGS["mistral-7b"], n_layers=sizes.depth, scan_layers=True,
        max_seq=sizes.max_len,
    )
    if dry:  # toy widths: the dry run checks the script, not the model
        cfg = dataclasses.replace(
            cfg, d_model=128, n_heads=4, n_kv_heads=2, d_ff=256, vocab_size=512,
            sliding_window=48,
        )
    return cfg


def flash_batches_in_hlo(hlo: str, cfg, seq: int) -> list:
    """Leading (batch) dim of every ``[b, H, seq, head_dim]`` operand or result of a
    Mosaic custom call in compiled HLO — the flash kernels' q / o / dq tensors."""
    pat = re.compile(rf"\[(\d+),{cfg.n_heads},{seq},{cfg.head_dim}\]")
    return [
        int(b)
        for line in hlo.splitlines() if "tpu_custom_call" in line
        for b in pat.findall(line)
    ]


def train(cfg, sizes: Sizes, devices, steps: int, compiles: Compiles, dry: bool):
    """The train phase on ``devices`` (one device, or four under fsdp=4) →
    (losses, trained params). Every check of ISSUE 21's train phase lives here."""
    import optax

    from accelerate_tpu import Accelerator
    from accelerate_tpu.models import llama
    from accelerate_tpu.parallel import MeshConfig
    from accelerate_tpu.parallel.mesh import mesh_context
    from accelerate_tpu.state import AcceleratorState, GradientState, PartialState
    from accelerate_tpu.telemetry import fence
    from accelerate_tpu.utils.dataclasses import FullyShardedDataParallelPlugin

    n = len(devices)
    name = f"train x{n}"
    for singleton in (AcceleratorState, GradientState, PartialState):
        singleton._reset_state()
    mark, t0 = compiles.mark(), time.perf_counter()
    acc = Accelerator(
        mixed_precision="bf16",
        mesh_config=MeshConfig(dp=1, fsdp=n, devices=devices),
        fsdp_plugin=FullyShardedDataParallelPlugin() if n > 1 else None,
    )
    state = acc.create_train_state(
        llama.init_params(cfg, jax.random.PRNGKey(SEED)), optax.adamw(3e-4)
    )
    step = acc.build_train_step(lambda p, b: llama.loss_fn(p, b, cfg), max_grad_norm=1.0)
    rng = np.random.default_rng(SEED)
    batch = {"tokens": rng.integers(
        0, cfg.vocab_size, size=(sizes.batch, sizes.seq + 1)).astype(np.int32)}
    n_params = sum(l.size for l in jax.tree_util.tree_leaves(state.params))
    say(f"[{name}] mistral-7b widths, depth {cfg.n_layers}, {n_params / 1e6:.0f} M "
        f"params, global batch {sizes.batch} x seq {sizes.seq}, window "
        f"{cfg.sliding_window}, mesh fsdp={n}")

    losses, times = [], []
    for _ in range(steps):
        t = time.perf_counter()
        state, metrics = step(state, batch)
        losses.append(float(fence(metrics)["loss"]))  # per-step: each loss is checked
        times.append(time.perf_counter() - t)
    say(f"[{name}] losses {[round(l, 4) for l in losses]}")
    check(all(math.isfinite(l) for l in losses), "every loss is finite")
    if steps > 1:
        check(losses[-1] < losses[0], "last loss below the first")

    if not dry:
        # The compiled step must hold the Mosaic flash kernels (forward, dq, dkv) and
        # each must see the PER-DEVICE batch: GSPMD cannot partition a Mosaic custom
        # call, so the model runs it under shard_map, one batch shard per chip.
        with mesh_context(acc.mesh):
            compiled = step.apply_fn.lower(state, batch).compile()
        mem = compiled.memory_analysis()
        say(f"[{name}] compiled step, bytes per device: arguments "
            f"{mem.argument_size_in_bytes}, outputs {mem.output_size_in_bytes} (of which "
            f"aliased onto donated arguments {mem.alias_size_in_bytes}), temporaries "
            f"{mem.temp_size_in_bytes}")
        seen = flash_batches_in_hlo(compiled.as_text(), cfg, sizes.seq)
        check(len(seen) >= 3 and set(seen) == {sizes.batch // n},
              f"flash custom calls in the compiled step see batch {sizes.batch // n} "
              f"per device (found {sorted(set(seen))} in {len(seen)} operands)")
    if n > 1:
        # Leaves under the plugin's min_weight_size stay replicated by design; at
        # mistral-7b widths the smallest (a norm gain, 4096 floats) is above it.
        floor = acc.state.fsdp_plugin.min_weight_size
        sharded = [
            leaf for leaf in jax.tree_util.tree_leaves((state.params, state.opt_state))
            if leaf.size >= floor
        ]
        check(not any(l.sharding.is_fully_replicated for l in sharded),
              f"none of the {len(sharded)} parameter / moment leaves of >= {floor} "
              "elements is fully replicated")
        if not dry:  # the CPU backend keeps no allocator ledger
            in_use = [d.memory_stats()["bytes_in_use"] for d in devices]
            check(max(in_use) <= 1.25 * min(in_use),
                  f"bytes_in_use comparable across devices (within 25%): {in_use}")
    facts(name, compiles, mark, time.perf_counter() - t0 - sum(times[1:]), times[1:])

    # Serving takes the trained weights in bfloat16 on ONE device (serving.py places
    # nothing: four replicas in one process would all sit on device 0 — known gap).
    params = jax.device_put(
        jax.tree_util.tree_map(lambda x: x.astype(jnp.bfloat16), state.params),
        devices[0],
    )
    jax.block_until_ready(params)
    del state, step, acc
    gc.collect()
    return losses, params


# ------------------------------------------------------------------------------ serve
def serve(params, cfg, sizes: Sizes, compiles: Compiles, dry: bool) -> None:
    from accelerate_tpu.models import llama
    from accelerate_tpu.serving import ContinuousBatcher

    if not dry:
        # The paged read must be the Pallas kernel, not the gather path: the traced
        # decode forward has to contain a pallas_call.
        cache = jax.eval_shape(
            lambda: llama.init_paged_cache(cfg, SLOTS, sizes.max_len, 8, PAGE_SIZE))
        jaxpr = jax.make_jaxpr(
            lambda p, c, tok, pos, tab: llama.forward_slots(
                p, tok, c, pos, cfg, tables=tab, page_size=PAGE_SIZE)
        )(params, cache, jax.ShapeDtypeStruct((SLOTS, 1), jnp.int32),
          jax.ShapeDtypeStruct((SLOTS,), jnp.int32),
          jax.ShapeDtypeStruct((SLOTS, sizes.max_len // PAGE_SIZE), jnp.int32))
        check("pallas_call" in str(jaxpr), "paged decode traces to the Pallas kernel")

    mark, t0 = compiles.mark(), time.perf_counter()
    engine = ContinuousBatcher(
        params, cfg, max_slots=SLOTS, max_len=sizes.max_len,
        prompt_bucket=sizes.prompt_bucket, page_size=PAGE_SIZE,
        decode_steps=DECODE_STEPS,
    )
    rng = np.random.default_rng(SEED + 1)
    wave_s = []
    for w, wave in enumerate(sizes.waves):
        t = time.perf_counter()
        reqs = [
            engine.submit(rng.integers(0, cfg.vocab_size, size=(plen,)), max_new_tokens=new)
            for plen, new in wave
        ]
        done = engine.run()
        wave_s.append(time.perf_counter() - t)
        check(len(done) == len(reqs) and all(r.done for r in reqs),
              f"wave {w}: all {len(reqs)} requests finished (slots {SLOTS})")
        check(all(len(r.tokens) == new for r, (_, new) in zip(reqs, wave)),
              f"wave {w}: every request returned exactly its token budget")
        check(all(0 <= tok < cfg.vocab_size for r in reqs for tok in r.tokens),
              f"wave {w}: every token id in [0, {cfg.vocab_size})")
        check(not any(r.failed for r in reqs), f"wave {w}: no request has failed set")
    stats = engine.stats()
    check(stats["quarantined"] == 0 and stats["step_failures"] == 0,
          "stats: quarantined == 0 and step_failures == 0")
    say(f"[serve] prompts {[p for p, _ in sizes.waves[0]]} tokens, max_len "
        f"{sizes.max_len}, page_size {PAGE_SIZE}, decode_steps {DECODE_STEPS}")
    facts("serve", compiles, mark, time.perf_counter() - t0 - sum(wave_s[1:]), wave_s[1:])
    del engine
    gc.collect()


# ---------------------------------------------------------------------------- kernels
# Tolerances. Each side of a bf16 comparison rounds its probabilities / outputs to
# bfloat16 (8 mantissa bits: 2**-8 relative per rounding) and accumulates in fp32; the
# reference runs its matmuls at "highest" precision. Errors are therefore measured
# against the tensor's own scale: max|got - want| <= TOL * max|want|.
TOL_BF16 = 2.0 ** -5   # a few bf16 roundings stacked (p, o, ds; int8 page codes)
TOL_F32 = 1e-4         # fp32 elementwise math; differs by fusion / reassociation only


def close(got, want, tol: float, what: str) -> None:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    check(got.shape == want.shape and np.isfinite(got).all(),
          f"{what}: finite, shape {got.shape}")
    scale = float(np.abs(want).max())
    err = float(np.abs(got - want).max())
    check(err <= tol * scale,
          f"{what}: max|err| {err:.3e} <= {tol:.1e} x max|ref| {scale:.3e}")


def twice(name: str, fn, *args):
    """Run a jitted kernel entry twice → (result, first-call s, second-call s)."""
    t = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    first = time.perf_counter() - t
    t = time.perf_counter()
    jax.block_until_ready(fn(*args))
    later = time.perf_counter() - t
    say(f"  [{name}] first call {first:.2f}s (compile included), second {later:.3f}s")
    return out


def attention_reference(q, k, v, cfg, heads: slice):
    """llama's XLA attention over the q heads of ONE kv-head group (the full-head
    fp32 score tensor at seq 8192 would not fit beside the kernel's operands)."""
    from accelerate_tpu.models import llama

    S = q.shape[1]
    idx = jnp.arange(S)
    mask = (idx[None, :] <= idx[:, None]) & (idx[None, :] > idx[:, None] - cfg.sliding_window)
    kv = slice(heads.start // cfg.q_per_kv, heads.stop // cfg.q_per_kv)
    with jax.default_matmul_precision("highest"):
        return llama._attention_xla(q[:, :, heads], k[:, :, kv], v[:, :, kv], mask[None], cfg)


def kernel_flash(cfg, sizes: Sizes) -> None:
    """flash fwd / dq / dkv at [1, seq, H, hd] with the window, vs llama's XLA path."""
    from accelerate_tpu.ops.flash_attention import flash_attention

    S, H, K, hd = sizes.seq, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    kq, kk, kv, kw = jax.random.split(jax.random.PRNGKey(SEED + 2), 4)
    q = jax.random.normal(kq, (1, S, H, hd), jnp.bfloat16)
    k = jax.random.normal(kk, (1, S, K, hd), jnp.bfloat16)
    v = jax.random.normal(kv, (1, S, K, hd), jnp.bfloat16)
    w = jax.random.normal(kw, (1, S, H, hd), jnp.bfloat16)
    sm = 1.0 / math.sqrt(hd)
    heads = slice(H - cfg.q_per_kv, H)  # the last kv head's group
    kvh = slice(K - 1, K)

    def kernel_loss(q, k, v):
        o = flash_attention(q, k, v, window=cfg.sliding_window, sm_scale=sm, interpret=False)
        return (o.astype(jnp.float32) * w).sum(), o

    def ref_loss(q, k, v):
        o = attention_reference(q, k, v, cfg, heads)
        return (o.astype(jnp.float32) * w[:, :, heads]).sum(), o

    (_, o), (dq, dk, dv) = twice(
        "flash fwd+dq+dkv", jax.jit(jax.value_and_grad(kernel_loss, (0, 1, 2), has_aux=True)),
        q, k, v)
    (_, o_ref), (dq_ref, dk_ref, dv_ref) = jax.jit(
        jax.value_and_grad(ref_loss, (0, 1, 2), has_aux=True))(q, k, v)
    close(o[:, :, heads], o_ref, TOL_BF16, "flash forward")
    close(dq[:, :, heads], dq_ref[:, :, heads], TOL_BF16, "flash dq")
    close(dk[:, :, kvh], dk_ref[:, :, kvh], TOL_BF16, "flash dk")
    close(dv[:, :, kvh], dv_ref[:, :, kvh], TOL_BF16, "flash dv")


def kernel_flash_prefill(cfg, sizes: Sizes) -> None:
    """the flash forward as the serving prefill calls it (a chunk of ``prompt_bucket``
    queries at a traced cache index against the band of a ``max_len`` row, the valid mask
    as a segment pair, S != T) vs llama's ``_attention_cached`` on the same arrays: a
    prompt's first chunk, left-padded, and a chunk past the window."""
    from accelerate_tpu.models import llama
    from accelerate_tpu.models.common import cached_prefill_attention

    T, C, H, K, hd = sizes.prompt_bucket, sizes.max_len, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(SEED + 11), 3)
    q = jax.random.normal(kq, (1, T, H, hd), jnp.bfloat16)
    ck = jax.random.normal(kk, (1, C, K, hd), jnp.bfloat16)   # noise above the fill too
    cv = jax.random.normal(kv, (1, C, K, hd), jnp.bfloat16)
    pad, slots = T // 5, jnp.arange(C)

    def kernel(q, ck, cv, index, valid):
        return cached_prefill_attention(
            q, ck, cv, index, valid, impl="flash", sm_scale=llama._sm_scale(cfg),
            window=cfg.sliding_window, softcap=cfg.attn_softcap, xla_attention=None)

    def reference(q, ck, cv, index, valid):
        positions = index + jnp.arange(T, dtype=jnp.int32)[None]
        with jax.default_matmul_precision("highest"):
            return llama._attention_cached(q, ck, cv, positions, valid, cfg)

    kernel, reference = jax.jit(kernel), jax.jit(reference)
    for index in (0, C - 2 * T):
        valid = ((slots >= pad) & (slots < index + T))[None]
        has_key = (index + jnp.arange(T) >= pad)[None, :, None, None]   # pad rows: zeros
        got = twice(f"flash prefill at {index}", kernel, q, ck, cv, jnp.int32(index), valid)
        want = reference(q, ck, cv, jnp.int32(index), valid)
        close(jnp.where(has_key, got, 0), jnp.where(has_key, want, 0), TOL_BF16,
              f"flash prefill chunk at index {index}")


def kernel_flash_packed(cfg, sizes: Sizes) -> None:
    """the flash kernels' packed-rows variant (segment ids in-kernel: what sample
    packing trains through) at [2, seq/4, H, hd] vs llama's XLA path + segment_mask."""
    from accelerate_tpu.models import llama
    from accelerate_tpu.ops.flash_attention import flash_attention

    S, H, K, hd = sizes.seq // 4, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(SEED + 9), 3)
    q = jax.random.normal(kq, (2, S, H, hd), jnp.bfloat16)
    k = jax.random.normal(kk, (2, S, K, hd), jnp.bfloat16)
    v = jax.random.normal(kv, (2, S, K, hd), jnp.bfloat16)
    a, b = S // 3, S // 2                      # row 0: three documents and a padded tail
    seg = jnp.asarray(np.stack([
        np.repeat([1, 2, 3, 0], [a, b, S - a - b - S // 40, S // 40]), np.ones(S, int),
    ]), jnp.int32)
    live = (seg != 0)[:, :, None, None]        # padded rows: kernel zeros, reference junk

    def kernel_loss(q, k, v):
        o = flash_attention(q, k, v, segment_ids=seg, interpret=False)
        return jnp.where(live, o, 0).astype(jnp.float32).sum()

    def ref_loss(q, k, v):
        with jax.default_matmul_precision("highest"):
            o = llama._attention_xla(q, k, v, llama.segment_mask(seg), cfg)
        return jnp.where(live, o, 0).astype(jnp.float32).sum()

    got = twice("flash packed fwd+dq+dkv", jax.jit(jax.grad(kernel_loss, (0, 1, 2))), q, k, v)
    want = jax.jit(jax.grad(ref_loss, (0, 1, 2)))(q, k, v)
    for name, g, w in zip(("dq", "dk", "dv"), got, want):
        close(g, w, TOL_BF16, f"flash packed rows {name}")


def lane_tables(rng, lens, left_pad, P: int, MP: int, ps: int):
    """Block tables [B, MP] over a shuffled pool of P pages (entry P = unallocated) and
    the valid mask [B, MP * ps] of lanes that hold ``lens[b]`` slots, the first
    ``left_pad[b]`` of them invalid pads."""
    tables = np.full((len(lens), MP), P, np.int32)
    free = list(rng.permutation(P))
    valid = np.zeros((len(lens), MP * ps), bool)
    for b, n in enumerate(lens):
        for j in range(-(-int(n) // ps)):
            tables[b, j] = free.pop()
        valid[b, left_pad[b]:n] = True
    return tables, valid


def kernel_paged(cfg, sizes: Sizes, quantized: bool) -> None:
    """paged attention (T=1 decode and T=3 verify) vs paged_attention_reference."""
    from accelerate_tpu.models.common import paged_kv_planes, write_kv_paged
    from accelerate_tpu.ops.paged_attention import paged_attention, paged_attention_reference

    H, K, hd, ps = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, PAGE_SIZE
    B, C = SLOTS, sizes.max_len
    MP = C // ps
    P = B * MP
    rng = np.random.default_rng(SEED + 3)
    lens = np.array([max(8, C // 200), C // 2 + C // 16, C - 1, C // 11])[:B]
    tables, valid = lane_tables(rng, lens, [2] * B, P, MP, ps)   # two left-pad slots a lane
    pos = np.arange(C)
    pages = np.where(valid, tables[np.arange(B)[:, None], pos // ps], P)
    offs = np.broadcast_to(pos % ps, (B, C))
    kk, kv, kq = jax.random.split(jax.random.PRNGKey(SEED + 4), 3)
    pool = paged_kv_planes(P, ps, K, hd, jnp.bfloat16, quantized)
    pool = {
        **write_kv_paged(pool, "k", jax.random.normal(kk, (B, C, K, hd), jnp.bfloat16),
                         jnp.asarray(pages), jnp.asarray(offs)),
        **write_kv_paged(pool, "v", jax.random.normal(kv, (B, C, K, hd), jnp.bfloat16),
                         jnp.asarray(pages), jnp.asarray(offs)),
    }
    kw = dict(page_size=ps, sm_scale=1.0 / math.sqrt(hd), window=cfg.sliding_window)
    tag = "int8" if quantized else "bf16"
    kernel = jax.jit(lambda *a: paged_attention(*a, interpret=False, **kw))
    reference = jax.jit(lambda *a: paged_attention_reference(*a, **kw))
    for T in (1, 3):
        q = jax.random.normal(jax.random.fold_in(kq, T), (B, T, H, hd), jnp.bfloat16)
        args = (q, pool, jnp.asarray(tables), jnp.asarray((lens - T).astype(np.int32)),
                jnp.asarray(valid))
        got = twice(f"paged {tag} T={T}", kernel, *args)
        with jax.default_matmul_precision("highest"):
            want = reference(*args)
        close(got, want, TOL_BF16, f"paged attention {tag} pages, T={T}")


def kernel_paged_stacked() -> None:
    """The layer-indexed read of the decode program, at the serve cell's shapes: the
    stacked pools of 16 layers (3840 pages of 16, 8 kv heads x 128, bf16: 2 GB a plane)
    under 32 lanes; ``paged_attention(..., layer=l)`` vs the reference on ``pool[l]``."""
    from accelerate_tpu.ops.paged_attention import paged_attention, paged_attention_reference

    L, P, ps, K, H, hd, B, C, window = 16, 3840, PAGE_SIZE, 8, 32, 128, 32, 8192, 4096
    MP = C // ps
    rng = np.random.default_rng(SEED + 9)
    lens = np.concatenate([[1, ps, ps + 1, C - 1, 4097], rng.integers(64, 3000, B - 5)])
    check(sum(-(-int(n) // ps) for n in lens) <= P, "stacked pool: the lanes fit the pool")
    # lane b is left-padded by b slots
    tables, valid = lane_tables(rng, lens, [min(b, int(n) - 1) for b, n in enumerate(lens)],
                                P, MP, ps)
    plane = jax.jit(lambda key: jnp.stack([
        jax.random.normal(jax.random.fold_in(key, l), (P, ps, K, hd), jnp.bfloat16)
        for l in range(L)]))
    kk, kv, kq = jax.random.split(jax.random.PRNGKey(SEED + 10), 3)
    pool = {"k": plane(kk), "v": plane(kv)}
    q = jax.random.normal(kq, (B, 1, H, hd), jnp.bfloat16)
    kw = dict(page_size=ps, sm_scale=1.0 / math.sqrt(hd), window=window)
    args = (jnp.asarray(tables), jnp.asarray((lens - 1).astype(np.int32)), jnp.asarray(valid))
    kernel = jax.jit(lambda pool, l: paged_attention(
        q, pool, *args, layer=l, interpret=False, **kw))
    reference = jax.jit(lambda pool, l: paged_attention_reference(
        q, {"k": pool["k"][l], "v": pool["v"][l]}, *args, **kw))
    for l in (0, 7, L - 1):
        got = twice(f"paged stacked layer {l}", kernel, pool, jnp.int32(l))
        with jax.default_matmul_precision("highest"):
            want = reference(pool, jnp.int32(l))
        close(got, want, TOL_BF16, f"paged attention on the stacked pool, layer {l}")


def kernel_mla() -> None:
    """mla_paged_attention at DeepSeek-V3's published widths (128 heads over latent rows
    of 512 + 64) on 32 lanes of 1 to 13 056 keys, vs mla_paged_attention_reference."""
    from accelerate_tpu.models import deepseek
    from accelerate_tpu.models.common import paged_latent_planes, write_latent_paged
    from accelerate_tpu.ops.mla_attention import (
        mla_paged_attention, mla_paged_attention_reference)

    cfg = deepseek.DeepseekConfig()
    H, R, r, ps = cfg.n_heads, cfg.kv_lora_rank, cfg.qk_rope_dim, PAGE_SIZE
    B, C = 32, 16384
    MP = C // ps
    rng = np.random.default_rng(SEED + 8)
    lens = np.concatenate([[0, 1, ps, 767, 768, 769], rng.integers(4096, 13057, B - 6)])
    P = int(sum(-(-int(n) // ps) for n in lens)) + 1
    # lane b is left-padded by b slots
    tables, valid = lane_tables(rng, lens, [min(b, int(n)) for b, n in enumerate(lens)],
                                P, MP, ps)
    kl, kq, kr = jax.random.split(jax.random.PRNGKey(SEED + 8), 3)
    pool = paged_latent_planes(P, ps, R + r, jnp.bfloat16)
    write = jax.jit(write_latent_paged, donate_argnums=0)
    for b in range(B):                          # a lane at a time: the rows are 19 MB each
        pos = np.arange(C)
        pages = np.where(valid[b], tables[b, pos // ps], P)[None]
        rows = jax.random.normal(jax.random.fold_in(kl, b), (1, C, R + r), jnp.bfloat16)
        pool = write(pool, rows, jnp.asarray(pages), jnp.asarray(pos % ps)[None])
    q_lat = jax.random.normal(kq, (B, H, R), jnp.bfloat16)
    q_rope = jax.random.normal(kr, (B, H, r), jnp.bfloat16)
    kw = dict(page_size=ps, sm_scale=deepseek.sm_scale(cfg) / math.sqrt(R / cfg.qk_nope_dim))
    args = (q_lat, q_rope, pool["latent"], jnp.asarray(tables),
            jnp.asarray(np.maximum(lens - 1, 0).astype(np.int32)), jnp.asarray(valid))
    got = twice("mla paged", jax.jit(
        lambda *a: mla_paged_attention(*a, interpret=False, **kw)), *args)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda *a: mla_paged_attention_reference(*a, **kw))(*args)
    close(got, want, TOL_BF16, "mla paged attention")
    check(not np.asarray(got[0], np.float32).any(), "mla paged attention: an empty lane emits zeros")


def kernel_dsa_index() -> None:
    """dsa_index_scores at dots3-note-prev's published widths (64 index heads of 128 over
    an index-key pool) on 32 lanes of 0 to 25 000 keys of a 32 768-slot row, vs
    dsa_index_scores_reference: the same scores, the same -inf outside each live range."""
    from accelerate_tpu.models import dots3
    from accelerate_tpu.ops.sparse_attention import (
        dsa_index_scores, dsa_index_scores_reference)

    cfg = dots3.Dots3Config()
    Hi, Di, ps = cfg.index_heads, cfg.index_dim, PAGE_SIZE
    B, C = 32, 32768
    MP = C // ps
    rng = np.random.default_rng(SEED + 9)
    lens = np.concatenate([[0, 1, ps, 1023, 1024, 1025], rng.integers(8192, 25001, B - 6)])
    P = int(sum(-(-int(n) // ps) for n in lens)) + 1
    tables, valid = lane_tables(rng, lens, [min(b, int(n)) for b, n in enumerate(lens)],
                                P, MP, ps)
    kp, kq, kw_ = jax.random.split(jax.random.PRNGKey(SEED + 9), 3)
    pool = jax.random.normal(kp, (P, ps, Di), jnp.bfloat16)
    q = jax.random.normal(kq, (B, Hi, Di), jnp.bfloat16)
    w = jax.random.normal(kw_, (B, Hi), jnp.float32) * (Hi * Di) ** -0.5
    args = (q, w, pool, jnp.asarray(tables),
            jnp.asarray(np.maximum(lens - 1, 0).astype(np.int32)), jnp.asarray(valid))
    got = np.asarray(twice("dsa index", jax.jit(
        lambda *a: dsa_index_scores(*a, page_size=ps, interpret=False)), *args))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(jax.jit(
            lambda *a: dsa_index_scores_reference(*a, page_size=ps))(*args))
    live = np.isfinite(want)
    check(bool((np.isfinite(got) == live).all()) and not np.isnan(got).any(),
          f"dsa index scores: -inf outside the live ranges ({int(live.sum())} live keys)")
    check(not live[0].any() and not live[1].any(), "dsa index scores: an empty lane scores nothing")
    close(np.where(live, got, 0.0), np.where(live, want, 0.0), TOL_BF16, "dsa index scores")


def kernel_mla_window_and_gathered() -> None:
    """mla_paged_attention in the two call shapes dots3-note-prev adds: a sliding layer's
    widths (64 heads over latent rows of 1024 + 64) through a ring's computed table, and
    a full layer's widths over a gathered buffer of 2048 rows a lane under an identity
    table with lanes that hold fewer; vs mla_paged_attention_reference."""
    from accelerate_tpu.models import deepseek, dots3
    from accelerate_tpu.models.common import latent_width, ring_pages, ring_tables
    from accelerate_tpu.ops.mla_attention import (
        mla_paged_attention, mla_paged_attention_reference)

    cfg = dots3.Dots3Config()
    ps, B, C = PAGE_SIZE, 32, 32768
    rng = np.random.default_rng(SEED + 10)
    keys = jax.random.split(jax.random.PRNGKey(SEED + 10), 6)

    def both(name, q_lat, q_rope, pool, tables, positions, valid, spec):
        kw = dict(page_size=ps, sm_scale=deepseek.sm_scale(spec))
        args = (q_lat, q_rope, pool, tables, positions, valid)
        got = twice(name, jax.jit(
            lambda *a: mla_paged_attention(*a, interpret=False, **kw)), *args)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(lambda *a: mla_paged_attention_reference(*a, **kw))(*args)
        close(got, want, TOL_BF16, name)

    sw = cfg.attn_spec(2)                               # a sliding layer
    ring = ring_pages(sw.window, ps)
    pos = jnp.asarray(np.concatenate([[0, 5, 512, 513, 8191], rng.integers(8192, C, B - 5)]),
                      jnp.int32)
    valid = jnp.arange(C)[None, :] <= pos[:, None]
    seen = valid & (jnp.arange(C)[None, :] > pos[:, None] - sw.window)
    both("mla over a ring", jax.random.normal(keys[0], (B, sw.n_heads, sw.kv_lora_rank), jnp.bfloat16),
         jax.random.normal(keys[1], (B, sw.n_heads, sw.qk_rope_dim), jnp.bfloat16),
         jax.random.normal(keys[2], (B * ring, ps, latent_width(sw.latent_dim)), jnp.bfloat16),
         ring_tables(pos, C // ps, ps, sw.window), pos, seen, sw)

    fl, K = cfg.attn_spec(0), cfg.index_topk            # a full layer's selected rows
    held = jnp.asarray(np.concatenate([[0, 1, ps, 2047], np.full(B - 4, K)]), jnp.int32)
    both("mla over gathered rows",
         jax.random.normal(keys[3], (B, fl.n_heads, fl.kv_lora_rank), jnp.bfloat16),
         jax.random.normal(keys[4], (B, fl.n_heads, fl.qk_rope_dim), jnp.bfloat16),
         jax.random.normal(keys[5], (B * K // ps, ps, latent_width(fl.latent_dim)), jnp.bfloat16),
         jnp.arange(B * K // ps, dtype=jnp.int32).reshape(B, -1),
         jnp.full((B,), K - 1, jnp.int32), jnp.arange(K)[None, :] < held[:, None], fl)


def kernel_xent(cfg) -> None:
    """fused_xent fwd / dx / dw at [2048, d_model] x [d_model, vocab] vs chunked_ce."""
    from accelerate_tpu.models.common import chunked_ce
    from accelerate_tpu.ops.fused_xent import fused_cross_entropy

    T, D, V = 2048, cfg.d_model, cfg.vocab_size
    kx, kw, kt = jax.random.split(jax.random.PRNGKey(SEED + 5), 3)
    x = jax.random.normal(kx, (T, D), jnp.bfloat16)
    w = (jax.random.normal(kw, (D, V), jnp.float32) / math.sqrt(D)).astype(jnp.bfloat16)
    t = jax.random.randint(kt, (T,), 0, V)

    def kernel_loss(x, w):
        return fused_cross_entropy(x, w, t, interpret=False).sum()

    def ref_loss(x, w):
        with jax.default_matmul_precision("highest"):
            return chunked_ce(x[None], w, t[None], jnp.ones((1, T), jnp.float32),
                              min(512, T), jnp.bfloat16)

    loss, (dx, dw) = twice("fused_xent fwd+dx+dw",
                           jax.jit(jax.value_and_grad(kernel_loss, (0, 1))), x, w)
    loss_ref, (dx_ref, dw_ref) = jax.jit(jax.value_and_grad(ref_loss, (0, 1)))(x, w)
    close(loss, loss_ref, TOL_BF16, "fused_xent summed nll")
    close(dx, dx_ref, TOL_BF16, "fused_xent dx")
    close(dw, dw_ref, TOL_BF16, "fused_xent dw")


def kernel_adamw(cfg) -> None:
    """fused_adamw on one [d_model, d_ff] fp32 leaf, two steps, vs optax.adamw."""
    import optax

    from accelerate_tpu.ops.fused_optim import FusedAdamW

    kp, kg = jax.random.split(jax.random.PRNGKey(SEED + 6))
    p = {"w": jax.random.normal(kp, (cfg.d_model, cfg.d_ff), jnp.float32) * 0.02}
    g = {"w": jax.random.normal(kg, (cfg.d_model, cfg.d_ff), jnp.float32) * 1e-3}
    ours = FusedAdamW(learning_rate=1e-3, weight_decay=1e-2, interpret=False)
    ref = optax.adamw(1e-3, weight_decay=1e-2)

    @jax.jit
    def two_ours(p, g):
        s = ours.init(p)
        for _ in range(2):
            p, s = ours.fused_apply(g, s, p)
        return p, s

    @jax.jit
    def two_ref(p, g):
        s = ref.init(p)
        for _ in range(2):
            u, s = ref.update(g, s, p)
            p = optax.apply_updates(p, u)
        return p, s[0]

    (p1, s1), (p2, s2) = twice("fused_adamw", two_ours, p, g), two_ref(p, g)
    close(p1["w"], p2["w"], TOL_F32, "fused_adamw params")
    close(s1.mu["w"], s2.mu["w"], TOL_F32, "fused_adamw first moment")
    close(s1.nu["w"], s2.nu["w"], TOL_F32, "fused_adamw second moment")


def kernel_int8_matmul(cfg) -> None:
    """the int8 dequant matmul at [256, d_model] x [d_model, d_ff] vs dequantize_weight."""
    from accelerate_tpu.ops.quantization import (
        dequantize_weight, quant_matmul, quantize_weight,
    )

    kx, kw = jax.random.split(jax.random.PRNGKey(SEED + 7))
    x = jax.random.normal(kx, (256, cfg.d_model), jnp.bfloat16)
    qw = quantize_weight(
        jax.random.normal(kw, (cfg.d_model, cfg.d_ff), jnp.float32) * 0.02, "int8")
    got = twice("int8 matmul",
                jax.jit(lambda x, qw: quant_matmul(x, qw, out_dtype=jnp.float32)), x, qw)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda x, qw: x.astype(jnp.float32) @ dequantize_weight(qw))(x, qw)
    close(got, want, TOL_BF16, "int8 dequant matmul")


def kernel_ring(cfg, sizes: Sizes) -> None:
    """ring attention (the flash kernels per ring step) over a four-device sp axis."""
    from accelerate_tpu.parallel import MeshConfig, build_mesh
    from accelerate_tpu.parallel.sequence import make_sp_attention

    S, H, K, hd = sizes.seq, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    mesh = build_mesh(MeshConfig(dp=1, sp=4, devices=jax.devices()[:4]))
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(SEED + 8), 3)
    q = jax.random.normal(kq, (1, S, H, hd), jnp.bfloat16)
    k = jax.random.normal(kk, (1, S, K, hd), jnp.bfloat16)
    v = jax.random.normal(kv, (1, S, K, hd), jnp.bfloat16)
    attn = make_sp_attention(mesh, mode="ring", window=cfg.sliding_window,
                             sm_scale=1.0 / math.sqrt(hd))
    got = twice("ring x4", jax.jit(attn), q, k, v)
    heads = slice(0, cfg.q_per_kv)
    want = jax.jit(lambda q, k, v: attention_reference(q, k, v, cfg, heads))(q, k, v)
    close(got[:, :, heads], want, TOL_BF16, "ring attention forward (4 devices)")


def kernels(cfg, sizes: Sizes, compiles: Compiles, dry: bool) -> None:
    mark, t0 = compiles.mark(), time.perf_counter()
    if dry:  # Mosaic needs a TPU: the dry run only walks the wiring of the references
        say("[kernels] skipped: interpret=False needs the chip")
        return
    kernel_flash(cfg, sizes)
    kernel_flash_prefill(cfg, sizes)
    kernel_flash_packed(cfg, sizes)
    kernel_paged(cfg, sizes, quantized=False)
    kernel_paged(cfg, sizes, quantized=True)
    kernel_paged_stacked()
    kernel_mla()
    kernel_dsa_index()
    kernel_mla_window_and_gathered()
    kernel_xent(cfg)
    kernel_adamw(cfg)
    kernel_int8_matmul(cfg)
    if len(jax.devices()) >= 4:
        kernel_ring(cfg, sizes)
    facts("kernels", compiles, mark, time.perf_counter() - t0, [])


# ------------------------------------------------------------------------------- main
def main() -> int:
    global _PREFIX
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--cpu-dry-run", action="store_true",
                        help="toy widths on the CPU backend; never a pass on its own")
    args = parser.parse_args()
    if args.cpu_dry_run:
        _PREFIX = "cpu dry run: "
        jax.config.update("jax_platforms", "cpu")
    cache_dir = place_compile_cache()

    devices = jax.devices()
    d0 = devices[0]
    import jaxlib

    say(f"jax {jax.__version__} jaxlib {jaxlib.__version__} platform={d0.platform} "
        f"device_kind={d0.device_kind!r} device_count={len(devices)} "
        f"compile_cache={cache_dir or 'JAX_COMPILATION_CACHE_DIR'}")
    if d0.platform != "tpu" and not args.cpu_dry_run:
        print(f"chip_smoke: platform is {d0.platform!r}, not 'tpu' — refusing to run "
              "(--cpu-dry-run exists for checking the script itself)", file=sys.stderr)
        return 2

    sizes = DRY if args.cpu_dry_run else Sizes()
    cfg = model_config(sizes, args.cpu_dry_run)
    compiles = Compiles()
    t0 = time.perf_counter()

    four = len(devices) >= 4
    # On a four-chip host the one-device pass only has to give the first-step loss.
    losses, params = train(cfg, sizes, devices[:1], 1 if four else TRAIN_STEPS, compiles,
                           args.cpu_dry_run)
    if four:
        del params
        gc.collect()
        losses4, params = train(cfg, sizes, devices[:4], TRAIN_STEPS, compiles,
                                args.cpu_dry_run)
        # Same seed, same global batch: only the reduction order and the bf16 rounding
        # of per-shard partial sums differ — 2**-8 relative is one bf16 ulp of the loss.
        check(abs(losses4[0] - losses[0]) <= 2.0 ** -8 * abs(losses[0]),
              f"first-step loss on four devices {losses4[0]:.5f} equals the one-device "
              f"{losses[0]:.5f} within 2**-8 relative")
    serve(params, cfg, sizes, compiles, args.cpu_dry_run)
    del params
    gc.collect()
    kernels(cfg, sizes, compiles, args.cpu_dry_run)

    say(f"all phases passed in {time.perf_counter() - t0:.0f}s")
    say(json.dumps({
        "ok": True,
        "device": {"platform": d0.platform, "kind": d0.device_kind, "count": len(devices)},
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
