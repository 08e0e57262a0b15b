"""GPT-style causal decoder with a KV cache — beyond-reference family.

The reference streams fixed-shape CNN inference; the modern serving
workload is autoregressive decoding, which is only fast if the K/V
projections of past tokens are cached instead of recomputed per step.
TPU-shaped design:

  * static cache buffers [L, B, H, S_max, Dh] updated in place with
    `lax.dynamic_update_slice` — no dynamic shapes, so the decode step
    compiles ONCE and every token reuses it;
  * one jitted step serves both PREFILL (T prompt tokens at once, MXU-
    friendly) and DECODE (T=1): same code path, two compiled shapes;
  * attention masks by cache position (j <= pos + t), so padding slots
    beyond the write head never contribute;
  * layers run under `lax.scan` over the stacked params + cache —
    one compiled block body regardless of depth;
  * reuses the shared pre-LN transformer stack parameters
    (`init_stack`), so checkpoints interchange with SpmdBert/SpmdVit
    stacks of the same config.

`generate` drives greedy/temperature sampling from a host loop with
donated cache buffers (the returned cache aliases the input's memory).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from defer_tpu.ops.gated_delta import gdn_chunked, gdn_step
from defer_tpu.parallel.transformer_stack import (
    TransformerConfig,
    _layer_norm,
    _rms_norm,
    apply_rope,
    EXPERT_LEAVES,
    LINEAR,
    act_einsum,
    embed_lookup,
    held_experts_ffn,
    init_stack,
    leaf_group,
    norm_apply,
)

# The float32 scores of a multi-token attention step, [B, Hq, T, S],
# may take this many bytes; over it the step attends one KV group and
# a chunk of its queries at a time, the chunk sized from the shapes
# alone. 32 heads x 1024 queries x 4096 keys is exactly this, and stays
# whole.
_SCORE_BYTES = 1 << 29
# Rows of scores longer than this take their maximum behind an
# optimization barrier. XLA's TPU compiler fuses `x - max(x)` into one
# pass by turning the row maximum into a reduce-window as wide as the
# row; over 8192 columns that one fusion took 47 ms per 2^27 scores
# (PERF.md, PR 28), fifty times the two products beside it. Rows of
# 4096 and under keep `jax.nn.softmax`, and their programs with it.
_SOFTMAX_ROW = 4096


def _query_chunk(b: int, g: int, t: int, s: int) -> int:
    """The most queries (a divisor of `t`) of ONE KV group of `g`
    heads whose float32 scores over `s` keys fit `_SCORE_BYTES`."""
    per_query = b * g * s * 4
    return next(
        t // n for n in range(1, t + 1)
        if t % n == 0 and (t // n) * per_query <= _SCORE_BYTES or n == t
    )


def seen_tokens_mask(ids: jax.Array, vocab: int) -> jax.Array:
    """[B, V] presence mask of `ids` [B, T]. Build it ONCE from the
    prompt, then mark each emitted token with a single-element scatter
    — O(B) per step instead of re-scattering the whole growing
    sequence."""
    b = ids.shape[0]
    return (
        jnp.zeros((b, vocab), bool)
        .at[jnp.arange(b)[:, None], ids]
        .set(True)
    )


def repetition_penalty(
    logits: jax.Array, seen: jax.Array, penalty: float
) -> jax.Array:
    """Discourage already-emitted tokens (HF semantics: a positive
    logit divides by the penalty, a negative one multiplies — both
    push the score down for penalty > 1). `seen` is a [B, V] presence
    mask (seen_tokens_mask) or, for one-shot use, a [B, T] id array."""
    if penalty == 1.0:
        return logits
    if seen.dtype != jnp.bool_:
        seen = seen_tokens_mask(seen, logits.shape[-1])
    penalized = jnp.where(logits > 0, logits / penalty, logits * penalty)
    return jnp.where(seen, penalized, logits)


def truncate_logits(
    logits: jax.Array,
    *,
    top_k: int = 0,
    top_p: float = 1.0,
    min_p: float = 0.0,
) -> jax.Array:
    """Mask logits outside the sampling support to -inf.

    top_k > 0 keeps the k highest logits (ties at the k-th value all
    survive). top_p < 1 keeps the nucleus: tokens whose cumulative
    probability mass, accumulated in descending-probability order,
    is needed to first reach top_p (the top token always survives).
    min_p > 0 keeps tokens whose probability is at least min_p times
    the top token's probability — a confidence-scaled floor that
    adapts to how peaked the distribution is. All filters are
    static-shape (top_k / sort + cumsum / max), so the policy jits
    into the decode step without host round trips.
    """
    neg = jnp.finfo(logits.dtype).min
    if top_k and top_k < logits.shape[-1]:
        kth = lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, neg, logits)
    if min_p > 0.0:
        probs = jax.nn.softmax(logits, axis=-1)
        floor = min_p * jnp.max(probs, axis=-1, keepdims=True)
        logits = jnp.where(probs < floor, neg, logits)
    if top_p < 1.0:
        desc = jnp.sort(logits, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(desc, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # A token stays iff the mass strictly before it is < top_p;
        # the cutoff is the smallest surviving logit. Column 0 is the
        # highest-probability token — pinned so even top_p <= 0 keeps
        # it (otherwise everything masks and sampling turns uniform).
        keep = (cum - probs) < top_p
        keep = keep.at[..., 0].set(True)
        cutoff = jnp.min(
            jnp.where(keep, desc, jnp.inf), axis=-1, keepdims=True
        )
        logits = jnp.where(logits < cutoff, neg, logits)
    return logits


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Per-request sampling policy for the serving stack
    (runtime/decode_server.py, runtime/paged.py): the same knobs
    `generate` takes, plus the seed that makes a server slot reproduce
    the solo stream exactly. temperature 0 = greedy (filters unused).

    `constraint` names a server-registered constraint DFA
    (defer_tpu/constrain/; servers take `constraints={name: dfa}`):
    the slot's logits are masked to grammar-admissible tokens every
    tick, composing with any temperature/filter setting — including
    the temperature-0 greedy fast path, which stays greedy over the
    masked logits."""

    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    min_p: float = 0.0
    seed: int = 0
    constraint: str | None = None

    def validate(self) -> None:
        if self.temperature < 0:
            raise ValueError(f"temperature {self.temperature} < 0")
        if self.top_k < 0:
            raise ValueError(f"top_k {self.top_k} < 0")
        if not 0 < self.top_p <= 1:
            raise ValueError(f"top_p {self.top_p} not in (0, 1]")
        if not 0 <= self.min_p <= 1:
            raise ValueError(f"min_p {self.min_p} not in [0, 1]")


def truncate_logits_batched(
    logits: jax.Array,
    top_k: jax.Array,
    top_p: jax.Array,
    min_p: jax.Array,
) -> jax.Array:
    """truncate_logits with PER-ROW (B,) parameter vectors instead of
    static scalars — the jitted decode tick of the serving stack runs
    every slot's policy in one batched pass. Same filters in the same
    order; a disabled filter (top_k <= 0 or >= V, top_p >= 1,
    min_p <= 0) reduces to a neutral threshold that compares
    identically to the skipped branch, so each row's output is
    BIT-IDENTICAL to truncate_logits on that row with its static
    params (the serving parity contract)."""
    neg = jnp.finfo(logits.dtype).min
    v = logits.shape[-1]
    # top_k: threshold at the row's k-th highest value (ties survive,
    # as with lax.top_k); disabled rows threshold at -inf.
    desc = jnp.sort(logits, axis=-1)[..., ::-1]
    kth = jnp.take_along_axis(
        desc, (jnp.clip(top_k, 1, v) - 1)[:, None], axis=-1
    )
    kth = jnp.where(
        ((top_k > 0) & (top_k < v))[:, None], kth, -jnp.inf
    )
    logits = jnp.where(logits < kth, neg, logits)
    # min_p: confidence-scaled floor over the top_k-masked rows
    # (min_p = 0 -> floor 0, nothing masks).
    probs = jax.nn.softmax(logits, axis=-1)
    floor = min_p[:, None] * jnp.max(probs, axis=-1, keepdims=True)
    logits = jnp.where(probs < floor, neg, logits)
    # top_p: nucleus over the re-sorted masked rows; disabled rows get
    # a -inf cutoff (everything survives).
    desc2 = jnp.sort(logits, axis=-1)[..., ::-1]
    probs2 = jax.nn.softmax(desc2, axis=-1)
    cum = jnp.cumsum(probs2, axis=-1)
    keep = (cum - probs2) < top_p[:, None]
    keep = keep.at[..., 0].set(True)
    cutoff = jnp.min(
        jnp.where(keep, desc2, jnp.inf), axis=-1, keepdims=True
    )
    cutoff = jnp.where((top_p < 1.0)[:, None], cutoff, -jnp.inf)
    return jnp.where(logits < cutoff, neg, logits)


@jax.jit
@jax.named_scope("sample")
def sample_token_batched(
    logits_last: jax.Array,
    keys: jax.Array,
    temperature: jax.Array,
    top_k: jax.Array,
    top_p: jax.Array,
    min_p: jax.Array,
) -> tuple[jax.Array, jax.Array]:
    """sample_token with per-row (B,) policies and ONE PRNG key per
    row: each row splits its key exactly once per emitted token — the
    key schedule solo generate follows — and draws its categorical on
    the row's filtered logits, so a server slot seeded with
    jax.random.key(seed) reproduces `generate(..., rng=key(seed))`
    bit-for-bit. Greedy rows (temperature <= 0) take argmax of the raw
    logits; their key advances harmlessly (re-seeded at admission).
    Returns (tokens (B,), advanced keys (B,))."""
    pair = jax.vmap(lambda kk: jax.random.split(kk, 2))(keys)
    carry, sub = pair[:, 0], pair[:, 1]
    greedy = temperature <= 0
    safe_t = jnp.where(greedy, 1.0, temperature)
    filtered = truncate_logits_batched(
        logits_last / safe_t[:, None], top_k, top_p, min_p
    )
    sampled = jax.vmap(jax.random.categorical)(sub, filtered)
    toks = jnp.where(
        greedy, jnp.argmax(logits_last, axis=-1), sampled
    )
    return toks, carry


@jax.jit
@jax.named_scope("sample")
def sample_token_batched_nosort(
    logits_last: jax.Array,
    keys: jax.Array,
    temperature: jax.Array,
    min_p: jax.Array,
) -> tuple[jax.Array, jax.Array]:
    """sample_token_batched for ticks where NO row enables top-k or
    top-p: both of truncate_logits_batched's full-vocab O(V log V)
    sorts exist only to find the kth/nucleus thresholds, and with the
    filters disabled those thresholds are -inf, making their masking
    `where`s bitwise identity. This variant drops the sorts and keeps
    every op the survivors see — temperature scale, the min_p
    floor (same softmax over the same scaled logits), the categorical
    on the same advanced key — so each row's token is BIT-IDENTICAL
    to sample_token_batched with top_k=0 / top_p=1 on that row, and
    the key state advances identically (servers can switch variants
    tick-by-tick). Dispatch is the caller's job: SlotSampler tracks
    per-slot policies on the host and routes here only when no active
    slot sorts."""
    pair = jax.vmap(lambda kk: jax.random.split(kk, 2))(keys)
    carry, sub = pair[:, 0], pair[:, 1]
    greedy = temperature <= 0
    safe_t = jnp.where(greedy, 1.0, temperature)
    logits = logits_last / safe_t[:, None]
    # min_p exactly as in truncate_logits_batched (the top_k where it
    # follows there is identity at kth = -inf).
    neg = jnp.finfo(logits.dtype).min
    probs = jax.nn.softmax(logits, axis=-1)
    floor = min_p[:, None] * jnp.max(probs, axis=-1, keepdims=True)
    filtered = jnp.where(probs < floor, neg, logits)
    sampled = jax.vmap(jax.random.categorical)(sub, filtered)
    toks = jnp.where(
        greedy, jnp.argmax(logits_last, axis=-1), sampled
    )
    return toks, carry


def _flash_decode_mode() -> str | None:
    """Which attention path the T=1 decode step takes: "tpu" (the
    pallas flash-decode kernel, on a TPU), "interpret"
    (DEFER_TPU_PALLAS_INTERPRET=1 — the kernel through the pallas
    interpreter, for CI parity tests off-TPU), or None (the XLA
    einsum, everywhere else). Checked at trace time; set the env
    before building steps (compiled steps are memoized)."""
    import os

    if os.environ.get("DEFER_TPU_PALLAS_INTERPRET") == "1":
        return "interpret"
    from defer_tpu.ops.attention import _pallas_available

    return "tpu" if _pallas_available() else None


#: Host-sync cadence for eos early-stop polling: `finished.all()` is a
#: blocking device round trip, so the decode loops check it every K
#: tokens instead of every token — early stop costs at most K-1 wasted
#: ticks while the loop keeps its host run-ahead.
EOS_POLL_EVERY = 8


def apply_eos(
    nxt: jax.Array, finished: jax.Array, eos_id: int
) -> tuple[jax.Array, jax.Array]:
    """Shared stop-token step for every decode loop (generate, T5):
    pin already-finished rows to eos_id BEFORE updating the mask, so a
    pinned row keeps counting as finished and a row finishes ON its
    first eos emission. Returns (next_tokens [B, 1], finished [B])."""
    nxt = jnp.where(finished[:, None], eos_id, nxt)
    finished = finished | (nxt[:, 0] == eos_id)
    return nxt, finished


def sampled_decode_loop(
    step,
    params: dict,
    cache,
    last: jax.Array,
    ids: jax.Array,
    num_steps: int,
    *,
    temperature: float = 0.0,
    top_k: int = 0,
    top_p: float = 1.0,
    min_p: float = 0.0,
    rep_penalty: float = 1.0,
    eos_id: int | None = None,
    stop_sequences=None,
    pad_id: int | None = None,
    rng: jax.Array | None = None,
) -> jax.Array:
    """The one host-side decode loop both decoder families drive
    (GptDecoder.generate, T5.generate): sample from `last`, append to
    `ids`, feed the compiled `step(params, cache, nxt)` — with the
    eos machinery (pin finished rows, poll-every-K early break, pad
    back to the [B, T + num_steps] shape contract) in a single place.
    The final sampled token needs no forward pass; its logits would
    never be used.

    `stop_sequences` — multi-token stops (runtime/stopping.py): a row
    whose GENERATED tail completes any sequence stops mid-budget, its
    output ending with the stop sequence; later positions pin to
    `pad_id` (defaults to eos_id, else 0). Suffix matching is
    host-side, so stop-sequence decoding costs one device->host token
    transfer per step (the eos-only path keeps its poll-every-K
    run-ahead)."""
    b = ids.shape[0]
    dtype = ids.dtype
    if rng is None:
        rng = jax.random.key(0)
    finished = jnp.zeros((b,), bool) if eos_id is not None else None
    matchers = None
    if stop_sequences:
        from defer_tpu.runtime.stopping import StopMatcher, normalize_stops

        seqs = normalize_stops(stop_sequences)
        matchers = [StopMatcher(seqs) for _ in range(b)]
        stopped = np.zeros((b,), bool)
    pad_tok = (
        pad_id
        if pad_id is not None
        else (eos_id if eos_id is not None else 0)
    )
    # Presence mask built once from the prompt; each emitted token is
    # a single-element scatter (not a re-scan of the whole sequence).
    seen = None
    steps_done = 0
    for i in range(num_steps):
        if rep_penalty != 1.0:
            if seen is None:
                seen = seen_tokens_mask(ids, last.shape[-1])
            last = repetition_penalty(last, seen, rep_penalty)
        nxt, rng = sample_token(
            last, rng, temperature, top_k=top_k, top_p=top_p, min_p=min_p
        )
        nxt = nxt[:, None].astype(dtype)
        if eos_id is not None:
            nxt, finished = apply_eos(nxt, finished, eos_id)
        if matchers is not None:
            if stopped.any():
                # Rows that already hit a stop sequence emit padding.
                nxt = jnp.where(
                    jnp.asarray(stopped)[:, None],
                    jnp.asarray(pad_tok, dtype),
                    nxt,
                )
            # analysis: ignore[host-sync-in-hot-loop] stop matching is
            # a host automaton: one batched [B] transfer per step is
            # the documented price of stop_sequences (this branch only
            # runs when they are set)
            host_nxt = np.asarray(nxt[:, 0])
            # The per-token host sync is already paid here, so the eos
            # mask is free every step — it guards the matchers (an
            # eos-finished row's pinned padding must never stop-match;
            # matching covers GENERATED tokens only) and breaks the
            # loop without waiting for the EOS_POLL_EVERY cadence.
            eos_done = (
                # analysis: ignore[host-sync-in-hot-loop] rides the
                # per-token sync already paid just above — see comment
                np.asarray(finished) if eos_id is not None else None
            )
            for r in range(b):
                if stopped[r] or (
                    eos_done is not None and eos_done[r]
                ):
                    continue
                if matchers[r].push(int(host_nxt[r])):
                    stopped[r] = True
        if seen is not None:
            seen = seen.at[jnp.arange(b), nxt[:, 0]].set(True)
        ids = jnp.concatenate([ids, nxt], axis=1)
        steps_done = i + 1
        # Early break: the stop path is host-synchronous every step;
        # the eos-only path keeps its poll-every-K run-ahead.
        if matchers is not None:
            done_rows = (
                stopped if eos_done is None else (stopped | eos_done)
            )
            if done_rows.all():
                break
        elif (
            eos_id is not None
            and (i + 1) % EOS_POLL_EVERY == 0
            and bool(finished.all())
        ):
            break
        if i + 1 < num_steps:
            logits, cache = step(params, cache, nxt)
            last = logits[:, -1, :]
    if steps_done < num_steps:
        pad = jnp.full(
            (b, num_steps - steps_done),
            eos_id if eos_id is not None and matchers is None else pad_tok,
            dtype,
        )
        ids = jnp.concatenate([ids, pad], axis=1)
    return ids


def sample_token(
    logits_last: jax.Array,
    rng: jax.Array,
    temperature: float,
    *,
    top_k: int = 0,
    top_p: float = 1.0,
    min_p: float = 0.0,
) -> tuple[jax.Array, jax.Array]:
    """One sampling policy for every decode loop (generate, examples):
    greedy at temperature 0 (filters ignored), otherwise categorical
    over logits/temperature restricted by truncate_logits.
    Returns (token_ids, next_rng)."""
    if temperature > 0:
        rng, sub = jax.random.split(rng)
        logits = truncate_logits(
            logits_last / temperature,
            top_k=top_k,
            top_p=top_p,
            min_p=min_p,
        )
        tok = jax.random.categorical(sub, logits, axis=-1)
    else:
        tok = jnp.argmax(logits_last, axis=-1)
    return tok, rng


@dataclasses.dataclass
class GptDecoder:
    """Decoder-only transformer with weight-tied output head.

    rolling_cache=True (sliding-window models only, rotary positions):
    the KV cache holds cfg.window slots instead of cfg.max_len, each
    new row overwriting slot position%W — cache memory is bounded by
    the window and generation length becomes unbounded. Attention runs
    over [cache, current-step keys] with explicit absolute positions,
    so a multi-token (prefill) step never loses in-window keys to
    same-step overwrites."""

    cfg: TransformerConfig
    compute_dtype: Any = jnp.bfloat16
    rolling_cache: bool = False
    # The precision of every matrix product of a step that states none
    # of its own ("high", "highest"; `jax.default_matmul_precision`'s
    # names). None leaves the platform's default: on a TPU one bf16
    # pass, which rounds float32 activations to bf16 on their way in.
    # With compute_dtype float32 over bf16 weights this is how a model
    # whose layers multiply a rounding (a recurrent layer's q.k nearly
    # cancels) is served to float32's accuracy.
    matmul_precision: str | None = None
    # What K and V rows are stored as, in the flat cache and in the
    # paged server's pool; None = compute_dtype. float32 activations
    # over a bf16 cache keep a cached row at two bytes a lane.
    cache_dtype: Any = None

    @property
    def kv_dtype(self):
        return self.cache_dtype or self.compute_dtype

    def __post_init__(self):
        if self.cfg.norm_style != "pre":
            raise ValueError(
                "GptDecoder uses pre-LN blocks: cfg.norm_style must be 'pre'"
            )
        if self.cfg.num_experts and self.cfg.ffn_style != "swiglu":
            raise ValueError(
                "the decoder's expert layer is SwiGLU "
                "(held_experts_ffn): GELU experts are the training "
                "stack's (parallel/transformer_stack.py::moe_ffn)"
            )
        if self.rolling_cache and (
            self.cfg.layer_kinds is not None or self.cfg.num_experts
        ):
            raise ValueError(
                "rolling_cache holds one window for the whole stack and "
                "no counters: it does not serve layer kinds "
                "(cfg.layer_kinds) or experts (cfg.num_experts)"
            )
        if self.cfg.lora_rank:
            raise ValueError(
                "GptDecoder serves merged weights only: fold adapters "
                "with parallel.lora.merge_lora and build the decoder "
                "from a lora_rank=0 config (same serving cost, no "
                "adapter keys in the cacheable step)"
            )
        if self.rolling_cache and (
            self.cfg.window is None or self.cfg.pos_style != "rope"
        ):
            raise ValueError(
                "rolling_cache needs cfg.window (sliding-window "
                "attention) and pos_style='rope' (positions are "
                "unbounded, a learned table is not)"
            )

    # -- params / cache ---------------------------------------------------

    def init(self, rng: jax.Array) -> dict:
        cfg = self.cfg
        k_embed, k_stack, k_ln = jax.random.split(rng, 3)
        p = {
            "token_embedding": jax.random.normal(
                k_embed, (cfg.vocab_size, cfg.dim)
            )
            * 0.02,
            "final_ln_scale": (
                jnp.zeros if cfg.norm_offset else jnp.ones
            )((cfg.dim,)),
            "stack": init_stack(k_stack, cfg),
        }
        if cfg.untied_head:
            p["lm_head"] = (
                jax.random.normal(k_ln, (cfg.vocab_size, cfg.dim)) * 0.02
            )
        if cfg.pos_style == "learned":
            p["pos_embedding"] = (
                jax.random.normal(
                    jax.random.fold_in(k_embed, 1), (cfg.max_len, cfg.dim)
                )
                * 0.02
            )
        if cfg.norm_type == "layer" and cfg.norm_bias:
            p["final_ln_bias"] = jnp.zeros((cfg.dim,))
        return p

    def cast_params(self, params: dict) -> dict:
        """Float params re-stored in compute_dtype — the serving
        configuration. Decode is weight-HBM-read bound, so fp32-stored
        params (init's default, kept for test precision) cost 2x the
        bandwidth of bf16 storage; the step's per-use astype then
        becomes a no-op."""
        return jax.tree_util.tree_map(
            lambda a: a.astype(self.compute_dtype)
            if jnp.issubdtype(a.dtype, jnp.floating)
            else a,
            params,
        )

    def init_cache(self, batch: int) -> dict:
        cfg = self.cfg
        dh = cfg.dh
        # GQA caches store KV heads only — the architecture's memory
        # win: cache bytes scale with kv_heads, not num_heads. Rolling
        # caches bound the slot count by the attention window instead
        # of max_len.
        slots = cfg.window if self.rolling_cache else cfg.max_len
        # Only the layers with keys and values have rows here.
        shape = (cfg.layers_of("attn"), batch, cfg.kv_heads, slots, dh)
        cache = {
            "k": jnp.zeros(shape, self.kv_dtype),
            "v": jnp.zeros(shape, self.kv_dtype),
            "pos": jnp.zeros((), jnp.int32),
        }
        if cfg.has_linear:
            # A recurrent layer's state does not grow with the
            # sequence: the rule's S (float32) and the rows its
            # convolution still needs.
            cache["gdn_s"], cache["gdn_conv"] = self.init_linear_state(batch)
        if cfg.num_experts or cfg.has_linear:
            # A step's first `moe_live` rows are real, the rest the
            # padding of a bucket: the expert layer's counters count
            # the real ones, and a recurrent layer's state moves on
            # them alone.
            cache["moe_live"] = jnp.full((), cfg.max_len, jnp.int32)
        if cfg.num_experts:
            # The expert layer's counters ride in the cache: per layer
            # [assignments on held experts, distinct held experts
            # touched].
            cache["moe"] = jnp.zeros((cfg.num_layers, 2), jnp.int32)
        return cache

    def init_linear_state(self, batch: int) -> tuple:
        """The recurrent layers' two states for `batch` sequences,
        zeros: S [Ll, batch, Hv, dk, dv] float32 and the convolution's
        last rows [Ll, batch, gdn_conv - 1, channels]."""
        cfg = self.cfg
        n = cfg.layers_of("linear")
        return (
            jnp.zeros(
                (n, batch, cfg.gdn_v_heads, cfg.gdn_k_dim, cfg.gdn_v_dim),
                jnp.float32,
            ),
            jnp.zeros(
                (n, batch, cfg.gdn_conv - 1, cfg.gdn_channels),
                self.compute_dtype,
            ),
        )

    def split_experts(self, stack: dict) -> tuple[dict, dict]:
        """(the leaves a layer scan slices, the expert leaves it must
        leave whole): `held_experts_ffn` indexes the second by layer
        where it reads them. A dense stack has none."""
        if not self.cfg.num_experts:
            return stack, {}
        whole = {k: v for k, v in stack.items() if k in EXPERT_LEAVES}
        return {k: v for k, v in stack.items() if k not in whole}, whole

    def scan_layers(self, body, carry, stack, caches=()):
        """`lax.scan` of `body(carry, p, caches_l, kind, layer) ->
        (out, ys)` over the layers of `stack` and the layer-stacked
        `caches`; returns (carry, ys, stats). `carry` is the
        activations, or a tuple that holds more (the paged step's
        pools). A homogeneous dense stack scans layer by layer with
        kind and layer None, as it always has. With `cfg.layer_kinds`
        the scan runs over PERIODS and the period's layers are
        unrolled in its body, so that each layer's kind (its window
        and rotary flag, or "linear") stays static. An expert
        decoder's `p` holds its expert leaves whole beside the layer's
        own (`split_experts`) and its `out` is `(carry, stats)`,
        stacked into stats [L, 2]; stats is None for a dense one.

        A stack with recurrent layers is stacked by GROUP: the leaves
        only an attention layer has over the attention layers, the
        `gdn_*` ones over the recurrent layers, the rest over all
        (`leaf_group`). Its `caches` and `ys` are dicts {"attn": ...,
        "linear": ...}, each group's stacked over its own layers, and
        a layer's body is handed its group's."""
        cfg = self.cfg
        if cfg.layer_kinds is None and not cfg.num_experts:
            carry, ys = lax.scan(
                lambda c, xs: body(c, *xs, None, None), carry, (stack, caches)
            )
            return carry, ys, None
        kinds = cfg.kinds
        per = len(kinds)
        n_per = cfg.num_layers // per
        sliced, whole = self.split_experts(stack)
        by_group = cfg.has_linear
        group_of = [LINEAR if k == LINEAR else "attn" for k in kinds]
        # Where each layer of a period lies within each group.
        place = {
            g: [j for j in range(per) if g in ("all", group_of[j])]
            for g in ("all", "attn", LINEAR)
        }

        def fold(tree, group):
            return jax.tree.map(
                lambda a: a.reshape(n_per, len(place[group]), *a.shape[1:]), tree
            )

        def pick(tree, group, j):
            return jax.tree.map(lambda a: a[place[group].index(j)], tree)

        def period(carry, xs):
            layers, caches_p, first = xs
            ys = {g: [] for g in place}
            stats = []
            for j, kind in enumerate(kinds):
                p = {
                    k: pick(v, leaf_group(k), j)
                    for k, v in layers.items()
                    if j in place[leaf_group(k)]
                }
                group = group_of[j] if by_group else "all"
                caches_l = pick(
                    caches_p[group] if by_group else caches_p, group, j
                )
                out, y = body(carry, {**p, **whole}, caches_l, kind, first + j)
                carry, st = out if cfg.num_experts else (out, None)
                ys[group].append(y)
                stats.append(st)
            stack_up = lambda *a: jnp.stack(a)  # noqa: E731
            return carry, (
                {g: jax.tree.map(stack_up, *v) for g, v in ys.items() if v},
                jax.tree.map(stack_up, *stats),
            )

        carry, (ys, stats) = lax.scan(
            period, carry,
            (
                {k: fold(v, leaf_group(k)) for k, v in sliced.items()},
                {g: fold(c, g) for g, c in caches.items()}
                if by_group else fold(caches, "all"),
                jnp.arange(n_per) * per,
            ),
        )
        ys, stats = jax.tree.map(
            lambda a: a.reshape(-1, *a.shape[2:]), (ys, stats)
        )
        return carry, (ys if by_group else ys["all"]), stats

    # -- one step (prefill or decode) -------------------------------------

    def _split_heads(self, x: jax.Array) -> jax.Array:
        # Head count inferred from the actual width: under tensor
        # parallelism each shard sees D/tp == local_heads * Dh.
        b, t, d = x.shape
        dh = self.cfg.dh
        return x.reshape(b, t, d // dh, dh).transpose(0, 2, 1, 3)

    def _proj_fns(self, p: dict, dt, adapter_ids=None):
        """The (bias, proj) closures every block stage shares —
        factored out so the paged block-native steps
        (runtime/paged.py) run the EXACT projection code `_block`
        runs, not a reimplementation."""
        from defer_tpu.models.quant import dequantize_leaf

        def W(name):
            # Plain bf16/fp32 matrices pass through; int8-quantized
            # leaves ({"q","s"}, models/quant.py) widen here and XLA
            # fuses the dequant into the matmul (HBM reads stay int8).
            return dequantize_leaf(p[name], dt)

        def bias(h, name):
            return h + p[name].astype(dt) if name in p else h

        def proj(h, name):
            """Base matmul plus, in multi-LoRA serving, each batch
            row's OWN adapter delta: the per-layer adapter banks
            ({name}:a [A, in, r] / {name}:b [A, r, out], pre-scaled —
            parallel/lora.py::stack_adapters) are gathered by the
            slot's adapter id, so one weight read serves every tenant
            and only the two skinny per-row einsums differ."""
            if getattr(p[name], "dtype", None) == jnp.bfloat16 != dt:
                # float32 activations over a bf16 matrix: one pass of
                # three bf16 pieces (`act_einsum`).
                y = act_einsum("...d,df->...f", h, p[name])
            else:
                y = h @ W(name)
            a = p.get(f"{name}:a")
            if a is not None and adapter_ids is not None:
                a_sel = a[adapter_ids].astype(dt)  # [B, in, r]
                b_sel = p[f"{name}:b"][adapter_ids].astype(dt)
                low = jnp.einsum("btd,bdr->btr", h, a_sel)
                y = y + jnp.einsum("btr,bro->bto", low, b_sel)
            return y

        return bias, proj

    @jax.named_scope("attn_qkv")
    def _attn_qkv(
        self, p: dict, x, pos, adapter_ids=None, kind=None, with_gate=False
    ):
        """ln1 + q/k/v projections (+rope at the step's absolute
        positions) + head split: everything a block does BEFORE the
        cache layout matters. Returns (q [B,Hq,T,Dh], k, v
        [B,Hkv,T,Dh]). Shared verbatim by `_block` and the paged
        block-native steps so their new K/V rows are bit-identical.
        `kind` is the layer's entry of `cfg.layer_kinds` (a layer that
        is not rotary gets no positions at all). With `cfg.qk_norm` q
        and k are RMS-normed per head before the rotation, with
        `cfg.rotary_dim` only a head's first lanes rotate, and with
        `cfg.attn_gate` wq holds per head [q | gate]: `with_gate` then
        adds the gate [B, T, Hq*Dh] as a fourth result."""
        cfg = self.cfg
        dt = x.dtype
        dh = cfg.dh
        per_slot = getattr(pos, "ndim", 0) == 1
        bias, proj = self._proj_fns(p, dt, adapter_ids)
        h = norm_apply(cfg, x, p, "ln1")
        qf = bias(proj(h, "wq"), "bq")
        kf = bias(proj(h, "wk"), "bk")
        vf = bias(proj(h, "wv"), "bv")
        gate = None
        if cfg.attn_gate:
            b, t, _ = qf.shape
            qf, gate = (
                a.reshape(b, t, -1)
                for a in jnp.split(qf.reshape(b, t, -1, 2 * dh), 2, axis=-1)
            )
        if cfg.qk_norm:

            def head_norm(a, scale):
                heads = a.reshape(*a.shape[:2], -1, dh)
                return _rms_norm(
                    heads, scale, cfg.layer_norm_eps, cfg.norm_offset
                ).reshape(a.shape)

            qf = head_norm(qf, p["q_norm_scale"])
            kf = head_norm(kf, p["k_norm_scale"])
        if cfg.kind_of(kind)[1]:
            steps_r = jnp.arange(qf.shape[1])
            positions = (
                pos[:, None] + steps_r[None] if per_slot else pos + steps_r
            )
            qf = apply_rope(
                qf, dh, positions, cfg.rope_theta, cfg.rope_pairing,
                cfg.rotary_dim,
            )
            kf = apply_rope(
                kf, dh, positions, cfg.rope_theta, cfg.rope_pairing,
                cfg.rotary_dim,
            )
        heads = (
            self._split_heads(qf),
            self._split_heads(kf),
            self._split_heads(vf),
        )
        return heads + (gate,) if with_gate else heads

    def _attn_out(
        self, p: dict, x, attn, tp_axis=None, adapter_ids=None, live=None,
        layer=None, gate=None, wo="wo",
    ):
        """Everything a block does AFTER attention: wo projection
        (+psum under tp), residual, ln2, FFN. `attn` is the merged
        [B, T, Hq*Dh] attention output. Shared by `_block` and the
        paged block-native steps. A parallel block (`cfg.parallel_block`)
        feeds the FFN the block's one norm of its INPUT and adds both
        branches to it. An expert decoder returns `(out, stats)`, the
        expert layer's counters over the `live` rows
        (`held_experts_ffn`, which is handed `layer` where the expert
        leaves of `p` are still layer-stacked: `split_experts`). `gate`
        (`cfg.attn_gate`) scales the attention output by its sigmoid
        before the projection, and `wo` names the projection's leaf (a
        recurrent layer's is `gdn_out`)."""
        cfg = self.cfg
        bias, proj = self._proj_fns(p, x.dtype, adapter_ids)
        if gate is not None:
            with jax.named_scope("attn_gate"):
                attn = attn * jax.nn.sigmoid(
                    gate.astype(jnp.float32)
                ).astype(attn.dtype)
        with jax.named_scope("attn_out"):
            attn = proj(attn, wo)
            if tp_axis is not None:
                attn = lax.psum(attn, tp_axis)
            attn = bias(attn, "bo")
            x_in, x = x, x + attn
        with jax.named_scope("mlp"):
            if cfg.parallel_block:
                h2 = norm_apply(cfg, x_in, p, "ln1")
            else:
                h2 = norm_apply(cfg, x, p, "ln2")
            if cfg.num_experts:
                ff, stats = held_experts_ffn(p, h2, cfg, live, layer)
                return x + ff, stats
            if cfg.ffn_style == "swiglu":
                gate = jax.nn.silu(proj(h2, "w1"))
                ff = proj(gate * proj(h2, "w3"), "w2")
                if tp_axis is not None:
                    ff = lax.psum(ff, tp_axis)
                return x + ff
            ff = bias(proj(h2, "w1"), "b1")
            ff = jax.nn.gelu(ff)
            ff = proj(ff, "w2")
            if tp_axis is not None:
                ff = lax.psum(ff, tp_axis)
            return bias(x + ff, "b2")

    def _block(
        self,
        p: dict,
        x,
        k_cache,
        v_cache,
        pos,
        tp_axis=None,
        adapter_ids=None,
        kind=None,
        live=None,
        layer=None,
    ):
        """One decoder block on [B, T, D] with cache update; returns
        (out, new_k, new_v); `kind` is the layer's entry of
        `cfg.layer_kinds`, and an expert decoder's `out` is `(x,
        stats)` over the `live` rows (`_attn_out`). Under shard_map
        with tp_axis set, the projections arrive column-sharded (this shard's head group),
        the caches hold only local heads, and wo/w2 are row-sharded
        with psum — the Megatron pattern on the decode path.

        GQA attends grouped: q reshapes to [B, Hkv, G, T, Dh] against
        the [B, Hkv, S, Dh] cache, so the shared KV head is READ once
        per group instead of materialized G times — decode is KV-cache
        bandwidth bound, which is the whole point of GQA.

        `pos` is the cache write head: a scalar (all batch elements at
        the same depth — generate/prefill), or a (B,) vector when
        every slot sits at its own depth (continuous batching,
        runtime/decode_server.py); the branch is trace-time static.

        Dtype contract for callers that own their cache storage: the
        caches arrive here ALREADY in the block's compute dtype. The
        paged server's int8 pool (runtime/paged.py kv_dtype="int8")
        dequantizes at its gather and requantizes the returned new
        rows at its scatter, so this read path — and the new_k/new_v
        it hands back — is storage-dtype-agnostic by construction."""
        q, k, v, gate = self._attn_qkv(
            p, x, pos, adapter_ids, kind, with_gate=True
        )
        attn, k_cache, v_cache = self._attn_core(
            q, k, v, k_cache, v_cache, pos, x.dtype, kind
        )
        out = self._attn_out(
            p, x, attn, tp_axis, adapter_ids, live, layer, gate
        )
        return out, k_cache, v_cache

    def _linear_block(
        self, p: dict, x, s_pool, c_pool, at, n_live=None, live=None,
        layer=None,
    ):
        """One recurrent (Gated DeltaNet) block on [B, T, D]: the
        mixer in the attention's place, then `_attn_out`'s residual,
        second norm and FFN. Its two states are read and written at
        index `at` of their POOLS, s_pool [n, B, Hv, dk, dv] float32
        and c_pool [n, B, gdn_conv - 1, channels]: the paged step hands
        its pools of every recurrent layer and slot and the layer's
        index among them, so that they are updated where they lie; the
        flat step one layer's with n = 1. Rows from `n_live` on (None =
        none) are a bucket's padding and leave both states as the last
        real row left them. Returns (out, s_pool, c_pool), `out` as
        `_attn_out`'s."""
        h = norm_apply(self.cfg, x, p, "ln1")
        mix, s_pool, c_pool = self._gdn_mixer(p, h, s_pool, c_pool, at, n_live)
        out = self._attn_out(
            p, x, mix, live=live, layer=layer, wo="gdn_out"
        )
        return out, s_pool, c_pool

    def _gdn_mixer(self, p: dict, h, s_pool, c_pool, at, n_live=None):
        """The Gated DeltaNet mixer on the normed [B, T, D] (the
        equations and the three forms of the rule: ops/gated_delta.py).
        One projection holds per key head [q | k | v | z], one [b | a];
        u = [q, k, v] over all heads goes through a causal depthwise
        convolution and SiLU; the rule runs in float32 on L2-normed q
        (scaled by dk^-0.5) and k, with beta = sigmoid(b) and g =
        -exp(A_log) softplus(a + dt_bias); the output is RMS-normed
        per head and gated by silu(z). T = 1 is the decode step (one
        read and one write of each state), T > 1 the chunked rule.
        Returns (mix [B, T, Hv*dv] before `gdn_out`, s_pool, c_pool)."""
        cfg = self.cfg
        dt = h.dtype
        f32 = jnp.float32
        hk, hv, dk, dv = (
            cfg.gdn_k_heads, cfg.gdn_v_heads, cfg.gdn_k_dim, cfg.gdn_v_dim
        )
        r = hv // hk
        taps = cfg.gdn_conv
        b, t, _ = h.shape
        _, proj = self._proj_fns(p, dt)
        with jax.named_scope("gdn_proj"):
            qkvz = proj(h, "gdn_qkvz").reshape(b, t, hk, 2 * dk + 2 * r * dv)
            q, k, v, z = jnp.split(qkvz, (dk, 2 * dk, 2 * dk + r * dv), axis=-1)
            u = jnp.concatenate(
                [a.reshape(b, t, -1) for a in (q, k, v)], axis=-1
            )
            ba = proj(h, "gdn_ba").reshape(b, t, hk, 2 * r).astype(f32)
            beta = jax.nn.sigmoid(ba[..., :r].reshape(b, t, hv))
            g = -jnp.exp(p["gdn_A_log"].astype(f32)) * jax.nn.softplus(
                ba[..., r:].reshape(b, t, hv) + p["gdn_dt_bias"].astype(f32)
            )
            if n_live is not None:
                real = (jnp.arange(t) < n_live)[None, :, None]
                beta, g = jnp.where(real, beta, 0.0), jnp.where(real, g, 0.0)
        with jax.named_scope("gdn_conv"):
            before = lax.dynamic_index_in_dim(c_pool, at, 0, keepdims=False)
            rows = jnp.concatenate([before.astype(dt), u], axis=1)
            w = p["gdn_conv"].astype(f32)
            y = jax.nn.silu(
                sum(rows[:, i : i + t].astype(f32) * w[i] for i in range(taps))
            )
            # The rows the next step's convolution still needs: the
            # last `taps - 1` real ones.
            c_pool = lax.dynamic_update_index_in_dim(
                c_pool,
                lax.dynamic_slice_in_dim(
                    rows, t if n_live is None else n_live, taps - 1, axis=1
                ).astype(c_pool.dtype),
                at, 0,
            )
        with jax.named_scope("gdn_rule"):
            q, k, v = jnp.split(y, (hk * dk, 2 * hk * dk), axis=-1)

            def unit(a):
                a = a.reshape(b, t, hk, dk)
                return a * lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + 1e-6)

            q, k = unit(q) * dk**-0.5, unit(k)
            v = v.reshape(b, t, hv, dv)
            if t == 1:
                o, s_pool = gdn_step(
                    s_pool, at, q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                    beta[:, 0], _flash_decode_mode(),
                )
                o = o[:, None]
            else:
                o, s = gdn_chunked(
                    jnp.repeat(q, r, axis=2), jnp.repeat(k, r, axis=2),
                    v, g, beta,
                    lax.dynamic_index_in_dim(s_pool, at, 0, keepdims=False),
                )
                s_pool = lax.dynamic_update_index_in_dim(s_pool, s, at, 0)
        with jax.named_scope("gdn_out"):
            o = o * lax.rsqrt(
                jnp.mean(o * o, -1, keepdims=True) + cfg.layer_norm_eps
            )
            o = o * p["gdn_norm_scale"].astype(f32) * jax.nn.silu(
                z.reshape(b, t, hv, dv).astype(f32)
            )
        return o.astype(dt).reshape(b, t, hv * dv), s_pool, c_pool

    @jax.named_scope("attn_core")
    def _attn_core(self, q, k, v, k_cache, v_cache, pos, dt, kind=None):
        """The part of `_block` between the projections: write the T
        new K/V rows into the caches and attend over them. Returns
        (attn [B, T, Hq*Dh] in `dt`, new_k, new_v). The layer's window
        is its `kind`'s (`cfg.layer_kinds`), else the stack's. A
        multi-token step whose float32 scores would pass
        `_SCORE_BYTES` attends one KV group and a chunk of its queries
        at a time."""
        cfg = self.cfg
        window = cfg.kind_of(kind)[0]
        per_slot = getattr(pos, "ndim", 0) == 1
        b, h_q, t, dh = q.shape
        mask_of = None

        if self.rolling_cache:
            win = cfg.window
            if per_slot:
                # Continuous batching over rolling caches: each slot's
                # write lands at ITS OWN pos % win, and the in-place
                # mask vectorizes per slot. T=1 only — admission
                # prefills each request through the scalar path
                # (runtime/decode_server.py) before lane insertion.
                if t != 1:
                    raise NotImplementedError(
                        "per-slot rolling caches decode one token per "
                        "tick; prefill requests individually before "
                        "lane insertion"
                    )
                slots = pos % win  # (B,)
                rows_b = jnp.arange(b)
                k_cache = k_cache.at[rows_b, :, slots, :].set(k[:, :, 0, :])
                v_cache = v_cache.at[rows_b, :, slots, :].set(v[:, :, 0, :])
                k_att, v_att = k_cache, v_cache
                s_idx = jnp.arange(win)
                held = pos[:, None] - (
                    (pos[:, None] - s_idx[None, :]) % win
                )  # (B, win)
                # Broadcasts over the shared [b, hkv, g, t, s] logits.
                mask = (held >= 0)[:, None, None, None, :]
            elif t > win:
                raise ValueError(
                    f"a rolling-cache step takes at most window={win} "
                    f"tokens at once (got {t}); prefill with chunk<={win}"
                )
            if not per_slot:
                # New rows land at position % win (scatter; t <= win
                # so slot indices are unique).
                slots = (pos + jnp.arange(t)) % win
                s_idx = jnp.arange(win)
                if t == 1:
                    # Decode fast path: write first, attend the cache
                    # IN PLACE (no per-step concat copies of the whole
                    # window). After the write every slot holds the
                    # latest position <= pos congruent to it — always
                    # inside the window — so only never-written slots
                    # mask out.
                    k_cache = k_cache.at[:, :, slots, :].set(k)
                    v_cache = v_cache.at[:, :, slots, :].set(v)
                    k_att, v_att = k_cache, v_cache
                    held = pos - ((pos - s_idx) % win)  # (win,)
                    mask = (held >= 0)[None, :]  # (1, win)
                else:
                    # Multi-token (prefill) step: attend over [cache,
                    # this step's keys] with EXPLICIT absolute
                    # positions — same-step rows never overwrite keys
                    # a same-step query still needs. Slot s holds the
                    # latest position <= pos-1 congruent to s
                    # (negative = never written).
                    held = pos - 1 - ((pos - 1 - s_idx) % win)  # (win,)
                    k_att = jnp.concatenate([k_cache, k], axis=2)
                    v_att = jnp.concatenate([v_cache, v], axis=2)
                    kpos = jnp.concatenate([held, pos + jnp.arange(t)])
                    qpos = pos + jnp.arange(t)[:, None]  # (T, 1)
                    mask = (
                        (kpos[None, :] <= qpos)
                        & (kpos[None, :] > qpos - win)
                        & (kpos[None, :] >= 0)
                    )  # (T, win+T)
                    k_cache = k_cache.at[:, :, slots, :].set(k)
                    v_cache = v_cache.at[:, :, slots, :].set(v)
        else:
            # Write the T new K/V rows at the cache head (in the
            # cache's own dtype, where that is not the step's).
            k, v = k.astype(k_cache.dtype), v.astype(v_cache.dtype)
            if per_slot:
                upd = jax.vmap(
                    lambda c, new, pb: lax.dynamic_update_slice(
                        c, new, (0, pb, 0)
                    )
                )
                k_cache = upd(k_cache, k, pos)
                v_cache = upd(v_cache, v, pos)
            else:
                k_cache = lax.dynamic_update_slice(
                    k_cache, k, (0, 0, pos, 0)
                )
                v_cache = lax.dynamic_update_slice(
                    v_cache, v, (0, 0, pos, 0)
                )
            k_att, v_att = k_cache, v_cache
            # Causal-by-position: query t (absolute pos+t) sees cache
            # slot j iff j <= pos + t; empty slots beyond the head are
            # excluded by the same test. A sliding window additionally
            # drops slots more than `window`-1 behind (Mistral-style).
            j = jnp.arange(k_att.shape[2])

            def mask_of(n, start=None):
                """The mask of the `n` queries from offset `start`."""

                def steps():
                    r = jnp.arange(n)
                    return r if start is None else start + r

                if per_slot:
                    tt = pos[:, None] + steps()  # (B, Tc)
                    m = j[None, None, :] <= tt[:, :, None]  # (B, Tc, S)
                    if window is not None:
                        m &= j[None, None, :] > tt[:, :, None] - window
                    return m[:, None, None, :, :]
                tt = pos + steps()[:, None]  # (Tc, 1)
                m = j[None, :] <= tt  # (Tc, S)
                if window is not None:
                    m &= j[None, :] > tt - window
                return m

            mask = mask_of(t)

        from defer_tpu.ops.pallas_attention import decode_k_block

        flash_mode = (
            _flash_decode_mode()
            if t == 1
            and not self.rolling_cache
            and decode_k_block(k_att.shape[2], k_att.dtype) is not None
            else None
        )
        if flash_mode is not None:
            # Serving hot path: the pallas flash-decode kernel fuses
            # mask + online softmax + weighted sum over only the LIVE
            # cache rows (ops/pallas_attention.py::flash_decode);
            # position masking semantics match the einsum path (query
            # at pos attends j <= pos, window optional).
            from defer_tpu.ops.pallas_attention import flash_decode

            posv = pos if per_slot else jnp.broadcast_to(pos, (b,))
            attn = flash_decode(
                q[:, :, 0, :],
                k_att,
                v_att,
                posv,
                window=window,
                interpret=flash_mode == "interpret",
            )  # [B, Hq, Dh]
            attn = attn.astype(dt).reshape(b, t, h_q * dh)
        else:
            hkv = k_att.shape[1]
            qg = q.reshape(b, hkv, h_q // hkv, t, dh)

            def attend(qg, mask, k_att=k_att, v_att=v_att):
                logits = jnp.einsum(
                    "bkgtd,bksd->bkgts",
                    qg,
                    k_att,
                    preferred_element_type=jnp.float32,
                ) * (dh**-0.5)
                logits = jnp.where(mask, logits, -jnp.inf)
                if logits.shape[-1] > _SOFTMAX_ROW:
                    top = lax.optimization_barrier(
                        jnp.max(logits, axis=-1, keepdims=True)
                    )
                    e = jnp.exp(logits - top)
                    weights = (e / jnp.sum(e, axis=-1, keepdims=True)).astype(dt)
                else:
                    weights = jax.nn.softmax(logits, axis=-1).astype(dt)
                return jnp.einsum("bkgts,bksd->bkgtd", weights, v_att)

            s_len = k_att.shape[2]
            if mask_of is None or b * h_q * t * s_len * 4 <= _SCORE_BYTES:
                attn = attend(qg, mask)
            else:
                # One KV group and a chunk of its queries after the
                # other: a piece's scores are the step's largest
                # temporary.
                g = h_q // hkv
                tc = _query_chunk(b, g, t, s_len)
                n = t // tc
                pieces = (
                    qg.reshape(b, hkv, g, n, tc, dh)
                    .transpose(1, 3, 0, 2, 4, 5)
                    .reshape(hkv * n, b, 1, g, tc, dh)
                )

                def piece(c):
                    qc, kv, start = c
                    return attend(
                        qc, mask_of(tc, start),
                        lax.dynamic_slice_in_dim(k_att, kv, 1, axis=1),
                        lax.dynamic_slice_in_dim(v_att, kv, 1, axis=1),
                    )

                attn = lax.map(
                    piece,
                    (
                        pieces,
                        jnp.repeat(jnp.arange(hkv), n),
                        jnp.tile(jnp.arange(n) * tc, hkv),
                    ),
                )  # [hkv * n, b, 1, g, tc, dh]
                attn = (
                    attn.reshape(hkv, n, b, g, tc, dh)
                    .transpose(2, 0, 3, 1, 4, 5)
                )
            attn = attn.reshape(b, h_q, t, dh)
            attn = attn.transpose(0, 2, 1, 3).reshape(b, t, h_q * dh)
        return attn, k_cache, v_cache

    def _step_fn(self, tp_axis: str | None = None):
        """The ONE step body (embed -> scan over blocks -> final LN ->
        tied head) shared by the single-device and tensor-parallel
        paths; the tp variant adds psum inside _block, Megatron vocab
        sharding around the embedding/tied head, and a shard_map
        wrapper around this."""

        @self._with_precision
        def step(params, cache, ids):
            t = ids.shape[1]
            pos = cache["pos"]
            # Multi-LoRA serving: the slot -> adapter assignment is
            # per-slot state and rides in the cache.
            adapter_ids = cache.get("adapter")
            x = self._embed_tokens(params, ids, pos, tp_axis)

            live = n_live = None
            if "moe_live" in cache:
                n_live = jnp.minimum(cache["moe_live"], t)
                live = jnp.broadcast_to(jnp.arange(t) < n_live, ids.shape)

            def body(x, p, caches_l, kind, layer):
                if kind == LINEAR:
                    # One layer's states as a pool of one.
                    s, c = (a[None] for a in caches_l)
                    out, s, c = self._linear_block(
                        p, x, s, c, 0, n_live=n_live, live=live, layer=layer
                    )
                    return out, (s[0], c[0])
                out, kc, vc = self._block(
                    p, x, *caches_l, pos,
                    tp_axis=tp_axis, adapter_ids=adapter_ids,
                    kind=kind, live=live, layer=layer,
                )
                return out, (kc, vc)

            kv = (cache["k"], cache["v"])
            caches = kv
            if self.cfg.has_linear:
                caches = {
                    "attn": kv, LINEAR: (cache["gdn_s"], cache["gdn_conv"])
                }
            x, ys, stats = self.scan_layers(body, x, params["stack"], caches)
            logits = self._final_logits(params, x)
            new_cache = {"pos": pos + t}
            if self.cfg.has_linear:
                new_cache["gdn_s"], new_cache["gdn_conv"] = ys[LINEAR]
                ys = ys["attn"]
            new_cache["k"], new_cache["v"] = ys
            if adapter_ids is not None:
                new_cache["adapter"] = adapter_ids
            if "moe_live" in cache:
                new_cache["moe_live"] = cache["moe_live"]
            if stats is not None:
                new_cache["moe"] = stats
            return logits, new_cache

        return step

    def _with_precision(self, step):
        """`step` traced under `matmul_precision` (itself where None)."""
        if self.matmul_precision is None:
            return step

        def precise(*args):
            with jax.default_matmul_precision(self.matmul_precision):
                return step(*args)

        return precise

    @jax.named_scope("embed")
    def _embed_tokens(self, params, ids, pos, tp_axis=None):
        """Token (+learned position) embedding for a step at write
        head `pos` (scalar, or (B,) per-slot depths — continuous
        batching gathers each element's own position rows)."""
        cfg = self.cfg
        cd = self.compute_dtype
        t = ids.shape[1]
        emb = embed_lookup(params["token_embedding"], ids, tp_axis)
        if cfg.pos_style == "rope":
            # Rotary positions enter inside each block's q/k.
            return emb.astype(cd)
        if getattr(pos, "ndim", 0) == 1:
            posv = jnp.take(
                params["pos_embedding"],
                pos[:, None] + jnp.arange(t),
                axis=0,
            )
            return (emb + posv).astype(cd)
        posv = lax.dynamic_slice_in_dim(
            params["pos_embedding"], pos, t, axis=0
        )
        return (emb + posv).astype(cd)

    @jax.named_scope("logits")
    def _final_logits(self, params, x):
        """Final norm + output head, fp32: tied to the embedding
        unless the checkpoint shipped a distinct lm_head (untied llama
        releases). Under tp each shard produces its vocab slice
        [B, T, Vpad/tp]; the caller's out_specs concatenate the slices
        into the global logits (no in-body collective, and shard_map's
        replication checking stays on)."""
        from defer_tpu.models.quant import dequantize_leaf

        cfg = self.cfg
        xf = x.astype(jnp.float32)
        if cfg.norm_type == "rms":
            xn = _rms_norm(
                xf, params["final_ln_scale"], cfg.layer_norm_eps,
                cfg.norm_offset,
            )
        else:
            xn = _layer_norm(
                xf,
                params["final_ln_scale"],
                params.get("final_ln_bias"),
                cfg.layer_norm_eps,
            )
        head = params.get("lm_head", params["token_embedding"])
        if self.matmul_precision is not None and getattr(
            head, "dtype", None
        ) == jnp.bfloat16:
            # The step asks for float32's accuracy: three bf16 pieces
            # of the norm against the bf16 head, in one pass.
            return act_einsum("...d,vd->...v", xn, head)
        head = dequantize_leaf(head, jnp.float32)
        return xn @ head.T

    def stage_params(self, params: dict, first: int, last: int) -> dict:
        """The param subtree one contiguous pipeline stage of layers
        [first, last) needs (runtime/paged.py pp_stages=): its slice
        of the stacked block params, plus the embedding tables when it
        holds layer 0 (`_embed_tokens` inputs) and the final norm +
        (tied) head when it holds the last layer (`_final_logits`
        inputs). Slices are views of the same device buffers until a
        stage placement copies them — the layer axis leads every stack
        leaf, so one tree_map covers float and quantized trees
        alike."""
        L = self.cfg.num_layers
        if self.cfg.has_linear:
            raise ValueError(
                "stage_params slices every stack leaf by layer: a stack "
                "with recurrent layers (cfg.layer_kinds 'linear') stacks "
                "its leaves by kind and is not cut into stages"
            )
        if not (0 <= first < last <= L):
            raise ValueError(
                f"stage layer range [{first}, {last}) out of bounds "
                f"for {L} layers"
            )
        out: dict = {
            "stack": jax.tree_util.tree_map(
                lambda a: a[first:last], params["stack"]
            )
        }
        if first == 0:
            out["token_embedding"] = params["token_embedding"]
            if "pos_embedding" in params:
                out["pos_embedding"] = params["pos_embedding"]
        if last == L:
            out["final_ln_scale"] = params["final_ln_scale"]
            if "final_ln_bias" in params:
                out["final_ln_bias"] = params["final_ln_bias"]
            if "lm_head" in params:
                out["lm_head"] = params["lm_head"]
            else:
                out["token_embedding"] = params["token_embedding"]
        return out

    def _memo_key(self, donate: bool):
        """Memo key for make_step; subclasses extend it when the
        compiled step depends on more than the donate flag."""
        return donate

    def _memoized(self, donate: bool, build):
        from defer_tpu.utils.memo import cached_step

        return cached_step(
            self,
            self._memo_key(donate),
            lambda: jax.jit(build(), donate_argnums=(1,) if donate else ()),
        )

    def make_step(self, *, donate: bool = True):
        """Jitted (params, cache, ids [B, T]) -> (logits [B, T, V],
        cache). With donate=True (default) the cache argument's buffers
        are reused in place — the serving configuration."""
        return self._memoized(donate, self._step_fn)

    def decode_step_fn(self):
        """The RAW (unjitted) single-step body `(params, cache, ids)
        -> (logits, cache)` — trace-compatible with `lax.scan`, so the
        serving layer can fuse `decode_window=K` decode sub-steps into
        one jitted window program (runtime/decode_server.py /
        runtime/paged.py) instead of dispatching make_step K times
        from the host. Identical math to make_step's body: a window of
        K applications is bit-identical to K host-dispatched ticks."""
        return self._step_fn()

    # -- generation --------------------------------------------------------

    def prefill(
        self,
        params: dict,
        cache: dict,
        ids: jax.Array,
        *,
        chunk: int | None = None,
    ) -> tuple[jax.Array, dict]:
        """Consume a [B, T] prompt into the cache; returns
        (last_logits [B, V], cache).

        chunk=None runs one T-length step. A chunk size processes the
        prompt in fixed-size pieces instead: peak activation memory is
        O(chunk x T) rather than O(T^2) for the attention logits, and
        ONE compiled shape serves any prompt length — short prompts
        and tail pieces are zero-padded to the chunk (padded rows sit
        beyond the advanced position, so they are never attended and
        later writes overwrite them). Works on a warm cache: all
        bounds are taken from the cache's actual write head."""
        t0 = ids.shape[1]
        if getattr(cache["pos"], "ndim", 0) != 0:
            raise ValueError(
                "prefill needs a scalar-position cache (per-slot "
                "caches admit through runtime/decode_server.py)"
            )
        # analysis: ignore[host-sync-in-hot-loop] one scalar sync per
        # prefill (admission time, not per tick) to guard overflow
        base = int(jax.device_get(cache["pos"]))
        if self.rolling_cache:
            # Rolling caches have no end to overflow — positions are
            # unbounded and slots recycle — but a single step is
            # capped at the window, so long prompts auto-chunk.
            if chunk is None and t0 > self.cfg.window:
                chunk = self.cfg.window
        elif base + t0 > self.cfg.max_len:
            raise ValueError(
                f"cache position {base} + prompt {t0} exceeds max_len "
                f"{self.cfg.max_len}"
            )
        step = self.make_step()
        if chunk is None:
            logits, cache = step(params, cache, ids)
            return logits[:, -1, :], cache
        if chunk < 1:
            raise ValueError(f"chunk={chunk} must be >= 1")
        last = None
        for start in range(0, t0, chunk):
            piece = ids[:, start : start + chunk]
            real = piece.shape[1]
            # Pad short/tail pieces to the fixed chunk shape — but
            # only when the padded write stays inside the cache:
            # dynamic_update_slice CLAMPS an out-of-range start, which
            # would silently shift the write over earlier rows. At the
            # boundary, feed the short piece as its own compiled shape.
            # Rolling caches never pad: a pad row would EVICT the live
            # slot at its position%W while the rewound mask still
            # credits that slot with the evicted row's position.
            if (
                real < chunk
                and not self.rolling_cache
                and base + start + chunk <= self.cfg.max_len
            ):
                piece = jnp.concatenate(
                    [
                        piece,
                        jnp.zeros((ids.shape[0], chunk - real), ids.dtype),
                    ],
                    axis=1,
                )
            if piece.shape[1] > real and "moe_live" in cache:
                # The padded rows count for nothing and move no
                # recurrent state.
                every = cache["moe_live"]
                cache = {**cache, "moe_live": jnp.asarray(real, jnp.int32)}
            logits, cache = step(params, cache, piece)
            last = logits[:, real - 1, :]
            if piece.shape[1] > real:
                # Rewind the write head past the padded rows.
                cache = {**cache, "pos": cache["pos"] - (chunk - real)}
                if "moe_live" in cache:
                    cache["moe_live"] = every
        return last, cache

    def generate(
        self,
        params: dict,
        prompt_ids: jax.Array,
        num_steps: int,
        *,
        temperature: float = 0.0,
        top_k: int = 0,
        top_p: float = 1.0,
        min_p: float = 0.0,
        rep_penalty: float = 1.0,
        eos_id: int | None = None,
        stop_sequences=None,
        pad_id: int | None = None,
        rng: jax.Array | None = None,
        prefill_chunk: int | None = None,
    ) -> jax.Array:
        """Greedy (temperature 0) or sampled continuation of
        `prompt_ids` [B, T0]; returns [B, T0 + num_steps]. Prefill runs
        the whole prompt in one step (or fixed `prefill_chunk` pieces
        for long prompts — see prefill); each new token reuses the
        compiled T=1 step with donated cache.

        With `eos_id` set, a sequence that emits it is FINISHED: its
        remaining positions are pinned to eos_id (the shape contract
        stays [B, T0 + num_steps]), and the host loop stops early once
        every sequence has finished — the serving-standard stop-token
        behavior without any dynamic shapes."""
        cfg = self.cfg
        b, t0 = prompt_ids.shape
        if self.rolling_cache:
            # No length bound (slots recycle); prefill itself
            # auto-chunks long prompts at the window.
            pass
        elif t0 + num_steps > cfg.max_len:
            raise ValueError(
                f"prompt {t0} + steps {num_steps} exceeds max_len "
                f"{cfg.max_len}"
            )
        step = self.make_step()
        cache = self.init_cache(b)
        last, cache = self.prefill(
            params, cache, prompt_ids, chunk=prefill_chunk
        )
        return sampled_decode_loop(
            step,
            params,
            cache,
            last,
            prompt_ids,
            num_steps,
            temperature=temperature,
            top_k=top_k,
            top_p=top_p,
            min_p=min_p,
            rep_penalty=rep_penalty,
            eos_id=eos_id,
            stop_sequences=stop_sequences,
            pad_id=pad_id,
            rng=rng,
        )

    # -- reference (no cache) ---------------------------------------------

    def reference_logits(self, params: dict, ids: jax.Array) -> jax.Array:
        """Full causal forward (fresh cache, whole sequence in one
        non-donating step) — the correctness oracle for incremental
        decoding. A rolling-cache decoder streams the sequence in
        window-sized pieces instead (a single step is capped at the
        window), collecting every position's logits."""
        cache = self.init_cache(ids.shape[0])
        step = self.make_step(donate=False)
        if not self.rolling_cache or ids.shape[1] <= self.cfg.window:
            logits, _ = step(params, cache, ids)
            return logits
        outs = []
        for start in range(0, ids.shape[1], self.cfg.window):
            logits, cache = step(
                params, cache, ids[:, start : start + self.cfg.window]
            )
            outs.append(logits)
        return jnp.concatenate(outs, axis=1)


@dataclasses.dataclass
class SpmdGptDecoder(GptDecoder):
    """Tensor-parallel KV-cache decoding: one jitted shard_map step
    over a 'model' mesh axis.

    Each shard holds its head group's column-sharded q/k/v projections
    and a cache of ONLY its local heads ([L, B, H/tp, S_max, Dh] per
    device); attention is collective-free, and the wo/w2 row-parallel
    matmuls psum over ICI; the embedding/tied head is vocab-row
    sharded (masked lookup + psum in, per-shard logits + all_gather
    out) — so EVERY weight matrix is read 1/tp per chip, which is
    what decode latency needs (weights, not activations, dominate
    decode HBM traffic)."""

    mesh: Any = None
    tp_axis: str = "model"

    def _memo_key(self, donate: bool):
        # The sharded step's in_specs depend on which param leaves are
        # int8 trees (set by shard_params) — key the memo on that too,
        # or a step built before shard_params would keep stale specs.
        return (
            donate,
            getattr(self, "_quantized_emb", False),
            getattr(self, "_quantized_keys", frozenset()),
        )
    # Optional batch sharding: set to a mesh axis name (e.g. "data")
    # to shard the cache/ids/logits batch dim over it — dp x tp
    # serving in one program.
    dp_axis: str | None = None

    def __post_init__(self):
        super().__post_init__()
        if self.mesh is None or self.tp_axis not in self.mesh.axis_names:
            raise ValueError(
                f"SpmdGptDecoder needs a mesh with a {self.tp_axis!r} axis"
            )
        if self.dp_axis is not None:
            if self.dp_axis not in self.mesh.axis_names:
                raise ValueError(
                    f"dp_axis {self.dp_axis!r} is not a mesh axis "
                    f"({self.mesh.axis_names})"
                )
            if self.dp_axis == self.tp_axis:
                raise ValueError(
                    f"dp_axis and tp_axis must differ (both "
                    f"{self.dp_axis!r})"
                )
        tp = self.mesh.shape[self.tp_axis]
        cfg = self.cfg
        if cfg.num_heads % tp or cfg.dim % tp or cfg.ffn_dim % tp:
            raise ValueError(
                f"heads={cfg.num_heads}, dim={cfg.dim}, "
                f"ffn_dim={cfg.ffn_dim} must all divide by tp={tp}"
            )
        if cfg.kv_heads % tp:
            raise ValueError(
                f"num_kv_heads={cfg.kv_heads} must divide by tp={tp} "
                "(each shard needs whole kv head groups)"
            )
        # Real vocab sizes (50257, 32000, ...) rarely divide by tp:
        # pad the sharded table instead of rejecting (padded rows are
        # zeros, masked out of lookups and sliced off the logits).
        self._vocab_padded = -(-cfg.vocab_size // tp) * tp

    def _specs(self):
        from defer_tpu.parallel.transformer_stack import stack_specs
        from jax.sharding import PartitionSpec as P

        tp = self.tp_axis
        stack = stack_specs(None, tp, cfg=self.cfg)
        emb_spec = P(tp, None)
        qkeys = getattr(self, "_quantized_keys", frozenset())
        if qkeys:

            def qwrap(spec: P) -> dict:
                # The scale is keepdims-shaped like q with middle axes
                # of size 1: shard only the leading (layer) and
                # trailing (channel) axes the way q does.
                n = len(spec)
                s_spec = (
                    P(spec[0], *([None] * (n - 2)), spec[-1])
                    if n >= 3
                    else P(None, spec[-1])
                )
                return {"q": spec, "s": s_spec}

            stack = {
                k: qwrap(v) if k in qkeys else v for k, v in stack.items()
            }
        if getattr(self, "_quantized_emb", False):
            # Vocab-sharded int8 table: rows over tp, per-channel
            # scales replicated (they span D, not vocab).
            emb_spec = {"q": P(tp, None), "s": P(None, None)}
        specs = {
            # Megatron vocab sharding: embedding rows over tp; the
            # tied head reuses the same shards.
            "token_embedding": emb_spec,
            "final_ln_scale": P(),
            "stack": stack,
        }
        if self.cfg.pos_style == "learned":
            specs["pos_embedding"] = P()
        if self.cfg.norm_type == "layer":
            specs["final_ln_bias"] = P()
        return specs

    def shard_params(self, params: dict) -> dict:
        """Place replicated-init params onto the mesh: column/row
        sharded stack, vocab-row sharded embedding/tied head (padded
        to a tp multiple), replicated norms/positions."""
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        if "lm_head" in params:
            raise NotImplementedError(
                "untied output heads are not supported under tensor "
                "parallelism yet — the single-device GptDecoder serves "
                "untied checkpoints"
            )
        # Weight-only int8 trees (models/quant.py) shard like their
        # float counterparts: q takes the weight's spec, the
        # per-channel scale replicates its size-1 axes. Record which
        # leaves are quantized BEFORE _specs/make_step so the step's
        # in_specs match the tree (and key the step memo on it).
        self._quantized_keys = frozenset(
            k
            for k, v in params["stack"].items()
            if isinstance(v, dict) and "q" in v
        )
        emb = params["token_embedding"]
        self._quantized_emb = isinstance(emb, dict) and "q" in emb
        rows = emb["q"] if self._quantized_emb else emb
        pad = self._vocab_padded - rows.shape[0]
        if pad:
            padded = jnp.pad(rows, ((0, pad), (0, 0)))
            params = {
                **params,
                "token_embedding": {"q": padded, "s": emb["s"]}
                if self._quantized_emb
                else padded,
            }
        return jax.device_put(
            params,
            jax.tree_util.tree_map(
                lambda s: NamedSharding(self.mesh, s),
                self._specs(),
                is_leaf=lambda s: isinstance(s, P),
            ),
        )

    def _cache_spec(self):
        from jax.sharding import PartitionSpec as P

        tp, dp = self.tp_axis, self.dp_axis
        return {
            # Cache batch shards over dp (axis 1), heads over tp
            # (axis 2) of [L,B,H,S,Dh].
            "k": P(None, dp, tp, None, None),
            "v": P(None, dp, tp, None, None),
            "pos": P(),
        }

    def make_step(self, *, donate: bool = True):
        from jax.sharding import PartitionSpec as P

        from defer_tpu.utils.compat import shard_map

        vocab = self.cfg.vocab_size

        def build():
            cache_spec = self._cache_spec()
            dp = self.dp_axis
            smapped = shard_map(
                self._step_fn(tp_axis=self.tp_axis),
                self.mesh,
                in_specs=(self._specs(), cache_spec, P(dp, None)),
                # Logits stay vocab-sharded inside; shard_map itself
                # concatenates the [B/dp, T, Vpad/tp] slices.
                out_specs=(P(dp, None, self.tp_axis), cache_spec),
            )

            def step(params, cache, ids):
                logits, cache = smapped(params, cache, ids)
                # Drop the pad vocab rows (zeros from padded weights —
                # leaving them in could win an argmax).
                return logits[..., :vocab], cache

            return step

        return self._memoized(donate, build)

    def decode_step_fn(self):
        # Inheriting GptDecoder's raw body would silently drop the
        # shard_map wrapper (tp psums, vocab sharding) — the window
        # fusion would trace but compute garbage on a mesh. Servers
        # asked for decode_window > 1 call this at construction to
        # fail fast instead.
        raise NotImplementedError(
            "decode_window > 1 is not supported under shard_map "
            "tensor parallelism: the fused window step would bypass "
            "SpmdGptDecoder's sharded make_step — serve with "
            "decode_window=1"
        )

    def init_cache(self, batch: int) -> dict:
        from jax.sharding import NamedSharding

        cfg = self.cfg
        dh = cfg.dh
        shape = (cfg.num_layers, batch, cfg.kv_heads, cfg.max_len, dh)
        spec = self._cache_spec()
        # Allocate DIRECTLY sharded: materializing the full replicated
        # cache on device 0 first would transiently need tp x the
        # per-device footprint — an OOM at serving scale.
        kv_sh = NamedSharding(self.mesh, spec["k"])
        return {
            "k": jnp.zeros(shape, self.compute_dtype, device=kv_sh),
            "v": jnp.zeros(shape, self.compute_dtype, device=kv_sh),
            "pos": jax.device_put(
                jnp.zeros((), jnp.int32),
                NamedSharding(self.mesh, spec["pos"]),
            ),
        }


def tiny_gpt(seq_len: int = 32) -> GptDecoder:
    """Small config for tests / CPU."""
    return GptDecoder(
        TransformerConfig(
            num_layers=4,
            dim=64,
            num_heads=4,
            ffn_dim=128,
            vocab_size=128,
            max_len=seq_len,
            norm_style="pre",
        ),
        compute_dtype=jnp.float32,
    )
