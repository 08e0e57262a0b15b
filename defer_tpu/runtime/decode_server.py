"""Continuous-batching decode server: admit requests into batch slots
mid-flight.

A plain batched `generate` convoys requests: the batch finishes when
its LAST member does, and new arrivals wait for the whole batch. Here
the decode batch is a set of SLOTS, each at its own depth — the cache
write head is a (B,) position VECTOR (models/gpt.py `per_slot`), so
one jitted (B, 1) step advances every active request regardless of
age, and a finished slot is immediately re-admitted with the next
queued request:

  * admission = single-request prefill (prompt padded to a pow2
    bucket, so the compiled-shape set stays tiny) whose K/V rows are
    inserted into the slot's lane of the big cache; stale rows past
    the slot's position are never attended (position masking) and are
    overwritten as the slot advances;
  * every decode tick is ONE weight read shared by all active slots —
    exactly the batching economics decode wants (weights dominate,
    models/gpt.py), now without convoy latency;
  * shapes are static everywhere: max_batch slots, bucketed prefill,
    (B, 1) ticks; inactive slots decode a dummy token into row 0 and
    their position is pinned back to 0 after each tick.

Greedy by default, per-request sampling on demand: `submit(...,
sampling=SamplingParams(temperature, top_k, top_p, min_p, seed))`
routes that slot through a batched in-tick sampler keyed by its OWN
seeded PRNG stream (SlotSampler), while greedy slots keep the argmax
fast path. Either way each request's output is BIT-IDENTICAL to a solo
`dec.generate` of that request (same seed) at the tested scales — the
correctness contract the tests pin. (At large widths/vocabs with random weights,
greedy decoding itself is ill-conditioned: near-ties in the softmax
mean the bucketed/offset prefill's different-but-equivalent reduction
shapes can flip an argmax; examples/serve_decode.py --check therefore
verifies greedy-validity under a tie tolerance instead.) The
reference's serving story is a fixed stream of identical CNN frames
(reference src/test.py:30-41); this is the autoregressive
counterpart, composing with runtime/batching.py's request coalescing.

Prefix caching (`prefix_ids=`): serving workloads share a system
prompt; its K/V rows are identical for every request, so the server
prefills the prefix ONCE into a one-lane cache and each admission
copies that lane and prefills only the request's suffix — admission
cost drops from O(prefix + prompt) to O(prompt) while outputs stay
bit-identical to solo generation over the concatenated ids.
"""

from __future__ import annotations

import collections
import dataclasses
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from defer_tpu.constrain import runtime as crt
from defer_tpu.models.gpt import (
    sample_token_batched,
    sample_token_batched_nosort,
)
from defer_tpu.obs.serving import ServerStats, ServingMetrics
from defer_tpu.runtime.batching import window_drain_order
from defer_tpu.runtime.stopping import matcher_or_none, normalize_stops
from defer_tpu.utils.memo import cached_step


class SlotSampler:
    """Per-slot sampling state shared by both continuous-batching
    servers (flat and paged): one PRNG key per slot plus the policy
    vectors sample_token_batched reads. A slot admitted with
    SamplingParams draws inside the shared batched tick from its OWN
    key stream (jax.random.key(seed), one split per emitted token —
    the schedule solo generate follows), so its output reproduces
    `generate(..., rng=jax.random.key(seed))` bit-for-bit. Greedy
    slots keep the argmax fast path."""

    def __init__(self, max_batch: int):
        self.keys = jax.vmap(jax.random.key)(
            jnp.zeros((max_batch,), jnp.uint32)
        )
        self.temp = jnp.zeros((max_batch,), jnp.float32)
        self.topk = jnp.zeros((max_batch,), jnp.int32)
        self.topp = jnp.ones((max_batch,), jnp.float32)
        self.minp = jnp.zeros((max_batch,), jnp.float32)
        # Host mirror of `temp`: a greedy admission into a slot a
        # sampled request vacated must reset that row (a stale
        # temperature would re-route the greedy slot through the
        # categorical path).
        self.row_temp = [0.0] * max_batch
        # Host mirror of "this row's policy needs the sorting filters"
        # (top_k or top_p enabled). While no admitted row does, draw()
        # routes through the sort-free tick variant — same bits, no
        # O(V log V) sorts. Rows are set at admission and cleared by
        # release() the moment the slot finishes, so one top-k request
        # costs the batch the sorting path only while it is actually
        # live.
        self.row_sort = [False] * max_batch
        # Host mirror of "this row installed truncation filters"
        # (top_k/top_p/min_p): release() must reset those device rows
        # too — see release() — and the mirror keeps the greedy
        # common case free of device writes.
        self.row_filters = [False] * max_batch
        # Constrained decoding (defer_tpu/constrain/): per-slot DFA
        # policy rows — which stacked constraint table (cid, 0 = the
        # free accept-everything row) and the current DFA state. The
        # host mirror routes ticks through the constrained program
        # variants only while a constrained row is live (the row_sort
        # dispatch pattern).
        self.cid = jnp.zeros((max_batch,), jnp.int32)
        self.cstate = jnp.zeros((max_batch,), jnp.int32)
        self.row_constrained = [False] * max_batch

    def admit_first(self, i, samp, logits_row, dtype):
        """First generated token of an admission [1, 1]: greedy
        argmax, or the first draw of the request's key stream, with
        the advanced key and policy installed into slot i's rows."""
        if samp is None:
            self.row_sort[i] = False
            if self.row_temp[i] != 0.0:
                self.temp = self.temp.at[i].set(0.0)
                self.row_temp[i] = 0.0
            return jnp.argmax(logits_row, axis=-1)[:, None].astype(
                dtype
            )
        tok, key1 = sample_token_batched(
            logits_row,
            jax.random.key(samp.seed)[None],
            jnp.full((1,), samp.temperature, jnp.float32),
            jnp.full((1,), samp.top_k, jnp.int32),
            jnp.full((1,), samp.top_p, jnp.float32),
            jnp.full((1,), samp.min_p, jnp.float32),
        )
        self.keys = self.keys.at[i].set(key1[0])
        self.temp = self.temp.at[i].set(samp.temperature)
        self.topk = self.topk.at[i].set(samp.top_k)
        self.topp = self.topp.at[i].set(samp.top_p)
        self.minp = self.minp.at[i].set(samp.min_p)
        self.row_temp[i] = samp.temperature
        self.row_sort[i] = samp.top_k > 0 or samp.top_p < 1.0
        self.row_filters[i] = (
            samp.top_k > 0 or samp.top_p < 1.0 or samp.min_p > 0.0
        )
        return tok[:, None].astype(dtype)

    def admit_constraint(self, i: int, cid, state) -> None:
        """Install slot i's constraint policy rows (cid into the
        server's stacked DFA tables, state AFTER the admission's first
        token — a device scalar, no sync). The host mirror routes
        later ticks through the constrained program variants."""
        self.cid = self.cid.at[i].set(cid)
        self.cstate = self.cstate.at[i].set(state)
        self.row_constrained[i] = True

    def release(self, i: int) -> None:
        """Retire slot i's sampling policy the moment its request
        FINISHES (both servers' _finish), not when the slot is next
        reused: a stale row_sort=True would keep routing every tick
        through the sorting sampler long after the top-k request is
        gone, and a stale temperature would route the idle row's dummy
        draw through the categorical path. ALL policy rows reset —
        temperature AND the top_k/top_p/min_p filter rows (a greedy
        re-admit into a vacated sampled slot routes through the argmax
        path, but a later sampled temp-only admit into that slot would
        otherwise inherit the dead request's filters) AND the
        constraint rows. Greedy unconstrained rows are already
        released — the common case stays free of device writes. Idle
        rows' keys keep advancing in draw(), which is fine: admission
        re-seeds them."""
        self.row_sort[i] = False
        if self.row_temp[i] != 0.0:
            self.temp = self.temp.at[i].set(0.0)
            self.row_temp[i] = 0.0
        if self.row_filters[i]:
            self.topk = self.topk.at[i].set(0)
            self.topp = self.topp.at[i].set(1.0)
            self.minp = self.minp.at[i].set(0.0)
            self.row_filters[i] = False
        if self.row_constrained[i]:
            self.cid = self.cid.at[i].set(0)
            self.cstate = self.cstate.at[i].set(0)
            self.row_constrained[i] = False

    def draw(self, logits_last):
        """One batched draw over every slot's policy (B,): sampled
        rows split their own key exactly once, greedy rows reduce to
        the same argmax as the fast path. Advances the key state.
        While no admitted row enables top-k/top-p, the draw takes the
        sort-free variant (bit-identical, see
        sample_token_batched_nosort)."""
        if not any(self.row_sort):
            nxt, self.keys = sample_token_batched_nosort(
                logits_last, self.keys, self.temp, self.minp
            )
            return nxt
        nxt, self.keys = sample_token_batched(
            logits_last,
            self.keys,
            self.temp,
            self.topk,
            self.topp,
            self.minp,
        )
        return nxt


class DraftLanes:
    """Per-slot flat lanes for a speculative DRAFT decoder — the
    draft-side bookkeeping seam the paged server's `spec_k` mode rides
    (runtime/paged.py).

    The target's K/V lives in the paged pool; the draft keeps a plain
    flat cache of max_batch lanes (draft models are small, so lane
    waste is cheap and the contiguous layout keeps the k-step proposal
    scan trivial). Host-side `pos` is the truth for how many COMMITTED
    tokens each lane covers: the server passes it down every round
    (idle/non-speculating rows pinned to 0, the flat server's
    idle-slot idiom), so device-side position drift from dummy rows
    can never accumulate.

    `propose()` is ONE fused dispatch per round: a [B, 2] catch-up
    step consumes each slot's 1-2 committed-but-unconsumed tokens
    (1 after a rejection, 2 after a full accept — the lag the solo
    speculative loop's `n0 - d_pos in (1, 2)` assertion pins), then a
    `lax.scan` of k-1 single-token greedy steps emits the remaining
    proposals. Slots with lag 1 feed their token twice and advance by
    1 — the duplicate row is written at pos+1 and immediately
    overwritten by the first scan step."""

    def __init__(
        self,
        dec: Any,
        params: dict,
        max_batch: int,
        *,
        target: Any = None,
    ):
        if getattr(dec, "rolling_cache", False):
            raise ValueError(
                "a rolling-cache draft cannot rewind rejected rows"
            )
        if getattr(dec, "decode_step_fn", None) is None:
            raise ValueError(
                "the draft decoder must expose decode_step_fn() "
                f"(models/gpt.py GptDecoder); {type(dec).__name__} "
                "does not"
            )
        dec.decode_step_fn()  # SpmdGptDecoder raises at construction
        if target is not None:
            self._check_geometry(dec.cfg, target.cfg)
        self.dec = dec
        self.params = params
        self.B = max_batch
        cache = dec.init_cache(max_batch)
        self.ck = cache["k"]
        self.cv = cache["v"]
        self.pos = np.zeros((max_batch,), np.int32)

    def admit(self, i: int, prompt: jax.Array) -> None:
        """Prefill slot i's draft lane with the request's FULL prompt
        (pow2-bucketed, the shared admission idiom) and lane-insert it
        — `_install_lane` for the draft cache. Afterwards the lane
        covers the t0 prompt tokens; the first generated token is the
        slot's initial pending feed (server-side)."""
        t0 = prompt.shape[1]
        pad = 1 << (t0 - 1).bit_length()
        pad = min(pad, self.dec.cfg.max_len)
        padded = jnp.concatenate(
            [prompt, jnp.zeros((1, pad - t0), prompt.dtype)], axis=1
        )
        small = self.dec.init_cache(1)
        _, small = self.dec.make_step()(self.params, small, padded)
        self.ck = lax.dynamic_update_slice(
            self.ck, small["k"], (0, i, 0, 0, 0)
        )
        self.cv = lax.dynamic_update_slice(
            self.cv, small["v"], (0, i, 0, 0, 0)
        )
        self.pos[i] = t0

    @staticmethod
    def _check_geometry(draft_cfg, target_cfg) -> None:
        """Draft-vs-target geometry gates, each with the fix spelled
        out. The draft proposes TOKEN IDS the target scores, so the
        vocabularies must be the same id space; kv_heads and the
        position encoding must match so a transplant-carved draft
        (models/transplant.py::make_draft) is attending with the same
        per-head/rotary geometry the verifier will re-score under —
        anything else silently tanks acceptance."""
        if draft_cfg.vocab_size != target_cfg.vocab_size:
            raise ValueError(
                f"draft vocab_size={draft_cfg.vocab_size} != target "
                f"vocab_size={target_cfg.vocab_size}: proposals are "
                "target-vocab token ids. Fix: build the draft from the "
                "target with models/transplant.py::make_draft (it "
                "preserves the vocabulary), or retrain the draft on "
                "the target's tokenizer."
            )
        if draft_cfg.kv_heads != target_cfg.kv_heads:
            raise ValueError(
                f"draft kv_heads={draft_cfg.kv_heads} != target "
                f"kv_heads={target_cfg.kv_heads}. Fix: carve the draft "
                "with make_draft(width=...) — it prunes QUERY heads to "
                "a multiple of the target's kv_heads and never touches "
                "the KV width — instead of hand-shrinking num_kv_heads."
            )
        if draft_cfg.pos_style != target_cfg.pos_style:
            raise ValueError(
                f"draft pos_style={draft_cfg.pos_style!r} != target "
                f"pos_style={target_cfg.pos_style!r}: the two models "
                "would disagree about every position. Fix: make_draft "
                "keeps the target's position encoding; use it."
            )
        if (
            draft_cfg.pos_style == "rope"
            and draft_cfg.rope_theta != target_cfg.rope_theta
        ):
            raise ValueError(
                f"draft rope_theta={draft_cfg.rope_theta} != target "
                f"rope_theta={target_cfg.rope_theta}: rotary frequency "
                "bases must match or long-context proposals rotate "
                "away from the verifier. Fix: make_draft preserves "
                "rope_theta (and the head dim it applies to); rebuild "
                "the draft with it."
            )

    def release(self, i: int) -> None:
        """Clear lane i COMPLETELY: pos back to 0 AND the cached K/V
        rows zeroed. pos alone is not enough — an idle lane still
        rides through every propose dispatch (masked by posm=0), and
        stale rows from a slot retired MID-ROUND would otherwise sit
        in device memory until the next admit overwrites them."""
        self.pos[i] = 0
        self.ck = self.ck.at[:, i].set(0)
        self.cv = self.cv.at[:, i].set(0)

    def release_all(self) -> None:
        """Drop every lane — the replica-death / server-teardown path
        (fleet/replica.py): no slot survives, so no lane may either."""
        self.pos[:] = 0
        self.ck = jnp.zeros_like(self.ck)
        self.cv = jnp.zeros_like(self.cv)

    def _propose_body(self, k: int):
        """The RAW (unjitted) propose body `(params, dk, dv, dpos,
        feed2, adv) -> (dk, dv, props)` — trace-compatible with
        `lax.scan`, so the paged server can fuse W draft+verify rounds
        into ONE `decode_window` program (runtime/paged.py::
        _tick_spec_window) instead of dispatching propose W times."""
        raw = self.dec.decode_step_fn()

        def propose(params, dk, dv, dpos, feed2, adv):
            cache = {"k": dk, "v": dv, "pos": dpos}
            logits2, cache = raw(params, cache, feed2)
            # Row adv-1 is the prediction after the LAST real
            # pending token; later rows are duplicate-feed noise.
            first_l = jnp.take_along_axis(
                logits2,
                jnp.maximum(adv - 1, 0)[:, None, None],
                axis=1,
            )[:, 0, :]
            nxt = jnp.argmax(first_l, axis=-1).astype(jnp.int32)
            # Correct per-slot positions after the variable-lag
            # catch-up (the raw step advanced every row by 2).
            pos1 = dpos + adv

            def body(carry, _):
                ck, cv, pos, tok = carry
                lg, c2 = raw(
                    params,
                    {"k": ck, "v": cv, "pos": pos},
                    tok[:, None],
                )
                t2 = jnp.argmax(lg[:, -1, :], axis=-1).astype(
                    jnp.int32
                )
                return (c2["k"], c2["v"], c2["pos"], t2), t2

            (dk, dv, _, _), rest = lax.scan(
                body,
                (cache["k"], cache["v"], pos1, nxt),
                None,
                length=k - 1,
            )
            props = jnp.concatenate([nxt[:, None], rest.T], axis=1)
            return dk, dv, props

        return propose

    def _propose_body_c(self, k: int, eos: int):
        """Constrained propose body (defer_tpu/constrain/): the same
        catch-up + k-step greedy scan, but each proposal argmax is
        masked by the slot's DFA row and a LOCAL DFA state walks
        forward with the proposals — so a constrained slot's draft
        chain stays inside its grammar and the target's accept rule
        sees grammar-valid candidates instead of rejecting everything
        at position 0. Free rows (cid 0) fold an all-True mask: their
        proposals are bit-identical to _propose_body's. A dead local
        state needs no special case: its garbage argmax can never
        match the target's forced out-of-vocab pred, so acceptance
        truncates there."""
        from defer_tpu.constrain import runtime as crt

        raw = self.dec.decode_step_fn()

        def propose(params, dk, dv, dpos, feed2, adv, cid, cstate,
                    ctrans, cacc):
            cvec = cid > 0
            cache = {"k": dk, "v": dv, "pos": dpos}
            logits2, cache = raw(params, cache, feed2)
            first_l = jnp.take_along_axis(
                logits2,
                jnp.maximum(adv - 1, 0)[:, None, None],
                axis=1,
            )[:, 0, :]
            crow, acc = crt.constrain_rows(ctrans, cacc, cid, cstate)
            cmask = crt.constrain_mask(crow, acc, eos)
            nxt = jnp.argmax(
                crt.fold_mask(first_l, cmask), axis=-1
            ).astype(jnp.int32)
            cstate = crt.advance_state(crow, cstate, nxt, cvec)
            pos1 = dpos + adv

            def body(carry, _):
                ck, cv, pos, tok, cs = carry
                lg, c2 = raw(
                    params,
                    {"k": ck, "v": cv, "pos": pos},
                    tok[:, None],
                )
                crow, acc = crt.constrain_rows(ctrans, cacc, cid, cs)
                cmask = crt.constrain_mask(crow, acc, eos)
                t2 = jnp.argmax(
                    crt.fold_mask(lg[:, -1, :], cmask), axis=-1
                ).astype(jnp.int32)
                cs = crt.advance_state(crow, cs, t2, cvec)
                return (c2["k"], c2["v"], c2["pos"], t2, cs), t2

            (dk, dv, _, _, _), rest = lax.scan(
                body,
                (cache["k"], cache["v"], pos1, nxt, cstate),
                None,
                length=k - 1,
            )
            props = jnp.concatenate([nxt[:, None], rest.T], axis=1)
            return dk, dv, props

        return propose

    def _build_propose(self, k: int):
        def build():
            return jax.jit(self._propose_body(k), donate_argnums=(1, 2))

        return cached_step(self.dec, ("spec_propose", self.B, k), build)

    def _build_propose_c(self, k: int, eos: int):
        def build():
            return jax.jit(
                self._propose_body_c(k, eos), donate_argnums=(1, 2)
            )

        return cached_step(
            self.dec, ("spec_propose_c", self.B, k, eos), build
        )

    def propose_c(self, k, posm, feed2, adv, eos, cid, cstate,
                  ctrans, cacc):
        """Constrained twin of propose() (separate memo key — the
        unconstrained program is untouched): proposals are masked by
        each slot's DFA walk (_propose_body_c). `cstate` is the
        server's COMMITTED per-slot state — every emitted token is
        already folded in, so the local walk continues exactly where
        the target's mask will check."""
        prog = self._build_propose_c(k, eos)
        self.ck, self.cv, props = prog(
            self.params,
            self.ck,
            self.cv,
            jnp.asarray(posm, jnp.int32),
            jnp.asarray(feed2, jnp.int32),
            jnp.asarray(adv, jnp.int32),
            cid,
            cstate,
            ctrans,
            cacc,
        )
        return props

    def propose(self, k, posm, feed2, adv):
        """One fused draft dispatch: catch up on pending committed
        tokens, then emit k greedy proposals per slot. `posm` [B] =
        host-truth lane coverage, non-speculating rows 0; `feed2`
        [B, 2] pending tokens (lag-1 rows duplicated); `adv` [B] in
        {0, 1, 2} = real pending count. Returns device [B, k]
        proposals (garbage rows for adv=0 slots — the caller masks by
        slot). Lane coverage afterwards is posm + adv + k - 1 for
        speculating rows: the k-th proposal is never self-consumed."""
        prog = self._build_propose(k)
        self.ck, self.cv, props = prog(
            self.params,
            self.ck,
            self.cv,
            jnp.asarray(posm, jnp.int32),
            jnp.asarray(feed2, jnp.int32),
            jnp.asarray(adv, jnp.int32),
        )
        return props


@dataclasses.dataclass
class _Slot:
    req: int | None = None
    remaining: int = 0
    last: Any = None  # next token to feed, [1, 1]
    toks: list | None = None
    sampling: bool = False  # this request runs at temperature > 0
    stop: Any = None  # per-request StopMatcher (runtime/stopping.py)
    cid: int = 0  # stacked-constraint index (0 = unconstrained)


class DecodeServer:
    """Continuous-batching decoder over `max_batch` slots; greedy by
    default, per-request sampling via `submit(..., sampling=)`."""

    def __init__(
        self,
        dec: Any,
        params: dict,
        *,
        max_batch: int = 4,
        prefix_ids: jax.Array | None = None,
        on_token: Any = None,
        eos_id: int | None = None,
        decode_window: int = 1,
        constraints: dict | None = None,
    ):
        """`on_token(request_id, token_id, done)` — optional streaming
        callback fired for every generated token as its batched tick
        resolves (`done=True` on the request's final token). Keep it
        cheap: it runs on the serving thread between ticks.

        `constraints` — named constraint DFAs ({name:
        constrain.TokenDFA}, compiled against this decoder's
        vocabulary) a request selects with
        SamplingParams(constraint=name): that slot's logits are
        masked to grammar-admissible tokens (eos admitted only in
        accepting states) before argmax/categorical, and the DFA
        state advances on device inside the same tick/window
        programs. Requires `eos_id` (a satisfied constraint must be
        able to stop). With the default None, every traced program is
        byte-identical to a server built before this feature existed.

        `eos_id` — stop token: a request that emits it finishes
        immediately (its output ends with the eos) and its slot
        re-admits the next queued request, so num_steps becomes a
        budget rather than an exact length.

        `decode_window` — decode sub-steps fused into ONE jitted host
        dispatch (K). At the default 1 the server is the classic
        tick-per-token loop, bit-identical to before the window path
        existed. At K > 1 a `lax.scan` advances every active slot up
        to K tokens on device — sampling and eos detection included —
        and the host sees one batched [B, K] transfer per WINDOW
        instead of one [B, 1] transfer per token; admissions and
        retirements happen at window boundaries. Outputs stay
        token-identical to decode_window=1 (greedy bit-identical;
        sampled streams follow the same per-slot key schedule). A slot
        that hits eos or its budget mid-window is frozen on device
        (its position pinned, its tail tokens discarded on drain) —
        the latency cost of a larger K is finishing slots idling until
        the window boundary."""
        if decode_window < 1:
            raise ValueError(
                f"decode_window must be >= 1, got {decode_window}"
            )
        if getattr(dec.cfg, "has_linear", False):
            raise ValueError(
                "DecodeServer keeps K and V lanes per slot and no other "
                "state: it does not serve a model with recurrent layers "
                "(cfg.layer_kinds 'linear'). Serve this model on "
                "PagedDecodeServer's default path."
            )
        self.decode_window = decode_window
        if decode_window > 1:
            raw = getattr(dec, "decode_step_fn", None)
            if raw is None:
                raise ValueError(
                    "decode_window > 1 needs a decoder exposing "
                    "decode_step_fn() (models/gpt.py GptDecoder); "
                    f"{type(dec).__name__} does not"
                )
            raw()  # SpmdGptDecoder raises here: fail at construction
        self.dec = dec
        self.params = params
        self.B = max_batch
        self.step = dec.make_step()  # batched ticks (donating)
        cache = dec.init_cache(max_batch)
        cache["pos"] = jnp.zeros((max_batch,), jnp.int32)
        # Multi-LoRA serving: adapter banks attached to the params
        # (parallel/lora.py::stack_adapters) make the slot -> adapter
        # assignment per-slot cache state; id 0 = base model.
        from defer_tpu.parallel.lora import adapter_bank_info

        n_adapters = adapter_bank_info(params)
        self.multi_lora = n_adapters is not None
        if self.multi_lora:
            cache["adapter"] = jnp.zeros((max_batch,), jnp.int32)
            self.num_adapters = n_adapters
        self.cache = cache
        self.prefix_len = 0
        self._prefix_cache = None
        if prefix_ids is not None:
            if self.multi_lora:
                raise ValueError(
                    "prefix caching + multi-LoRA is unsupported: the "
                    "shared prefix K/V would be adapter-dependent"
                )
            if getattr(dec, "rolling_cache", False):
                raise ValueError(
                    "prefix caching over a rolling cache is not "
                    "supported (prefix rows would be recycled)"
                )
            if prefix_ids.ndim != 2 or prefix_ids.shape[0] != 1:
                raise ValueError("prefix_ids must be [1, P]")
            self.prefix_len = int(prefix_ids.shape[1])
            if self.prefix_len >= dec.cfg.max_len:
                raise ValueError(
                    f"prefix of {self.prefix_len} leaves no room under "
                    f"max_len {dec.cfg.max_len}"
                )
            # One shared prefill; every admission copies this lane.
            pre = dec.init_cache(1)
            _, pre = self.step(params, pre, prefix_ids)
            self._prefix_cache = pre
        # Constrained decoding tables (defer_tpu/constrain/): stacked
        # [C, S_max, V] transitions + [C, S_max] accepting bits, cid 0
        # the synthetic free row. None when the feature is off — every
        # tick then takes the exact pre-constraint code path.
        self._ctrans = None
        self._cacc = None
        self._cnames: dict[str, int] = {}
        self._cdfas: list = [None]
        if constraints is not None:
            if eos_id is None:
                raise ValueError(
                    "constraints= requires eos_id: a satisfied "
                    "constraint stops by emitting eos"
                )
            self._cnames, self._ctrans, self._cacc = (
                crt.stack_token_dfas(constraints, dec.cfg.vocab_size)
            )
            self._cdfas += [
                constraints[n]
                for n in sorted(self._cnames, key=self._cnames.get)
            ]
        # Per-request constraint failures (hand-built DFA dead ends):
        # rid -> message. The slot finishes cleanly; compiled DFAs
        # never land here (dfa.py prunes dead states).
        self.errors: dict[int, str] = {}
        self.constrained_tokens_n = 0
        self.constraint_dead_ends_n = 0
        self.slots = [_Slot() for _ in range(max_batch)]
        # Persistent tick feed: each slot's next input token lives in
        # row i, updated by .at[i].set at admission and one
        # full-vector write after each draw — not rebuilt by
        # concatenating max_batch [1,1] arrays every tick (host
        # dispatch overhead that dominates at small models). Idle
        # rows are dummies.
        self._feed = jnp.zeros((max_batch, 1), jnp.int32)
        self._sampler = SlotSampler(max_batch)
        # Deque, not list: admission pops from the head every time a
        # seat frees, and a list's pop(0) is O(queue depth) — a deep
        # backlog would make each admission scan the whole tail.
        self.pending: collections.deque[tuple] = collections.deque()
        self.done: dict[int, jax.Array] = {}
        self._next_id = 0
        self.ticks = 0
        self.on_token = on_token
        self.eos_id = eos_id
        self.solo_steps = 0  # what per-request loops would have cost
        # Dispatch-efficiency accounting (fused windows): host
        # dispatches of the decode program and tokens accepted from
        # them. At decode_window=1, dispatches == ticks.
        self.dispatches = 0
        self.window_tokens = 0
        # Metric handles resolved once; the tick/admission paths touch
        # pre-bound attributes only (obs/serving.py).
        self.obs = ServingMetrics("flat")
        self._submit_t: dict[int, float] = {}
        self._last_tick_t: float | None = None

    # -- public API -------------------------------------------------------

    def submit(
        self,
        prompt_ids: jax.Array,
        num_steps: int,
        *,
        adapter_id: int = 0,
        sampling: Any = None,
        stop: Any = None,
    ) -> int:
        """Queue a request; returns its id (resolved in .done).
        `adapter_id` selects the request's LoRA adapter when banks are
        attached (0 = base model). `sampling` — an optional
        models/gpt.py SamplingParams: the slot then samples inside the
        shared batched tick with its own temperature/top-k/top-p/min-p
        and a per-request key, reproducing
        `generate(..., rng=jax.random.key(seed))` bit-for-bit; None =
        greedy (the temperature-0 special case). `stop` — optional
        multi-token stop sequences (iterable of int sequences,
        runtime/stopping.py): the request finishes the moment its
        GENERATED tail equals any of them, output ending with the stop
        sequence — the multi-token generalization of `eos_id`."""
        if prompt_ids.shape[0] != 1:
            raise ValueError("submit one request at a time ([1, T])")
        cid = 0
        if sampling is not None:
            sampling.validate()
            # The constraint survives the greedy normalization below:
            # temperature-0 JSON mode is the common case.
            cid = self._resolve_constraint(sampling.constraint)
            if sampling.temperature == 0:
                sampling = None  # greedy: keep the argmax fast path
        stop_seqs = normalize_stops(stop)
        if adapter_id:
            if not self.multi_lora:
                raise ValueError(
                    "adapter_id set but params carry no adapter banks "
                    "(parallel/lora.py::stack_adapters)"
                )
            if not 0 <= adapter_id < self.num_adapters:
                raise ValueError(
                    f"adapter_id {adapter_id} out of range "
                    f"[0, {self.num_adapters})"
                )
        t0 = prompt_ids.shape[1]
        if t0 < 1:
            raise ValueError("prompt must have at least one token")
        if num_steps < 1:
            raise ValueError(
                f"num_steps={num_steps}: need at least one generated "
                "token (a non-positive count would never complete)"
            )
        if (
            not getattr(self.dec, "rolling_cache", False)
            and self.prefix_len + t0 + num_steps > self.dec.cfg.max_len
        ):
            # Rolling caches have no length bound — slots recycle.
            raise ValueError(
                f"prefix {self.prefix_len} + prompt {t0} + steps "
                f"{num_steps} exceeds max_len {self.dec.cfg.max_len}"
            )
        rid = self._next_id
        self._next_id += 1
        self.pending.append(
            (rid, prompt_ids, num_steps, adapter_id, sampling,
             stop_seqs, cid)
        )
        self.solo_steps += num_steps
        self._submit_t[rid] = time.perf_counter()
        return rid

    def _resolve_constraint(self, name: str | None) -> int:
        return crt.resolve_constraint(
            name, self._ctrans, self._cnames, self._cdfas
        )

    def run(self) -> dict[int, jax.Array]:
        """Serve until every submitted request completes; returns
        {request_id: ids [1, T0 + num_steps]}."""
        while self.pending or any(s.req is not None for s in self.slots):
            self._admit()
            self._tick()
        return self.done

    # -- internals --------------------------------------------------------

    def _admit(self) -> None:
        for i, slot in enumerate(self.slots):
            if slot.req is not None or not self.pending:
                continue
            (rid, prompt, steps, adapter_id, samp,
             stop_seqs, cid) = self.pending.popleft()
            t0 = prompt.shape[1]
            self.obs.requests_admitted.inc()
            self.obs.prefill_tokens.inc(t0)
            # Strict lookup: an unknown rid would silently observe a
            # zero queue wait — a missing submit timestamp is a bug.
            self.obs.queue_wait.observe(
                time.perf_counter() - self._submit_t[rid]
            )
            P = self.prefix_len
            rolling = getattr(self.dec, "rolling_cache", False)
            win = self.dec.cfg.window if rolling else None
            if rolling and t0 > win:
                # Longer-than-window prompt: window-chunked rolling
                # prefill (fixed window pieces + at most `win` distinct
                # tail shapes — bounded compile set; padding a rolling
                # step on a WARM cache would evict live slots).
                small = self.dec.init_cache(1)
                if self.multi_lora:
                    small["adapter"] = jnp.full(
                        (1,), adapter_id, jnp.int32
                    )
                last, small = self.dec.prefill(
                    self.params, small, prompt, chunk=win
                )
                first = self._first_token(i, samp, last, prompt.dtype,
                                          cid)
                self._install_lane(
                    i, slot, rid, steps, prompt, small, first,
                    t0, adapter_id, samp, stop_seqs, cid,
                )
                continue
            # Bucketed prefill keeps the compiled-shape set small.
            # Rolling admission always starts from a FRESH lane, so
            # padded rows sit at held < 0 (masked) and the window caps
            # the bucket instead of max_len.
            pad = 1 << (t0 - 1).bit_length()
            pad = min(pad, win if rolling else self.dec.cfg.max_len - P)
            padded = jnp.concatenate(
                [prompt, jnp.zeros((1, pad - t0), prompt.dtype)], axis=1
            )
            if self._prefix_cache is None:
                small = self.dec.init_cache(1)
                if self.multi_lora:
                    small["adapter"] = jnp.full(
                        (1,), adapter_id, jnp.int32
                    )
                logits, small = self.step(self.params, small, padded)
            else:
                # Suffix prefill through a NON-donating step: the
                # master prefix lane is read in place (no per-admission
                # deep copy of two [L, 1, Hkv, max_len, Dh] buffers —
                # the cost prefix caching exists to avoid) and the
                # returned cache is a fresh tree. (prefix caching +
                # multi-LoRA is rejected at construction.)
                small = dict(self._prefix_cache)
                logits, small = self.dec.make_step(donate=False)(
                    self.params, small, padded
                )
            first = self._first_token(
                i, samp, logits[:, t0 - 1, :], prompt.dtype, cid
            )
            self._install_lane(
                i, slot, rid, steps, prompt, small, first,
                P + t0, adapter_id, samp, stop_seqs, cid,
            )

    def _first_token(self, i, samp, lrow, dtype, cid):
        """Admission's first generated token: constrained slots mask
        the prefill logits row with their DFA's START-state row before
        the shared argmax/first-draw, then install the advanced state
        (a device scalar — admission stays sync-free beyond its
        existing bookkeeping)."""
        if cid:
            row = self._ctrans[cid, 0]
            mask = (row >= 0).at[self.eos_id].set(self._cacc[cid, 0])
            lrow = jnp.where(mask[None, :], lrow,
                             jnp.finfo(lrow.dtype).min)
        first = self._sampler.admit_first(i, samp, lrow, dtype)
        if cid:
            state = jnp.maximum(row[first[0, 0].astype(jnp.int32)], 0)
            self._sampler.admit_constraint(i, cid, state)
            frac = crt.masked_frac(mask[None, :], jnp.asarray([True]))
            # analysis: ignore[host-sync-in-hot-loop] once per
            # CONSTRAINED admission (first token only), not per tick —
            # the paged server's mixed-mode flips made _first_token
            # tick-reachable by name; the steady-state tick never
            # reaches this branch in either server
            self.obs.constrain_masked_frac.observe(float(frac[0]))
            self.obs.constrained_tokens.inc()
            self.constrained_tokens_n += 1
        return first

    def _install_lane(
        self, i, slot, rid, steps, prompt, small, first, pos_val,
        adapter_id, samp=None, stop_seqs=(), cid=0,
    ) -> None:
        """The one admission tail both prefill paths share: insert the
        prefilled lane into slot i (rows past pos_val are stale but
        position-masked until overwritten), set per-slot state, and
        run the eos/streaming/finish bookkeeping."""
        new_cache = {
            "k": jax.lax.dynamic_update_slice(
                self.cache["k"], small["k"], (0, i, 0, 0, 0)
            ),
            "v": jax.lax.dynamic_update_slice(
                self.cache["v"], small["v"], (0, i, 0, 0, 0)
            ),
            "pos": self.cache["pos"].at[i].set(pos_val),
        }
        if self.multi_lora:
            new_cache["adapter"] = (
                self.cache["adapter"].at[i].set(adapter_id)
            )
        self.cache = new_cache
        # TTFT is host-side: submit() to first-token DISPATCH (the
        # token array may still be in flight on device — honesty note
        # in ARCHITECTURE.md "Observability").
        # ttft spans queue + prefill (popped here, the drain point —
        # strict: a missing rid means the timestamp was never pinned).
        self.obs.ttft.observe(
            time.perf_counter() - self._submit_t.pop(rid)
        )
        self.obs.tokens_generated.inc()
        slot.req = rid
        slot.remaining = steps - 1
        slot.last = first
        slot.toks = [prompt, first]
        slot.sampling = samp is not None
        slot.stop = matcher_or_none(stop_seqs)
        slot.cid = cid
        self._feed = self._feed.at[i].set(first[0].astype(jnp.int32))
        need_host = (
            self.eos_id is not None
            or self.on_token is not None
            or slot.stop is not None
        )
        tok_host = int(first[0, 0]) if need_host else None
        if self.eos_id is not None and tok_host == self.eos_id:
            slot.remaining = 0
        if slot.stop is not None and slot.stop.push(tok_host):
            slot.remaining = 0
        if self.on_token is not None:
            self.on_token(rid, tok_host, slot.remaining == 0)
        if slot.remaining == 0:
            self._finish(i, slot)

    def _tick(self) -> None:
        if self.decode_window > 1:
            return self._tick_window()
        active = [s.req is not None for s in self.slots]
        if not any(active):
            return
        # Persistent [B,1] device feed (constructor note): admissions
        # set their row, draws below overwrite the whole vector.
        logits, cache = self.step(self.params, self.cache, self._feed)
        self.ticks += 1
        self.dispatches += 1
        n_active = sum(active)
        now = time.perf_counter()
        if self._last_tick_t is not None:
            self.obs.itl.observe(now - self._last_tick_t, n_active)
        self._last_tick_t = now
        self.obs.ticks.inc()
        self.obs.host_dispatches.inc()
        self.obs.tokens_per_dispatch.set(float(n_active))
        self.window_tokens += n_active
        self.obs.tokens_generated.inc(n_active)
        # Inactive slots wrote a dummy row at their position; pin them
        # back to 0 so they never creep toward max_len.
        mask = jnp.asarray(active)
        cache = {**cache, "pos": jnp.where(mask, cache["pos"], 0)}
        self.cache = cache
        ll = logits[:, -1, :]
        sm = self._sampler
        # Constrained rows (defer_tpu/constrain/): fold the DFA mask
        # into the batched logits BEFORE argmax/draw, advance states
        # after. Guarded by the host mirror so unconstrained serving
        # dispatches the exact pre-constraint op sequence.
        constrained = any(sm.row_constrained)
        if constrained:
            crow, cacc = crt.constrain_rows(
                self._ctrans, self._cacc, sm.cid, sm.cstate
            )
            cmask = crt.constrain_mask(crow, cacc, self.eos_id)
            cvec = jnp.asarray(sm.row_constrained)
            # Dead end (hand-built DFAs only — dfa.py prunes): no
            # admissible token. Force eos so the row freezes; the
            # drain drops the forced token and surfaces the error.
            dead = cvec & mask & ~cmask.any(-1)
            ll = crt.fold_mask(ll, cmask)
        if any(
            s.req is not None and s.sampling for s in self.slots
        ):
            nxt = self._sampler.draw(ll)
        else:
            nxt = jnp.argmax(ll, axis=-1)  # (B,)
        if constrained:
            nxt = jnp.where(dead, self.eos_id, nxt)
            sm.cstate = crt.advance_state(
                crow, sm.cstate, nxt, cvec & ~dead
            )
            mfrac = crt.masked_frac(cmask, cvec & mask)
        self._feed = nxt[:, None].astype(jnp.int32)
        # One device->host transfer per tick for streaming/eos/stop
        # matching, not one blocking int() per slot.
        need_host = (
            self.on_token is not None
            or self.eos_id is not None
            or any(
                s.req is not None and s.stop is not None
                for s in self.slots
            )
        )
        # analysis: ignore[host-sync-in-hot-loop] single batched
        # transfer per WINDOW (a window of one token here), and only
        # when an eos/stop/stream consumer needs host tokens — the
        # sync this serving loop is designed around
        host_nxt = np.asarray(nxt) if need_host else None
        if constrained:
            # analysis: ignore[host-sync-in-hot-loop] one batched
            # per-tick transfer of the dead-end flags + mask
            # fractions, and only while a constrained row is live
            dead_host = np.asarray(dead)
            # analysis: ignore[host-sync-in-hot-loop] ready with the
            # vector above (same sync point)
            mfrac_host = np.asarray(mfrac)
        for i, slot in enumerate(self.slots):
            if slot.req is None:
                continue
            if constrained and slot.cid:
                if bool(dead_host[i]):
                    # The forced eos never enters the output: the
                    # request ends at its last admissible token with
                    # a per-request error, not a hang.
                    self.errors[slot.req] = (
                        "constraint dead end: DFA state admits no "
                        "token and is not accepting"
                    )
                    self.constraint_dead_ends_n += 1
                    self.obs.constrain_dead_ends.inc()
                    slot.remaining = 0
                    self._finish(i, slot)
                    continue
                self.constrained_tokens_n += 1
                self.obs.constrained_tokens.inc()
                self.obs.constrain_masked_frac.observe(
                    float(mfrac_host[i])
                )
            tok = nxt[i][None, None].astype(slot.last.dtype)
            slot.last = tok
            slot.toks.append(tok)
            slot.remaining -= 1
            if (
                self.eos_id is not None
                and int(host_nxt[i]) == self.eos_id
            ):
                slot.remaining = 0
            if slot.stop is not None and slot.stop.push(
                int(host_nxt[i])
            ):
                slot.remaining = 0
            if self.on_token is not None:
                self.on_token(
                    slot.req, int(host_nxt[i]), slot.remaining == 0
                )
            if slot.remaining == 0:
                self._finish(i, slot)

    def _build_window(self, mode: str):
        """The fused K-sub-step decode program for one sampling mode
        ("argmax" | "nosort" | "sort" — picked per window, same
        bit-identical trio SlotSampler.draw switches between). A
        `lax.scan` over the raw single-step body (decode_step_fn)
        advances every row; each sub-step pins inactive rows' position
        (the K=1 tick's exact rule, applied with the sub-step-START
        active mask), samples on device, counts the token against the
        row's budget, and freezes rows that hit eos or budget for the
        REST of the window. Fixed length K — no early exit — so the
        trace is stable regardless of where rows finish. Memoized on
        the decoder (utils/memo.cached_step), which also puts it where
        analysis/sanitizer.py auto-watches for retraces."""
        K = self.decode_window
        eos = self.eos_id
        dec = self.dec

        def build():
            raw = dec.decode_step_fn()

            def window(params, cache, feed, active, keys, temp,
                       topk, topp, minp, budget):
                def body(carry, _):
                    cache, feed, active, keys, n = carry
                    logits, cache = raw(params, cache, feed)
                    cache = {
                        **cache,
                        "pos": jnp.where(active, cache["pos"], 0),
                    }
                    ll = logits[:, -1, :]
                    if mode == "argmax":
                        nxt = jnp.argmax(ll, axis=-1)
                    elif mode == "nosort":
                        nxt, keys = sample_token_batched_nosort(
                            ll, keys, temp, minp
                        )
                    else:
                        nxt, keys = sample_token_batched(
                            ll, keys, temp, topk, topp, minp
                        )
                    n = n + active.astype(jnp.int32)
                    alive = active & (n < budget)
                    if eos is not None:
                        alive = alive & (nxt != eos)
                    feed = nxt[:, None].astype(jnp.int32)
                    return (cache, feed, alive, keys, n), nxt

                init = (
                    cache, feed, active, keys,
                    jnp.zeros_like(budget),
                )
                (cache, feed, alive, keys, n), toks = lax.scan(
                    body, init, None, length=K
                )
                return cache, feed, alive, keys, n, toks.T

            return jax.jit(window, donate_argnums=(1,))

        return cached_step(
            self.dec, ("flat_window", K, mode, eos), build
        )

    def _build_window_c(self, mode: str):
        """Constrained variant of the fused window program: same scan
        skeleton plus the per-sub-step DFA gather/mask-fold/advance
        (constrain/runtime.py). A SEPARATE memo key — the
        unconstrained program stays byte-identical to pre-constraint
        builds, and a constrained server only pays this trace while a
        constrained row is actually live (_tick_window dispatch).
        Extra outputs: final DFA states, a per-row "hit a dead end"
        flag (hand-built DFAs only; the forced-eos token is dropped on
        drain) and the [B, K] masked-fraction buffer for obs."""
        K = self.decode_window
        eos = self.eos_id
        dec = self.dec

        def build():
            raw = dec.decode_step_fn()

            def window(params, cache, feed, active, keys, temp,
                       topk, topp, minp, budget, cid, cstate,
                       ctrans, cacc):
                cvec = cid > 0

                def body(carry, _):
                    cache, feed, active, keys, n, cstate, died = carry
                    logits, cache = raw(params, cache, feed)
                    cache = {
                        **cache,
                        "pos": jnp.where(active, cache["pos"], 0),
                    }
                    ll = logits[:, -1, :]
                    crow, acc = crt.constrain_rows(
                        ctrans, cacc, cid, cstate
                    )
                    cmask = crt.constrain_mask(crow, acc, eos)
                    dead = cvec & active & ~cmask.any(-1)
                    ll = crt.fold_mask(ll, cmask)
                    if mode == "argmax":
                        nxt = jnp.argmax(ll, axis=-1)
                    elif mode == "nosort":
                        nxt, keys = sample_token_batched_nosort(
                            ll, keys, temp, minp
                        )
                    else:
                        nxt, keys = sample_token_batched(
                            ll, keys, temp, topk, topp, minp
                        )
                    nxt = jnp.where(dead, eos, nxt)
                    cstate = crt.advance_state(
                        crow, cstate, nxt, cvec & ~dead
                    )
                    frac = crt.masked_frac(cmask, cvec & active)
                    n = n + active.astype(jnp.int32)
                    alive = active & (n < budget) & (nxt != eos)
                    feed = nxt[:, None].astype(jnp.int32)
                    carry = (
                        cache, feed, alive, keys, n, cstate,
                        died | dead,
                    )
                    return carry, (nxt, frac)

                init = (
                    cache, feed, active, keys,
                    jnp.zeros_like(budget), cstate,
                    jnp.zeros_like(cvec),
                )
                (cache, feed, alive, keys, n, cstate, died), (
                    toks, fracs
                ) = lax.scan(body, init, None, length=K)
                return (
                    cache, feed, alive, keys, n, toks.T, cstate,
                    died, fracs.T,
                )

            return jax.jit(window, donate_argnums=(1,))

        return cached_step(
            self.dec, ("flat_window_c", K, mode, eos), build
        )

    def _tick_window(self) -> None:
        """One fused dispatch of up to decode_window tokens per active
        slot; ONE batched host transfer drains the [B, K] token buffer
        (plus tiny per-slot valid-length/alive vectors when eos is
        configured)."""
        active = [s.req is not None for s in self.slots]
        if not any(active):
            return
        K = self.decode_window
        sampling = any(
            s.req is not None and s.sampling for s in self.slots
        )
        if not sampling:
            mode = "argmax"
        elif any(self._sampler.row_sort):
            mode = "sort"
        else:
            mode = "nosort"
        budget = [
            s.remaining if s.req is not None else 0
            for s in self.slots
        ]
        sm = self._sampler
        constrained = any(sm.row_constrained)
        died = fracs = None
        if constrained:
            window = self._build_window_c(mode)
            (cache, feed, alive, keys, n_dev, toks, cstate, died,
             fracs) = window(
                self.params, self.cache, self._feed,
                jnp.asarray(active), sm.keys, sm.temp, sm.topk,
                sm.topp, sm.minp, jnp.asarray(budget, jnp.int32),
                sm.cid, sm.cstate, self._ctrans, self._cacc,
            )
            sm.cstate = cstate
        else:
            window = self._build_window(mode)
            cache, feed, alive, keys, n_dev, toks = window(
                self.params, self.cache, self._feed,
                jnp.asarray(active), sm.keys, sm.temp, sm.topk,
                sm.topp, sm.minp, jnp.asarray(budget, jnp.int32),
            )
        self.cache = cache
        self._feed = feed
        sm.keys = keys
        self.ticks += 1
        self.dispatches += 1
        n_live = sum(active)
        now = time.perf_counter()
        if self._last_tick_t is not None:
            self.obs.itl.observe(now - self._last_tick_t, n_live)
        self._last_tick_t = now
        self.obs.ticks.inc()
        self.obs.host_dispatches.inc()
        need_toks = self.on_token is not None or any(
            s.req is not None and s.stop is not None
            for s in self.slots
        )
        if self.eos_id is not None:
            # analysis: ignore[host-sync-in-hot-loop] one batched
            # per-WINDOW transfer of the valid-length/alive vectors
            # — K tokens amortize this sync, the point of the window
            emitted = np.asarray(n_dev).tolist()
            # analysis: ignore[host-sync-in-hot-loop] same per-window
            # sync point (ready with the vector above)
            alive_host = np.asarray(alive).tolist()
        else:
            # No eos: the device can only freeze rows on budget, which
            # the host already knows — no transfer needed.
            emitted = [min(b, K) for b in budget]
            alive_host = [b > K for b in budget]
        # analysis: ignore[host-sync-in-hot-loop] the ONE batched
        # [B, K] token transfer per window that replaces K per-tick
        # [B, 1] transfers — only when a stream/stop consumer exists
        toks_host = np.asarray(toks).tolist() if need_toks else None
        died_host = fracs_host = None
        if constrained:
            # analysis: ignore[host-sync-in-hot-loop] rides the same
            # per-window sync: batched dead-end flags + [B, K] mask
            # fractions, only while a constrained row is live
            died_host = np.asarray(died).tolist()
            # analysis: ignore[host-sync-in-hot-loop] same per-window
            # sync point (ready with the vector above)
            fracs_host = np.asarray(fracs)
        self._drain_window(toks, toks_host, emitted, alive_host,
                           budget, died_host, fracs_host)

    def _drain_window(
        self, toks, toks_host, emitted, alive_host, budget,
        died_host=None, fracs_host=None,
    ) -> None:
        """Host-side window drain, per-token-equivalent to the K=1
        tick loop: stop sequences truncate the window's overshoot
        (StopMatcher.push_window — discarded tokens never enter the
        match history), budgets and finishes mirror the per-token
        bookkeeping, and streaming callbacks fire in tick-major order
        (batching.window_drain_order) so consumers see the exact
        K=1 interleaving."""
        K = self.decode_window
        accepted = [0] * self.B
        finishing = [False] * self.B
        for i, slot in enumerate(self.slots):
            if slot.req is None:
                continue
            n_i = emitted[i]
            a_i = n_i
            stopped = False
            dead = bool(
                died_host is not None and died_host[i] and slot.cid
            )
            if dead:
                # Dead-end DFA state mid-window: the device froze the
                # row with a FORCED eos (counted in n_i) — drop it, so
                # the output ends at the last admissible token and the
                # failure surfaces as a per-request error, not a hang.
                a_i = n_i - 1
            if slot.stop is not None:
                hit = slot.stop.push_window(toks_host[i][:a_i])
                if hit is not None:
                    a_i, stopped = hit, True
            accepted[i] = a_i
            if a_i < min(budget[i], K):
                self.obs.window_truncated.inc()
            slot.remaining -= a_i
            if stopped or not alive_host[i]:
                # eos froze the row on device, a stop sequence cut it
                # on drain, or its budget ran out mid-window.
                slot.remaining = 0
            if dead:
                slot.remaining = 0
                self.errors[slot.req] = (
                    "constraint dead end: DFA state admits no token "
                    "and is not accepting"
                )
                self.constraint_dead_ends_n += 1
                self.obs.constrain_dead_ends.inc()
            if slot.cid and fracs_host is not None:
                self.constrained_tokens_n += a_i
                if a_i:
                    self.obs.constrained_tokens.inc(a_i)
                for fr in fracs_host[i][:a_i].tolist():
                    self.obs.constrain_masked_frac.observe(fr)
            tok_block = toks[i, :a_i][None, :].astype(
                slot.last.dtype
            )
            slot.toks.append(tok_block)
            slot.last = tok_block[:, -1:]
            finishing[i] = slot.remaining == 0
            self.obs.tokens_generated.inc(a_i)
            self.window_tokens += a_i
        self.obs.tokens_per_dispatch.set(float(sum(accepted)))
        if self.on_token is not None:
            for t, i in window_drain_order(accepted, K):
                slot = self.slots[i]
                self.on_token(
                    slot.req,
                    toks_host[i][t],
                    finishing[i] and t == accepted[i] - 1,
                )
        for i, slot in enumerate(self.slots):
            if finishing[i]:
                self._finish(i, slot)

    def _finish(self, i: int, slot: _Slot) -> None:
        self.obs.requests_finished.inc()
        self.done[slot.req] = jnp.concatenate(slot.toks, axis=1)
        slot.req = None
        slot.toks = None
        slot.last = None
        slot.sampling = False
        slot.stop = None
        slot.cid = 0
        # Release the slot's sampling policy row NOW, not at reuse —
        # a lingering row_sort would drag every later tick through
        # the sorting sampler (SlotSampler.release).
        self._sampler.release(i)


def serve_greedy(
    dec: Any,
    params: dict,
    requests: list[tuple[jax.Array, int]],
    *,
    max_batch: int = 4,
    prefix_ids: jax.Array | None = None,
    eos_id: int | None = None,
    sampling: list | None = None,
    decode_window: int = 1,
    constraints: dict | None = None,
) -> tuple[list[jax.Array], dict]:
    """One-shot convenience: serve `[(prompt, steps), ...]`, returning
    outputs in submission order plus stats (`ticks` batched decode
    steps taken vs `solo_steps` a per-request loop would take; with a
    shared prefix, `saved_prefill_tokens` counts the K/V rows each
    admission reused instead of recomputing). Stats is an
    obs.ServerStats: the same dict plus attribute access and the
    process metrics snapshot under `stats.metrics`. With `prefix_ids`, each
    prompt is the per-request SUFFIX and outputs cover suffix +
    generation (the prefix ids are not repeated in the result).

    `decode_window=K` fuses K decode sub-steps into one host dispatch
    (DecodeServer docstring has the semantics); outputs stay
    token-identical to the default K=1. Stats then also carry
    `decode_window`, `host_dispatches` (decode dispatches issued) and
    `tokens_per_dispatch` (mean tokens accepted per dispatch — the
    dispatch-amortization win, approaching K * active slots).

    `constraints={name: TokenDFA}` registers grammar constraints
    (defer_tpu/constrain/) a request selects via
    SamplingParams(constraint=name)."""
    srv = DecodeServer(
        dec, params, max_batch=max_batch, prefix_ids=prefix_ids,
        eos_id=eos_id, decode_window=decode_window,
        constraints=constraints,
    )
    samps = sampling or [None] * len(requests)
    if len(samps) != len(requests):
        raise ValueError(
            f"sampling has {len(samps)} entries for "
            f"{len(requests)} requests"
        )
    rids = [
        srv.submit(p, s, sampling=sp)
        for (p, s), sp in zip(requests, samps)
    ]
    done = srv.run()
    stats = ServerStats.snapshot(
        srv.obs.registry,
        ticks=srv.ticks,
        solo_steps=srv.solo_steps,
        saved_prefill_tokens=srv.prefix_len * len(requests),
        decode_window=srv.decode_window,
        host_dispatches=srv.dispatches,
        tokens_per_dispatch=(
            srv.window_tokens / srv.dispatches if srv.dispatches else 0.0
        ),
        constrained_tokens=srv.constrained_tokens_n,
        constraint_dead_ends=srv.constraint_dead_ends_n,
    )
    return [done[r] for r in rids], stats
