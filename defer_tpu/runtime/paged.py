"""Paged KV cache: a shared block pool instead of per-slot max_len
lanes (the vLLM idea, TPU-shaped).

A contiguous continuous-batching cache (runtime/decode_server.py)
reserves `max_batch x max_len` K/V rows even when every request is
short — decode HBM is cache-bound, so reserved-but-unused rows are the
serving memory ceiling. Here the cache is a pool of fixed-size BLOCKS
([L, num_blocks, H_kv, block_size, Dh]); each slot holds a BLOCK TABLE
of pool indices, and memory scales with the sum of actual request
budgets, not slots x max_len.

Static-shape design (everything jits once):

  * the decode step runs one of THREE attention paths, selected by
    `attention=` (default "gathered"):

      - "gathered": gather each slot's blocks into the standard
        contiguous [B, H_kv, S, Dh] view (one gather per layer) and
        run the EXACT SAME block math as the flat decoder
        (GptDecoder._block) — numerical parity is inherited, not
        re-proven (bit-exact vs the flat server at tested scales) —
        then scatter the single new K/V row back to its block. The
        plain tick gathers the table up to the rung above the deepest
        live slot (`span_rungs`: a quarter, three eighths or the
        whole, one program per rung built before the first tick), so
        it reads O(B * rung * block_size) rows; every other tick kind
        reads the whole table, O(B * max_blocks * block_size) rows
        regardless of request depth, which is the baseline all paths
        are measured against.
      - "blockwise": attend THROUGH the block table — scatter the new
        K/V row into the pool first, then fold pool blocks into an
        online-softmax carry (running max / denominator,
        flash-attention recurrence) one table column at a time,
        stopping at the deepest LIVE block across the batch
        (`lax.fori_loop` with a traced bound). Pure XLA, runs
        everywhere CPU tier-1 runs. Reads O(B * live_blocks *
        block_size) rows per tick. Parity contract: TIE-TOLERANT —
        the projections/FFN are `_block`'s own code (bit-identical),
        but the softmax reduction order differs, so logits agree only
        to float tolerance; at tested scales the emitted tokens are
        identical (tests pin that), while near-ties could in
        principle resolve differently.
      - "pallas": the block-table-indexed flash-decode kernel
        (ops/pallas_attention.py::paged_flash_decode) — the table
        indirection happens in the kernel's index maps, dead columns
        are clamped so each slot DMAs only ITS OWN live blocks:
        per-slot bandwidth O(own live blocks), the full
        paged-attention win. Runs natively on TPU (Mosaic), and
        through the pallas interpreter anywhere else (slow; CI
        exercises it under the `slow` marker). Same tie-tolerant
        contract as "blockwise".

    The win is observable: `defer_kv_rows_read_total` vs
    `defer_kv_rows_gathered_baseline_total` (obs/serving.py) count
    per-tick rows read vs the gathered baseline, and
    tests/test_paged_attention.py holds the ratio per mode;
  * block tables are a fixed [B, max_blocks] shape; unallocated
    entries point at the reserved TRASH block 0 (never allocated to a
    request), so out-of-budget writes land in scrap instead of another
    request's memory and garbage reads sit beyond the position mask —
    every attention path keeps this invariant and the
    scatter-new-row write unchanged;
  * allocation is host-side and exact: a request's block need is known
    at submit time (prompt + step budget, eos can only shorten it), so
    admission takes ceil(total/block_size) blocks from the free list
    and finishing returns them — when the pool is exhausted, requests
    simply wait (the pool, not the slot count, is the admission
    limit).

Prefill reuses the flat decoder's admission path (single-request
contiguous prefill), and the resulting rows are scattered into the
allocated blocks in one jitted op.
"""

from __future__ import annotations

import bisect
import collections
import hashlib
import queue
import threading
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
from defer_tpu.models.quant import (
    dequantize_symmetric,
    quantize_symmetric,
)
from defer_tpu.obs import spans
from defer_tpu.obs.serving import ServerStats, ServingMetrics
from defer_tpu.ops.gated_delta import CHUNK
from defer_tpu.ops.pallas_attention import _MASK_VALUE
from defer_tpu.parallel.transformer_stack import LINEAR
from defer_tpu.runtime.batching import (
    accept_lengths,
    microbatch_groups,
    pp_schedule_occupancy,
    window_drain_order,
)
from defer_tpu.runtime.decode_server import DraftLanes, SlotSampler
from defer_tpu.runtime.schedule import PrefillSeat, plan_mixed_tick
from defer_tpu.runtime.stopping import matcher_or_none, normalize_stops


def _pool_arr(pool):
    """The array leaf carrying the pool geometry ([.., NB, Hkv, bs,
    Dh]): the int8 payload of a quantized {"q","s"} pool, or the fp
    pool itself."""
    return pool["q"] if isinstance(pool, dict) else pool


# Sixteenths of the table span at which the gathered decode step is
# built: a quarter, three eighths, the whole. Every rung is one more
# program traced, lowered and loaded at each server's start (0.7-0.9 s
# each at Mistral-7B's widths on a v5e's host, PERF.md PR 26), so the
# ladder is held to what chat-length traffic on a long table needs.
_RUNG_SIXTEENTHS = (4, 6, 16)


def span_rungs(mb: int) -> tuple[int, ...]:
    """The ladder of table spans, in columns of a `mb`-column block
    table, that the gathered decode step is built for: each rung a
    share of the table rounded up to whole blocks, duplicates dropped
    (a table too small to split holds fewer rungs), the last the whole
    table."""
    return tuple(sorted({-(-mb * s // 16) for s in _RUNG_SIXTEENTHS}))


def pick_rung(rungs: tuple[int, ...], bs: int, depth: int) -> int:
    """The lowest rung whose rows hold position `depth`."""
    return rungs[bisect.bisect_right(rungs, depth // bs)]


class _SpanSteps:
    """The decode step as the plain tick calls it: one program per
    table span, lowered and compiled from abstract shapes before the
    first tick and picked by the width of the table it is handed, so
    that a depth crossing a rung builds nothing. `lower` is the jitted
    function's, for whoever reads the program."""

    def __init__(self, jitted, programs: dict):
        self.programs = programs
        self.lower = jitted.lower

    def __call__(self, params, pk, pv, tables, *rest):
        return self.programs[tables.shape[1]](params, pk, pv, tables, *rest)


def _pool_gather(pool_l, idx, dtype, layer=None):
    """Gather per-layer pool blocks at `idx` and widen to `dtype`.
    `pool_l` is [NB, Hkv, bs, Dh] — a plain fp array, or an int8
    {"q","s"} pair with [NB, Hkv] per-(block, head) scales
    (models/quant.py convention). The scale folds in AT THE GATHER,
    so every attend path downstream sees ordinary fp blocks and the
    attention math stays exactly the fp path's. idx may be [B] (one
    block per slot) or [B, MB] (a whole table): s broadcasts as
    s[..., None, None] against q's trailing (bs, Dh) in either case.
    With `layer` the pool is the whole [L, NB, ...] stack and the
    blocks are read at [layer, idx] in one gather: no layer slice of
    the pool is ever made."""
    at = idx if layer is None else (layer, idx)
    if isinstance(pool_l, dict):
        return dequantize_symmetric(
            pool_l["q"][at], pool_l["s"][at][..., None, None], dtype
        )
    return pool_l[at].astype(dtype)


def _pool_write_rows(pool_l, dest, rowi, val, layer=None):
    """Scatter one fresh K/V row per batch entry into a per-layer
    pool slice: dest [N] block ids, rowi [N] rows-in-block, val
    [N, Hkv, Dh]; with `layer`, into the whole [L, NB, ...] pool at
    that layer, in place. For an fp pool the values and places are
    exactly the historical `.at[dest, :, rowi, :].set(val)`'s, but
    the head axis is INDEXED (block, head and row are all explicit,
    the update window one head row of [Dh]): with the head axis a
    slice between block and row the window is [Hkv, Dh], and the TPU
    compiler re-lays the whole pool, rows ahead of heads, so that the
    window is contiguous — a copy of the pool into that layout and
    one back, per layer where the pool is scanned and at the
    program's edge where it is carried (PERF.md PR 33). A window of
    trailing elements scatters in the layout the pool is stored in.

    An int8 pool can't write a row in place — symmetric int8 keeps
    ONE scale per (block, head), so landing a row means re-deriving
    the block scale: gather the touched blocks, dequantize, insert
    the new row, ZERO the stale rows past it (rows > rowi are a
    previous tenant's garbage; folding them into amax would blow up
    the scale and crush the live rows' precision — in fp they hide
    behind the position mask, here they'd poison the whole block),
    re-quantize over (bs, Dh), scatter payload + scale back (whole
    blocks: windows of trailing axes, so no re-lay either).
    Duplicate dest entries (trash block 0) race over garbage, the
    module invariant; radix-shared blocks are never a live dest, so
    no other request's scale is ever perturbed."""
    pre = () if layer is None else (layer,)
    if not isinstance(pool_l, dict):
        heads = jnp.arange(pool_l.shape[-3])
        at = pre + (dest[:, None], heads[None, :], rowi[:, None])
        return pool_l.at[at].set(val)
    n = dest.shape[0]
    bs = pool_l["q"].shape[-2]
    at = pre + (dest,)
    blk = dequantize_symmetric(
        pool_l["q"][at],
        pool_l["s"][at][..., None, None],
        jnp.float32,
    )  # [N, Hkv, bs, Dh]
    blk = blk.at[jnp.arange(n), :, rowi, :].set(val.astype(jnp.float32))
    live = jnp.arange(bs)[None, :] <= rowi[:, None]  # [N, bs]
    blk = blk * live[:, None, :, None]
    q, s = quantize_symmetric(blk, axis=(-2, -1))  # s [N, Hkv]
    return {
        "q": pool_l["q"].at[at].set(q),
        "s": pool_l["s"].at[at].set(s),
    }


def _pool_write_rows_mt(pool_l, dest, rowi, val):
    """Multi-token sibling of _pool_write_rows: dest/rowi [B, T], val
    [B, T, Hkv, Dh] (T fresh rows per slot — a verify span or a
    prefill chunk). The fp path keeps the one-shot multi-row scatter.
    The int8 path loops the T columns SEQUENTIALLY through the
    single-row write: consecutive rows of one slot land in the same
    block, so each write must see the previous one's payload and
    scale — a parallel gather/requant would drop its siblings' rows.
    T is a small static bound (spec_k + 1, or a prefill chunk), and
    positions ascend with t, so the stale-row zeroing stays exact."""
    if not isinstance(pool_l, dict):
        return pool_l.at[dest, :, rowi, :].set(val)
    t = dest.shape[1]

    def body(j, pool):
        return _pool_write_rows(
            pool, dest[:, j], rowi[:, j], val[:, j]
        )

    return lax.fori_loop(0, t, body, pool_l)


def _quantize_blocks(blocks):
    """[L, n, Hkv, bs, Dh] fp block stack -> ({"q","s"}) int8 payload
    + [L, n, Hkv] scales, the pool's storage convention."""
    q, s = quantize_symmetric(
        blocks.astype(jnp.float32), axis=(-2, -1)
    )
    return q, s


def _blockwise_attend(q, pk_l, pv_l, tables, pos, bs, nb_live, window):
    """Single-token attention THROUGH a block table: fold pool blocks
    into the online-softmax carry (running max m, denominator l,
    accumulator — the flash recurrence, in fp32) one table column at a
    time, `lax.fori_loop`ed to `nb_live` = the deepest live block
    across the batch, so reads stop at actual depth instead of pool
    width. Per column the gather touches B blocks (one per slot); a
    slot shallower than the column has its whole block masked (its
    table entry points at live-or-trash rows the position mask
    excludes), which is what keeps the trash-block-0 invariant safe
    here. GQA folds grouped, [B, Hkv, G, *] against the [B, Hkv, bs,
    Dh] block — same head-major grouping as GptDecoder._block.

    q [B, Hq, 1, Dh]; pk_l/pv_l [NB, Hkv, bs, Dh]; tables [B, MB];
    pos [B] inclusive last valid key. Returns [B, 1, Hq*Dh] in
    q.dtype. Numerics: the recurrence computes the same softmax as
    the gathered path's one-pass einsum up to reduction order —
    tie-tolerant, not bit-exact (module docstring)."""
    b, hq, _, dh = q.shape
    hkv = _pool_arr(pk_l).shape[1]
    g = hq // hkv
    qg = q[:, :, 0, :].reshape(b, hkv, g, dh).astype(jnp.float32)
    qg = qg * (dh**-0.5)
    span = jnp.arange(bs)

    def body(j, carry):
        m, l, acc = carry
        blk = tables[:, j]  # [B]
        k = _pool_gather(pk_l, blk, jnp.float32)  # [B, Hkv, bs, Dh]
        v = _pool_gather(pv_l, blk, jnp.float32)
        s = jnp.einsum("bkgd,bksd->bkgs", qg, k)
        cols = j * bs + span  # [bs]
        mask = cols[None, :] <= pos[:, None]  # [B, bs]
        if window is not None:
            mask &= cols[None, :] > pos[:, None] - window
        s = jnp.where(mask[:, None, None, :], s, _MASK_VALUE)
        m_new = jnp.maximum(m, s.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l * alpha + p.sum(axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bkgs,bksd->bkgd", p, v
        )
        return m_new, l_new, acc_new

    init = (
        jnp.full((b, hkv, g), _MASK_VALUE, jnp.float32),
        jnp.zeros((b, hkv, g), jnp.float32),
        jnp.zeros((b, hkv, g, dh), jnp.float32),
    )
    _, l, acc = lax.fori_loop(0, nb_live, body, init)
    out = acc / l[..., None]  # [B, Hkv, G, Dh]
    return out.astype(q.dtype).reshape(b, 1, hq * dh)


def _blockwise_attend_mt(q, pk_l, pv_l, tables, pos, bs, nb_live, window):
    """Multi-token sibling of _blockwise_attend: T query rows per slot
    (a speculative verify window or a prefill chunk), each causally
    masked at its OWN position pos[b] + t, folded through the block
    table with the same per-column online-softmax recurrence. Rows a
    slot is not using (pad rows of a prefill tail, the k speculative
    rows of a sampled slot) produce garbage the caller ignores — the
    mask keeps them from reading past their qpos, nothing more.

    q [B, Hq, T, Dh]; pos [B] = the FIRST query row's position (row t
    attends through pos + t inclusive). Returns [B, T, Hq*Dh] in
    q.dtype, the layout _attn_out takes. Same tie-tolerant contract as
    the single-token fold."""
    b, hq, t, dh = q.shape
    hkv = _pool_arr(pk_l).shape[1]
    g = hq // hkv
    qg = q.reshape(b, hkv, g, t, dh).astype(jnp.float32)
    qg = qg * (dh**-0.5)
    qpos = pos[:, None] + jnp.arange(t)[None, :]  # [B, T]
    span = jnp.arange(bs)

    def body(j, carry):
        m, l, acc = carry
        blk = tables[:, j]  # [B]
        k = _pool_gather(pk_l, blk, jnp.float32)  # [B, Hkv, bs, Dh]
        v = _pool_gather(pv_l, blk, jnp.float32)
        s = jnp.einsum("bkgtd,bksd->bkgts", qg, k)
        cols = j * bs + span  # [bs]
        mask = cols[None, None, :] <= qpos[:, :, None]  # [B, T, bs]
        if window is not None:
            mask &= cols[None, None, :] > qpos[:, :, None] - window
        s = jnp.where(mask[:, None, None, :, :], s, _MASK_VALUE)
        m_new = jnp.maximum(m, s.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l * alpha + p.sum(axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bkgts,bksd->bkgtd", p, v
        )
        return m_new, l_new, acc_new

    init = (
        jnp.full((b, hkv, g, t), _MASK_VALUE, jnp.float32),
        jnp.zeros((b, hkv, g, t), jnp.float32),
        jnp.zeros((b, hkv, g, t, dh), jnp.float32),
    )
    _, l, acc = lax.fori_loop(0, nb_live, body, init)
    out = acc / l[..., None]  # [B, Hkv, G, T, Dh]
    return (
        out.transpose(0, 3, 1, 2, 4)
        .reshape(b, t, hq * dh)
        .astype(q.dtype)
    )


class HostKVSpill:
    """Bounded host-RAM spill tier for evicted prefix blocks: under
    pool pressure `PrefixBlockCache.evict` forgets warm blocks, and a
    later radix hit becomes a full re-prefill. This store keeps the
    evicted payload (already-quantized int8 + scale, or the fp bytes
    on an fp pool) keyed by the block's chained digest, so a *spill
    hit* revives the block into the pool with its EXACT stored bytes
    — token-identical to a resident hit — instead of recomputing it.

    Mutation domains (the disagg/ingest.py split, applied to spill):

      * the SERVING thread only enqueues device-array slices
        (`offer`, async dispatch — no blocking copy on the tick path)
        and reads/touches the store under `_lock` (`get`);
      * the DRAIN thread owns every blocking device->host copy and
        all insert/trim mutation of the store (under the same lock).

    The store is byte-bounded: inserts trim oldest-first (dict order
    is insertion order; `get` re-inserts on hit, so it is LRU). The
    offer queue is bounded too — under a burst of evictions spill is
    best-effort and sheds, never backpressuring admission. The race
    where a revival looks up a block that was evicted but not yet
    drained simply misses (a normal re-prefill), never corrupts."""

    def __init__(self, cap_bytes: int, obs: Any = None):
        self.cap = int(cap_bytes)
        self._q: queue.Queue = queue.Queue(maxsize=256)
        # key -> (own-block token bytes, host payload tuple, nbytes)
        self._store: dict[bytes, tuple] = {}
        self._bytes = 0
        self._lock = threading.Lock()
        self._obs = obs
        self._thread = threading.Thread(
            target=self._drain_loop, name="kv-spill-drain", daemon=True
        )
        self._thread.start()

    def offer(self, key: bytes, tok: bytes, arrays: tuple) -> None:
        """Serving thread: hand over async device slices of an
        evicted block. Never blocks — a full queue sheds the spill
        (the block is simply lost to the tier, as before this tier
        existed)."""
        try:
            self._q.put_nowait((key, tok, arrays))
        except queue.Full:
            pass

    # analysis: domain(drain) owns every blocking device->host copy and all store mutation (under _lock); serving only offers/gets
    def _drain_loop(self) -> None:
        while True:
            item = self._q.get()
            if item is None:
                self._q.task_done()
                return
            key, tok, arrays = item
            # The blocking device->host copies, off the tick path.
            host = tuple(np.asarray(a) for a in arrays)
            nbytes = sum(a.nbytes for a in host)
            with self._lock:
                old = self._store.pop(key, None)
                if old is not None:
                    self._bytes -= old[2]
                self._store[key] = (tok, host, nbytes)
                self._bytes += nbytes
                while self._bytes > self.cap and self._store:
                    k0 = next(iter(self._store))
                    _, _, nb0 = self._store.pop(k0)
                    self._bytes -= nb0
                stored_bytes = self._bytes
            if self._obs is not None:
                self._obs.prefix_spilled.inc()
                self._obs.spill_bytes.set(stored_bytes)
            self._q.task_done()

    def get(self, key: bytes, tok: bytes) -> tuple | None:
        """Serving thread: the spill lookup on a radix walk miss.
        Token-byte guarded like every radix hit (collision
        discipline); a hit is LRU-touched and its host payload
        returned for re-upload. The entry stays resident — the block
        may be evicted again later."""
        with self._lock:
            ent = self._store.get(key)
            if ent is None or ent[0] != tok:
                return None
            self._store[key] = self._store.pop(key)  # LRU touch
            return ent[1]

    def flush(self) -> None:
        """Block until every offered payload has drained into the
        store (test determinism; never on the tick path)."""
        self._q.join()

    @property
    def stored_blocks(self) -> int:
        with self._lock:
            return len(self._store)

    @property
    def stored_bytes(self) -> int:
        with self._lock:
            return self._bytes

    def close(self) -> None:
        self._q.put(None)
        self._thread.join(timeout=5)


class PrefixBlockCache:
    """Host-side EXACT radix cache over pool blocks (the vLLM/SGLang
    automatic-prefix-caching idea, block-granular).

    A K/V block's content is a pure function of the token ANCESTRY it
    covers — every token from position 0 through its last row — so the
    cache keys each block by the bytes of that ancestry: lookups walk
    a request's leading full prompt blocks and stop at the first miss
    (exactly the radix-tree path walk, flattened into one dict).
    Blocks referenced by active requests carry a refcount; at
    refcount 0 a block is RETAINED in LRU order and revived on a
    later hit, evicted (key dropped, block returned to the caller's
    free list) only under allocation pressure. Only full blocks whose
    rows are all prompt content are ever registered — any block a
    request will write generated tokens into stays private.

    Keys are CHAINED digests, not raw ancestry bytes: block j's key is
    blake2b(key_{j-1} || block_j's own bs tokens), so a walk over n
    full blocks hashes O(n * bs) bytes total instead of the
    O(n^2 * bs) a per-block full-ancestry key costs on long prompts.
    Because a digest could in principle collide, every hit is guarded
    by an EXACT comparison of the candidate block's own token bytes
    (`tok_of`): along a sequential walk the ancestor blocks were
    already byte-verified, so by induction a guarded hit matches the
    full ancestry — a false hit would need a genuine blake2b-128
    collision AND identical own-block tokens.

    `obs` — optional obs.serving.ServingMetrics whose prefix-cache
    counters (parks / revivals / evictions) this cache drives; hit and
    miss counts are the admitting server's job (it knows whether an
    admission sticks)."""

    def __init__(self, obs: Any = None, on_evict: Any = None):
        # `on_evict(key, tok, blk)` — optional spill hook, called on
        # the evicting (serving) thread BEFORE the block is forgotten,
        # while its pool payload is still addressable: the server's
        # spill path snapshots the block for HostKVSpill there.
        self._on_evict = on_evict
        self.by_key: dict[bytes, int] = {}
        self.ref: dict[int, int] = {}
        self.key_of: dict[int, bytes] = {}
        self.tok_of: dict[int, bytes] = {}  # own-block tokens (guard)
        self.lru: dict[int, None] = {}  # refcount-0 blocks, dict=LRU
        self._obs = obs
        # Advertisement seam (fleet routing): `generation` bumps on
        # every change to the RESIDENT KEY SET (register / evict /
        # displacement), never on refcount churn, so a router can
        # compare one int to skip unchanged snapshots. The lock covers
        # only key-set mutation and snapshotting — the owning serving
        # thread is the sole mutator, the router's snapshot reader the
        # sole other party — so hot-path walk()/release() stay
        # lock-free.
        self.generation = 0
        self._lock = threading.Lock()

    @staticmethod
    def _hash(prev_key: bytes, block_bytes: bytes) -> bytes:
        """One chain link: key_j = H(key_{j-1} || block_j bytes)."""
        return hashlib.blake2b(
            prev_key + block_bytes, digest_size=16
        ).digest()

    def walk(
        self, tokens: np.ndarray, n_full: int, bs: int
    ) -> tuple[list[int], list[bytes], list[bytes]]:
        """Leading-hit walk over the n_full full prompt blocks:
        returns (hit pool blocks for blocks 0..k-1 where k is the
        first miss, the chained key of EVERY full block, each block's
        own token bytes). Keys/bytes for the miss tail feed
        `register` after the owner prefills — computed here in the
        same single O(n * bs) pass. Bumps refcounts on hits (reviving
        LRU entries); a digest hit whose own-block tokens mismatch is
        a collision, treated as a miss."""
        flat = tokens[: n_full * bs].astype(np.int64)
        keys: list[bytes] = []
        toks: list[bytes] = []
        prev = b""
        for j in range(n_full):
            bb = flat[j * bs : (j + 1) * bs].tobytes()
            prev = self._hash(prev, bb)
            keys.append(prev)
            toks.append(bb)
        hits: list[int] = []
        for j in range(n_full):
            blk = self.by_key.get(keys[j])
            if blk is None or self.tok_of[blk] != toks[j]:
                break
            if self.ref[blk] == 0:
                self.lru.pop(blk, None)
                if self._obs is not None:
                    self._obs.prefix_revivals.inc()
            self.ref[blk] += 1
            hits.append(blk)
        return hits, keys, toks

    def register(
        self, key: bytes, block_bytes: bytes, blk: int
    ) -> int | None:
        """Publish a freshly prefilled full prompt block under its
        chained `key` (from the same walk that missed it), with
        refcount 1 held by the registrant. Returns a DISPLACED block
        to free, if this key was still cached from an earlier,
        partially-evicted chain: the walk stops at the first miss, so
        a deeper same-key survivor is unreachable and must be
        forgotten here — silently overwriting the maps would leave its
        key_of entry aliasing the new block and corrupt a later
        eviction. A displaced block is always refcount 0: any ACTIVE
        holder of a deeper block also holds (and refcounts) the whole
        chain above it, which would have made this key a hit.
        (Deepest-first parking in _finish makes shallow keys outlive
        deep ones, so this path should be unreachable — it stays as
        defense for the invariant, raising so the check survives
        `python -O`.)"""
        displaced = self.by_key.get(key)
        if displaced is not None:
            if self.ref[displaced] != 0:
                raise RuntimeError(
                    f"prefix-cache invariant violated: key "
                    f"{key.hex()} would displace block {displaced} "
                    f"which still has {self.ref[displaced]} live "
                    f"reference(s) — an active chain holder should "
                    f"have made this key a hit"
                )
            del self.lru[displaced]
            del self.ref[displaced]
            del self.key_of[displaced]
            del self.tok_of[displaced]
        with self._lock:
            self.by_key[key] = blk
            self.generation += 1
        self.ref[blk] = 1
        self.key_of[blk] = key
        self.tok_of[blk] = block_bytes
        return displaced

    def release(self, blk: int) -> None:
        """Drop one reference; at 0 the block parks in LRU (still
        cached) rather than returning to the free list."""
        self.ref[blk] -= 1
        if self.ref[blk] == 0:
            self.lru[blk] = None
            if self._obs is not None:
                self._obs.prefix_parks.inc()

    def evict(self, n: int) -> list[int]:
        """Forget up to n least-recently-parked blocks; returns them
        for the free list."""
        out = []
        while self.lru and len(out) < n:
            blk = next(iter(self.lru))
            if self._on_evict is not None:
                self._on_evict(self.key_of[blk], self.tok_of[blk], blk)
            del self.lru[blk]
            with self._lock:
                del self.by_key[self.key_of.pop(blk)]
                self.generation += 1
            del self.ref[blk]
            del self.tok_of[blk]
            out.append(blk)
        if out and self._obs is not None:
            self._obs.prefix_evictions.inc(len(out))
        return out

    def resident_digests(self) -> tuple[int, frozenset[bytes]]:
        """(generation, resident chained digests) — the routing
        advertisement. A SHALLOW snapshot: the frozenset copies only
        key references (16-byte digests already interned in by_key),
        never block payloads or token bytes, so a router can poll this
        from another thread at advertisement frequency without taxing
        admission. The generation lets callers drop unchanged
        snapshots with one int compare before building anything."""
        with self._lock:
            return self.generation, frozenset(self.by_key)

    @property
    def cached_blocks(self) -> int:
        return len(self.by_key)


# -- pipeline-parallel stages (PagedDecodeServer pp_stages=) ---------------


def _pp_stage_step(dec, bs, attention, first, last, tp_axis):
    """RAW per-stage multi-token paged step for pipeline-parallel
    serving: `_mt_body`'s computation restricted to the contiguous
    layer range [first, last). The first stage embeds token ids, every
    other stage takes the previous stage's [B, T, D] activations; the
    last stage ends in the final norm + head (vocab slices all_gather
    to replicated logits under tp, exactly like _replicate_logits).
    Every stage recomputes the same write destinations from the
    replicated tables/pos operands, so each one scatters its layers'
    K/V rows into ITS OWN pool slice — the pool never crosses a stage
    boundary, only the [B, T, D] activation does.

    step(params_stage, pk, pv, tables, pos, xin, n_keep, keep_from,
    adapter_ids) -> (x_or_logits, pk, pv); decode rounds ride it at
    T=1 / n_keep=1 / keep_from=0, chunked pool-native prefill at
    T=chunk — one compiled program per (stage, shape), exactly the
    jit-cache behaviour the monolithic _mt has."""
    window = dec.cfg.window
    L = dec.cfg.num_layers
    tp = tp_axis
    if attention == "pallas":
        from defer_tpu.models.gpt import _flash_decode_mode
        from defer_tpu.ops.pallas_attention import paged_flash_prefill

        interpret = _flash_decode_mode() != "tpu"

    def step(
        params, pk, pv, tables, pos, xin, n_keep, keep_from,
        adapter_ids,
    ):
        b, t = xin.shape[0], xin.shape[1]
        mb = tables.shape[1]
        rows = jnp.arange(b)
        steps_t = jnp.arange(t)
        pvec = pos[:, None] + steps_t[None, :]  # [B, T]
        # Write destinations: identical math to _mt_body — dropped
        # rows (pad tails, radix-hit positions, frozen slots' zeroed
        # tables) redirect to trash block 0.
        blk = tables[
            rows[:, None], jnp.minimum(pvec // bs, mb - 1)
        ]
        keep = (steps_t[None, :] < n_keep[:, None]) & (
            pvec >= keep_from[:, None]
        )
        dest = jnp.where(keep, blk, 0)
        rowi = pvec % bs
        x = (
            dec._embed_tokens(params, xin, pos, tp)
            if first == 0
            else xin
        )

        if attention == "gathered":

            def body(carry, layer):
                x = carry
                p, pk_l, pv_l = layer
                kc = _pool_gather(pk_l, tables, dec.compute_dtype)
                vc = _pool_gather(pv_l, tables, dec.compute_dtype)
                b_, mb_, hkv, _, dh = kc.shape
                kc = kc.transpose(0, 2, 1, 3, 4).reshape(
                    b_, hkv, mb_ * bs, dh
                )
                vc = vc.transpose(0, 2, 1, 3, 4).reshape(
                    b_, hkv, mb_ * bs, dh
                )
                out, kc, vc = dec._block(
                    p, x, kc, vc, pos, tp_axis=tp,
                    adapter_ids=adapter_ids,
                )
                new_k = kc[rows[:, None], :, pvec, :]
                new_v = vc[rows[:, None], :, pvec, :]
                pk_l = _pool_write_rows_mt(pk_l, dest, rowi, new_k)
                pv_l = _pool_write_rows_mt(pv_l, dest, rowi, new_v)
                return out, (pk_l, pv_l)

        elif attention == "blockwise":

            def body(carry, layer):
                x = carry
                p, pk_l, pv_l = layer
                q, k_new, v_new = dec._attn_qkv(
                    p, x, pos, adapter_ids=adapter_ids
                )
                pk_l = _pool_write_rows_mt(
                    pk_l, dest, rowi, k_new.transpose(0, 2, 1, 3)
                )
                pv_l = _pool_write_rows_mt(
                    pv_l, dest, rowi, v_new.transpose(0, 2, 1, 3)
                )
                nb_live = jnp.minimum(
                    (jnp.max(pos) + t - 1) // bs + 1, mb
                )
                attn = _blockwise_attend_mt(
                    q, pk_l, pv_l, tables, pos, bs, nb_live,
                    window,
                )
                out = dec._attn_out(
                    p, x, attn, tp, adapter_ids=adapter_ids
                )
                return out, (pk_l, pv_l)

        else:  # pallas

            def body(carry, layer):
                x = carry
                p, pk_l, pv_l = layer
                q, k_new, v_new = dec._attn_qkv(
                    p, x, pos, adapter_ids=adapter_ids
                )
                pk_l = _pool_write_rows_mt(
                    pk_l, dest, rowi, k_new.transpose(0, 2, 1, 3)
                )
                pv_l = _pool_write_rows_mt(
                    pv_l, dest, rowi, v_new.transpose(0, 2, 1, 3)
                )
                b_, hq, t_, dh = q.shape
                attn = paged_flash_prefill(
                    q,
                    _pool_arr(pk_l),
                    _pool_arr(pv_l),
                    tables,
                    pos,
                    window=window,
                    interpret=interpret,
                )
                attn = (
                    attn.transpose(0, 2, 1, 3)
                    .reshape(b_, t_, hq * dh)
                    .astype(x.dtype)
                )
                out = dec._attn_out(
                    p, x, attn, tp, adapter_ids=adapter_ids
                )
                return out, (pk_l, pv_l)

        x, (pk, pv) = lax.scan(body, x, (params["stack"], pk, pv))
        if last == L:
            logits = dec._final_logits(params, x)
            if tp is not None:
                logits = lax.all_gather(
                    logits, tp, axis=-1, tiled=True
                )[..., : dec.cfg.vocab_size]
            return logits, pk, pv
        return x, pk, pv

    return step


def _pp_stage_specs(full_specs: dict, first: int, last: int, cfg) -> dict:
    """The shard_map in_specs subtree matching
    GptDecoder.stage_params(params, first, last): stack leaf specs are
    layer-leading (slicing the layer axis never changes them), the
    boundary stages add the embedding / final-norm / tied-head specs
    their extra params carry."""
    out = {"stack": full_specs["stack"]}
    if first == 0:
        out["token_embedding"] = full_specs["token_embedding"]
        if "pos_embedding" in full_specs:
            out["pos_embedding"] = full_specs["pos_embedding"]
    if last == cfg.num_layers:
        out["final_ln_scale"] = full_specs["final_ln_scale"]
        if "final_ln_bias" in full_specs:
            out["final_ln_bias"] = full_specs["final_ln_bias"]
        if "token_embedding" not in out:
            out["token_embedding"] = full_specs["token_embedding"]
    return out


class _PPLocalStage:
    """One pipeline stage resident in this process: the stage's param
    slice (GptDecoder.stage_params) and its [last-first, num_blocks,
    kv_heads, block_size, Dh] slice of the paged KV pool, placed
    together on one device (the in-process device-to-device tier) or
    one tensor-parallel submesh (pp x tp: the submesh is one slice of
    the joint {stage, model} mesh, so the stage's psums stay on its
    own ICI ring). `pp_dispatch` is the stage-boundary interface both
    placements share with _PPTransportStage: feed the six replicated
    operands, get the boundary activation (or final logits) back — an
    ASYNC device future here, which is what lets the server's
    round-major loop keep M microbatches in flight."""

    def __init__(
        self, dec, params, first, last, *, num_blocks, block_size,
        attention, device=None, submesh=None, model_axis="model",
    ):
        from defer_tpu.utils.memo import cached_step

        self.first = first
        self.last = last
        self.device = device
        self.submesh = submesh
        self.model_axis = model_axis if submesh is not None else None
        cfg = dec.cfg
        dh = cfg.dh
        pool_shape = (
            last - first, num_blocks, cfg.kv_heads, block_size, dh,
        )
        if submesh is not None:
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as PSpec

            from defer_tpu.models.gpt import SpmdGptDecoder

            sdec = cached_step(
                dec,
                ("pp_spmd_view", submesh, model_axis),
                lambda: SpmdGptDecoder(
                    cfg,
                    compute_dtype=dec.compute_dtype,
                    mesh=submesh,
                    tp_axis=model_axis,
                ),
            )
            # Full params placed on THIS submesh (vocab pad + int8
            # bookkeeping), then sliced: the stack slices are fresh
            # per-stage buffers, the boundary tables alias the
            # placement.
            self.params = dec.stage_params(
                sdec.shard_params(params), first, last
            )
            self._param_specs = _pp_stage_specs(
                sdec._specs(), first, last, cfg
            )
            self._pool_spec = PSpec(None, None, model_axis, None, None)
            pool_sh = NamedSharding(submesh, self._pool_spec)
            self.pk = jnp.zeros(
                pool_shape, dec.kv_dtype, device=pool_sh
            )
            self.pv = jnp.zeros(
                pool_shape, dec.kv_dtype, device=pool_sh
            )
            self._sink = NamedSharding(submesh, PSpec())
        else:
            sp = dec.stage_params(params, first, last)
            if device is not None:
                sp = jax.device_put(sp, device)
            self.params = sp
            self._param_specs = None
            self._pool_spec = None
            self.pk = jnp.zeros(pool_shape, dec.kv_dtype)
            self.pv = jnp.zeros(pool_shape, dec.kv_dtype)
            if device is not None:
                self.pk = jax.device_put(self.pk, device)
                self.pv = jax.device_put(self.pv, device)
            self._sink = device
        self.pool_bytes = self.pk.nbytes + self.pv.nbytes
        self._fn = cached_step(
            dec,
            (
                "paged_pp_stage", block_size, attention, first, last,
                device, submesh, self.model_axis,
            ),
            lambda: self._build_fn(dec, block_size, attention),
        )

    def _build_fn(self, dec, bs, attention):
        body = _pp_stage_step(
            dec, bs, attention, self.first, self.last, self.model_axis
        )
        if self.submesh is None:
            return jax.jit(body, donate_argnums=(1, 2))
        from jax.sharding import PartitionSpec as PSpec

        from defer_tpu.utils.compat import shard_map

        pool, r = self._pool_spec, PSpec()
        sm = shard_map(
            body,
            self.submesh,
            in_specs=(self._param_specs, pool, pool) + (r,) * 6,
            out_specs=(r, pool, pool),
            # analysis: ignore[shard-spec] same waiver as _jit_tick: the body ends in slot scatters (and, on the last stage, a tiled all_gather) whose replication the checker cannot infer; psum placement is pinned by the defer_tp_psum_total mirror
            check_rep=False,
        )
        return jax.jit(sm, donate_argnums=(1, 2))

    def _put(self, a):
        """Commit an operand to this stage's placement — the
        in-process activation handoff (device-to-device copy; async,
        so chained stage dispatches overlap)."""
        if self._sink is None:
            return jnp.asarray(a)
        return jax.device_put(a, self._sink)

    def pp_dispatch(self, tables, pos, xin, n_keep, keep_from,
                    adapter_ids):
        out, self.pk, self.pv = self._fn(
            self.params,
            self.pk,
            self.pv,
            self._put(tables),
            self._put(pos),
            self._put(xin),
            self._put(n_keep),
            self._put(keep_from),
            self._put(adapter_ids),
        )
        return out

    def close(self):  # interface symmetry with _PPTransportStage
        pass


class _PPTransportStage:
    """A pipeline stage served by ANOTHER process over the framed
    activation transport (runtime/transport.py): `pp_dispatch` ships
    the six operands through an ArraySender to the stage worker
    (runtime/remote_stage.py::serve_pp_stage, which wraps a
    _PPLocalStage) and blocks on its one result array from the paired
    ArrayReceiver. The round trip is SYNCHRONOUS per dispatch — this
    placement is the cross-host parity/placement tier (same
    serve_stage session shape remote_stage.py uses), not an overlap
    win; in-process stages keep pipelining around it.

    `spec` is (host, port, result_receiver): the worker's listen
    address plus the caller-owned ArrayReceiver its results arrive
    on."""

    def __init__(self, spec, *, first, last, pool_bytes=0):
        from defer_tpu.runtime.transport import ArraySender

        host, port, receiver = spec
        self.first = first
        self.last = last
        self.pool_bytes = pool_bytes
        self._send = ArraySender(host, port)
        self._recv = receiver
        self._it = iter(receiver)

    def pp_dispatch(self, tables, pos, xin, n_keep, keep_from,
                    adapter_ids):
        for a in (tables, pos, xin, n_keep, keep_from, adapter_ids):
            # analysis: ignore[host-sync-in-hot-loop] the stage boundary IS a host transport here — framing the operand synchronizes it by design (documented parity tier)
            self._send.send(np.asarray(a))
        return next(self._it)

    def close(self):
        """Send the transport STOP so the worker's serve loop exits."""
        self._send.close()


class PagedDecodeServer:
    """Continuous batching over a paged KV pool; greedy by default,
    per-request sampling via `submit(..., sampling=)`.

    Protocol-compatible with runtime/decode_server.DecodeServer
    (submit -> run -> {rid: ids}), with the pool replacing per-slot
    max_len lanes. `num_blocks` INCLUDES the reserved trash block 0.

    `prefix_cache=True` turns on PER-REQUEST shared-prefix paging
    (PrefixBlockCache): any subset of requests sharing any leading
    prompt content automatically shares those full blocks — admission
    gathers the hit blocks into a flat lane and prefills only the
    suffix, finished requests park their shared blocks at refcount 0
    for later revival, and eviction happens only under pool pressure.
    This generalizes the constructor-level `prefix_ids` (one global
    system prompt, still supported, mutually exclusive).

    A stack with recurrent layers (`cfg.layer_kinds` entries
    "linear") is served on the default path with a second kind of
    cache beside the block pool: `pool_state`, per slot and recurrent
    layer a state of fixed size, indexed by slot and not by block
    table. The K/V pool's layer axis then counts only the layers that
    have keys and values; the step carries both through its layer loop
    and updates both in place, and admission copies the prefill's final
    states into the slot's row (nothing clears it: the next admission
    overwrites it).

    A live slot (`slots[i]`) records its request on the host: `prompt`
    as it arrived and `out`, the generated tokens as Python ints from
    the tick's one batched transfer. With no eos, callback or stop
    sequence a tick makes no transfer, and `out` holds `(device array,
    index)` in a token's place, which `_finish` fetches. `done[rid]`
    is the two joined on the host: a `[1, T0 + n]` array in the
    prompt's dtype.
    """

    def __init__(
        self,
        dec: Any,
        params: dict,
        *,
        num_blocks: int,
        block_size: int = 16,
        max_batch: int = 4,
        eos_id: int | None = None,
        on_token: Any = None,
        prefix_ids: jax.Array | None = None,
        prefix_cache: bool = False,
        attention: str = "gathered",
        kv_dtype: str = "fp",
        spill_bytes: int = 0,
        decode_window: int = 1,
        spec_draft: Any = None,
        spec_params: dict | None = None,
        spec_k: int = 0,
        prefill_chunk: int | None = None,
        prefill_budget: int | None = None,
        prefill_lookahead: int = 2,
        mesh: Any = None,
        model_axis: str = "model",
        device: Any = None,
        constraints: dict | None = None,
        pp_stages: int = 1,
        pp_inflight: int | None = None,
        pp_cuts: Any = None,
        pp_devices: Any = None,
        pp_remote: dict | None = None,
        pp_balance: str = "equal",
        pp_stage_axis: str = "stage",
    ):
        """`on_token(request_id, token_id, done)` — optional streaming
        callback, same contract as the flat server's.

        `constraints` — named constraint DFAs ({name:
        constrain.TokenDFA}, compiled against this decoder's
        vocabulary, defer_tpu/constrain/) a request selects with
        SamplingParams(constraint=name): that slot's logits are masked
        to grammar-admissible tokens (eos admitted only in accepting
        states) before argmax/categorical, and the DFA state advances
        on device inside the same tick/window/spec programs —
        constrained greedy output is token-identical across
        decode_window, spec_k, attention modes, and meshes. Requires
        `eos_id` (a satisfied constraint must be able to stop). With
        the default None every traced program is byte-identical to a
        server built before this feature existed.

        `pp_stages` — PIPELINE-PARALLEL serving (ARCHITECTURE.md
        "Pipeline-parallel serving"): partition the decoder's layer
        stack into S contiguous stages, each owning ONLY its layers'
        slice of the paged KV block pool (per-stage HBM ~1/S; one
        shared block table / free list indexes every slice), and run
        the decode tick as a pipelined window — `pp_inflight` (M,
        default min(S, max_batch)) microbatch slot groups flow through
        the stage chain round-major with overlapped async dispatch, so
        the schedule's bubble fraction is (S-1)/(K*M + S-1) and is
        MEASURED per window (defer_pp_bubble_fraction), never assumed.
        Greedy output is token-identical to pp_stages=1 across
        attention modes x prefix_cache x decode_window x tp. Stage
        boundaries are activation handoffs behind one interface with
        two placements: in-process device-to-device (stage i on
        `pp_devices[i]`, default jax.devices()), or the framed
        transport for stages served by another process (`pp_remote`,
        runtime/remote_stage.py::serve_pp_stage). With `mesh=` the
        mesh must carry `pp_stage_axis` OUTERMOST around `model_axis`
        (parallel/multihost.py::make_multihost_mesh puts it there), and
        each stage runs tensor-parallel on its own submesh. `pp_cuts`
        pins explicit stage start layers; `pp_balance="probe"`
        auto-balances cuts by per-layer probe cost
        (parallel/pipeline.py::balance_stage_cuts). Admission prefill
        always runs pool-native through the stage chain (chunked by
        `prefill_chunk` when set). Deferred compositions raise with
        the fix spelled out: spec_k > 0, disagg ingest
        (submit_prefilled/deliver_kv), constraints, multi-LoRA,
        constructor prefix_ids, spill_bytes, kv_dtype="int8".

        `spec_k` — speculative decoding (ARCHITECTURE.md "Speculative
        serving"): a DRAFT decoder (`spec_draft`/`spec_params`, same
        tokenizer/vocab, typically much smaller) proposes k greedy
        tokens per GREEDY slot per round, and the target verifies all
        k+1 positions in ONE block-table-indexed multi-token forward —
        accepted rows land in the paged pool as one multi-row scatter,
        rows a slot is not speculating (sampled slots, idle slots)
        redirect to trash block 0, and rejected rows go stale behind
        the position mask until the next round rewrites them. Greedy
        output is bit-identical to spec_k=0; sampled slots ride the
        verify forward's first row and advance one token per round
        from the SAME key stream as spec_k=0. The default 0 keeps the
        classic tick loop untouched. Composes with prefix_cache,
        mixed sampling, decode_window > 1 (the window scan's sub-steps
        become whole draft+verify rounds — W rounds per host
        dispatch), submit_prefilled admissions (the draft lane
        re-prefills locally from the prompt ids), and tensor-parallel
        meshes (the draft is replicated; only the verify forward is
        sharded). Still raises with constructor prefix_ids (the draft
        lane has no shared-prefix plumbing) and multi-LoRA (the draft
        is one model — per-adapter proposals would need per-adapter
        drafts).

        `prefill_chunk` — chunked POOL-NATIVE prefill: admission runs
        the prompt through the multi-token paged step in chunks of
        this many tokens, writing K/V straight into the allocated
        blocks through the block table instead of materializing a
        contiguous max_len lane and paging it in afterwards. With
        attention="blockwise"/"pallas" the chunk's reads scale with
        the prompt's LIVE blocks, never with pool size (the
        `defer_kv_rows_*` counters price it). None (default) keeps
        the contiguous prefill + insert path.

        `prefill_budget` — STALL-FREE continuous batching
        (ARCHITECTURE.md "Continuous batching & prefill scheduling"):
        instead of running each admitted prompt's prefill to
        completion while every live slot stalls, a new request takes
        a SEAT whose `pos` advances chunk by chunk, and each decode
        dispatch carries the live decode rows PLUS up to this many
        prompt tokens from the seated prefills, fused into one
        multi-token forward (runtime/schedule.py plans the tick;
        _tick_mixed dispatches it). Decode rows always advance
        exactly one token per mixed tick — sampling/eos/stop apply
        only to them — and a seat flips to decoding the tick its
        last chunk lands (that chunk's final logits row seeds the
        slot's first token, exactly the stall path's admission draw).
        Greedy output is token-identical to `prefill_budget=None`
        across attention modes x prefix_cache x decode_window x tp;
        radix admits schedule only the non-shared suffix and publish
        their fresh blocks at flip time; `submit_prefilled` seats
        bypass the budget (their compute is already spent). At most
        `prefill_lookahead` seats prefill concurrently (bounded
        lookahead keeps admission near-FIFO). None (default) keeps
        the serialized stall-prefill admission path bit-identically.
        Deferred compositions raise with the fix spelled out:
        spec_k > 0 and pp_stages > 1.

        `decode_window` — decode sub-steps fused into ONE jitted host
        dispatch (K), the paged twin of DecodeServer's parameter (its
        docstring has the full semantics). A `lax.scan` over the raw
        paged step advances every live slot up to K tokens on device;
        rows frozen mid-window (eos / budget) have their position and
        block-table row zeroed per sub-step, so their dead writes land
        in trash block 0 row 0 — exactly where an idle K=1 slot
        writes. One batched [B, K] transfer per window feeds
        streaming/stop consumers; admissions and block
        allocation/release stay at window boundaries. The default 1 is
        the classic tick-per-token loop, bit-identical to before.

        `kv_dtype` — the pool's storage dtype. "fp" (default) keeps
        the compute-dtype pool, bit-identical to before the knob
        existed. "int8" stores K/V rows as symmetric int8 with ONE
        fp32 scale per (layer, block, kv_head) — half the HBM bytes
        of a bf16 pool — quantizing inside the same jitted scatters
        that land KV today and dequantizing on read in all three
        `attention` modes (the pallas kernels take the int8 pool plus
        its scale refs, so read traffic halves too). Greedy output is
        NOT bit-identical to fp — the accuracy contract is the
        bounded logit-error parity pinned in tests/test_kv_quant.py.

        `spill_bytes` — host-RAM spill tier for evicted prefix blocks
        (requires prefix_cache=True): when the radix cache evicts a
        parked block under pool pressure, its payload (quantized rows
        + scales for int8; compute-dtype rows for fp) is snapshotted
        asynchronously and drained to a bounded host store keyed by
        the block's chain digest, off the tick hot path (same
        drain-thread shape as disagg/ingest.py). A later walk miss
        that hits the spill store revives the block into the pool
        token-identically to a resident radix hit instead of
        re-prefilling. 0 (default) disables the tier.

        `attention` — which decode attention path the tick compiles
        (module docstring): "gathered" (contiguous-view reference,
        bit-exact, the default), "blockwise" (pure-XLA block-native,
        reads stop at the deepest live block, tie-tolerant), or
        "pallas" (block-table-indexed kernel, per-slot live-block
        DMA; interpret-mode fallback off-TPU, tie-tolerant).

        `mesh` / `model_axis` — TENSOR-PARALLEL serving
        (ARCHITECTURE.md "Sharded serving"): shard the decoder weights
        (Megatron column/row split + vocab-sharded embedding) and the
        paged KV pool's head axis over the mesh's `model_axis`, and run
        every jitted tick body under shard_map so each device reads
        only its local KV heads. Host-side mechanics (admission, block
        tables, sampling, radix cache, obs) stay single-writer and
        unsharded; sampling sees the replicated post-psum logits, so
        per-window transfer and dispatch counts are unchanged.
        mesh=None (default) is bit-identical to the single-device
        server; a model_axis of size 1 is token-identical to it.

        `device` — pin this server's params/pool (and hence every tick)
        to one specific jax.Device instead of the process default —
        how fleet replicas spread over a multi-chip host without
        tensor parallelism. Mutually exclusive with `mesh`.

        `prefix_ids` [1, P] — SHARED-prefix paging: the system
        prompt's K/V blocks are allocated ONCE and every request's
        block table points at them (the flat server copies the prefix
        lane per admission; here the pool holds one copy, period).
        Requires P to be a block_size multiple so suffix writes can
        never touch a shared block. Admissions prefill only the
        suffix."""
        if getattr(dec, "rolling_cache", False):
            raise ValueError("paged serving does not support rolling caches")
        # Layer kinds, experts and a parallel block are served by the
        # default path alone (the gathered step, the flat prefill at
        # admission, `_tick_plain`): every other option names what it
        # cannot serve instead of running a homogeneous dense stack.
        from defer_tpu.parallel.transformer_stack import refuse_mechanisms

        for option, asked in (
            (f"attention={attention!r}", attention != "gathered"),
            (f"kv_dtype={kv_dtype!r}", kv_dtype != "fp"),
            ("decode_window > 1", decode_window > 1),
            ("spec_k > 0", bool(spec_k)),
            ("prefill_chunk", prefill_chunk is not None),
            ("prefill_budget", prefill_budget is not None),
            ("pp_stages > 1", pp_stages > 1),
            ("mesh=", mesh is not None),
            ("prefix_cache=True", prefix_cache),
            ("prefix_ids=", prefix_ids is not None),
        ):
            if asked:
                refuse_mechanisms(dec.cfg, f"PagedDecodeServer({option})")
        # Multi-LoRA: adapter banks (parallel/lora.py::stack_adapters)
        # make the slot -> adapter assignment per-slot state, same as
        # the flat server; id 0 = base model.
        from defer_tpu.parallel.lora import adapter_bank_info

        n_adapters = adapter_bank_info(params)
        self.multi_lora = n_adapters is not None
        if self.multi_lora:
            self.num_adapters = n_adapters
            refuse_mechanisms(dec.cfg, "PagedDecodeServer(multi-LoRA banks)")
        if block_size < 1 or num_blocks < 2:
            raise ValueError(
                f"need block_size >= 1 and num_blocks >= 2 (one trash "
                f"block + one usable), got {block_size}/{num_blocks}"
            )
        if attention not in ("gathered", "blockwise", "pallas"):
            raise ValueError(
                f"attention must be 'gathered', 'blockwise' or "
                f"'pallas', got {attention!r}"
            )
        if kv_dtype not in ("fp", "int8"):
            raise ValueError(
                f"kv_dtype must be 'fp' or 'int8', got {kv_dtype!r}"
            )
        if spill_bytes < 0:
            raise ValueError(
                f"spill_bytes must be >= 0, got {spill_bytes}"
            )
        if spill_bytes and not prefix_cache:
            raise ValueError(
                "spill_bytes > 0 needs prefix_cache=True — the spill "
                "tier stores evicted PREFIX blocks keyed by the radix "
                "cache's chain digests"
            )
        if decode_window < 1:
            raise ValueError(
                f"decode_window must be >= 1, got {decode_window}"
            )
        if spec_k < 0:
            raise ValueError(f"spec_k must be >= 0, got {spec_k}")
        if (spec_draft is not None or spec_params is not None) and not spec_k:
            raise ValueError(
                "spec_draft/spec_params provided but spec_k == 0 — "
                "pass spec_k >= 1 to turn speculation on"
            )
        if spec_k:
            if spec_draft is None or spec_params is None:
                raise ValueError(
                    "spec_k > 0 needs both spec_draft and spec_params "
                    "(the proposal model and its weights)"
                )
            if prefix_ids is not None:
                raise ValueError(
                    "spec_k > 0 does not compose with constructor "
                    "prefix_ids (the draft lane has no shared-prefix "
                    "plumbing); use prefix_cache=True"
                )
            if self.multi_lora:
                raise ValueError(
                    "spec_k > 0 with multi-LoRA is unsupported: one "
                    "draft model cannot propose for per-slot adapters"
                )
            if spec_draft.cfg.max_len < dec.cfg.max_len:
                raise ValueError(
                    f"draft max_len {spec_draft.cfg.max_len} < target "
                    f"max_len {dec.cfg.max_len}: the draft lane must "
                    "cover every position the target can reach"
                )
        if prefill_chunk is not None and prefill_chunk < 1:
            raise ValueError(
                f"prefill_chunk must be >= 1, got {prefill_chunk}"
            )
        if prefill_lookahead < 1:
            raise ValueError(
                f"prefill_lookahead must be >= 1, got {prefill_lookahead}"
            )
        if prefill_budget is not None:
            if prefill_budget < 1:
                raise ValueError(
                    f"prefill_budget must be >= 1 prompt tokens per "
                    f"tick, got {prefill_budget}"
                )
            if spec_k:
                raise ValueError(
                    "prefill_budget does not compose with spec_k > 0 "
                    "yet: the verify forward already owns the "
                    "multi-token rows a mixed tick would budget, and "
                    "fusing draft catch-up with mid-prefill seats "
                    "needs a draft-side seat lifecycle. Fix: serve "
                    "speculation on a prefill_budget=None server, or "
                    "set spec_k=0 here."
                )
            if pp_stages > 1:
                raise ValueError(
                    "prefill_budget does not compose with pp_stages "
                    "> 1 yet: the pipelined window schedules whole "
                    "microbatch groups and a mixed tick would need "
                    "per-stage budget accounting across the in-flight "
                    "groups. Fix: run mixed-mode admission on a "
                    "pp_stages=1 server (tensor-parallel via mesh= "
                    "composes), or set prefill_budget=None here."
                )
        if mesh is not None and device is not None:
            raise ValueError(
                "mesh= and device= are mutually exclusive: a mesh "
                "already pins the server to its devices"
            )
        if pp_stages < 1:
            raise ValueError(f"pp_stages must be >= 1, got {pp_stages}")
        self.pp = pp_stages
        if pp_stages == 1 and (
            pp_inflight is not None
            or pp_cuts is not None
            or pp_devices is not None
            or pp_remote is not None
        ):
            raise ValueError(
                "pp_inflight/pp_cuts/pp_devices/pp_remote only apply "
                "with pp_stages > 1"
            )
        _pp_M = 1
        if pp_stages > 1:
            if pp_stages > dec.cfg.num_layers:
                raise ValueError(
                    f"pp_stages={pp_stages} exceeds num_layers="
                    f"{dec.cfg.num_layers}: every stage needs at least "
                    "one layer. Fix: lower pp_stages (or serve a "
                    "deeper model)."
                )
            if spec_k:
                raise ValueError(
                    "spec_k > 0 does not compose with pp_stages > 1 "
                    "yet: the draft lane proposes against a monolithic "
                    "pool and the verify forward would have to thread "
                    "k+1 candidate rows through every stage boundary. "
                    "Fix: serve speculation on a pp_stages=1 server, "
                    "or set spec_k=0 here."
                )
            if constraints is not None:
                raise ValueError(
                    "constraints= does not compose with pp_stages > 1 "
                    "yet: the DFA advance is fused into the monolithic "
                    "window program. Fix: serve constrained requests "
                    "on a pp_stages=1 server."
                )
            if self.multi_lora:
                raise ValueError(
                    "multi-LoRA does not compose with pp_stages > 1: "
                    "adapter banks are not stage-sliced. Fix: merge "
                    "the adapter (parallel/lora.py) or serve adapters "
                    "on pp_stages=1."
                )
            if prefix_ids is not None:
                raise ValueError(
                    "constructor prefix_ids does not compose with "
                    "pp_stages > 1: the one-shot prefix insert runs "
                    "through the monolithic flat path. Fix: use "
                    "prefix_cache=True (shares prefixes per request, "
                    "pool-native) instead."
                )
            if spill_bytes:
                raise ValueError(
                    "spill_bytes > 0 does not compose with "
                    "pp_stages > 1 yet: spill snapshots slice a "
                    "monolithic pool. Fix: set spill_bytes=0 (evicted "
                    "prefix blocks are then re-prefilled)."
                )
            if kv_dtype != "fp":
                raise ValueError(
                    f"kv_dtype={kv_dtype!r} does not compose with "
                    "pp_stages > 1 yet: the per-stage pool slices are "
                    "compute-dtype only. Fix: use kv_dtype='fp' with "
                    "pp, or int8 on a pp_stages=1 server."
                )
            if device is not None:
                raise ValueError(
                    "device= pins ONE device but pp_stages > 1 places "
                    "each stage on its own. Fix: pass the stage "
                    "placement as pp_devices=[dev0, dev1, ...] "
                    "instead."
                )
            if pp_balance not in ("equal", "probe"):
                raise ValueError(
                    f"pp_balance must be 'equal' or 'probe', got "
                    f"{pp_balance!r}"
                )
            _pp_M = (
                pp_inflight
                if pp_inflight is not None
                else min(pp_stages, max_batch)
            )
            if _pp_M < 1:
                raise ValueError(
                    f"pp_inflight must be >= 1, got {_pp_M}"
                )
            if max_batch % _pp_M:
                raise ValueError(
                    f"max_batch={max_batch} does not divide into "
                    f"pp_inflight={_pp_M} equal microbatch slot "
                    "groups. Fix: pick max_batch a multiple of "
                    "pp_inflight (or pass pp_inflight= a divisor of "
                    "max_batch)."
                )
        self.mesh = mesh
        self.model_axis = model_axis
        self.device = device
        self.tp = 1
        self._sdec = None
        if mesh is not None:
            if getattr(dec, "mesh", None) is not None:
                raise ValueError(
                    "pass the plain single-device decoder together "
                    "with mesh= — the server builds its own sharded "
                    "step (an SpmdGptDecoder here would double-wrap "
                    "shard_map)"
                )
            if model_axis not in mesh.axis_names:
                raise ValueError(
                    f"model_axis {model_axis!r} is not an axis of the "
                    f"mesh (axes: {mesh.axis_names}); build the mesh "
                    f"with parallel.mesh.make_mesh({{{model_axis!r}: "
                    "N})"
                )
            tp = int(mesh.shape[model_axis])
            kvh = dec.cfg.kv_heads
            if kvh < tp:
                raise ValueError(
                    f"GQA num_kv_heads={kvh} is smaller than the "
                    f"{model_axis!r} axis size {tp}: the paged pool "
                    "shards whole KV heads, so some devices would own "
                    "none. Fix: serve on a mesh whose model axis has "
                    f"at most {kvh} devices (put the rest on a data "
                    "axis), or replicate KV heads in the checkpoint."
                )
            if kvh % tp:
                fit = max(
                    d for d in range(1, kvh + 1)
                    if kvh % d == 0 and d <= tp
                )
                raise ValueError(
                    f"num_kv_heads={kvh} does not divide by the "
                    f"{model_axis!r} axis size {tp}: each device must "
                    "own an equal whole-head slice of the paged pool. "
                    f"Fix: use a model axis size that divides {kvh} "
                    f"(largest that fits: {fit}), or pad kv_heads to "
                    f"a multiple of {tp} in the checkpoint."
                )
            if self.multi_lora:
                raise ValueError(
                    "mesh= with multi-LoRA is unsupported: the adapter "
                    "banks are not sharded — serve adapters on "
                    "mesh=None"
                )
            self.tp = tp
            if pp_stages > 1:
                # pp x tp: the joint mesh carries the stage axis
                # OUTERMOST (DCN-crossing, one activation per
                # boundary) around the model axis (ICI-heavy psums
                # stay inside a stage's submesh) — the
                # make_multihost_mesh/dcn_aware_axes layout rule.
                from defer_tpu.parallel.multihost import stage_submeshes

                if pp_stage_axis not in mesh.axis_names:
                    raise ValueError(
                        f"pp_stages={pp_stages} with mesh= needs a "
                        f"{pp_stage_axis!r} mesh axis for the stage "
                        f"dimension (axes: {mesh.axis_names}). Fix: "
                        "build the mesh with parallel.multihost."
                        f"make_multihost_mesh({{{pp_stage_axis!r}: "
                        f"{pp_stages}, {model_axis!r}: tp}})."
                    )
                if int(mesh.shape[pp_stage_axis]) != pp_stages:
                    raise ValueError(
                        f"mesh {pp_stage_axis!r} axis has size "
                        f"{int(mesh.shape[pp_stage_axis])} but "
                        f"pp_stages={pp_stages}; the two must match"
                    )
                self._pp_submeshes = stage_submeshes(
                    mesh, pp_stage_axis
                )
        if mesh is not None and pp_stages == 1:
            # One sharded view of the decoder per (dec, mesh, axis):
            # SpmdGptDecoder supplies the param specs, vocab padding,
            # sharded flat prefill step, and the remaining divisibility
            # validation (heads/dim/ffn % tp).
            from defer_tpu.models.gpt import SpmdGptDecoder
            from defer_tpu.utils.memo import cached_step

            self._sdec = cached_step(
                dec,
                ("spmd_view", mesh, model_axis),
                lambda: SpmdGptDecoder(
                    dec.cfg,
                    compute_dtype=dec.compute_dtype,
                    mesh=mesh,
                    tp_axis=model_axis,
                ),
            )
        # Memo-key component for every compiled program: a mesh-built
        # step and a single-device step must never share a cache slot
        # on the same decoder instance.
        self._mesh_key = (mesh, model_axis) if mesh is not None else None
        self.mesh_label = f"{model_axis}={self.tp}" if mesh is not None else None
        # Collectives one sharded forward issues: per layer an attn
        # psum + an ffn psum, plus the embedding psum and the final
        # logits all_gather. Host-side mirror for defer_tp_psum_total.
        self._psums_per_fwd = (
            2 * dec.cfg.num_layers + 2 if mesh is not None else 0
        )
        self.tp_psums = 0
        self.decode_window = decode_window
        self.attention = attention
        self.dec = dec
        self.params = params
        self.B = max_batch
        self.bs = block_size
        self.eos_id = eos_id
        self.on_token = on_token
        cfg = dec.cfg
        # Max logical blocks any sequence can span.
        self.MB = -(-cfg.max_len // block_size)
        # The gathered step's cost is linear in the span it gathers, so
        # the plain tick hands it the table up to the rung above the
        # deepest live slot; the other attention paths read through
        # the whole table and bound their own reads.
        self._rungs = (
            span_rungs(self.MB) if attention == "gathered" else (self.MB,)
        )
        dh = cfg.dh
        self.kv_dtype = kv_dtype
        self.num_blocks = num_blocks
        # The pool's layer axis counts the layers that have keys and
        # values: a recurrent layer (cfg.layer_kinds "linear") owns no
        # rows here but a state of fixed size in `pool_state` below.
        kv_layers = cfg.layers_of("attn")
        pool_shape = (
            kv_layers, num_blocks, cfg.kv_heads, block_size, dh,
        )
        # int8 pools are a {"q", "s"} pytree: int8 rows plus one fp32
        # scale per (layer, block, kv_head). Scales start at 1.0 so a
        # never-written block dequantizes to the zeros an fp pool
        # holds. The fp pool stays a PLAIN array — its jitted
        # programs trace byte-identical to pre-int8 builds.
        scale_shape = (kv_layers, num_blocks, cfg.kv_heads)
        if self.pp > 1:
            # Pipeline-parallel: the pool never exists monolithically
            # — each _PPLocalStage allocates its own layer slice on
            # its own placement (built below, after the bookkeeping
            # state the cut probe needs). The None handles make any
            # path that would touch a monolithic pool fail loudly.
            self._pool_spec = None
            self._head_spec = None
            self.pool_k = None
            self.pool_v = None
        elif mesh is not None:
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as PSpec

            # Pool sharded on the KV-head axis: each device holds
            # [L, num_blocks, kv_heads/tp, block_size, Dh] — every
            # block present on every shard, but only its local heads.
            # Allocated DIRECTLY sharded (no transient replicated
            # pool), params placed by the Megatron specs (vocab table
            # padded to a tp multiple by shard_params). The int8
            # scale tensor splits on the SAME head axis (index 2 in
            # both layouts), so a shard's rows and scales travel
            # together.
            self._pool_spec = PSpec(None, None, model_axis, None, None)
            self._head_spec = PSpec(None, None, model_axis)
            pool_sh = NamedSharding(mesh, self._pool_spec)
            if kv_dtype == "int8":
                scale_sh = NamedSharding(mesh, self._head_spec)
                self.pool_k = {
                    "q": jnp.zeros(pool_shape, jnp.int8, device=pool_sh),
                    "s": jnp.ones(scale_shape, jnp.float32, device=scale_sh),
                }
                self.pool_v = {
                    "q": jnp.zeros(pool_shape, jnp.int8, device=pool_sh),
                    "s": jnp.ones(scale_shape, jnp.float32, device=scale_sh),
                }
            else:
                self.pool_k = jnp.zeros(
                    pool_shape, dec.kv_dtype, device=pool_sh
                )
                self.pool_v = jnp.zeros(
                    pool_shape, dec.kv_dtype, device=pool_sh
                )
            self.params = self._sdec.shard_params(params)
        else:
            self._pool_spec = None
            self._head_spec = None
            if kv_dtype == "int8":
                self.pool_k = {
                    "q": jnp.zeros(pool_shape, jnp.int8),
                    "s": jnp.ones(scale_shape, jnp.float32),
                }
                self.pool_v = {
                    "q": jnp.zeros(pool_shape, jnp.int8),
                    "s": jnp.ones(scale_shape, jnp.float32),
                }
            else:
                self.pool_k = jnp.zeros(pool_shape, dec.kv_dtype)
                self.pool_v = jnp.zeros(pool_shape, dec.kv_dtype)
            if device is not None:
                self.pool_k = jax.device_put(self.pool_k, device)
                self.pool_v = jax.device_put(self.pool_v, device)
                self.params = jax.device_put(params, device)
        # shard_map / with_sharding_constraint spec matching the
        # pool's pytree structure (plain spec for fp, {"q","s"} tree
        # for int8).
        self._pool_specs = (
            {"q": self._pool_spec, "s": self._head_spec}
            if kv_dtype == "int8"
            else self._pool_spec
        )
        self.pool_bytes = sum(
            leaf.nbytes
            for leaf in jax.tree.leaves((self.pool_k, self.pool_v))
        )
        # The second kind of cache: per slot and recurrent layer the
        # rule's state S (float32) and the rows its convolution still
        # needs, indexed by SLOT, not by block table. Its size is
        # fixed; admission overwrites a slot's row and finishing need
        # not clear it. Empty where the stack has no such layer.
        self.pool_state: tuple = ()
        if cfg.has_linear:
            self.pool_state = dec.init_linear_state(max_batch)
            if device is not None:
                self.pool_state = jax.device_put(self.pool_state, device)
        self.state_bytes = sum(a.nbytes for a in self.pool_state)
        # Pipeline-parallel stage chain (pp_stages > 1): resolve the
        # layer cuts, build one stage per contiguous layer range, and
        # account the pool as the sum of the per-stage slices.
        self._pp_stage_objs: list = []
        self._pp_cut_starts: list[int] = [0]
        self._pp_inflight = _pp_M
        self._pp_groups: list[list[int]] = []
        self.pp_stage_pool_bytes: list[int] = []
        self.pp_stage_dispatch_n: list[int] = []
        self.pp_bubble_last = 0.0
        self.pp_occupancy_last: list[float] = []
        if self.pp > 1:
            from defer_tpu.parallel.pipeline import balance_stage_cuts

            L = cfg.num_layers
            if pp_cuts is not None:
                starts = [int(c) for c in pp_cuts]
                if (
                    len(starts) != self.pp
                    or starts[0] != 0
                    or any(
                        b <= a for a, b in zip(starts, starts[1:])
                    )
                    or starts[-1] >= L
                ):
                    raise ValueError(
                        f"pp_cuts={starts} must be {self.pp} strictly "
                        f"increasing stage START layers beginning at 0 "
                        f"and below num_layers={L} (e.g. [0, "
                        f"{L // 2}] for 2 stages). Fix: pass valid "
                        "cut starts, or drop pp_cuts for balanced "
                        "ones."
                    )
            elif pp_balance == "probe":
                starts = balance_stage_cuts(
                    self._probe_pp_layer_costs(num_blocks), self.pp
                )
            else:
                # Equal layer counts == min-max split of unit costs.
                starts = balance_stage_cuts([1.0] * L, self.pp)
            bounds = starts + [L]
            remote = pp_remote or {}
            if any(s not in range(self.pp) for s in remote):
                raise ValueError(
                    f"pp_remote stage indices {sorted(remote)} must "
                    f"lie in [0, {self.pp})"
                )
            devs = (
                list(pp_devices)
                if pp_devices is not None
                else jax.devices()
            )
            dh_ = cfg.dh
            itemsize = jnp.dtype(dec.compute_dtype).itemsize
            for s in range(self.pp):
                first_l, last_l = bounds[s], bounds[s + 1]
                if s in remote:
                    # The worker owns the slice; account its bytes
                    # here so per-stage HBM ~1/S stays inspectable.
                    stage = _PPTransportStage(
                        remote[s],
                        first=first_l,
                        last=last_l,
                        pool_bytes=2
                        * (last_l - first_l)
                        * num_blocks
                        * cfg.kv_heads
                        * block_size
                        * dh_
                        * itemsize,
                    )
                elif mesh is not None:
                    stage = _PPLocalStage(
                        dec, params, first_l, last_l,
                        num_blocks=num_blocks,
                        block_size=block_size,
                        attention=attention,
                        submesh=self._pp_submeshes[s],
                        model_axis=model_axis,
                    )
                else:
                    stage = _PPLocalStage(
                        dec, params, first_l, last_l,
                        num_blocks=num_blocks,
                        block_size=block_size,
                        attention=attention,
                        device=devs[s % len(devs)],
                    )
                self._pp_stage_objs.append(stage)
            self._pp_cut_starts = starts
            self._pp_groups = microbatch_groups(max_batch, _pp_M)
            self.pp_stage_pool_bytes = [
                st.pool_bytes for st in self._pp_stage_objs
            ]
            self.pool_bytes = sum(self.pp_stage_pool_bytes)
            self.pp_stage_dispatch_n = [0] * self.pp
        # Block 0 is trash: unallocated table entries point at it.
        self.free = list(range(1, num_blocks))
        self.tables = np.zeros((max_batch, self.MB), np.int32)
        self.pos = np.zeros((max_batch,), np.int32)
        self.adapter = np.zeros((max_batch,), np.int32)
        self.slots: list[dict | None] = [None] * max_batch
        # Persistent tick feed: each slot's next input token lives in
        # row i, updated by .at[i].set at admission and one full-vector
        # write after each draw — not rebuilt by concatenating
        # max_batch [1,1] arrays every tick (host dispatch overhead
        # that dominates at small models). Idle rows are dummies.
        self._feed = jnp.zeros((max_batch, 1), jnp.int32)
        self._sampler = SlotSampler(max_batch)
        # deque, not list: admission consumes from the head every
        # _admit pass, and a deep open-loop backlog would turn
        # list.pop(0) into O(queue) per admission.
        self.pending: collections.deque[tuple] = collections.deque()
        # Externally prefilled admissions (disagg/): rid -> request
        # entry whose "kv" field a transport ingest fills in from
        # another thread (deliver_kv). Admission order follows
        # _prefilled_order among entries whose KV has arrived. All
        # POOL mutation stays on the run/_admit thread; the ingest
        # thread only ever assigns the entry's "kv" slot.
        self.pending_prefilled: dict[int, dict] = {}
        self._prefilled_order: list[int] = []
        self.done: dict[int, jax.Array] = {}
        self._next_id = 0
        self.ticks = 0
        self.blocks_peak = 0
        # Dispatch-efficiency accounting (fused windows): host
        # dispatches of the decode program and tokens accepted from
        # them. At decode_window=1, dispatches == ticks.
        self.dispatches = 0
        self.window_tokens = 0
        # Metric handles resolved once; tick/admission paths touch
        # pre-bound attributes only (obs/serving.py).
        self.obs = ServingMetrics("paged", mesh_shape=self.mesh_label)
        self.obs.kv_pool_bytes.set(self.pool_bytes)
        self.obs.linear_state_pool_bytes.set(self.state_bytes)
        if self.pp > 1:
            # Stage-labeled pp instruments (occupancy gauges + dispatch
            # counters per stage) bind once the stage count is known.
            self.obs.bind_pp(self.pp)
            self.obs.pp_inflight.set(float(self._pp_inflight))
        self._submit_t: dict[int, float] = {}
        self._last_tick_t: float | None = None
        # Constrained decoding tables (defer_tpu/constrain/): stacked
        # [C, S_max, V] transitions + [C, S_max] accepting bits, cid 0
        # the synthetic free row. None when the feature is off — every
        # tick then takes the exact pre-constraint code path. The
        # tables are replicated on a mesh (tiny next to the pool) and
        # pinned with the params on a device= server.
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
                crt.stack_token_dfas(constraints, cfg.vocab_size)
            )
            if device is not None:
                self._ctrans = jax.device_put(self._ctrans, device)
                self._cacc = jax.device_put(self._cacc, device)
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
        self._step = None
        self._insert = None
        self._insert_state = None
        self._insert_dyn = None
        self._import = None
        self._mt = None
        self._spill_up = None
        self.spec_k = spec_k
        self.prefill_chunk = prefill_chunk
        self.prefill_budget = prefill_budget
        self.prefill_lookahead = prefill_lookahead
        # Stall/mixed accounting (host mirrors of the obs instruments,
        # for ServerStats snapshots without a registry read):
        # stall ticks = admission-prefill dispatches issued while at
        # least one decode slot sat waiting (always 0 in mixed mode);
        # mixed tokens = prompt tokens carried by fused mixed ticks.
        self.prefill_stall_ticks_n = 0
        self.mixed_prefill_tokens_n = 0
        self.mixed_ticks_n = 0
        self.decode_stall_fraction_last = 0.0
        # Draft lanes (runtime/decode_server.py::DraftLanes): the
        # draft model's flat per-slot K/V plus host position truth.
        self._draft = (
            DraftLanes(spec_draft, spec_params, max_batch, target=dec)
            if spec_k
            else None
        )
        # Host-side speculation totals (the obs counters' mirrors, for
        # ServerStats snapshots without a registry read).
        self.spec_rounds_n = 0
        self.spec_proposed_n = 0
        self.spec_accepted_n = 0
        self.spec_draft_tokens_n = 0
        self.prefix_len = 0
        self.shared_blocks: list[int] = []
        self._prefix_cache = None
        self.radix: PrefixBlockCache | None = None
        self._gather = None
        self.prefill_tokens_saved = 0
        self._spill: HostKVSpill | None = None
        self.spill_hits_n = 0
        if prefix_cache:
            if prefix_ids is not None:
                raise ValueError(
                    "prefix_cache=True subsumes the global prefix_ids "
                    "— pass the system prompt as part of each "
                    "request's prompt and it shares automatically"
                )
            if self.multi_lora:
                raise ValueError(
                    "prefix_cache + multi-LoRA is unsupported: cached "
                    "prefix K/V would be adapter-dependent"
                )
            if spill_bytes:
                self._spill = HostKVSpill(spill_bytes, obs=self.obs)
            self.radix = PrefixBlockCache(
                obs=self.obs,
                on_evict=(
                    self._spill_block if self._spill is not None else None
                ),
            )
        if prefix_ids is not None:
            if self.multi_lora:
                raise ValueError(
                    "prefix caching + multi-LoRA is unsupported: the "
                    "shared prefix K/V would be adapter-dependent"
                )
            if prefix_ids.ndim != 2 or prefix_ids.shape[0] != 1:
                raise ValueError("prefix_ids must be [1, P]")
            P = int(prefix_ids.shape[1])
            if P % block_size:
                raise ValueError(
                    f"shared-prefix paging needs the prefix length "
                    f"({P}) to be a block_size ({block_size}) multiple "
                    "— otherwise a suffix write would land in a "
                    "SHARED block and corrupt every other request"
                )
            if P >= cfg.max_len:
                raise ValueError(
                    f"prefix of {P} leaves no room under max_len "
                    f"{cfg.max_len}"
                )
            n_shared = P // block_size
            if n_shared > len(self.free):
                raise ValueError(
                    f"prefix needs {n_shared} blocks but the pool has "
                    f"{len(self.free)} usable"
                )
            # One prefix prefill through the flat path; its rows
            # become the pool's single shared copy (a skip-0 insert:
            # admissions later use a skip=n_shared insert that can
            # never write the shared blocks).
            from defer_tpu.utils.memo import cached_step

            full_insert = cached_step(
                dec,
                ("paged_insert", block_size, 0, kv_dtype, self._mesh_key),
                lambda: self._build_insert(0),
            )
            fdec = self._sdec if self._sdec is not None else dec
            pre = fdec.init_cache(1)
            _, pre = fdec.make_step()(self.params, pre, prefix_ids)
            self._account_psums(1)
            self.shared_blocks = [
                self.free.pop() for _ in range(n_shared)
            ]
            shared_row = np.zeros((self.MB,), np.int32)
            for j, blk in enumerate(self.shared_blocks):
                shared_row[j] = blk
            self.pool_k, self.pool_v = full_insert(
                self.pool_k,
                self.pool_v,
                pre["k"],
                pre["v"],
                jnp.asarray(shared_row),
            )
            # Keep the contiguous prefix lane for suffix admissions
            # (the suffix prefill needs the prefix rows in the flat
            # layout to attend at offset P).
            self._prefix_cache = pre
            self.prefix_len = P

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
        """`sampling` — optional models/gpt.py SamplingParams: the
        slot then samples inside the shared batched tick from its own
        seeded key stream (bit-identical to solo
        `generate(..., rng=jax.random.key(seed))`); None = greedy.
        `stop` — optional multi-token stop sequences (iterable of int
        sequences, runtime/stopping.py): the request finishes the
        moment its GENERATED tail equals any of them, freeing its
        blocks mid-budget."""
        if prompt_ids.ndim != 2 or prompt_ids.shape[0] != 1:
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
        if t0 < 1 or num_steps < 1:
            raise ValueError("need at least 1 prompt token and 1 step")
        # spec_k rows of write headroom: a verify forward at position
        # p writes candidate rows through p + spec_k, and the gathered
        # path's contiguous-lane write must never clamp (clamping
        # would shift real rows). spec_k is 0 when speculation is off.
        if (
            self.prefix_len + t0 + num_steps + self.spec_k
            > self.dec.cfg.max_len
        ):
            extra = (
                f" + spec_k {self.spec_k} headroom" if self.spec_k else ""
            )
            raise ValueError(
                f"prefix {self.prefix_len} + prompt {t0} + steps "
                f"{num_steps}{extra} exceeds max_len "
                f"{self.dec.cfg.max_len}"
            )
        need = self._own_need(t0, num_steps)
        usable = self.num_blocks - 1 - len(self.shared_blocks)
        if need > usable:
            # Not even an empty pool could hold it — waiting would
            # deadlock the queue.
            raise ValueError(
                f"request needs {need} own blocks but the pool has "
                f"{usable} usable beyond the shared prefix"
            )
        rid = self._next_id
        self._next_id += 1
        self.pending.append(
            (rid, prompt_ids, num_steps, adapter_id, sampling,
             stop_seqs, cid)
        )
        self._submit_t[rid] = time.perf_counter()
        return rid

    def _resolve_constraint(self, name: str | None) -> int:
        return crt.resolve_constraint(
            name, self._ctrans, self._cnames, self._cdfas
        )

    def _own_need(self, t0: int, steps: int) -> int:
        """Blocks a request must own: its total span minus the shared
        prefix blocks its table merely points at."""
        total = -(-(self.prefix_len + t0 + steps) // self.bs)
        return total - len(self.shared_blocks)

    def submit_prefilled(
        self,
        prompt_ids: Any,
        num_steps: int,
        *,
        sampling: Any = None,
        stop: Any = None,
    ) -> int:
        """Register a request whose prefill runs ELSEWHERE (a disagg
        prefill worker): the request waits in `pending_prefilled`
        until `deliver_kv` hands over its finished KV blocks, then
        admission seats those blocks directly in the pool — no local
        prefill step. Same sampling/stop semantics as `submit`.

        Restricted to the base model without a global shared prefix:
        externally computed K/V can't be checked against a
        constructor-level `prefix_ids` lane, and adapter-specific K/V
        from a base-model worker would silently skew LoRA requests.
        (`prefix_cache=True` composes fine — ingested full prompt
        blocks register in the radix cache like locally prefilled
        ones. `spec_k>0` composes too: the TARGET K/V arrives over
        the wire, and admission re-prefills the DRAFT lane locally
        from the prompt ids — draft prefill is the cheap side of the
        asymmetry, so decode-worker speculation keeps the disagg
        split's point.)"""
        from defer_tpu.parallel.transformer_stack import refuse_mechanisms

        refuse_mechanisms(
            self.dec.cfg, "disagg ingest (submit_prefilled/deliver_kv)"
        )
        if self.pp > 1:
            raise ValueError(
                "disagg ingest (submit_prefilled/deliver_kv) does not "
                "compose with pp_stages > 1 yet: delivered KV blocks "
                "target a monolithic pool, not per-stage slices. Fix: "
                "point the prefill worker at a pp_stages=1 decode "
                "server, or submit() so prefill runs through the "
                "stage chain."
            )
        if self.shared_blocks or self.prefix_len:
            raise ValueError(
                "externally prefilled admission does not compose with "
                "constructor-level prefix_ids; use prefix_cache=True"
            )
        if self.multi_lora:
            raise ValueError(
                "externally prefilled admission supports the base "
                "model only (adapter-specific K/V would need the "
                "worker to run the same adapter banks)"
            )
        prompt = np.asarray(prompt_ids)
        if prompt.ndim != 2 or prompt.shape[0] != 1:
            raise ValueError("submit one request at a time ([1, T])")
        cid = 0
        if sampling is not None:
            sampling.validate()
            cid = self._resolve_constraint(sampling.constraint)
            if sampling.temperature == 0:
                sampling = None
        stop_seqs = normalize_stops(stop)
        t0 = prompt.shape[1]
        if t0 < 1 or num_steps < 1:
            raise ValueError("need at least 1 prompt token and 1 step")
        # Same spec_k write headroom as submit(): verify forwards
        # write candidate rows past the committed position.
        if t0 + num_steps + self.spec_k > self.dec.cfg.max_len:
            extra = (
                f" + spec_k {self.spec_k} headroom" if self.spec_k else ""
            )
            raise ValueError(
                f"prompt {t0} + steps {num_steps}{extra} exceeds "
                f"max_len {self.dec.cfg.max_len}"
            )
        need = self._own_need(t0, num_steps)
        usable = self.num_blocks - 1
        if need > usable:
            raise ValueError(
                f"request needs {need} blocks but the pool has "
                f"{usable} usable"
            )
        rid = self._next_id
        self._next_id += 1
        self.pending_prefilled[rid] = {
            "prompt": prompt.astype(np.int32),
            "steps": num_steps,
            "samp": sampling,
            "stop": stop_seqs,
            "cid": cid,
            "kv": None,
        }
        self._prefilled_order.append(rid)
        self._submit_t[rid] = time.perf_counter()
        return rid

    def deliver_kv(
        self,
        rid: int,
        k_blocks: np.ndarray,
        v_blocks: np.ndarray,
        first_logits: np.ndarray,
    ) -> None:
        """Hand a pending_prefilled request its finished KV state:
        [L, n_blocks, Hkv, bs, Dh] K/V block stacks covering the
        prompt rows, plus the [1, V] logits row of the last prompt
        position (the first generated token is sampled from it).
        Thread-safe against the run loop: this only assigns the
        entry's "kv" slot (one atomic dict write); the pool itself is
        touched exclusively by `_admit` on the serving thread."""
        entry = self.pending_prefilled.get(rid)
        if entry is None:
            raise KeyError(f"no pending prefilled request {rid}")
        t0 = entry["prompt"].shape[1]
        n_need = -(-t0 // self.bs)
        cfg = self.dec.cfg
        expect = (
            cfg.num_layers,
            n_need,
            cfg.kv_heads,
            self.bs,
            cfg.dh,
        )
        if tuple(k_blocks.shape) != expect or tuple(v_blocks.shape) != expect:
            raise ValueError(
                f"KV block stack shape {tuple(k_blocks.shape)}/"
                f"{tuple(v_blocks.shape)} != expected {expect} for "
                f"rid {rid} (t0={t0}, block_size={self.bs})"
            )
        if first_logits.shape != (1, cfg.vocab_size):
            raise ValueError(
                f"first_logits shape {tuple(first_logits.shape)} != "
                f"(1, {cfg.vocab_size})"
            )
        entry["kv"] = (k_blocks, v_blocks, first_logits)

    def run(self) -> dict[int, jax.Array]:
        while self.pending or self.pending_prefilled or any(self.slots):
            self._admit()
            if not any(s is not None for s in self.slots):
                if self.pending_prefilled:
                    # Nothing seated and at least one request is
                    # waiting on EXTERNAL KV delivery — yield instead
                    # of spinning the admit/tick loop hot.
                    time.sleep(1e-3)
                continue
            self._tick()
        return self.done

    @property
    def blocks_in_use(self) -> int:
        if self.radix is not None:
            # Exact pool accounting: everything that is neither free
            # nor parked at refcount 0 is held by an active request
            # (shared blocks counted once, however many slots point at
            # them).
            return (
                (self.num_blocks - 1)
                - len(self.free)
                - len(self.radix.lru)
            )
        return sum(len(s["blocks"]) for s in self.slots if s)

    def resident_digests(self) -> tuple[int, frozenset[bytes]]:
        """Routing advertisement passthrough (PrefixBlockCache
        docstring); (0, empty) without prefix_cache=True so fleet
        callers need no radix check."""
        if self.radix is None:
            return 0, frozenset()
        return self.radix.resident_digests()

    def export_prefix_blocks(
        self, keys: list[bytes]
    ) -> tuple[list[bytes], np.ndarray, np.ndarray] | None:
        """Copy a resident prefix chain OUT of the pool for migration:
        `keys` is a root-anchored run of chained digests (the router's
        walk order); returns (own-block token bytes per block,
        [L, n, Hkv, bs, Dh] K and V block stacks) or None if any key
        was evicted since the advertisement the caller routed on.

        SERVING-THREAD ONLY: the decode step donates the pool buffers,
        so a reader on any other thread can observe an invalidated
        buffer mid-tick. Fleet replicas run this as an ops-queue
        command between ticks. The copy is host-side and
        self-contained — once returned, eviction on this replica
        cannot hurt the importer."""
        if self.radix is None:
            raise ValueError("export needs prefix_cache=True")
        blks: list[int] = []
        toks: list[bytes] = []
        for key in keys:
            blk = self.radix.by_key.get(key)
            if blk is None:
                return None  # evicted since the advert; stale route
            blks.append(blk)
            toks.append(self.radix.tok_of[blk])
        # analysis: ignore[host-sync-in-hot-loop] host-side block-id
        # list becoming device gather indices — no device readback
        idx = jnp.asarray(np.asarray(blks, np.int32))
        if isinstance(self.pool_k, dict):
            # int8 pools dequantize before export: the migration wire
            # format stays the compute-dtype block stack regardless of
            # either end's kv_dtype.
            kd = dequantize_symmetric(
                self.pool_k["q"][:, idx],
                self.pool_k["s"][:, idx][..., None, None],
                self.dec.compute_dtype,
            )
            vd = dequantize_symmetric(
                self.pool_v["q"][:, idx],
                self.pool_v["s"][:, idx][..., None, None],
                self.dec.compute_dtype,
            )
        else:
            kd = self.pool_k[:, idx]
            vd = self.pool_v[:, idx]
        # analysis: ignore[host-sync-in-hot-loop] deliberate sync — a
        # migration ships the payload over a host wire, so the copy to
        # host memory IS the operation
        k = np.asarray(kd)
        # analysis: ignore[host-sync-in-hot-loop] second half of the
        # same deliberate migration copy
        v = np.asarray(vd)
        return toks, k, v

    def _shard_ingest(self, arr) -> jax.Array:
        """Device placement for full-head host K/V entering the pool
        (migration imports, disagg wire blobs, flat-lane inserts). On a
        mesh the array is SPLIT ON ITS HEAD AXIS (index 2 — shared by
        the [L, n, Hkv, bs, Dh] block-stack and [L, 1, Hkv, S, Dh]
        lane layouts) as it lands on device, so each shard receives
        only its local heads and the wire/lane format never changes.
        3-D arrays are int8 block SCALES ([L, n, Hkv]) — same head
        axis, scale-rank spec. On a pinned single device it lands
        there; otherwise this is plain jnp.asarray."""
        if self.mesh is not None:
            from jax.sharding import NamedSharding

            spec = (
                self._head_spec
                if getattr(arr, "ndim", 5) == 3
                else self._pool_spec
            )
            return jax.device_put(arr, NamedSharding(self.mesh, spec))
        if self.device is not None:
            return jax.device_put(arr, self.device)
        return jnp.asarray(arr)

    def _ensure_import(self):
        if self._import is None:
            from defer_tpu.utils.memo import cached_step

            def build():
                def imp(pk, pv, k_blocks, v_blocks, dest):
                    # Pad entries in dest are 0: duplicate writes to
                    # trash block 0 race over garbage, by the module
                    # invariant.
                    if isinstance(pk, dict):
                        # Imported stacks arrive compute-dtype on the
                        # wire; quantize per (layer, block, head) as
                        # they land (imported blocks are always FULL —
                        # every row real prompt content).
                        kq, ks = _quantize_blocks(k_blocks)
                        vq, vs = _quantize_blocks(v_blocks)
                        pk = {
                            "q": pk["q"].at[:, dest].set(kq),
                            "s": pk["s"].at[:, dest].set(ks),
                        }
                        pv = {
                            "q": pv["q"].at[:, dest].set(vq),
                            "s": pv["s"].at[:, dest].set(vs),
                        }
                    else:
                        pk = pk.at[:, dest].set(k_blocks)
                        pv = pv.at[:, dest].set(v_blocks)
                    return self._pool_constraint(pk, pv)

                return jax.jit(imp, donate_argnums=(0, 1))

            self._import = cached_step(
                self.dec,
                ("fleet_import", self.bs, self.kv_dtype, self._mesh_key),
                build,
            )
        return self._import

    def import_prefix_blocks(
        self,
        toks: list[bytes],
        k_blocks: np.ndarray,
        v_blocks: np.ndarray,
    ) -> int:
        """Seat a migrated prefix chain (export_prefix_blocks payload)
        in this pool as PARKED radix entries — the next admission
        sharing the prefix revives them through the normal walk, no
        re-prefill. Chained digests are recomputed HERE from the token
        bytes (never trusted from the wire), so a corrupted payload
        mis-keys into digests nothing will ever look up, not into
        another chain. Already-resident leading blocks are skipped;
        allocation evicts parked LRU blocks under pressure and
        truncates the (deep) tail when the pool still can't cover it —
        the shallow end is the reusable end. Returns blocks imported.

        SERVING-THREAD ONLY, same donation rule as export."""
        if self.radix is None:
            raise ValueError("import needs prefix_cache=True")
        n = len(toks)
        cfg = self.dec.cfg
        expect = (
            cfg.num_layers, n, cfg.kv_heads, self.bs,
            cfg.dh,
        )
        if tuple(k_blocks.shape) != expect or tuple(v_blocks.shape) != expect:
            raise ValueError(
                f"prefix block stack shape {tuple(k_blocks.shape)}/"
                f"{tuple(v_blocks.shape)} != expected {expect}"
            )
        keys: list[bytes] = []
        prev = b""
        for bb in toks:
            prev = PrefixBlockCache._hash(prev, bb)
            keys.append(prev)
        # Skip the already-resident leading run (tok-guarded, same
        # collision discipline as walk()).
        m = 0
        while m < n:
            blk = self.radix.by_key.get(keys[m])
            if blk is None or self.radix.tok_of[blk] != toks[m]:
                break
            m += 1
        if m == n:
            return 0
        need = n - m
        if need > len(self.free):
            self.free.extend(self.radix.evict(need - len(self.free)))
        take = min(need, len(self.free))
        if take == 0:
            return 0
        own = [self.free.pop() for _ in range(take)]
        # Pow2-pad the imported span (capped at MB) so migration draws
        # from the same bounded compile-shape set as prefill; pad dest
        # entries point at trash block 0.
        n_pad = 1 << max(take - 1, 0).bit_length()
        n_pad = min(max(n_pad, 1), self.MB)
        dest = np.zeros((n_pad,), np.int32)
        dest[:take] = own
        kb = np.ascontiguousarray(k_blocks[:, m : m + take])
        vb = np.ascontiguousarray(v_blocks[:, m : m + take])
        if n_pad > take:
            pad = np.zeros(
                (expect[0], n_pad - take, *expect[2:]), kb.dtype
            )
            kb = np.concatenate([kb, pad], axis=1)
            vb = np.concatenate([vb, pad], axis=1)
        imp = self._ensure_import()
        self.pool_k, self.pool_v = imp(
            self.pool_k,
            self.pool_v,
            self._shard_ingest(kb.astype(self.dec.compute_dtype)),
            self._shard_ingest(vb.astype(self.dec.compute_dtype)),
            jnp.asarray(dest),
        )
        for j, blk in enumerate(own):
            displaced = self.radix.register(keys[m + j], toks[m + j], blk)
            if displaced is not None:
                self.free.append(displaced)
        # Park deepest-first (matches _finish): LRU then evicts the
        # deep end of the chain before its shallow prerequisites.
        for blk in reversed(own):
            self.radix.release(blk)
        self._update_pool_gauges()
        return take

    # -- internals --------------------------------------------------------

    def _build(self):
        if self.pp > 1:
            # Pipeline-parallel servers never run the monolithic tick
            # /insert programs: every forward goes through the stage
            # chain (_tick_pp / _prefill_paged), whose programs the
            # stages own.
            return
        if self._step is not None:
            return
        # Memoized ON THE DECODER (utils/memo.py): jit's cache is keyed
        # on the function object, so per-server closures would re-trace
        # and re-compile on every new server over the same decoder
        # (e.g. the servers one test file builds in turn).
        from defer_tpu.utils.memo import cached_step

        builders = {
            "gathered": self._build_step,
            "blockwise": self._build_step_blockwise,
            "pallas": self._build_step_pallas,
        }
        step_key = (
            "paged_step", self.bs, self.attention, self.kv_dtype,
            self._mesh_key,
        )
        jitted = cached_step(self.dec, step_key, builders[self.attention])
        self._step = _SpanSteps(
            jitted, self._build_span_programs(jitted, step_key)
        )
        skip = len(self.shared_blocks)
        self._insert = cached_step(
            self.dec,
            ("paged_insert", self.bs, skip, self.kv_dtype, self._mesh_key),
            lambda: self._build_insert(skip),
        )
        if self.pool_state:
            self._insert_state = cached_step(
                self.dec, ("paged_insert_state",), self._build_insert_state
            )
        if self.radix is not None and self._gather is None:
            self._gather = cached_step(
                self.dec,
                ("paged_gather", self.bs, self.kv_dtype, self._mesh_key),
                self._build_gather,
            )
            self._insert_dyn = cached_step(
                self.dec,
                (
                    "paged_insert_dyn", self.bs, self.kv_dtype,
                    self._mesh_key,
                ),
                self._build_insert_dynamic,
            )

    def _build_span_programs(self, jitted, step_key) -> dict:
        """The step compiled for every rung of the ladder, by table
        columns; none for a server whose ticks never call the step
        alone (windowed, speculative). Built here, before the first
        tick: a span first met while serving would stall every live
        slot for a compile. Memoised on the decoder like the jitted
        function, and keyed on every shape a program is fixed to."""
        if self.decode_window > 1 or self.spec_k:
            return {}
        from defer_tpu.utils.memo import cached_step

        # An array that was placed (a mesh, `device=`) says where the
        # program runs; with none placed it runs on the default device
        # and its outputs stay uncommitted, as a jit call's.
        fixed = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(
                a.shape, a.dtype,
                sharding=a.sharding
                if getattr(a, "committed", False) else None,
            ),
            (self.params, self.pool_k, self.pool_v, self.pool_state),
        )
        leaves, treedef = jax.tree.flatten(fixed)
        *fixed, state = fixed

        def i32(*shape):
            return jax.ShapeDtypeStruct(shape, jnp.int32)

        def temp_bytes(program) -> int:
            return program.memory_analysis().temp_size_in_bytes

        def build(nb):
            with spans.span(
                "jax.build", kind="paged_step", span_rows=nb * self.bs
            ) as sp:
                program = jitted.lower(
                    *fixed, i32(self.B, nb), i32(self.B),
                    i32(self.B, 1), i32(self.B),
                    *((state,) if state else ()),
                ).compile()
                sp.counts["temp_bytes"] = temp_bytes(program)
                return program

        programs = {
            nb: cached_step(
                self.dec,
                step_key + (self.B, nb, treedef, tuple(leaves)),
                lambda: build(nb),
            )
            for nb in self._rungs
        }
        # What says whether the pool is updated in place: a step that
        # holds a second pool reads a pool's bytes and more here.
        for nb, program in programs.items():
            self.obs.step_temp_bytes(nb * self.bs).set(temp_bytes(program))
        return programs

    def _tp_axis(self):
        """The tp_axis threaded into the tick bodies: the mesh's model
        axis when serving sharded, None otherwise — with None every
        body traces EXACTLY the single-device program (the mesh=None
        bit-identity contract)."""
        return self.model_axis if self.mesh is not None else None

    def _flat_dec(self):
        """The decoder whose contiguous-lane (flat) prefill programs
        this server dispatches: on a mesh the memoized SpmdGptDecoder
        view — its make_step/init_cache produce head-sharded lanes the
        insert programs consume shard-local — otherwise the user's
        decoder, unchanged."""
        return self._sdec if self._sdec is not None else self.dec

    def _account_kv_rows(self, rows_read: int, baseline: int) -> None:
        """Publish one dispatch's KV-row traffic. On a mesh both
        counters report PER-SHARD traffic: each device reads only its
        kv_heads/tp local heads, so rows scale by 1/model-axis-size
        (the counter-pinned TP contract; the read/baseline ratio still
        isolates the blockwise/pallas win because both sides scale)."""
        tp = self.tp
        self.obs.kv_rows_read.inc(rows_read // tp)
        self.obs.kv_rows_gathered.inc(baseline // tp)

    def _account_moe(self, counters, stats: np.ndarray) -> None:
        """Add one forward's expert-layer counters (`stats` [L, 2]:
        per layer the assignments on held experts and the held experts
        touched) to the phase's three instruments."""
        assignments, touched, layer_steps = counters
        assignments.inc(int(stats[:, 0].sum()))
        touched.inc(int(stats[:, 1].sum()))
        layer_steps.inc(stats.shape[0])

    def _account_psums(self, n_forwards: int) -> None:
        """Count the cross-shard collectives `n_forwards` sharded
        transformer forwards issue (per forward: attn + ffn psum per
        layer, the embedding psum, the final-logits all_gather).
        Host-side mirror of the traced program — no-op on mesh=None,
        where no collective exists."""
        if self._psums_per_fwd:
            n = self._psums_per_fwd * n_forwards
            self.tp_psums += n
            self.obs.tp_psums.inc(n)

    def _jit_tick(self, body, n_rep: int):
        """jit one of the raw tick bodies `(params, pk, pv, *rest) ->
        (out_tree..., pk, pv)`-shaped as `(logits, pk, pv)`. On a mesh
        the body is wrapped in shard_map first: params by the Megatron
        specs, the two pool operands on the KV-head axis, the `n_rep`
        trailing host-fed operands (tables, positions, ids, ...)
        replicated. Logits come back replicated — the body ends in a
        tiled all_gather of the vocab-sharded slices — so sampling
        stays on post-psum logits and check_rep must be off (the
        checker cannot infer the gather's replication)."""
        if self.mesh is None:
            return jax.jit(body, donate_argnums=(1, 2))
        from jax.sharding import PartitionSpec as PSpec

        from defer_tpu.utils.compat import shard_map

        pool, r = self._pool_specs, PSpec()
        sm = shard_map(
            body,
            self.mesh,
            in_specs=(self._sdec._specs(), pool, pool) + (r,) * n_rep,
            out_specs=(r, pool, pool),
            # analysis: ignore[shard-spec] body ends in slot scatters whose replication the checker cannot infer; psum placement is pinned by the defer_tp_psum_total mirror instead
            check_rep=False,
        )
        return jax.jit(sm, donate_argnums=(1, 2))

    def _replicate_logits(self, logits):
        """Inside a shard_map tick body: turn this shard's vocab slice
        [B, T, Vpad/tp] into the full replicated [B, T, V] logits
        (concatenate the slices, drop the vocab padding). Identity on
        mesh=None."""
        if self.mesh is None:
            return logits
        logits = lax.all_gather(
            logits, self.model_axis, axis=-1, tiled=True
        )
        return logits[..., : self.dec.cfg.vocab_size]

    def _build_step(self):
        if self.pool_state:
            # The state pools are one more donated operand, after the
            # four host-fed ones (no mesh serves such a stack).
            return jax.jit(self._step_body(), donate_argnums=(1, 2, 7))
        return self._jit_tick(self._step_body(), n_rep=4)

    def _step_body(self):
        """The RAW (unjitted) gathered-attention step body — jitted
        standalone for the K=1 tick (_build_step) and traced inside
        the fused-window scan (_build_window) for decode_window > 1,
        so both paths run identical math by construction. One body
        for every stack `GptDecoder.scan_layers` scans: a homogeneous
        dense one layer by layer (fp or int8 pool, LoRA banks, a
        shard_map's local heads), one whose layers differ in kind
        (`cfg.layer_kinds`: a window or none, rotary or not) or hold
        experts period by period, each layer's window the
        flash-decode kernel's static argument.

        The pool rides in the scan's CARRY and is read and written at
        [layer, block] in place, under the donation `_jit_tick` sets.
        Scanned as an input and an output it is two buffers: XLA
        slices a layer out, updates the slice and stacks it into a
        second pool, a whole-pool copy a tick and a pool more of
        temporaries (18 ms of a 77 ms tick at Mistral-7B's widths,
        PERF.md PR 33). The layer's index is itself a scanned input,
        since a dense stack's scan hands `layer` None.

        Where the stack has recurrent layers their state pools are
        one more carried operand (`state`, last in and last out,
        donated): read and written at [layer, slot] by the same rule.

        Returns `(logits, stats)` first where the decoder has experts:
        stats int32 [L, 2], per layer the assignments that fell on
        held experts and the distinct held experts touched, over live
        rows (an idle slot sits at position 0, which no live one
        does)."""
        dec, bs = self.dec, self.bs
        tp = self._tp_axis()
        experts = bool(dec.cfg.num_experts)
        recurrent = dec.cfg.has_linear

        @dec._with_precision
        def step(params, pk, pv, tables, pos, ids, adapter_ids, state=()):
            b = ids.shape[0]
            x = dec._embed_tokens(params, ids, pos, tp)
            rows = jnp.arange(b)
            live = (pos > 0)[:, None]
            blk = tables[rows, pos // bs]  # [B]
            row = pos % bs

            def body(carry, p, l, kind, layer):
                x, pk, pv, *state = carry
                if kind == LINEAR:
                    # A recurrent layer reads and writes its slot rows
                    # of the state pools at [l], in place like the K/V
                    # pool, and touches no block.
                    out, *state = dec._linear_block(
                        p, x, *state, l, live=live, layer=layer
                    )
                    x, stats = out if experts else (out, None)
                    carry = (x, pk, pv, *state)
                    return ((carry, stats) if experts else carry), None
                # Gather this slot's pages into the contiguous view
                # the flat block math expects: [B, Hkv, MB*bs, Dh].
                # An int8 pool dequantizes AT the gather (scale folds
                # into the block values), so _block sees fp blocks.
                with jax.named_scope("kv_gather"):
                    kc = _pool_gather(pk, tables, dec.kv_dtype, l)
                    vc = _pool_gather(pv, tables, dec.kv_dtype, l)
                    b_, mb, hkv, _, dh = kc.shape
                    kc = kc.transpose(0, 2, 1, 3, 4).reshape(
                        b_, hkv, mb * bs, dh
                    )
                    vc = vc.transpose(0, 2, 1, 3, 4).reshape(
                        b_, hkv, mb * bs, dh
                    )
                out, kc, vc = dec._block(
                    p, x, kc, vc, pos, tp_axis=tp,
                    adapter_ids=adapter_ids, kind=kind, live=live,
                    layer=layer,
                )
                x, stats = out if experts else (out, None)
                # Scatter ONLY the new row back to its page.
                with jax.named_scope("kv_scatter"):
                    pk = _pool_write_rows(
                        pk, blk, row, kc[rows, :, pos, :], l
                    )
                    pv = _pool_write_rows(
                        pv, blk, row, vc[rows, :, pos, :], l
                    )
                carry = (x, pk, pv, *state)
                return ((carry, stats) if experts else carry), None

            # Each layer's index into the pool of its kind.
            layers = jnp.arange(_pool_arr(pk).shape[0])
            if recurrent:
                layers = {
                    "attn": layers, LINEAR: jnp.arange(state[0].shape[0])
                }
            (x, pk, pv, *state), _, stats = dec.scan_layers(
                body, (x, pk, pv, *state), params["stack"], layers
            )
            logits = self._replicate_logits(dec._final_logits(params, x))
            out = (logits, stats) if experts else logits
            return (out, pk, pv, tuple(state)) if recurrent else (out, pk, pv)

        return step

    def _build_step_blockwise(self):
        return self._jit_tick(self._step_body_blockwise(), n_rep=4)

    def _step_body_blockwise(self):
        """The block-native pure-XLA step: same embed/projection/FFN
        code as the gathered step (GptDecoder._attn_qkv/_attn_out, so
        the new K/V rows are bit-identical), but attention folds pool
        blocks through the block table directly — no contiguous
        [B, Hkv, MB*bs, Dh] copy is ever materialized, and the fold
        stops at the deepest live block across the batch. The new row
        is scattered into the pool BEFORE attention (write-then-attend,
        like the flat path), through the same (blk, row) indices as
        the gathered path's scatter-back — idle slots write trash
        block 0 row 0, the module invariant."""
        dec, bs = self.dec, self.bs
        window = dec.cfg.window
        tp = self._tp_axis()

        def step(params, pk, pv, tables, pos, ids, adapter_ids):
            b = ids.shape[0]
            x = dec._embed_tokens(params, ids, pos, tp)
            rows = jnp.arange(b)
            blk_w = tables[rows, pos // bs]  # [B]
            row_w = pos % bs
            # Deepest live block over the batch: the fold's traced
            # bound — reads scale with actual depth, not pool size.
            nb_live = jnp.max(pos) // bs + 1

            def body(carry, layer):
                x = carry
                p, pk_l, pv_l = layer  # [NB, Hkv, bs, Dh]
                q, k_new, v_new = dec._attn_qkv(
                    p, x, pos, adapter_ids=adapter_ids
                )
                with jax.named_scope("kv_scatter"):
                    pk_l = _pool_write_rows(
                        pk_l, blk_w, row_w, k_new[:, :, 0, :]
                    )
                    pv_l = _pool_write_rows(
                        pv_l, blk_w, row_w, v_new[:, :, 0, :]
                    )
                with jax.named_scope("attn_core"):
                    attn = _blockwise_attend(
                        q, pk_l, pv_l, tables, pos, bs, nb_live, window
                    )
                out = dec._attn_out(
                    p, x, attn, tp, adapter_ids=adapter_ids
                )
                return out, (pk_l, pv_l)

            x, (pk, pv) = lax.scan(
                body, x, (params["stack"], pk, pv)
            )
            logits = self._replicate_logits(dec._final_logits(params, x))
            return logits, pk, pv

        return step

    def _build_step_pallas(self):
        return self._jit_tick(self._step_body_pallas(), n_rep=4)

    def _step_body_pallas(self):
        """The kernel variant of the block-native step: attention goes
        through ops/pallas_attention.py::paged_flash_decode, whose
        index maps resolve the block table inside the kernel grid —
        per slot only its OWN live blocks are DMAed. Compiles to
        Mosaic on a real TPU; anywhere else the kernel runs through
        the pallas interpreter (functionally identical, slow — the CI
        parity test rides the `slow` marker)."""
        from defer_tpu.models.gpt import _flash_decode_mode
        from defer_tpu.ops.pallas_attention import paged_flash_decode

        dec, bs = self.dec, self.bs
        window = dec.cfg.window
        interpret = _flash_decode_mode() != "tpu"
        tp = self._tp_axis()

        def step(params, pk, pv, tables, pos, ids, adapter_ids):
            b = ids.shape[0]
            x = dec._embed_tokens(params, ids, pos, tp)
            rows = jnp.arange(b)
            blk_w = tables[rows, pos // bs]
            row_w = pos % bs

            def body(carry, layer):
                x = carry
                p, pk_l, pv_l = layer
                q, k_new, v_new = dec._attn_qkv(
                    p, x, pos, adapter_ids=adapter_ids
                )
                with jax.named_scope("kv_scatter"):
                    pk_l = _pool_write_rows(
                        pk_l, blk_w, row_w, k_new[:, :, 0, :]
                    )
                    pv_l = _pool_write_rows(
                        pv_l, blk_w, row_w, v_new[:, :, 0, :]
                    )
                b_, hq, _, dh = q.shape
                quantized = isinstance(pk_l, dict)
                with jax.named_scope("attn_core"):
                    attn = paged_flash_decode(
                        q[:, :, 0, :],
                        _pool_arr(pk_l),
                        _pool_arr(pv_l),
                        tables,
                        pos,
                        window=window,
                        interpret=interpret,
                        scale_k=pk_l["s"] if quantized else None,
                        scale_v=pv_l["s"] if quantized else None,
                    )  # [B, Hq, Dh]
                    attn = attn.astype(x.dtype).reshape(b_, 1, hq * dh)
                out = dec._attn_out(
                    p, x, attn, tp, adapter_ids=adapter_ids
                )
                return out, (pk_l, pv_l)

            x, (pk, pv) = lax.scan(
                body, x, (params["stack"], pk, pv)
            )
            logits = self._replicate_logits(dec._final_logits(params, x))
            return logits, pk, pv

        return step

    def _ensure_mt(self):
        """The multi-token paged step (speculative verify forwards and
        chunked pool-native prefill share it): built lazily, memoized
        on the decoder like every other paged program. One memo entry
        per attention mode; jit then caches per (B, T) shape — the
        spec path runs a single (max_batch, k+1) trace in steady
        state, prefill chunks a single (1, chunk) trace plus pow2
        tails."""
        if self._mt is None:
            from defer_tpu.utils.memo import cached_step

            self._mt = cached_step(
                self.dec,
                (
                    "paged_mt", self.bs, self.attention, self.kv_dtype,
                    self._mesh_key,
                ),
                lambda: self._jit_tick(self._mt_body(), n_rep=6),
            )
        return self._mt

    def _mt_body(self):
        """The RAW multi-token paged step: T tokens per slot in one
        forward, reading K/V through the block table and scattering
        all T new rows back in one multi-row write.

        step(params, pk, pv, tables, pos, ids [B, T], n_keep [B],
        keep_from [B], adapter_ids) -> (logits [B, T, V], pk, pv).

        Row t of slot b sits at absolute position pos[b] + t. The
        write DESTINATION redirects to trash block 0 (the module
        invariant) for any row the slot is not keeping: row index
        >= n_keep[b] (a sampled slot keeps only its first row during a
        speculative round, an idle slot none, a prefill tail's pad
        rows none) or absolute position < keep_from[b] (radix HIT
        blocks are other requests' memory — same rule as the
        dynamic-skip insert). Speculative candidate rows ARE kept:
        accepted ones become committed history, rejected ones go
        stale behind the position mask and the next round's verify
        span rewrites them — the dead-write idiom, no second pass.

        Attention per mode mirrors the single-token step bodies:
        gathered runs GptDecoder._block on the contiguous pool view
        (bit-exact reference — row 0's logits are bit-identical to
        the K=1 tick's, which is what pins spec greedy parity);
        blockwise folds the pool through _blockwise_attend_mt;
        pallas calls the block-table-indexed prefill kernel
        (ops/pallas_attention.py::paged_flash_prefill)."""
        dec, bs = self.dec, self.bs
        attention = self.attention
        window = dec.cfg.window
        tp = self._tp_axis()
        if attention == "pallas":
            from defer_tpu.models.gpt import _flash_decode_mode
            from defer_tpu.ops.pallas_attention import (
                paged_flash_prefill,
            )

            interpret = _flash_decode_mode() != "tpu"

        def step(
            params, pk, pv, tables, pos, ids, n_keep, keep_from,
            adapter_ids,
        ):
            b, t = ids.shape
            mb = tables.shape[1]
            rows = jnp.arange(b)
            steps_t = jnp.arange(t)
            pvec = pos[:, None] + steps_t[None, :]  # [B, T]
            # Write destinations: each row's (block, row-in-block),
            # with dropped rows redirected to trash block 0. The
            # block-column clamp keeps headroom rows past the table
            # (only reachable for dead writes) in range.
            blk = tables[
                rows[:, None], jnp.minimum(pvec // bs, mb - 1)
            ]  # [B, T]
            keep = (steps_t[None, :] < n_keep[:, None]) & (
                pvec >= keep_from[:, None]
            )
            dest = jnp.where(keep, blk, 0)
            rowi = pvec % bs
            x = dec._embed_tokens(params, ids, pos, tp)

            if attention == "gathered":

                def body(carry, layer):
                    x = carry
                    p, pk_l, pv_l = layer
                    kc = _pool_gather(pk_l, tables, dec.compute_dtype)
                    vc = _pool_gather(pv_l, tables, dec.compute_dtype)
                    b_, mb_, hkv, _, dh = kc.shape
                    kc = kc.transpose(0, 2, 1, 3, 4).reshape(
                        b_, hkv, mb_ * bs, dh
                    )
                    vc = vc.transpose(0, 2, 1, 3, 4).reshape(
                        b_, hkv, mb_ * bs, dh
                    )
                    out, kc, vc = dec._block(
                        p, x, kc, vc, pos, tp_axis=tp,
                        adapter_ids=adapter_ids,
                    )
                    # Multi-row scatter-back: T fresh rows per slot.
                    new_k = kc[rows[:, None], :, pvec, :]
                    new_v = vc[rows[:, None], :, pvec, :]
                    pk_l = _pool_write_rows_mt(pk_l, dest, rowi, new_k)
                    pv_l = _pool_write_rows_mt(pv_l, dest, rowi, new_v)
                    return out, (pk_l, pv_l)

            elif attention == "blockwise":

                def body(carry, layer):
                    x = carry
                    p, pk_l, pv_l = layer
                    q, k_new, v_new = dec._attn_qkv(
                        p, x, pos, adapter_ids=adapter_ids
                    )  # q [B,Hq,T,Dh]; k/v_new [B,Hkv,T,Dh]
                    # Write-then-attend, like every paged step.
                    pk_l = _pool_write_rows_mt(
                        pk_l, dest, rowi, k_new.transpose(0, 2, 1, 3)
                    )
                    pv_l = _pool_write_rows_mt(
                        pv_l, dest, rowi, v_new.transpose(0, 2, 1, 3)
                    )
                    nb_live = jnp.minimum(
                        (jnp.max(pos) + t - 1) // bs + 1, mb
                    )
                    attn = _blockwise_attend_mt(
                        q, pk_l, pv_l, tables, pos, bs, nb_live,
                        window,
                    )
                    out = dec._attn_out(
                        p, x, attn, tp, adapter_ids=adapter_ids
                    )
                    return out, (pk_l, pv_l)

            else:  # pallas

                def body(carry, layer):
                    x = carry
                    p, pk_l, pv_l = layer
                    q, k_new, v_new = dec._attn_qkv(
                        p, x, pos, adapter_ids=adapter_ids
                    )
                    pk_l = _pool_write_rows_mt(
                        pk_l, dest, rowi, k_new.transpose(0, 2, 1, 3)
                    )
                    pv_l = _pool_write_rows_mt(
                        pv_l, dest, rowi, v_new.transpose(0, 2, 1, 3)
                    )
                    b_, hq, t_, dh = q.shape
                    quantized = isinstance(pk_l, dict)
                    attn = paged_flash_prefill(
                        q,
                        _pool_arr(pk_l),
                        _pool_arr(pv_l),
                        tables,
                        pos,
                        window=window,
                        scale_k=pk_l["s"] if quantized else None,
                        scale_v=pv_l["s"] if quantized else None,
                        interpret=interpret,
                    )  # [B, Hq, T, Dh]
                    attn = (
                        attn.transpose(0, 2, 1, 3)
                        .reshape(b_, t_, hq * dh)
                        .astype(x.dtype)
                    )
                    out = dec._attn_out(
                        p, x, attn, tp, adapter_ids=adapter_ids
                    )
                    return out, (pk_l, pv_l)

            x, (pk, pv) = lax.scan(
                body, x, (params["stack"], pk, pv)
            )
            logits = self._replicate_logits(dec._final_logits(params, x))
            return logits, pk, pv

        return step

    def _build_window(self, mode: str):
        """The fused K-sub-step paged decode program for one sampling
        mode ("argmax" | "nosort" | "sort" — the bit-identical trio
        SlotSampler.draw switches between, picked per window). A
        `lax.scan` over the raw step body (_step_body*) advances every
        row K times per host dispatch; each sub-step zeroes frozen
        rows' position and block-table row (their writes land in trash
        block 0 row 0, the idle-slot invariant), samples on device,
        counts the token against the row's budget, and freezes rows
        that hit eos or budget for the REST of the window. Fixed
        length K — trace-stable regardless of where rows finish.
        Memoized on the decoder (utils/memo.cached_step), where
        analysis/sanitizer.py auto-watches for retraces."""
        from defer_tpu.utils.memo import cached_step

        K = self.decode_window
        eos = self.eos_id
        bodies = {
            "gathered": self._step_body,
            "blockwise": self._step_body_blockwise,
            "pallas": self._step_body_pallas,
        }
        body_builder = bodies[self.attention]

        def build():
            raw = body_builder()

            def window(params, pk, pv, tables, pos, feed, active,
                       keys, temp, topk, topp, minp, budget,
                       adapter_ids):
                def body(carry, _):
                    pk, pv, pos, feed, active, keys, n = carry
                    # Frozen/idle rows: position 0 + all-trash table,
                    # exactly the state _finish leaves a K=1 slot in.
                    pos_eff = jnp.where(active, pos, 0)
                    tab_eff = jnp.where(active[:, None], tables, 0)
                    logits, pk, pv = raw(
                        params, pk, pv, tab_eff, pos_eff, feed,
                        adapter_ids,
                    )
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
                    adv = active.astype(jnp.int32)
                    pos = pos + adv
                    n = n + adv
                    alive = active & (n < budget)
                    if eos is not None:
                        alive = alive & (nxt != eos)
                    feed = nxt[:, None].astype(jnp.int32)
                    return (pk, pv, pos, feed, alive, keys, n), nxt

                init = (
                    pk, pv, pos, feed, active, keys,
                    jnp.zeros_like(budget),
                )
                (pk, pv, pos, feed, alive, keys, n), toks = lax.scan(
                    body, init, None, length=K
                )
                return pk, pv, feed, alive, keys, n, toks.T

            if self.mesh is None:
                return jax.jit(window, donate_argnums=(1, 2))
            # Sharded window: the whole K-sub-step scan runs inside
            # ONE shard_map — per sub-step the raw body all_gathers
            # its vocab slices, so sampling sees replicated post-psum
            # logits and every shard advances the identical feed/keys
            # state (sampler inputs are replicated operands).
            from jax.sharding import PartitionSpec as PSpec

            from defer_tpu.utils.compat import shard_map

            pool, r = self._pool_specs, PSpec()
            sm = shard_map(
                window,
                self.mesh,
                in_specs=(self._sdec._specs(), pool, pool)
                + (r,) * 11,
                out_specs=(pool, pool, r, r, r, r, r),
                # analysis: ignore[shard-spec] same as _jit_tick: scatter-heavy body, replication pinned by the psum mirror
                check_rep=False,
            )
            return jax.jit(sm, donate_argnums=(1, 2))

        return cached_step(
            self.dec,
            ("paged_window", self.bs, self.attention, self.kv_dtype,
             K, mode, eos, self._mesh_key),
            build,
        )

    def _build_window_c(self, mode: str):
        """Constrained variant of the fused paged window: the same
        scan skeleton plus the per-sub-step DFA gather/mask-fold/state
        advance (constrain/runtime.py). A SEPARATE memo key — the
        unconstrained program stays byte-identical to pre-constraint
        builds, and a constrained server pays this trace only while a
        constrained row is live (_tick_window dispatch). On a mesh the
        DFA tables ride in as replicated operands (tiny next to the
        pool) so every shard advances identical constraint state.
        Extra outputs: final DFA states, per-row dead-end flags
        (hand-built DFAs only; the forced-eos token is dropped on
        drain) and the [B, K] masked-fraction buffer for obs."""
        from defer_tpu.utils.memo import cached_step

        K = self.decode_window
        eos = self.eos_id
        bodies = {
            "gathered": self._step_body,
            "blockwise": self._step_body_blockwise,
            "pallas": self._step_body_pallas,
        }
        body_builder = bodies[self.attention]

        def build():
            raw = body_builder()

            def window(params, pk, pv, tables, pos, feed, active,
                       keys, temp, topk, topp, minp, budget,
                       adapter_ids, cid, cstate, ctrans, cacc):
                cvec = cid > 0

                def body(carry, _):
                    (pk, pv, pos, feed, active, keys, n, cstate,
                     died) = carry
                    pos_eff = jnp.where(active, pos, 0)
                    tab_eff = jnp.where(active[:, None], tables, 0)
                    logits, pk, pv = raw(
                        params, pk, pv, tab_eff, pos_eff, feed,
                        adapter_ids,
                    )
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
                    adv = active.astype(jnp.int32)
                    pos = pos + adv
                    n = n + adv
                    alive = active & (n < budget) & (nxt != eos)
                    feed = nxt[:, None].astype(jnp.int32)
                    carry = (
                        pk, pv, pos, feed, alive, keys, n, cstate,
                        died | dead,
                    )
                    return carry, (nxt, frac)

                init = (
                    pk, pv, pos, feed, active, keys,
                    jnp.zeros_like(budget), cstate,
                    jnp.zeros_like(cvec),
                )
                (pk, pv, pos, feed, alive, keys, n, cstate, died), (
                    toks, fracs
                ) = lax.scan(body, init, None, length=K)
                return (
                    pk, pv, feed, alive, keys, n, toks.T, cstate,
                    died, fracs.T,
                )

            if self.mesh is None:
                return jax.jit(window, donate_argnums=(1, 2))
            from jax.sharding import PartitionSpec as PSpec

            from defer_tpu.utils.compat import shard_map

            pool, r = self._pool_specs, PSpec()
            sm = shard_map(
                window,
                self.mesh,
                in_specs=(self._sdec._specs(), pool, pool)
                + (r,) * 15,
                out_specs=(pool, pool, r, r, r, r, r, r, r, r),
                # analysis: ignore[shard-spec] same as _jit_tick: scatter-heavy body, replication pinned by the psum mirror
                check_rep=False,
            )
            return jax.jit(sm, donate_argnums=(1, 2))

        return cached_step(
            self.dec,
            ("paged_window_c", self.bs, self.attention, self.kv_dtype,
             K, mode, eos, self._mesh_key),
            build,
        )

    def _build_spec_window(self, mode: str):
        """The fused spec x decode_window program: W = decode_window
        draft+verify rounds in ONE jitted dispatch. Each scan sub-step
        is a whole speculative round — the DraftLanes propose body
        (decode_server.py::_propose_body) followed by the multi-token
        verify forward (_mt_body) — plus the on-device mirror of the
        host accept test (first proposal/argmax mismatch, then the
        bonus row), eos/budget freezing exactly like _build_window
        (frozen rows pin position 0 and trash-redirect their writes),
        and the pend/lane-position recurrence _tick_spec runs on the
        host between rounds. Greedy rows therefore emit the TARGET's
        own chain token for token; sampled rows draw one token per
        round from the verify forward's row 0 through the same
        batched-sampler trio the plain window uses — streams identical
        to decode_window=1 speculation by construction.

        Per window the host gets ONE batched sync: the [W, B, k+1]
        token buffer plus the small per-round kept/accept vectors that
        drive drain bookkeeping — W rounds (up to W*(k+1) tokens per
        slot) amortize it, vs 2 dispatches + 1 sync per round
        unfused."""
        from defer_tpu.utils.memo import cached_step

        k = self.spec_k
        W = self.decode_window
        eos = self.eos_id
        draft = self._draft

        def build():
            propose_raw = draft._propose_body(k)
            mt_raw = self._mt_body()

            def window(params, pk, pv, dk, dv, dparams, tables, pos,
                       dpos, feed, feed2, adv, active, sampling_row,
                       keys, temp, topk, topp, minp, budget,
                       adapter_ids):
                B = pos.shape[0]
                steps = jnp.arange(k + 1)
                zero_from = jnp.zeros_like(pos)

                def body(carry, _):
                    (pk, pv, dk, dv, pos, dpos, feed, feed2, adv,
                     active, keys, n) = carry
                    greedy = active & ~sampling_row
                    # Draft propose: idle/sampled/frozen lanes pin to
                    # position 0 with adv 0, the idle-lane idiom.
                    dpos_eff = jnp.where(greedy, dpos, 0)
                    adv_eff = jnp.where(greedy, adv, 0)
                    dk, dv, props = propose_raw(
                        dparams, dk, dv, dpos_eff, feed2, adv_eff
                    )
                    # Verify all k+1 candidates; frozen rows write
                    # trash (n_keep 0, position 0, all-trash table).
                    verify_in = jnp.concatenate(
                        [feed, props.astype(jnp.int32)], axis=1
                    )
                    n_keep = jnp.where(
                        active,
                        jnp.where(sampling_row, 1, k + 1),
                        0,
                    ).astype(jnp.int32)
                    pos_eff = jnp.where(active, pos, 0)
                    tab_eff = jnp.where(active[:, None], tables, 0)
                    logits, pk, pv = mt_raw(
                        params, pk, pv, tab_eff, pos_eff, verify_in,
                        n_keep, zero_from, adapter_ids,
                    )
                    preds = jnp.argmax(logits, axis=-1).astype(
                        jnp.int32
                    )
                    # On-device accept test — the batching.py
                    # accept_lengths rule: first props/preds mismatch,
                    # k on full agreement.
                    mismatch = props != preds[:, :k]
                    a = jnp.where(
                        mismatch.any(axis=1),
                        jnp.argmax(mismatch, axis=1),
                        k,
                    ).astype(jnp.int32)
                    bonus = jnp.take_along_axis(
                        preds, a[:, None], axis=1
                    )[:, 0]
                    props_pad = jnp.concatenate(
                        [props, jnp.zeros((B, 1), jnp.int32)], axis=1
                    )
                    toks = jnp.where(
                        steps[None, :] < a[:, None],
                        props_pad,
                        bonus[:, None],
                    )
                    # Sampled rows: one draw per round from row 0 —
                    # the same key/policy stream as the plain paths.
                    ll = logits[:, 0, :]
                    if mode == "argmax":
                        nxt = jnp.argmax(ll, axis=-1).astype(jnp.int32)
                    elif mode == "nosort":
                        nxt, keys = sample_token_batched_nosort(
                            ll, keys, temp, minp
                        )
                    else:
                        nxt, keys = sample_token_batched(
                            ll, keys, temp, topk, topp, minp
                        )
                    nxt = nxt.astype(jnp.int32)
                    toks = jnp.where(
                        sampling_row[:, None], nxt[:, None], toks
                    )
                    cand = jnp.where(sampling_row, 1, a + 1)
                    cand = jnp.where(active, cand, 0)
                    kept = jnp.minimum(
                        cand, jnp.maximum(budget - n, 0)
                    )
                    alive = active
                    if eos is not None:
                        hit = (toks == eos) & (
                            steps[None, :] < kept[:, None]
                        )
                        any_eos = hit.any(axis=1)
                        kept = jnp.where(
                            any_eos,
                            jnp.argmax(hit, axis=1) + 1,
                            kept,
                        )
                        alive = alive & ~any_eos
                    n = n + kept
                    alive = alive & (n < budget)
                    last = jnp.take_along_axis(
                        toks, jnp.maximum(kept - 1, 0)[:, None], axis=1
                    )[:, 0]
                    feed = jnp.where(
                        (kept > 0)[:, None], last[:, None], feed
                    )
                    pos = pos + kept
                    # Continuing greedy rows: partial accept leaves
                    # only the correction token pending (adv 1), full
                    # accept also the never-consumed k-th proposal
                    # (adv 2) — _tick_spec's host recurrence, on
                    # device. Truncated rows froze above, so the
                    # update mask never sees a cut round.
                    full = a == k
                    adv_next = jnp.where(full, 2, 1).astype(jnp.int32)
                    f2a = jnp.where(full, props_pad[:, k - 1], last)
                    upd = alive & ~sampling_row
                    adv = jnp.where(upd, adv_next, adv)
                    feed2 = jnp.where(
                        upd[:, None],
                        jnp.stack([f2a, last], axis=1),
                        feed2,
                    )
                    dpos = jnp.where(upd, pos + 1 - adv_next, dpos)
                    out = (toks, kept, a, greedy, adv_eff)
                    return (
                        (pk, pv, dk, dv, pos, dpos, feed, feed2, adv,
                         alive, keys, n),
                        out,
                    )

                init = (
                    pk, pv, dk, dv, pos, dpos, feed, feed2, adv,
                    active, keys, jnp.zeros_like(budget),
                )
                (
                    (pk, pv, dk, dv, pos, dpos, feed, feed2, adv,
                     alive, keys, n),
                    (toks_a, kept_a, a_a, greedy_a, advu_a),
                ) = lax.scan(body, init, None, length=W)
                return (
                    pk, pv, dk, dv, feed, feed2, adv, alive, keys,
                    toks_a, kept_a, a_a, greedy_a, advu_a,
                )

            if self.mesh is None:
                return jax.jit(window, donate_argnums=(1, 2, 3, 4))
            # Sharded spec window: ONE shard_map around the whole
            # W-round scan. The target verify runs sharded exactly as
            # _ensure_mt's body does; the DRAFT is replicated state —
            # its params, lanes and propose math ride as replicated
            # operands and every shard computes identical proposals
            # (no collectives in the draft forward), so the accept
            # test and sampler advance identically per shard.
            from jax.sharding import PartitionSpec as PSpec

            from defer_tpu.utils.compat import shard_map

            pool, r = self._pool_specs, PSpec()
            sm = shard_map(
                window,
                self.mesh,
                in_specs=(self._sdec._specs(), pool, pool)
                + (r,) * 18,
                out_specs=(pool, pool) + (r,) * 12,
                # analysis: ignore[shard-spec] same as _jit_tick: scatter-heavy body, replication pinned by the psum mirror
                check_rep=False,
            )
            return jax.jit(sm, donate_argnums=(1, 2, 3, 4))

        return cached_step(
            self.dec,
            ("paged_spec_window", self.bs, self.attention,
             self.kv_dtype, W, k, mode, eos, draft.dec.cfg,
             str(draft.dec.compute_dtype), self._mesh_key),
            build,
        )

    def _build_spec_window_c(self, mode: str):
        """Constrained variant of the fused spec window (SEPARATE memo
        key — the unconstrained program stays byte-identical). Each
        scan round swaps in the draft's DFA-masked propose body
        (decode_server.py::_propose_body_c) and replays the
        _constrained_preds target walk in-scan: position j's pred is
        the masked argmax at the state reached via the proposal
        prefix, dead states force the -1 sentinel so the on-device
        accept mirror truncates there, and the emitted correction is
        swapped for a forced eos that freezes the row (the drain
        drops it and surfaces the per-request error — the
        _build_window_c idiom). Committed DFA states ride the carry:
        continuing greedy rows land on the post-state at their accept
        length, sampled rows advance one step by their draw, so the
        next round's draft + target walks resume from exactly the
        states the host would have uploaded between unfused rounds.
        Extra outputs: final states, per-row died flags, and the
        [W, B, k+1] masked-fraction buffer for obs."""
        from defer_tpu.utils.memo import cached_step

        k = self.spec_k
        W = self.decode_window
        eos = self.eos_id
        draft = self._draft

        def build():
            propose_raw = draft._propose_body_c(k, eos)
            mt_raw = self._mt_body()

            def window(params, pk, pv, dk, dv, dparams, tables, pos,
                       dpos, feed, feed2, adv, active, sampling_row,
                       keys, temp, topk, topp, minp, budget,
                       adapter_ids, cid, cstate, ctrans, cacc):
                B = pos.shape[0]
                steps = jnp.arange(k + 1)
                zero_from = jnp.zeros_like(pos)
                cvec = cid > 0

                def body(carry, _):
                    (pk, pv, dk, dv, pos, dpos, feed, feed2, adv,
                     active, keys, n, cstate, died) = carry
                    greedy = active & ~sampling_row
                    dpos_eff = jnp.where(greedy, dpos, 0)
                    adv_eff = jnp.where(greedy, adv, 0)
                    dk, dv, props = propose_raw(
                        dparams, dk, dv, dpos_eff, feed2, adv_eff,
                        cid, cstate, ctrans, cacc,
                    )
                    verify_in = jnp.concatenate(
                        [feed, props.astype(jnp.int32)], axis=1
                    )
                    n_keep = jnp.where(
                        active,
                        jnp.where(sampling_row, 1, k + 1),
                        0,
                    ).astype(jnp.int32)
                    pos_eff = jnp.where(active, pos, 0)
                    tab_eff = jnp.where(active[:, None], tables, 0)
                    logits, pk, pv = mt_raw(
                        params, pk, pv, tab_eff, pos_eff, verify_in,
                        n_keep, zero_from, adapter_ids,
                    )
                    # Target-side constrained walk along the proposal
                    # prefix (_constrained_preds, in-scan).
                    s = cstate
                    preds_l, posts_l = [], []
                    deads_l, fracs_l = [], []
                    crow0 = cmask0 = None
                    for j in range(k + 1):
                        crow_j, acc_j = crt.constrain_rows(
                            ctrans, cacc, cid, s
                        )
                        cmask_j = crt.constrain_mask(crow_j, acc_j, eos)
                        if j == 0:
                            crow0, cmask0 = crow_j, cmask_j
                        dead_j = cvec & ~cmask_j.any(-1)
                        p = jnp.argmax(
                            crt.fold_mask(logits[:, j, :], cmask_j),
                            axis=-1,
                        ).astype(jnp.int32)
                        p = jnp.where(dead_j, -1, p)
                        preds_l.append(p)
                        posts_l.append(
                            crt.advance_state(
                                crow_j, s, jnp.maximum(p, 0),
                                cvec & ~dead_j,
                            )
                        )
                        deads_l.append(dead_j)
                        fracs_l.append(
                            crt.masked_frac(cmask_j, cvec & active)
                        )
                        if j < k:
                            s = crt.advance_state(
                                crow_j, s, props[:, j], cvec
                            )
                    preds = jnp.stack(preds_l, 1)
                    postm = jnp.stack(posts_l, 1)
                    deadm = jnp.stack(deads_l, 1)
                    fracm = jnp.stack(fracs_l, 1)
                    mismatch = props != preds[:, :k]
                    a = jnp.where(
                        mismatch.any(axis=1),
                        jnp.argmax(mismatch, axis=1),
                        k,
                    ).astype(jnp.int32)
                    bonus = jnp.take_along_axis(
                        preds, a[:, None], axis=1
                    )[:, 0]
                    dead_at = jnp.take_along_axis(
                        deadm, a[:, None], axis=1
                    )[:, 0]
                    # The -1 sentinel never enters the stream: the
                    # correction at a dead state becomes a forced eos
                    # that freezes the row; the drain drops it.
                    bonus = jnp.where(dead_at, eos, bonus)
                    props_pad = jnp.concatenate(
                        [props, jnp.zeros((B, 1), jnp.int32)], axis=1
                    )
                    toks = jnp.where(
                        steps[None, :] < a[:, None],
                        props_pad,
                        bonus[:, None],
                    )
                    ll = crt.fold_mask(logits[:, 0, :], cmask0)
                    if mode == "argmax":
                        nxt = jnp.argmax(ll, axis=-1).astype(jnp.int32)
                    elif mode == "nosort":
                        nxt, keys = sample_token_batched_nosort(
                            ll, keys, temp, minp
                        )
                    else:
                        nxt, keys = sample_token_batched(
                            ll, keys, temp, topk, topp, minp
                        )
                    nxt = nxt.astype(jnp.int32)
                    nxt = jnp.where(deadm[:, 0], eos, nxt)
                    toks = jnp.where(
                        sampling_row[:, None], nxt[:, None], toks
                    )
                    cand = jnp.where(sampling_row, 1, a + 1)
                    cand = jnp.where(active, cand, 0)
                    kept = jnp.minimum(
                        cand, jnp.maximum(budget - n, 0)
                    )
                    alive = active
                    hit = (toks == eos) & (
                        steps[None, :] < kept[:, None]
                    )
                    any_eos = hit.any(axis=1)
                    kept = jnp.where(
                        any_eos,
                        jnp.argmax(hit, axis=1) + 1,
                        kept,
                    )
                    alive = alive & ~any_eos
                    # died only when the forced eos actually made the
                    # kept prefix (an earlier natural eos or a budget
                    # cut ends the row without the error).
                    fpos = jnp.where(sampling_row, 0, a)
                    died_now = jnp.where(
                        sampling_row, deadm[:, 0], dead_at
                    )
                    died_now = (
                        died_now & active & (kept == fpos + 1)
                    )
                    n = n + kept
                    alive = alive & (n < budget)
                    last = jnp.take_along_axis(
                        toks, jnp.maximum(kept - 1, 0)[:, None], axis=1
                    )[:, 0]
                    feed = jnp.where(
                        (kept > 0)[:, None], last[:, None], feed
                    )
                    pos = pos + kept
                    full = a == k
                    adv_next = jnp.where(full, 2, 1).astype(jnp.int32)
                    f2a = jnp.where(full, props_pad[:, k - 1], last)
                    upd = alive & ~sampling_row
                    adv = jnp.where(upd, adv_next, adv)
                    feed2 = jnp.where(
                        upd[:, None],
                        jnp.stack([f2a, last], axis=1),
                        feed2,
                    )
                    dpos = jnp.where(upd, pos + 1 - adv_next, dpos)
                    # Commit DFA states for rows continuing past the
                    # round (alive greedy rows always kept a + 1, so
                    # the post-state column at a IS the state after
                    # the round's last emitted token).
                    post_a = jnp.take_along_axis(
                        postm, a[:, None], axis=1
                    )[:, 0]
                    cstate = jnp.where(upd & cvec, post_a, cstate)
                    cstate = crt.advance_state(
                        crow0, cstate, nxt,
                        alive & sampling_row & cvec,
                    )
                    died = died | died_now
                    out = (toks, kept, a, greedy, adv_eff, fracm)
                    return (
                        (pk, pv, dk, dv, pos, dpos, feed, feed2, adv,
                         alive, keys, n, cstate, died),
                        out,
                    )

                init = (
                    pk, pv, dk, dv, pos, dpos, feed, feed2, adv,
                    active, keys, jnp.zeros_like(budget), cstate,
                    jnp.zeros_like(cvec),
                )
                (
                    (pk, pv, dk, dv, pos, dpos, feed, feed2, adv,
                     alive, keys, n, cstate, died),
                    (toks_a, kept_a, a_a, greedy_a, advu_a, fracs_a),
                ) = lax.scan(body, init, None, length=W)
                return (
                    pk, pv, dk, dv, feed, feed2, adv, alive, keys,
                    toks_a, kept_a, a_a, greedy_a, advu_a, cstate,
                    died, fracs_a,
                )

            if self.mesh is None:
                return jax.jit(window, donate_argnums=(1, 2, 3, 4))
            from jax.sharding import PartitionSpec as PSpec

            from defer_tpu.utils.compat import shard_map

            pool, r = self._pool_specs, PSpec()
            sm = shard_map(
                window,
                self.mesh,
                in_specs=(self._sdec._specs(), pool, pool)
                + (r,) * 22,
                out_specs=(pool, pool) + (r,) * 15,
                # analysis: ignore[shard-spec] same as _jit_tick: scatter-heavy body, replication pinned by the psum mirror
                check_rep=False,
            )
            return jax.jit(sm, donate_argnums=(1, 2, 3, 4))

        return cached_step(
            self.dec,
            ("paged_spec_window_c", self.bs, self.attention,
             self.kv_dtype, W, k, mode, eos, draft.dec.cfg,
             str(draft.dec.compute_dtype), self._mesh_key),
            build,
        )

    def _pool_constraint(self, *arrays):
        """Pin pool-layout (or flat-lane) outputs of the plain-jit
        data-movement programs (insert / gather / import) to the
        KV-head sharding when serving on a mesh: the programs stay
        ordinary GSPMD jits — XLA partitions the scatters — but the
        constraint stops the partitioner from ever materializing a
        gathered pool. No-op on mesh=None. All these layouts carry
        their head axis at index 2 — rank picks between the 5-D
        pool/lane spec and the 3-D int8 scale spec, and a {"q","s"}
        pool pytree pins per leaf."""
        if self.mesh is None:
            return arrays if len(arrays) > 1 else arrays[0]
        from jax.sharding import NamedSharding

        pool_sh = NamedSharding(self.mesh, self._pool_spec)
        head_sh = NamedSharding(self.mesh, self._head_spec)

        def pin(leaf):
            sh = head_sh if leaf.ndim == 3 else pool_sh
            return lax.with_sharding_constraint(leaf, sh)

        out = tuple(jax.tree.map(pin, a) for a in arrays)
        return out if len(out) > 1 else out[0]

    def _build_insert(self, skip: int = 0):
        bs = self.bs

        @jax.named_scope("kv_insert")
        def insert(pk, pv, small_k, small_v, table_row):
            """Scatter a contiguous single-request prefill cache
            ([L, 1, Hkv, S, Dh]) into this request's pool blocks.
            Rows beyond the prompt are garbage the position mask
            hides; unowned table entries point at trash block 0, so
            their writes land in scrap by the module invariant (no
            masking needed — duplicate trash writes just race over
            garbage)."""
            mb = table_row.shape[0]
            s_need = mb * bs
            k_rows = small_k[:, 0]  # [L, Hkv, S, Dh]
            v_rows = small_v[:, 0]
            pad = s_need - k_rows.shape[2]
            if pad > 0:
                k_rows = jnp.pad(
                    k_rows, ((0, 0), (0, 0), (0, pad), (0, 0))
                )
                v_rows = jnp.pad(
                    v_rows, ((0, 0), (0, 0), (0, pad), (0, 0))
                )
            else:
                k_rows = k_rows[:, :, :s_need]
                v_rows = v_rows[:, :, :s_need]
            L, hkv, _, dh = k_rows.shape
            k_blocks = k_rows.reshape(L, hkv, mb, bs, dh).transpose(
                0, 2, 1, 3, 4
            )  # [L, MB, Hkv, bs, Dh]
            v_blocks = v_rows.reshape(L, hkv, mb, bs, dh).transpose(
                0, 2, 1, 3, 4
            )
            # skip > 0 = shared-prefix mode: never write the shared
            # blocks (their rows in the small cache are identical by
            # construction, but they are not this request's to touch).
            dest = table_row[skip:]
            if isinstance(pk, dict):
                # Quantize as the blocks land. Lane rows past the
                # prompt are ZEROS here (flat prefill writes into an
                # init_cache-zeroed lane), so the block scales see
                # only real content.
                kq, ks = _quantize_blocks(k_blocks[:, skip:])
                vq, vs = _quantize_blocks(v_blocks[:, skip:])
                pk = {
                    "q": pk["q"].at[:, dest].set(kq),
                    "s": pk["s"].at[:, dest].set(ks),
                }
                pv = {
                    "q": pv["q"].at[:, dest].set(vq),
                    "s": pv["s"].at[:, dest].set(vs),
                }
            else:
                pk = pk.at[:, dest].set(k_blocks[:, skip:])
                pv = pv.at[:, dest].set(v_blocks[:, skip:])
            return self._pool_constraint(pk, pv)

        return jax.jit(insert, donate_argnums=(0, 1))

    def _build_insert_dynamic(self):
        """The radix variant of _build_insert: `skip` is a RUNTIME
        scalar (per-admission hit count), so one compiled program
        serves every skip value. Leading hit blocks are not this
        request's to touch — and their recomputed rows are only
        equivalent, not guaranteed bit-identical, so rewriting them
        would perturb concurrent readers — hence their writes are
        redirected to trash block 0 (duplicate trash writes race over
        garbage, by the module invariant).

        `valid` (runtime scalar, int8 pools only) — the count of REAL
        lane rows. A radix admission's lane is gathered from the pool,
        so rows past the prompt are a previous tenant's garbage (not
        the zeros a flat-prefill lane carries); folding them into a
        block's amax would inflate its scale and crush the live rows'
        precision, so the int8 path zeroes rows >= valid before
        quantizing. The fp path ignores it (garbage hides behind the
        position mask, and touching it would break bit-identity)."""
        bs = self.bs

        def insert(pk, pv, small_k, small_v, table_row, skip, valid):
            mb = table_row.shape[0]
            s_need = mb * bs
            k_rows = small_k[:, 0]
            v_rows = small_v[:, 0]
            pad = s_need - k_rows.shape[2]
            if pad > 0:
                k_rows = jnp.pad(
                    k_rows, ((0, 0), (0, 0), (0, pad), (0, 0))
                )
                v_rows = jnp.pad(
                    v_rows, ((0, 0), (0, 0), (0, pad), (0, 0))
                )
            else:
                k_rows = k_rows[:, :, :s_need]
                v_rows = v_rows[:, :, :s_need]
            L, hkv, _, dh = k_rows.shape
            if isinstance(pk, dict):
                live = (jnp.arange(s_need) < valid).astype(
                    k_rows.dtype
                )
                k_rows = k_rows * live[None, None, :, None]
                v_rows = v_rows * live[None, None, :, None]
            k_blocks = k_rows.reshape(L, hkv, mb, bs, dh).transpose(
                0, 2, 1, 3, 4
            )
            v_blocks = v_rows.reshape(L, hkv, mb, bs, dh).transpose(
                0, 2, 1, 3, 4
            )
            dest = jnp.where(jnp.arange(mb) >= skip, table_row, 0)
            if isinstance(pk, dict):
                kq, ks = _quantize_blocks(k_blocks)
                vq, vs = _quantize_blocks(v_blocks)
                pk = {
                    "q": pk["q"].at[:, dest].set(kq),
                    "s": pk["s"].at[:, dest].set(ks),
                }
                pv = {
                    "q": pv["q"].at[:, dest].set(vq),
                    "s": pv["s"].at[:, dest].set(vs),
                }
            else:
                pk = pk.at[:, dest].set(k_blocks)
                pv = pv.at[:, dest].set(v_blocks)
            return self._pool_constraint(pk, pv)

        return jax.jit(insert, donate_argnums=(0, 1))

    def _build_insert_state(self):
        """Jitted (state pools, a one-request prefill's final states
        [Ll, 1, ...], slot) -> pools with the slot's row of every
        recurrent layer overwritten, in place."""

        @jax.named_scope("state_insert")
        def insert(ps, pc, s, c, slot):
            return ps.at[:, slot].set(s[:, 0]), pc.at[:, slot].set(c[:, 0])

        return jax.jit(insert, donate_argnums=(0, 1))

    def _build_gather(self):
        """Jitted (pool_k, pool_v, table_row [MB]) -> flat single-lane
        K/V ([L, 1, Hkv, MB*bs, Dh]) — the exact inverse layout of
        _build_insert, used by radix admissions to hand cached prefix
        blocks to the flat suffix-prefill step. Reads the pool in
        place (no donation: the pool stays live)."""
        def gather(pk, pv, table_row):
            if isinstance(pk, dict):
                # Dequantize at the gather: the flat suffix-prefill
                # step downstream only ever sees compute-dtype lanes.
                kc = dequantize_symmetric(
                    pk["q"][:, table_row],
                    pk["s"][:, table_row][..., None, None],
                    self.dec.compute_dtype,
                )
                vc = dequantize_symmetric(
                    pv["q"][:, table_row],
                    pv["s"][:, table_row][..., None, None],
                    self.dec.compute_dtype,
                )
            else:
                kc = pk[:, table_row]  # [L, MB, Hkv, bs, Dh]
                vc = pv[:, table_row]
            L, mb, hkv, bs, dh = kc.shape
            kc = kc.transpose(0, 2, 1, 3, 4).reshape(
                L, 1, hkv, mb * bs, dh
            )
            vc = vc.transpose(0, 2, 1, 3, 4).reshape(
                L, 1, hkv, mb * bs, dh
            )
            return self._pool_constraint(kc, vc)

        return jax.jit(gather)

    def _prefill_paged(
        self, prompt, table_row, *, base, keep_from, adapter_id
    ):
        """Chunked POOL-NATIVE prefill: run `prompt` through the
        multi-token paged step in prefill_chunk-token chunks, writing
        K/V straight into the allocated blocks through the block
        table — no contiguous max_len lane, no insert pass, and with
        blockwise/pallas attention each chunk reads only the LIVE
        span (accounted per chunk, pool-size independent). Returns
        the [1, V] logits row of the LAST real prompt position (the
        first generated token samples from it).

        `base` — absolute position of prompt[:, 0] (the global
        prefix_ids length, or a radix walk's reuse point); `keep_from`
        — positions below it write to trash block 0 (radix HIT blocks
        already hold those rows and belong to every chain holder).
        Tail chunks pow2-pad, capped so the deepest write stays
        inside the table span (the gathered path's contiguous-lane
        write must never clamp)."""
        mt = self._ensure_mt() if self.pp == 1 else None
        # pp admission is ALWAYS pool-native: with prefill_chunk unset
        # the whole prompt rides one pow2-padded chunk through the
        # stage chain (the cap below bounds it to the table span).
        C = (
            self.prefill_chunk
            if self.prefill_chunk is not None
            else self.MB * self.bs
        )
        t0 = prompt.shape[1]
        tab = jnp.asarray(table_row[None, :].copy())
        adapter = jnp.full((1,), adapter_id, jnp.int32)
        kf = jnp.asarray([keep_from], jnp.int32)
        limit = self.MB * self.bs
        logits_row = None
        start = 0
        while start < t0:
            real = min(C, t0 - start)
            pos0 = base + start
            pad_t = 1 << (real - 1).bit_length()
            pad_t = min(max(pad_t, 1), min(C, limit - pos0))
            chunk = prompt[:, start : start + real]
            if pad_t > real:
                chunk = jnp.concatenate(
                    [chunk, jnp.zeros((1, pad_t - real), chunk.dtype)],
                    axis=1,
                )
            if self.pp > 1:
                # The chunk flows through the stage chain; each stage
                # scatters its own layers' K/V into its pool slice.
                x = chunk.astype(jnp.int32)
                pos_a = jnp.asarray([pos0], jnp.int32)
                nk = jnp.asarray([real], jnp.int32)
                for s, stage in enumerate(self._pp_stage_objs):
                    x = stage.pp_dispatch(tab, pos_a, x, nk, kf, adapter)
                    self.pp_stage_dispatch_n[s] += 1
                    self.obs.pp_stage_dispatches[s].inc()
                logits = x
            else:
                logits, self.pool_k, self.pool_v = mt(
                    self.params,
                    self.pool_k,
                    self.pool_v,
                    tab,
                    jnp.asarray([pos0], jnp.int32),
                    chunk.astype(jnp.int32),
                    jnp.asarray([real], jnp.int32),
                    kf,
                    adapter,
                )
            self._account_kv_rows_prefill(pos0, pad_t)
            self._account_psums(1)
            # Serialized-prefill interference: this chunk dispatch ran
            # INSTEAD of a decode tick for every live slot
            # (prefill_budget= admits through _tick_mixed and never
            # reaches here with decode slots live).
            self._note_prefill_stall(1)
            logits_row = logits[:, real - 1, :]
            start += real
        if self.pp > 1:
            # The sampler's state lives on the default device; commit
            # the last stage's logits row there so admission-side
            # first-token draws stay single-device (async transfer).
            logits_row = jax.device_put(logits_row, jax.devices()[0])
        return logits_row

    def _account_kv_rows_prefill(self, pos0: int, t: int) -> None:
        """Pool rows one prefill chunk's attention read (same
        units/contract as the decode-tick accounting): a B=1
        multi-token step whose deepest query row attends at
        pos0 + t - 1. Everything here derives from max_len (MB) and
        the chunk's live span — NEVER from pool size, the property
        the chunked-prefill acceptance test pins."""
        bs = self.bs
        baseline = self.MB * bs
        if self.attention == "gathered":
            rows_read = baseline
        elif self.attention == "blockwise":
            rows_read = ((pos0 + t - 1) // bs + 1) * bs
        else:  # pallas
            win = self.dec.cfg.window
            hi = (pos0 + t - 1) // bs
            lo = max(pos0 - win + 1, 0) // bs if win is not None else 0
            rows_read = (hi - lo + 1) * bs
        self._account_kv_rows(rows_read, baseline)

    def _spill_block(self, key: bytes, tok: bytes, blk: int) -> None:
        """PrefixBlockCache on_evict hook (serving thread): dispatch
        ASYNC device slices of the block being evicted and hand them
        to the spill drain thread. The slices are fresh buffers cut
        before any later donating dispatch can invalidate the pool;
        the blocking device->host copy happens on the drain thread
        (HostKVSpill._drain_loop), never here — eviction sits inside
        the admission/tick hot path."""
        b = blk  # python int: keepdim slice, no host round-trip
        if isinstance(self.pool_k, dict):
            arrays = (
                self.pool_k["q"][:, b : b + 1],
                self.pool_k["s"][:, b : b + 1],
                self.pool_v["q"][:, b : b + 1],
                self.pool_v["s"][:, b : b + 1],
            )
        else:
            arrays = (
                self.pool_k[:, b : b + 1],
                self.pool_v[:, b : b + 1],
            )
        self._spill.offer(key, tok, arrays)

    def _ensure_spill_up(self):
        """One-block pool upload for spill revival: scatter a stored
        block payload (int8 q + scales, or fp rows) back into block
        `blk`. Memoized like every paged program; donates the pool."""
        if self._spill_up is None:
            from defer_tpu.utils.memo import cached_step

            def build():
                def up(pk, pv, *rest):
                    if isinstance(pk, dict):
                        kq, ks, vq, vs, blk = rest
                        pk = {
                            "q": pk["q"].at[:, blk].set(kq[:, 0]),
                            "s": pk["s"].at[:, blk].set(ks[:, 0]),
                        }
                        pv = {
                            "q": pv["q"].at[:, blk].set(vq[:, 0]),
                            "s": pv["s"].at[:, blk].set(vs[:, 0]),
                        }
                    else:
                        kb, vb, blk = rest
                        pk = pk.at[:, blk].set(kb[:, 0])
                        pv = pv.at[:, blk].set(vb[:, 0])
                    return self._pool_constraint(pk, pv)

                return jax.jit(up, donate_argnums=(0, 1))

            self._spill_up = cached_step(
                self.dec,
                (
                    "paged_spill_up", self.bs, self.kv_dtype,
                    self._mesh_key,
                ),
                build,
            )
        return self._spill_up

    def _revive_spilled(
        self,
        hits: list[int],
        keys: list[bytes],
        toks: list[bytes],
        n_full: int,
    ) -> list[int]:
        """Extend a radix walk's leading hit run from the host spill
        tier: for each miss position, look up the chain digest in the
        spill store and, on a (token-byte-guarded) hit, re-upload the
        EXACT stored payload into a newly allocated block and register
        it. Raw-byte upload means a revived block is bit-identical to
        the parked block it was spilled from — dequantizing and
        re-quantizing instead could perturb values where round(x/s)
        landed on a clip boundary — which is what makes a spill hit
        token-identical to a resident hit. Stops at the first store
        miss (chain order is mandatory: block j is meaningless without
        0..j-1) or when the pool can't yield a block."""
        j = len(hits)
        while j < n_full:
            got = self._spill.get(keys[j], toks[j])
            if got is None:
                break
            if not self.free:
                self.free.extend(self.radix.evict(1))
                if not self.free:
                    break
            blk = self.free.pop()
            up = self._ensure_spill_up()
            if isinstance(self.pool_k, dict):
                kq, ks, vq, vs = got
                self.pool_k, self.pool_v = up(
                    self.pool_k,
                    self.pool_v,
                    self._shard_ingest(kq),
                    self._shard_ingest(ks),
                    self._shard_ingest(vq),
                    self._shard_ingest(vs),
                    jnp.asarray(blk, jnp.int32),
                )
            else:
                kb, vb = got
                self.pool_k, self.pool_v = up(
                    self.pool_k,
                    self.pool_v,
                    self._shard_ingest(kb),
                    self._shard_ingest(vb),
                    jnp.asarray(blk, jnp.int32),
                )
            displaced = self.radix.register(keys[j], toks[j], blk)
            if displaced is not None:
                self.free.append(displaced)
            hits.append(blk)
            self.spill_hits_n += 1
            self.obs.prefix_spill_hits.inc()
            j += 1
        return hits

    def _admit_radix(
        self, i, rid, prompt, steps, adapter_id, samp, stop_seqs,
        cid=0,
    ) -> bool:
        """Admission through the PrefixBlockCache: walk leading full
        prompt blocks for hits (refcount++), allocate the rest
        (evicting parked refcount-0 blocks only under pressure),
        gather the hit blocks into a flat lane, prefill ONLY the
        suffix, then publish this request's fresh full prompt blocks
        for future hits. Returns False (request waits, refcounts
        rolled back) when even eviction cannot cover the need."""
        bs = self.bs
        t0 = prompt.shape[1]
        tokens = np.asarray(prompt)[0]
        n_full = t0 // bs
        total = -(-(t0 + steps) // bs)
        hits, keys, toks = self.radix.walk(tokens, n_full, bs)
        if self._spill is not None and len(hits) < n_full:
            hits = self._revive_spilled(hits, keys, toks, n_full)
        need = total - len(hits)
        if need > len(self.free):
            self.free.extend(
                self.radix.evict(need - len(self.free))
            )
        if need > len(self.free):
            for blk in hits:
                self.radix.release(blk)
            return False
        own = [self.free.pop() for _ in range(need)]
        self.obs.requests_admitted.inc()
        self.obs.prefix_hits.inc(len(hits))
        self.obs.prefix_misses.inc(n_full - len(hits))
        # Strict lookup: an unknown rid would silently observe a zero
        # queue wait — admission without a submit timestamp is a bug.
        self.obs.queue_wait.observe(
            time.perf_counter() - self._submit_t[rid]
        )
        self._build()
        table_row = np.zeros((self.MB,), np.int32)
        for j, blk in enumerate(hits + own):
            table_row[j] = blk
        # Reuse at most t0-1 cached positions: the LAST prompt token
        # must go through the step so its logits exist to sample the
        # first generated token (its K/V row is rewritten with
        # identical content).
        suffix_pos = min(len(hits) * bs, t0 - 1)
        suffix = prompt[:, suffix_pos:]
        ts = suffix.shape[1]
        self.obs.prefill_tokens.inc(ts)
        if self.prefill_chunk is not None or self.pp > 1:
            # Pool-native chunked prefill: the hit blocks are read
            # straight from the pool by the block-table attention (no
            # gather into a flat lane), fresh rows scatter into this
            # request's blocks as each chunk computes, and writes
            # below keep_from (HIT rows, other holders' memory)
            # redirect to trash — the dynamic-skip rule, applied per
            # row instead of per block.
            logits_row = self._prefill_paged(
                suffix,
                table_row,
                base=suffix_pos,
                keep_from=len(hits) * bs,
                adapter_id=adapter_id,
            )
        else:
            if hits:
                gk, gv = self._gather(
                    self.pool_k, self.pool_v, jnp.asarray(table_row)
                )
                small = {
                    "k": gk,
                    "v": gv,
                    "pos": jnp.asarray(suffix_pos, jnp.int32),
                }
            else:
                small = self._flat_dec().init_cache(1)
            pad = 1 << (ts - 1).bit_length()
            pad = min(pad, self.dec.cfg.max_len - suffix_pos)
            padded = jnp.concatenate(
                [suffix, jnp.zeros((1, pad - ts), prompt.dtype)],
                axis=1,
            )
            logits, small = self._flat_dec().make_step()(
                self.params, small, padded
            )
            self._account_psums(1)
            self._note_prefill_stall(1)
            # Dynamic-skip insert: hit blocks are never rewritten
            # (their recomputed rows are equivalent but not guaranteed
            # bit-identical, and they belong to every other holder of
            # the chain); fresh rows land in this request's blocks;
            # unowned tail entries point at trash by the module
            # invariant.
            self.pool_k, self.pool_v = self._insert_dyn(
                self.pool_k,
                self.pool_v,
                small["k"],
                small["v"],
                jnp.asarray(table_row),
                jnp.asarray(len(hits), jnp.int32),
                jnp.asarray(t0, jnp.int32),
            )
            logits_row = logits[:, ts - 1, :]
        for j in range(len(hits), n_full):
            displaced = self.radix.register(
                keys[j], toks[j], int(table_row[j])
            )
            if displaced is not None:
                self.free.append(displaced)
        shared = hits + [int(table_row[j]) for j in range(len(hits), n_full)]
        owned = [int(table_row[j]) for j in range(n_full, total)]
        self.prefill_tokens_saved += suffix_pos
        self.blocks_peak = max(self.blocks_peak, self.blocks_in_use)
        first = self._first_token(
            i, samp, logits_row, prompt.dtype, cid
        )
        self.tables[i] = table_row
        self.pos[i] = t0
        self.adapter[i] = adapter_id
        slot = {
            "rid": rid,
            "remaining": steps - 1,
            "prompt": prompt,
            "out": [],
            "blocks": owned,
            "shared": shared,
            "sampling": samp is not None,
            "stop": matcher_or_none(stop_seqs),
            "cid": cid,
        }
        self.slots[i] = slot
        if self._draft is not None and not slot["sampling"]:
            # Seed speculation: the first token anchors the pend list
            # (it is emitted but not yet in any K/V), and the draft
            # lane prefills the FULL prompt — radix hits are a pool
            # concept the draft does not share.
            slot["pend"] = [int(first[0, 0])]
            self._draft.admit(i, prompt)
        self._feed = self._feed.at[i].set(first[0].astype(jnp.int32))
        # ttft spans queue + prefill (popped here, the drain point —
        # entries must not outlive their request).
        self.obs.ttft.observe(
            time.perf_counter() - self._submit_t.pop(rid)
        )
        self._update_pool_gauges()
        self._emit_token(i, slot, self._first_on_host(slot, first))
        return True

    def _ensure_insert_dyn(self):
        """The dynamic-skip insert is built lazily for radix servers
        (_build); externally prefilled admission needs it regardless
        of prefix_cache (skip = radix hit count, or 0), under the
        same memo key so the two users share one compile."""
        if self._insert_dyn is None:
            from defer_tpu.utils.memo import cached_step

            self._insert_dyn = cached_step(
                self.dec,
                (
                    "paged_insert_dyn", self.bs, self.kv_dtype,
                    self._mesh_key,
                ),
                self._build_insert_dynamic,
            )
        return self._insert_dyn

    def _blocks_to_lane(self, blocks: np.ndarray) -> jax.Array:
        """[L, n, Hkv, bs, Dh] block stack -> the flat [L, 1, Hkv, S,
        Dh] lane the insert programs take, zero-padded up to a pow2
        block count (capped at MB) so ingest admissions draw from the
        same bounded compile-shape set as pow2-padded prefill."""
        L, n, hkv, bs, dh = blocks.shape
        n_pad = 1 << max(n - 1, 0).bit_length()
        n_pad = min(max(n_pad, 1), self.MB)
        if n_pad > n:
            blocks = np.concatenate(
                [
                    blocks,
                    np.zeros((L, n_pad - n, hkv, bs, dh), blocks.dtype),
                ],
                axis=1,
            )
        lane = blocks.transpose(0, 2, 1, 3, 4).reshape(
            L, hkv, n_pad * bs, dh
        )
        # Under a mesh this is the disagg TP-ingest scatter: the wire
        # blob carries all kv heads, and the head-sharded device_put
        # slices each shard's heads out at ingest (wire unchanged).
        return self._shard_ingest(lane[:, None])

    def _admit_prefilled(self, i: int, rid: int, entry: dict) -> bool:
        """Seat a request whose KV arrived from a prefill worker:
        no prefill step runs here — the delivered block stacks scatter
        straight into allocated pool blocks (dynamic-skip insert, so
        radix HIT blocks are never rewritten), the first token is
        drawn from the shipped logits row, and fresh full prompt
        blocks register in the radix cache exactly like locally
        prefilled ones (cross-host prefix sharing: a later LOCAL
        request can hit blocks this host never prefilled). Returns
        False when the pool can't cover the request even after
        eviction (it stays pending)."""
        prompt = entry["prompt"]
        steps = entry["steps"]
        samp = entry["samp"]
        k_blocks, v_blocks, first_logits = entry["kv"]
        bs = self.bs
        t0 = prompt.shape[1]
        n_full = t0 // bs
        total = -(-(t0 + steps) // bs)
        if self.radix is not None:
            hits, keys, toks = self.radix.walk(prompt[0], n_full, bs)
        else:
            hits, keys, toks = [], [], []
        need = total - len(hits)
        if self.radix is not None and need > len(self.free):
            self.free.extend(self.radix.evict(need - len(self.free)))
        if need > len(self.free):
            for blk in hits:
                self.radix.release(blk)
            return False
        own = [self.free.pop() for _ in range(need)]
        self.obs.requests_admitted.inc()
        if self.radix is not None:
            self.obs.prefix_hits.inc(len(hits))
            self.obs.prefix_misses.inc(n_full - len(hits))
        # Strict lookup: an unknown rid would silently observe a zero
        # queue wait — admission without a submit timestamp is a bug.
        self.obs.queue_wait.observe(
            time.perf_counter() - self._submit_t[rid]
        )
        self._build()
        insert_dyn = self._ensure_insert_dyn()
        table_row = np.zeros((self.MB,), np.int32)
        for j, blk in enumerate(hits + own):
            table_row[j] = blk
        self.pool_k, self.pool_v = insert_dyn(
            self.pool_k,
            self.pool_v,
            self._blocks_to_lane(k_blocks),
            self._blocks_to_lane(v_blocks),
            jnp.asarray(table_row),
            jnp.asarray(len(hits), jnp.int32),
            jnp.asarray(t0, jnp.int32),
        )
        if self.radix is not None:
            for j in range(len(hits), n_full):
                displaced = self.radix.register(
                    keys[j], toks[j], int(table_row[j])
                )
                if displaced is not None:
                    self.free.append(displaced)
            shared = hits + [
                int(table_row[j]) for j in range(len(hits), n_full)
            ]
            owned = [int(table_row[j]) for j in range(n_full, total)]
            self.blocks_peak = max(self.blocks_peak, self.blocks_in_use)
        else:
            shared = None
            owned = own
            self.blocks_peak = max(
                self.blocks_peak, self.blocks_in_use + need
            )
        first = self._first_token(
            i, samp, jnp.asarray(first_logits), jnp.int32,
            entry.get("cid", 0),
        )
        self.tables[i] = table_row
        self.pos[i] = t0
        self.adapter[i] = 0
        slot = {
            "rid": rid,
            "remaining": steps - 1,
            "prompt": prompt,
            "out": [],
            "blocks": owned,
            "sampling": samp is not None,
            "stop": matcher_or_none(entry["stop"]),
            "cid": entry.get("cid", 0),
        }
        if shared is not None:
            slot["shared"] = shared
        self.slots[i] = slot
        if self._draft is not None and samp is None:
            # The delivered KV covers only the TARGET; the draft lane
            # re-prefills locally from the prompt ids (the draft never
            # saw this prompt on the prefill worker, and shipping its
            # tiny K/V would cost more coordination than recompute).
            slot["pend"] = [int(first[0, 0])]
            self._draft.admit(i, jnp.asarray(prompt))
        self._feed = self._feed.at[i].set(first[0].astype(jnp.int32))
        # ttft spans queue + prefill (popped here, the drain point —
        # entries must not outlive their request).
        self.obs.ttft.observe(
            time.perf_counter() - self._submit_t.pop(rid)
        )
        self._update_pool_gauges()
        self._emit_token(i, slot, self._first_on_host(slot, first))
        return True

    def _first_token(self, i, samp, lrow, dtype, cid):
        """Admission's first generated token (the flat server's twin):
        constrained slots mask the prefill logits row with their DFA's
        START-state row before the shared argmax/first-draw, then
        install the advanced state (a device scalar — admission stays
        sync-free beyond its existing bookkeeping)."""
        if cid:
            row = self._ctrans[cid, 0]
            mask = (row >= 0).at[self.eos_id].set(self._cacc[cid, 0])
            lrow = jnp.where(
                mask[None, :], lrow, jnp.finfo(lrow.dtype).min
            )
        first = self._sampler.admit_first(i, samp, lrow, dtype)
        if cid:
            state = jnp.maximum(row[first[0, 0].astype(jnp.int32)], 0)
            self._sampler.admit_constraint(i, cid, state)
            frac = crt.masked_frac(mask[None, :], jnp.asarray([True]))
            # analysis: ignore[host-sync-in-hot-loop] once per
            # CONSTRAINED admission (first token only), not per tick —
            # mixed-mode flips route here but a flip happens once per
            # request; the steady-state tick never reaches this branch
            self.obs.constrain_masked_frac.observe(float(frac[0]))
            self.obs.constrained_tokens.inc()
            self.constrained_tokens_n += 1
        return first

    def _first_on_host(self, slot: dict, first) -> int | tuple:
        """Admission's first token as `_emit_token` takes it: its value
        where speculation has read it or eos, streaming or a stop
        sequence consumes it (same guard as `_tick`), else where it
        lies, so that the plain path stays async."""
        if "pend" in slot:
            return slot["pend"][0]
        if (
            self.eos_id is not None
            or self.on_token is not None
            or slot["stop"] is not None
        ):
            # analysis: ignore[host-sync-in-hot-loop] one scalar
            # transfer per REQUEST (its first token; a mixed tick's
            # flip comes here too), and only for a consumer
            return int(first[0, 0])
        return first, (0, 0)

    def _constrained_preds(self, logits, props, k):
        """Target-side constrained greedy walk for one speculative
        round: position j's pred is the masked argmax at state s_j,
        where s_{j+1} = trans[s_j, props_j] follows the PROPOSAL
        chain — exactly the states the committed stream would visit
        if the proposals are accepted, so the accept test truncates
        at the first proposal the target's mask rejects and the
        output stays token-identical to the spec_k=0 constrained
        chain. Dead states force pred to -1 (out of vocab): never
        accepted, and the host drain drops the correction with a
        per-request error. All device jnp (gathers per position) —
        no host DFA lookups; runs eagerly alongside the eager argmax
        it replaces. Returns (preds [B,k+1], crow0, cmask0,
        post_states [B,k+1] = state AFTER committing pred_j,
        dead [B,k+1], fracs [B,k+1])."""
        sm = self._sampler
        cvec = jnp.asarray(sm.row_constrained)
        s = sm.cstate
        preds, posts, deads, fracs = [], [], [], []
        crow0 = cmask0 = None
        for j in range(k + 1):
            crow, acc = crt.constrain_rows(
                self._ctrans, self._cacc, sm.cid, s
            )
            cmask = crt.constrain_mask(crow, acc, self.eos_id)
            if j == 0:
                crow0, cmask0 = crow, cmask
            dead_j = cvec & ~cmask.any(-1)
            p = jnp.argmax(
                crt.fold_mask(logits[:, j, :], cmask), axis=-1
            ).astype(jnp.int32)
            p = jnp.where(dead_j, -1, p)
            preds.append(p)
            posts.append(
                crt.advance_state(
                    crow, s, jnp.maximum(p, 0), cvec & ~dead_j
                )
            )
            deads.append(dead_j)
            fracs.append(crt.masked_frac(cmask, cvec))
            if j < k:
                s = crt.advance_state(crow, s, props[:, j], cvec)
        return (
            jnp.stack(preds, 1), crow0, cmask0,
            jnp.stack(posts, 1), jnp.stack(deads, 1),
            jnp.stack(fracs, 1),
        )

    def _admit_prefilled_ready(self, i: int) -> bool | None:
        """Try to seat the oldest DELIVERED prefilled request in slot
        i. True = seated; False = one was ready but the pool can't
        cover it (caller should wait for a finisher); None = nothing
        deliverable right now."""
        for rid in self._prefilled_order:
            entry = self.pending_prefilled[rid]
            if entry["kv"] is None:
                continue
            if not self._admit_prefilled(i, rid, entry):
                return False
            self._prefilled_order.remove(rid)
            del self.pending_prefilled[rid]
            return True
        return None

    def _admit(self) -> None:
        waiting = len(self.pending) + len(self.pending_prefilled)
        with spans.span("paged.admit") as sp:
            self._admit_variant()
            seated = waiting - len(self.pending) - len(self.pending_prefilled)
            sp.counts["seated"] = seated
            sp.keep = seated > 0  # a poll of an empty queue leaves no record

    def _admit_variant(self) -> None:
        if self.prefill_budget is not None:
            # Mixed-mode admission: new prompts take SEATS and prefill
            # inside the decode dispatches (runtime/schedule.py) — the
            # serialized stall-prefill path below never runs.
            return self._admit_mixed()
        for i in range(self.B):
            if self.slots[i] is not None:
                continue
            # Externally prefilled requests seat first: their compute
            # is already spent, so every tick they wait is pure added
            # TTFT.
            seated = self._admit_prefilled_ready(i)
            if seated:
                continue
            if seated is False:
                return  # pool exhausted even after eviction
            if not self.pending:
                continue
            (rid, prompt, steps, adapter_id, samp,
             stop_seqs, cid) = self.pending[0]
            if self.radix is not None:
                if not self._admit_radix(
                    i, rid, prompt, steps, adapter_id, samp, stop_seqs,
                    cid,
                ):
                    return  # pool exhausted even after eviction
                self.pending.popleft()
                continue
            if self._own_need(prompt.shape[1], steps) > len(self.free):
                return  # pool exhausted: wait for a finisher
            self.pending.popleft()
            with spans.span(
                "paged.admit.seat", rid=rid, prompt_tokens=prompt.shape[1]
            ) as seat:
                self._admit_plain(
                    i, rid, prompt, steps, adapter_id, samp, stop_seqs,
                    cid, seat,
                )

    def _admit_plain(
        self, i, rid, prompt, steps, adapter_id, samp, stop_seqs, cid, seat
    ) -> None:
        """Seat the queue's head in slot i the default way: its blocks,
        a contiguous prefill that stalls every live slot, the rows
        paged in, the first token. `seat` is the request's
        `paged.admit.seat` span; the four phases are its children."""
        t0 = prompt.shape[1]
        P = self.prefix_len
        with spans.span("paged.admit.seat.plan"):
            n_shared = len(self.shared_blocks)
            need = self._own_need(t0, steps)
            blocks = [self.free.pop() for _ in range(need)]
            self.obs.requests_admitted.inc()
            self.obs.prefill_tokens.inc(t0)
            # Strict lookup (same rule as the radix/prefilled paths):
            # a missing rid is a bug, not a zero wait.
            t_submit = self._submit_t[rid]
            queue_s = time.perf_counter() - t_submit
            self.obs.queue_wait.observe(queue_s)
            self._build()
            self.blocks_peak = max(
                self.blocks_peak, self.blocks_in_use + need
            )
            table_row = np.zeros((self.MB,), np.int32)
            for j, blk in enumerate(self.shared_blocks):
                table_row[j] = blk
            for j, blk in enumerate(blocks):
                table_row[n_shared + j] = blk
        if self.prefill_chunk is not None or self.pp > 1:
            # Pool-native chunked prefill: rows land in the
            # allocated blocks as each chunk computes, and a
            # global shared prefix (base=P) is read from ITS pool
            # blocks by the block-table attention — no contiguous
            # prefix lane, no insert pass.
            with spans.span("paged.admit.seat.prefill"):
                logits_row = self._prefill_paged(
                    prompt,
                    table_row,
                    base=P,
                    keep_from=0,
                    adapter_id=adapter_id,
                )
        else:
            # Contiguous prefill through the flat decoder — pow2
            # bucketed like the flat server, so the compiled
            # prefill shape set stays tiny — then page the rows
            # in. With a shared prefix the suffix prefills at
            # offset P on a COPY of the contiguous prefix lane
            # (the flat step donates its cache), and only rows
            # past the shared blocks are paged.
            with spans.span("paged.admit.seat.prefill"):
                pad = 1 << (t0 - 1).bit_length()
                pad = min(pad, self.dec.cfg.max_len - P)
                seat.counts["pad"] = pad
                padded = jnp.concatenate(
                    [prompt, jnp.zeros((1, pad - t0), prompt.dtype)],
                    axis=1,
                )
                # Non-donating prefill step: the master prefix lane is
                # read directly (no per-admission deep copy of two
                # full max_len K/V buffers — the cost this feature
                # exists to avoid); the returned cache is a fresh
                # tree.
                if self._prefix_cache is None:
                    small = self._flat_dec().init_cache(1)
                else:
                    small = dict(self._prefix_cache)
                if self.multi_lora:
                    small["adapter"] = jnp.full(
                        (1,), adapter_id, jnp.int32
                    )
                if "moe_live" in small:
                    # The rows past the prompt are the bucket's padding.
                    small["moe_live"] = jnp.asarray(t0, jnp.int32)
                logits, small = self._flat_dec().make_step(donate=False)(
                    self.params, small, padded
                )
                self._account_psums(1)
                self._note_prefill_stall(1)
                moe = small.get("moe")
            with spans.span("paged.admit.seat.insert"):
                self.pool_k, self.pool_v = self._insert(
                    self.pool_k,
                    self.pool_v,
                    small["k"],
                    small["v"],
                    jnp.asarray(table_row),
                )
                logits_row = logits[:, t0 - 1, :]
                if self.pool_state:
                    # The prompt's final recurrent states (what its last
                    # real row left) into the slot's row of their pools.
                    with spans.span(
                        "paged.admit.seat.state",
                        state_bytes=self.state_bytes // self.B,
                    ):
                        self.pool_state = self._insert_state(
                            *self.pool_state, small["gdn_s"],
                            small["gdn_conv"], jnp.asarray(i, jnp.int32),
                        )
                    self.obs.linear_prefill_chunks.inc(
                        -(-pad // CHUNK) * self.dec.cfg.layers_of(LINEAR)
                    )
        with spans.span("paged.admit.seat.first_token"):
            first = self._first_token(
                i, samp, logits_row, prompt.dtype, cid
            )
            self.tables[i] = table_row
            self.pos[i] = P + t0
            self.adapter[i] = adapter_id
            slot = {
                "rid": rid,
                "remaining": steps - 1,
                "prompt": prompt,
                "out": [],
                "blocks": blocks,
                "sampling": samp is not None,
                "stop": matcher_or_none(stop_seqs),
                "cid": cid,
                # For the `paged.request` span _finish records.
                "submit_t": t_submit,
                "queue_s": queue_s,
            }
            self.slots[i] = slot
            if self._draft is not None and not slot["sampling"]:
                # The first generated token is the slot's initial
                # pending feed; the draft lane prefills the FULL
                # prompt (admission-time host read — not a hot-loop
                # sync, _admit is outside the analysis hot set).
                slot["pend"] = [int(first[0, 0])]
                self._draft.admit(i, prompt)
            self._feed = self._feed.at[i].set(
                first[0].astype(jnp.int32)
            )
            # ttft spans queue + prefill; popped here (the drain
            # point) so entries never outlive their request.
            self.obs.ttft.observe(
                time.perf_counter() - self._submit_t.pop(rid)
            )
            self._update_pool_gauges()
            # Where a consumer needs the value the host waits here for
            # the prefill to end, so it closes the phase.
            tok = self._first_on_host(slot, first)
            if self.dec.cfg.num_experts:
                # The prefill's expert counters came back with its
                # logits: ready where the host waited for the token.
                self._account_moe(self.obs.moe_prefill, np.asarray(moe))
        self._emit_token(i, slot, tok)

    # -- mixed-mode admission + tick (prefill_budget=) ----------------

    def _seat_slots(self) -> list[int]:
        """Slot indices currently holding a PREFILL SEAT (admitted,
        mid-prefill, not yet decoding), admission order == slot-scan
        order because _admit_mixed seats the queue head first."""
        return [
            i
            for i, s in enumerate(self.slots)
            if s is not None and "prefill" in s
        ]

    def _note_prefill_stall(self, n_dispatches: int) -> None:
        """Account `n_dispatches` admission-prefill dispatches issued
        by the SERIALIZED path: each one issued while a decode slot is
        live is a stall tick (that slot's tick loop sat waiting).
        Mixed-mode ticks never call this — their prefill rides inside
        the decode dispatch."""
        if any(
            s is not None and "prefill" not in s for s in self.slots
        ):
            self.prefill_stall_ticks_n += n_dispatches
            self.obs.prefill_stall_ticks.inc(n_dispatches)
        self._update_stall_fraction()

    def _update_stall_fraction(self) -> None:
        """Publish decode_stall_fraction = stall_ticks / (decode ticks
        + stall_ticks): of all the dispatch slots that could have
        advanced decode, the fraction admission prefill stole."""
        denom = self.ticks + self.prefill_stall_ticks_n
        frac = self.prefill_stall_ticks_n / denom if denom else 0.0
        self.decode_stall_fraction_last = frac
        self.obs.decode_stall_fraction.set(frac)

    def _admit_mixed(self) -> None:
        """Seat-only admission for `prefill_budget=` servers: a new
        request claims a free slot and its blocks immediately, but NO
        prefill runs here — its prompt tokens ride inside subsequent
        mixed decode dispatches (_tick_mixed) until the last chunk
        lands and the seat flips to decoding (_flip_seat). Externally
        prefilled requests (submit_prefilled) bypass the budget: their
        compute is already spent, so they seat exactly as before."""
        for i in range(self.B):
            if self.slots[i] is not None:
                continue
            seated = self._admit_prefilled_ready(i)
            if seated:
                continue
            if seated is False:
                return  # pool exhausted even after eviction
            if not self.pending:
                continue
            if len(self._seat_slots()) >= self.prefill_lookahead:
                # Bounded lookahead: enough prompts are already
                # sharing the budget — admission stays near-FIFO.
                return
            (rid, prompt, steps, adapter_id, samp,
             stop_seqs, cid) = self.pending[0]
            if self.radix is not None:
                ok = self._seat_radix(
                    i, rid, prompt, steps, adapter_id, samp,
                    stop_seqs, cid,
                )
            else:
                ok = self._seat_plain(
                    i, rid, prompt, steps, adapter_id, samp,
                    stop_seqs, cid,
                )
            if not ok:
                return  # pool exhausted: wait for a finisher
            self.pending.popleft()

    def _seat_common(
        self, i, rid, prompt, steps, adapter_id, samp, stop_seqs,
        cid, seat, blocks, shared,
    ) -> None:
        """Shared tail of both seat paths: install the mid-prefill
        slot dict + host rows. `pos[i]` starts at the seat's base and
        advances per chunk; sampling/stop/constraint state installs
        at FLIP time (admit_first reseeds the sampler row then, so
        sampled streams match the stall path token for token)."""
        self._build()
        self.blocks_peak = max(self.blocks_peak, self.blocks_in_use)
        self.adapter[i] = adapter_id
        self.pos[i] = seat.base
        self.slots[i] = {
            "rid": rid,
            "prefill": seat,
            "meta": {
                "prompt": prompt,
                "steps": steps,
                "samp": samp,
                "stop": stop_seqs,
                "cid": cid,
            },
            "blocks": blocks,
            "shared": shared,
            "sampling": samp is not None,
            "stop": None,
            "cid": 0,
        }
        self._update_pool_gauges()

    def _seat_plain(
        self, i, rid, prompt, steps, adapter_id, samp, stop_seqs, cid
    ) -> bool:
        """Seat a request on a non-radix server: allocate its blocks
        (plus pointers at the global shared prefix), schedule the
        whole prompt at base=prefix_len. False = pool can't cover it
        yet."""
        t0 = prompt.shape[1]
        need = self._own_need(t0, steps)
        if need > len(self.free):
            return False
        blocks = [self.free.pop() for _ in range(need)]
        self.obs.requests_admitted.inc()
        self.obs.prefill_tokens.inc(t0)
        # Strict lookup (satellite of the mixed-mode PR): a missing
        # rid is a bug, not a zero wait.
        self.obs.queue_wait.observe(
            time.perf_counter() - self._submit_t[rid]
        )
        n_shared = len(self.shared_blocks)
        table_row = np.zeros((self.MB,), np.int32)
        for j, blk in enumerate(self.shared_blocks):
            table_row[j] = blk
        for j, blk in enumerate(blocks):
            table_row[n_shared + j] = blk
        self.tables[i] = table_row
        seat = PrefillSeat(
            rid=rid,
            tokens=np.asarray(prompt)[0],
            base=self.prefix_len,
            keep_from=0,
        )
        self._seat_common(
            i, rid, prompt, steps, adapter_id, samp, stop_seqs, cid,
            seat, blocks, [],
        )
        return True

    def _seat_radix(
        self, i, rid, prompt, steps, adapter_id, samp, stop_seqs, cid
    ) -> bool:
        """Seat a request through the PrefixBlockCache: walk leading
        full prompt blocks for hits (refcount++ now — they must stay
        pinned while the seat prefills), allocate the rest, and
        schedule ONLY the non-shared suffix. The request's own fresh
        full-prompt blocks are NOT registered here: mid-prefill they
        hold unwritten rows, so publication waits for _flip_seat."""
        bs = self.bs
        t0 = prompt.shape[1]
        tokens = np.asarray(prompt)[0]
        n_full = t0 // bs
        total = -(-(t0 + steps) // bs)
        hits, keys, toks = self.radix.walk(tokens, n_full, bs)
        if self._spill is not None and len(hits) < n_full:
            hits = self._revive_spilled(hits, keys, toks, n_full)
        need = total - len(hits)
        if need > len(self.free):
            self.free.extend(self.radix.evict(need - len(self.free)))
        if need > len(self.free):
            for blk in hits:
                self.radix.release(blk)
            return False
        own = [self.free.pop() for _ in range(need)]
        self.obs.requests_admitted.inc()
        self.obs.prefix_hits.inc(len(hits))
        self.obs.prefix_misses.inc(n_full - len(hits))
        self.obs.queue_wait.observe(
            time.perf_counter() - self._submit_t[rid]
        )
        table_row = np.zeros((self.MB,), np.int32)
        for j, blk in enumerate(hits + own):
            table_row[j] = blk
        self.tables[i] = table_row
        # Reuse at most t0-1 cached positions: the LAST prompt token
        # must run so its logits exist to seed the first generated
        # token (same rule as the stall path).
        suffix_pos = min(len(hits) * bs, t0 - 1)
        self.obs.prefill_tokens.inc(t0 - suffix_pos)
        self.prefill_tokens_saved += suffix_pos
        seat = PrefillSeat(
            rid=rid,
            tokens=tokens[suffix_pos:],
            base=suffix_pos,
            keep_from=len(hits) * bs,
        )
        meta_extra = {
            "keys": keys,
            "toks": toks,
            "n_full": n_full,
            "n_hits": len(hits),
        }
        self._seat_common(
            i, rid, prompt, steps, adapter_id, samp, stop_seqs, cid,
            seat, own, list(hits),
        )
        self.slots[i]["meta"].update(meta_extra)
        return True

    def _flip_seat(self, i: int, slot: dict, lrow) -> None:
        """The seat's last chunk just landed: seed the first generated
        token from that chunk's final logits row (`lrow`, [1, V] —
        exactly the row the stall path samples at admission) and turn
        the seat into a decoding slot. Radix servers publish the
        request's fresh full-prompt blocks NOW — every row is finally
        written, so other requests may attend to them."""
        meta = slot.pop("meta")
        del slot["prefill"]
        rid = slot["rid"]
        prompt, steps = meta["prompt"], meta["steps"]
        samp, cid = meta["samp"], meta["cid"]
        if self.radix is not None:
            n_hits, n_full = meta["n_hits"], meta["n_full"]
            fresh = []
            for j in range(n_hits, n_full):
                blk = int(self.tables[i, j])
                if meta["keys"][j] in self.radix.by_key:
                    # A concurrently-prefilling seat with the same
                    # prefix flipped first and published this key
                    # (the stall path can't race here — its admits
                    # serialize, so the second one WALKS into a hit).
                    # Our duplicate block stays privately owned and
                    # frees at finish; future walks hit theirs.
                    continue
                displaced = self.radix.register(
                    meta["keys"][j], meta["toks"][j], blk
                )
                if displaced is not None:
                    self.free.append(displaced)
                fresh.append(blk)
            # Registered blocks are shared (released through the
            # radix at finish), no longer privately owned.
            slot["shared"] = slot["shared"] + fresh
            slot["blocks"] = [
                b for b in slot["blocks"] if b not in fresh
            ]
        first = self._first_token(i, samp, lrow, prompt.dtype, cid)
        slot["remaining"] = steps - 1
        slot["prompt"] = prompt
        slot["out"] = []
        slot["stop"] = matcher_or_none(meta["stop"])
        slot["cid"] = cid
        self._feed = self._feed.at[i].set(first[0].astype(jnp.int32))
        # ttft = queue wait + (shared) prefill ticks, observed at the
        # first token like every other admit path; strict pop drains
        # the submit timestamp with the request.
        self.obs.ttft.observe(
            time.perf_counter() - self._submit_t.pop(rid)
        )
        self._update_pool_gauges()
        self._emit_token(i, slot, self._first_on_host(slot, first))

    def _account_kv_rows_mixed(self, posm, t: int) -> None:
        """Pool rows one mixed dispatch's attention read (decode-tick
        units): a [B, T] multi-token step whose row b attends through
        position posm[b] + t - 1. Derived from max_len (MB) and live
        spans, never pool size."""
        bs = self.bs
        baseline = self.B * self.MB * bs
        if self.attention == "gathered":
            rows_read = baseline
        elif self.attention == "blockwise":
            rows_read = (
                self.B
                * ((int(posm.max()) + t - 1) // bs + 1)
                * bs
            )
        else:  # pallas
            win = self.dec.cfg.window
            hi = (posm + t - 1) // bs
            lo = (
                np.maximum(posm + t - win, 0) // bs
                if win is not None
                else np.zeros_like(posm)
            )
            rows_read = int(np.sum(hi - lo + 1)) * bs
        self._account_kv_rows(rows_read, baseline)

    def _tick_mixed(self) -> None:
        """One MIXED dispatch: every live decode row advances exactly
        one token AND up to `prefill_budget` prompt tokens from the
        prefill seats ride along, all in one jitted multi-token
        forward (_mt_body — the spec-verify/chunked-prefill program).
        Per-row mode: decode rows feed their last token at pos with
        n_keep=1; seat rows feed their next chunk at base+done with
        n_keep=len(chunk); idle rows keep nothing and write trash.
        Sampling/eos/stop apply ONLY to decode rows; seat rows'
        logits are discarded except the final chunk's last position,
        which seeds the flip (_flip_seat)."""
        seats = self._seat_slots()
        decode_live = [
            s is not None and "prefill" not in s for s in self.slots
        ]
        self._build()
        mt = self._ensure_mt()
        limit = self.MB * self.bs
        # The fused program writes T contiguous-lane rows at EVERY
        # row's position (gathered path), so T is bounded by the
        # deepest live row — the same never-clamp invariant as
        # submit()'s spec_k headroom and _prefill_paged's tail cap.
        max_pos = max(
            int(self.pos[i])
            for i, s in enumerate(self.slots)
            if s is not None
        )
        t_limit = limit - max_pos
        chunk_cap = (
            self.prefill_chunk
            if self.prefill_chunk is not None
            else limit
        )
        T, ns = plan_mixed_tick(
            [self.slots[i]["prefill"].remaining for i in seats],
            self.prefill_budget,
            chunk_cap,
            t_limit,
        )
        ids_np = np.zeros((self.B, T), np.int32)
        n_keep = np.zeros((self.B,), np.int32)
        keep_from = np.zeros((self.B,), np.int32)
        posm = np.zeros((self.B,), np.int32)
        emit_idx = np.zeros((self.B,), np.int32)
        for i, s in enumerate(self.slots):
            if decode_live[i]:
                n_keep[i] = 1
                posm[i] = self.pos[i]
        planned: list[tuple[int, int]] = []  # (slot, n) with n >= 1
        total_new = 0
        for i, n in zip(seats, ns):
            if n <= 0:
                continue  # budget exhausted: the seat idles (trash)
            seat = self.slots[i]["prefill"]
            posm[i] = seat.pos
            keep_from[i] = seat.keep_from
            chunk = seat.take(n)
            ids_np[i, :n] = chunk
            n_keep[i] = n
            emit_idx[i] = n - 1
            planned.append((i, n))
            total_new += n
        # Decode rows' input token comes from the persistent device
        # feed — merged on device so the host never syncs on it.
        dec_mask = jnp.asarray(decode_live)[:, None]
        ids = jnp.asarray(ids_np)
        ids = ids.at[:, :1].set(
            jnp.where(dec_mask, self._feed, ids[:, :1])
        )
        logits, self.pool_k, self.pool_v = mt(
            self.params,
            self.pool_k,
            self.pool_v,
            jnp.asarray(self.tables.copy()),
            jnp.asarray(posm),
            ids,
            jnp.asarray(n_keep),
            jnp.asarray(keep_from),
            jnp.asarray(self.adapter.copy()),
        )
        self.ticks += 1
        self.dispatches += 1
        self.mixed_ticks_n += 1
        n_live = sum(decode_live)
        now = time.perf_counter()
        if self._last_tick_t is not None and n_live:
            self.obs.itl.observe(now - self._last_tick_t, n_live)
        self._last_tick_t = now
        self.obs.ticks.inc()
        self.obs.host_dispatches.inc()
        self.obs.mixed_prefill_tokens.inc(total_new)
        self.mixed_prefill_tokens_n += total_new
        self._update_stall_fraction()
        self._account_psums(1)
        self._account_kv_rows_mixed(posm, T)
        # Per-row emit position: 0 for decode rows, the chunk's last
        # real token for seats (only consumed when the seat flips).
        ll = jnp.take_along_axis(
            logits, jnp.asarray(emit_idx)[:, None, None], axis=1
        )[:, 0, :]
        ll_raw = ll  # pre-constraint rows, for seat flips
        sm = self._sampler
        constrained = any(sm.row_constrained)
        if constrained:
            crow, cacc = crt.constrain_rows(
                self._ctrans, self._cacc, sm.cid, sm.cstate
            )
            cmask = crt.constrain_mask(crow, cacc, self.eos_id)
            cvec = jnp.asarray(sm.row_constrained)
            dead = cvec & jnp.asarray(decode_live) & ~cmask.any(-1)
            ll = crt.fold_mask(ll, cmask)
        # Seat rows never steer the draw-vs-argmax choice: their
        # sampler rows install at flip (admit_first reseeds), so the
        # key stream matches the stall path draw for draw.
        if any(
            s is not None and "prefill" not in s and s["sampling"]
            for s in self.slots
        ):
            nxt = self._sampler.draw(ll)
        else:
            nxt = jnp.argmax(ll, axis=-1)
        if constrained:
            nxt = jnp.where(dead, self.eos_id, nxt)
            sm.cstate = crt.advance_state(
                crow, sm.cstate, nxt, cvec & ~dead
            )
            mfrac = crt.masked_frac(
                cmask, cvec & jnp.asarray(decode_live)
            )
        self._feed = nxt[:, None].astype(jnp.int32)
        need_host = (
            self.eos_id is not None
            or self.on_token is not None
            or any(
                s is not None and s.get("stop") is not None
                for s in self.slots
            )
        )
        # analysis: ignore[host-sync-in-hot-loop] single batched
        # transfer per mixed tick, and only when an eos/stop/stream
        # consumer needs host tokens — same guard as every tick path
        host_nxt = np.asarray(nxt).tolist() if need_host else None
        if constrained:
            # analysis: ignore[host-sync-in-hot-loop] one batched
            # per-tick transfer of the dead-end flags + mask
            # fractions, only while a constrained row is live
            dead_host = np.asarray(dead)
            # analysis: ignore[host-sync-in-hot-loop] ready with the
            # vector above (same sync point)
            mfrac_host = np.asarray(mfrac)
        accepted = 0
        for i, slot in enumerate(self.slots):
            if slot is None or not decode_live[i]:
                continue
            if constrained and slot["cid"]:
                if bool(dead_host[i]):
                    self.errors[slot["rid"]] = (
                        "constraint dead end: DFA state admits no "
                        "token and is not accepting"
                    )
                    self.constraint_dead_ends_n += 1
                    self.obs.constrain_dead_ends.inc()
                    slot["remaining"] = 0
                    self._finish(i)
                    continue
                self.constrained_tokens_n += 1
                self.obs.constrained_tokens.inc()
                self.obs.constrain_masked_frac.observe(
                    float(mfrac_host[i])
                )
            slot["remaining"] -= 1
            self.pos[i] += 1
            accepted += 1
            self._emit_token(
                i, slot, host_nxt[i] if need_host else (nxt, i)
            )
        # Seats advance AFTER the decode drain: pos moves chunk by
        # chunk, and the seat whose last chunk just landed flips to
        # decoding this very tick.
        for i, n in planned:
            slot = self.slots[i]
            seat = slot["prefill"]
            self.pos[i] = seat.pos
            if seat.finished:
                self._flip_seat(i, slot, ll_raw[i : i + 1])
                accepted += 1
        self.obs.tokens_per_dispatch.set(float(accepted))
        self.window_tokens += accepted

    def _tick(self) -> None:
        live = sum(s is not None for s in self.slots)
        with spans.span("paged.tick", live=live) as sp:
            sp.keep = live > 0  # a poll of an empty server leaves no record
            sp.counts.update(self._tick_variant())
            if self.dec.cfg.num_experts:
                lo, hi = self.dec.cfg.held
                sp.counts["experts_held"] = hi - lo
            if self.pool_state:
                # Slots whose recurrent states the tick read and wrote.
                sp.counts["state_slots"] = live

    def _tick_variant(self) -> dict:
        """Run the tick this server's mode calls for; what to record
        of it: which `kind` it was and, for the plain tick, the
        `span_rows` of each slot's table that its step was handed."""
        if self.pp > 1:
            self._tick_pp()
            return {"kind": "pp"}
        if self.spec_k:
            if self.decode_window > 1:
                self._tick_spec_window()
            else:
                self._tick_spec()
            return {"kind": "spec"}
        if self._seat_slots():
            # Mixed mode engages only while a seat is mid-prefill;
            # pure-decode stretches fall through to the EXACT plain /
            # window programs (the prefill_budget=None bit-identity
            # contract, and the window scan's dispatch amortization).
            self._tick_mixed()
            return {"kind": "mixed"}
        if self.decode_window > 1:
            self._tick_window()
            return {"kind": "window"}
        return {"kind": "plain", "span_rows": self._tick_plain()}

    def _tick_plain(self) -> int:
        """One token for every live slot: the default tick, and the
        one whose phases the span log holds (plan, dispatch, sample,
        sync, drain — `obs/spans.py`). Returns the rows of each slot's
        table that the step was handed."""
        live = [s is not None for s in self.slots]
        if not any(live):
            return 0
        with spans.span("paged.tick.plan"):
            self._build()
            # Persistent [B,1] device feed (constructor note):
            # admissions set their row, draws below overwrite the
            # whole vector — no per-tick concat of max_batch [1,1]
            # arrays.
            feed = self._feed
            # Idle slots write into trash block 0 at position 0.
            posm = np.where(live, self.pos, 0).astype(np.int32)
            pos = jnp.asarray(posm)
            # COPY the mutable host state before handing it to the
            # device: jnp.asarray of a numpy array is zero-copy on
            # CPU, and the host loop mutates tables/adapter in place
            # (finish/admission) while the async-dispatched step may
            # still be reading them — the aliasing race corrupts
            # first-execution results.
            # The table up to the rung above the deepest live slot:
            # rows past a slot's position are masked, so the columns
            # dropped here change no logit.
            nb = pick_rung(self._rungs, self.bs, int(posm.max()))
            tables = jnp.asarray(self.tables[:, :nb].copy())
            adapter = jnp.asarray(self.adapter.copy())
        with spans.span("paged.tick.dispatch"):
            # The recurrent layers' state pools, where the stack has
            # them, are the step's last operand and last result.
            logits, self.pool_k, self.pool_v, *state = self._step(
                self.params,
                self.pool_k,
                self.pool_v,
                tables,
                pos,
                feed,
                adapter,
                *((self.pool_state,) if self.pool_state else ()),
            )
            if state:
                (self.pool_state,) = state
            moe = None
            if self.dec.cfg.num_experts:
                # An expert decoder's step hands its counters back
                # beside the logits (`_step_body`).
                logits, moe = logits
        with spans.span("paged.tick.sample"):
            self.ticks += 1
            self.dispatches += 1
            n_live = sum(live)
            now = time.perf_counter()
            if self._last_tick_t is not None:
                self.obs.itl.observe(now - self._last_tick_t, n_live)
            self._last_tick_t = now
            self.obs.ticks.inc()
            self.obs.host_dispatches.inc()
            # Every decode tick moves the stall fraction's denominator —
            # republished here so the gauge decays as decode resumes (the
            # [contract.mixed] budget gate reads it).
            self._update_stall_fraction()
            self._account_psums(1)
            self.obs.tokens_per_dispatch.set(float(n_live))
            self.window_tokens += n_live
            # K/V rows the attention path read this tick vs the gathered
            # baseline (host-side, exact — the counters the bandwidth win
            # is pinned by; units in obs/serving.py). "gathered" reads
            # every slot to the rung above the batch's deepest live
            # slot, "blockwise" to its deepest live block; "pallas"
            # clamps per slot, so each reads only its own live span.
            baseline = self.B * self.MB * self.bs
            if self.attention == "gathered":
                rows_read = self.B * nb * self.bs
            elif self.attention == "blockwise":
                rows_read = (
                    self.B * (int(posm.max()) // self.bs + 1) * self.bs
                )
            else:  # pallas
                win = self.dec.cfg.window
                lo = (
                    np.maximum(posm - win + 1, 0) // self.bs
                    if win is not None
                    else 0
                )
                rows_read = int(np.sum(posm // self.bs - lo + 1)) * self.bs
            self._account_kv_rows(rows_read, baseline)
            for w in (
                k[0] for k in self.dec.cfg.layer_kinds or () if k != LINEAR
            ):
                if w is not None:
                    # Rows behind this sliding layer's window (an idle
                    # slot sits at position 0 and adds none).
                    self.obs.kv_rows_window_masked.inc(
                        int(np.maximum(posm + 1 - w, 0).sum())
                        * (self.dec.cfg.num_layers // len(self.dec.cfg.layer_kinds))
                    )
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
                dead = cvec & jnp.asarray(live) & ~cmask.any(-1)
                ll = crt.fold_mask(ll, cmask)
            if any(s is not None and s["sampling"] for s in self.slots):
                nxt = self._sampler.draw(ll)
            else:
                nxt = jnp.argmax(ll, axis=-1)
            if constrained:
                nxt = jnp.where(dead, self.eos_id, nxt)
                sm.cstate = crt.advance_state(
                    crow, sm.cstate, nxt, cvec & ~dead
                )
                mfrac = crt.masked_frac(cmask, cvec & jnp.asarray(live))
            self._feed = nxt[:, None].astype(jnp.int32)
            # Host transfer only when eos/streaming/stop matching needs
            # the values — the plain path stays async (same guard as the
            # flat server).
            need_host = (
                self.eos_id is not None
                or self.on_token is not None
                or any(
                    s is not None and s["stop"] is not None
                    for s in self.slots
                )
            )
        with spans.span("paged.tick.sync"):
            # analysis: ignore[host-sync-in-hot-loop] single batched
            # transfer per WINDOW (a window of one token here), and only
            # when an eos/stop/stream consumer needs host tokens — the
            # sync this serving loop is designed around
            host_nxt = np.asarray(nxt).tolist() if need_host else None
            if moe is not None:
                # analysis: ignore[host-sync-in-hot-loop] [L, 2] int32
                # of the step that made the logits above: ready with
                # the tokens, at the same sync point
                self._account_moe(self.obs.moe_decode, np.asarray(moe))
            if constrained:
                # analysis: ignore[host-sync-in-hot-loop] one batched
                # per-tick transfer of the dead-end flags + mask
                # fractions, and only while a constrained row is live
                dead_host = np.asarray(dead)
                # analysis: ignore[host-sync-in-hot-loop] ready with the
                # vector above (same sync point)
                mfrac_host = np.asarray(mfrac)
        with spans.span("paged.tick.drain", tokens=n_live):
            for i, slot in enumerate(self.slots):
                if slot is None:
                    continue
                if constrained and slot["cid"]:
                    if bool(dead_host[i]):
                        # The forced eos never enters the output: the
                        # request ends at its last admissible token with
                        # a per-request error, not a hang.
                        self.errors[slot["rid"]] = (
                            "constraint dead end: DFA state admits no "
                            "token and is not accepting"
                        )
                        self.constraint_dead_ends_n += 1
                        self.obs.constrain_dead_ends.inc()
                        slot["remaining"] = 0
                        self._finish(i)
                        continue
                    self.constrained_tokens_n += 1
                    self.obs.constrained_tokens.inc()
                    self.obs.constrain_masked_frac.observe(
                        float(mfrac_host[i])
                    )
                slot["remaining"] -= 1
                self.pos[i] += 1
                self._emit_token(
                    i, slot, host_nxt[i] if need_host else (nxt, i)
                )
        return nb * self.bs

    def _tick_spec(self) -> None:
        """One speculative round: TWO host dispatches advance every
        greedy slot up to spec_k + 1 tokens (ARCHITECTURE.md
        "Speculative serving" has the full semantics).

        1. DRAFT PROPOSE (DraftLanes.propose, one fused program):
           each greedy slot's lane catches up on its 1-2 pending
           committed tokens, then emits k greedy proposals.
        2. TARGET VERIFY (_mt_body, T = k + 1): row 0 is the slot's
           feed token, rows 1..k the proposals; all k + 1 candidate
           K/V rows scatter into the slot's pool blocks in the same
           dispatch (sampled slots keep row 0 only, idle slots none —
           trash-redirected dead writes).
        3. ONE batched host transfer of (preds, props[, sampled
           draws]) feeds the accept test (batching.accept_lengths):
           slot i emits props[:a] plus the target's own token at the
           first mismatch (or the bonus row on full accept) — the
           greedy chain is the target's chain, token for token, so
           output is bit-identical to spec_k=0. Rejected rows sit
           stale behind the position mask; the next round's verify
           span rewrites them before they can ever be read.

        Sampled slots advance exactly ONE token per round, drawn from
        the verify forward's row 0 through the shared SlotSampler —
        one draw call per round, same as one draw per tick at
        spec_k=0, so sampled streams are bit-identical too."""
        live = [s is not None for s in self.slots]
        if not any(live):
            return
        self._build()
        k = self.spec_k
        mt = self._ensure_mt()
        # Per-slot draft-round inputs. pend = tokens emitted but not
        # yet in the draft lane (1 after a partial accept, 2 after a
        # full accept — the k-th proposal is never self-consumed, and
        # the bonus token never proposed); the lane's write head is
        # pos + 1 - len(pend) by that definition. Idle and sampled
        # rows pin to 0, the idle-lane idiom, so their dead writes
        # stay bounded and every live lane is re-fed from host truth.
        feed2 = np.zeros((self.B, 2), np.int32)
        adv = np.zeros((self.B,), np.int32)
        dposm = np.zeros((self.B,), np.int32)
        for i, slot in enumerate(self.slots):
            if slot is None or slot["sampling"]:
                continue
            pend = slot["pend"]
            adv[i] = len(pend)
            feed2[i, 0] = pend[0]
            feed2[i, 1] = pend[-1]  # len-1 pend feeds its token twice
            dposm[i] = self.pos[i] + 1 - len(pend)
        sm = self._sampler
        constrained = any(sm.row_constrained)
        if constrained:
            # Lane-side masking: the draft's proposal chain walks the
            # slot's DFA from its committed state, so candidates stay
            # grammar-valid (acceptance, not correctness — the
            # target-side masked preds below are the contract).
            props = self._draft.propose_c(
                k, dposm, feed2, adv, self.eos_id,
                sm.cid, sm.cstate, self._ctrans, self._cacc,
            )  # [B, k]
        else:
            props = self._draft.propose(k, dposm, feed2, adv)  # [B, k]
        # Verify all k+1 positions in ONE block-table forward: row 0
        # re-derives each slot's next token from its feed (the greedy
        # correctness anchor), rows 1..k check the proposals.
        verify_in = jnp.concatenate(
            [self._feed, props.astype(jnp.int32)], axis=1
        )
        n_keep = np.zeros((self.B,), np.int32)
        for i, slot in enumerate(self.slots):
            if slot is not None:
                n_keep[i] = 1 if slot["sampling"] else k + 1
        posm = np.where(live, self.pos, 0).astype(np.int32)
        # Same aliasing-copy rule as the K=1 tick: tables/adapter are
        # host-mutated by finish/admission while the dispatched verify
        # may still be reading them.
        logits, self.pool_k, self.pool_v = mt(
            self.params,
            self.pool_k,
            self.pool_v,
            jnp.asarray(self.tables.copy()),
            jnp.asarray(posm),
            verify_in,
            jnp.asarray(n_keep),
            jnp.zeros((self.B,), jnp.int32),
            jnp.asarray(self.adapter.copy()),
        )
        if constrained:
            # Target-side constrained preds: a device state walk along
            # the proposal prefix (pred_j = masked argmax at s_j,
            # s_{j+1} = trans[s_j, props_j]), so the accept rule below
            # truncates at the first proposal the TARGET's mask
            # rejects — constrained greedy output is the spec_k=0
            # constrained chain, token for token. Dead states force
            # pred_j to -1 (out of vocab): never accepted, and the
            # correction token is dropped host-side with the error.
            (preds, crow0, cmask0, post_states, dead_all,
             fracs) = self._constrained_preds(logits, props, k)
        else:
            preds = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        any_sampling = any(
            s is not None and s["sampling"] for s in self.slots
        )
        draw = None
        if any_sampling:
            ll0 = logits[:, 0, :]
            if constrained:
                # Sampled constrained rows draw from the masked row;
                # free rows' fold is an exact no-op (cid-0 mask).
                ll0 = crt.fold_mask(ll0, cmask0)
            draw = self._sampler.draw(ll0)
        self.ticks += 1
        self.dispatches += 2
        n_live = sum(live)
        now = time.perf_counter()
        if self._last_tick_t is not None:
            self.obs.itl.observe(now - self._last_tick_t, n_live)
        self._last_tick_t = now
        self.obs.ticks.inc()
        self.obs.host_dispatches.inc(2)
        # Only the verify forward runs sharded; the draft's flat lanes
        # are replicated host-side state, no collectives.
        self._account_psums(1)
        # Pool rows the verify forward read (same units/contract as
        # the K=1 tick; the draft reads its own flat lanes, not the
        # pool). The deepest query row of slot i attends at pos + k.
        baseline = self.B * self.MB * self.bs
        if self.attention == "gathered":
            rows_read = baseline
        elif self.attention == "blockwise":
            rows_read = (
                self.B
                * ((int(posm.max()) + k) // self.bs + 1)
                * self.bs
            )
        else:  # pallas
            win = self.dec.cfg.window
            hi = (posm + k) // self.bs
            lo = (
                np.maximum(posm - win + 1, 0) // self.bs
                if win is not None
                else np.zeros_like(posm)
            )
            rows_read = int(np.sum(hi - lo + 1)) * self.bs
        self._account_kv_rows(rows_read, baseline)
        # analysis: ignore[host-sync-in-hot-loop] the ONE batched
        # accept-test transfer per speculative ROUND — up to k+1
        # tokens per slot amortize it, the sync the round is designed
        # around (spec_accept fixtures pin the shape)
        preds_host = np.asarray(preds)
        # analysis: ignore[host-sync-in-hot-loop] proposal half of the
        # same batched round transfer (ready with the verify above)
        props_host = np.asarray(props)
        if draw is not None:
            # analysis: ignore[host-sync-in-hot-loop] sampled rows'
            # slice of the same per-round sync point
            draw_host = np.asarray(draw)
        if constrained:
            # analysis: ignore[host-sync-in-hot-loop] dead-end flags +
            # mask fractions ride the same batched round transfer,
            # only while a constrained row is live
            dead_host = np.asarray(dead_all)
            # analysis: ignore[host-sync-in-hot-loop] same per-round
            # sync point (ready with the matrix above)
            fracs_host = np.asarray(fracs)
        a_vec = accept_lengths(props_host, preds_host[:, :k])
        proposed = 0
        accepted_draft = 0
        draft_toks = 0
        accepted = [0] * self.B
        finishing = [False] * self.B
        toks_host: list[list[int] | None] = [None] * self.B
        feedv = np.zeros((self.B,), np.int32)
        for i, slot in enumerate(self.slots):
            if slot is None:
                continue
            dead_i = False
            if slot["sampling"]:
                emitted = [int(draw_host[i])]
                if constrained and slot["cid"] and dead_host[i][0]:
                    dead_i, emitted = True, []
            else:
                # analysis: ignore[host-sync-in-hot-loop] a_vec is
                # host numpy (accept_lengths of the batched fetch)
                a = int(a_vec[i])
                proposed += k
                accepted_draft += a
                # analysis: ignore[host-sync-in-hot-loop] adv is the
                # host round-0 seed (np.zeros filled from slot pend)
                draft_toks += int(adv[i]) + k - 1
                self.obs.spec_acceptance.observe(a)
                emitted = [int(t) for t in props_host[i, :a]]
                emitted.append(int(preds_host[i, a]))
                if constrained and slot["cid"] and dead_host[i][a]:
                    # The correction position hit a dead DFA state:
                    # its pred is the -1 sentinel, dropped here, so
                    # the stream ends at the still-valid accepted
                    # prefix with a per-request error, not a hang.
                    dead_i = True
                    emitted = emitted[:-1]
            # Per-token drain, K=1-equivalent: budget, then eos, then
            # stop — the first terminator wins and everything after it
            # is discarded (a truncated slot always finishes, so the
            # continuing-slot feed/pend math below never sees a cut).
            room = slot["remaining"]
            kept = 0
            stopped = False
            for tok in emitted:
                if kept >= room:
                    break
                kept += 1
                if self.eos_id is not None and tok == self.eos_id:
                    stopped = True
                    break
                if slot["stop"] is not None and slot["stop"].push(tok):
                    stopped = True
                    break
            if kept < len(emitted):
                self.obs.window_truncated.inc()
            slot["remaining"] -= kept
            if stopped:
                slot["remaining"] = 0
            if dead_i and kept == len(emitted) and not stopped:
                # Dead end actually reached (not pre-empted by a
                # budget cut or stop hit inside the kept prefix).
                slot["remaining"] = 0
                self.errors[slot["rid"]] = (
                    "constraint dead end: DFA state admits no token "
                    "and is not accepting"
                )
                self.constraint_dead_ends_n += 1
                self.obs.constrain_dead_ends.inc()
            if constrained and slot["cid"]:
                self.constrained_tokens_n += kept
                if kept:
                    self.obs.constrained_tokens.inc(kept)
                for j in range(kept):
                    self.obs.constrain_masked_frac.observe(
                        float(fracs_host[i][j])
                    )
            toks_host[i] = emitted[:kept]
            slot["out"] += toks_host[i]
            self.pos[i] += kept
            accepted[i] = kept
            finishing[i] = slot["remaining"] == 0
            self.obs.tokens_generated.inc(kept)
            self.window_tokens += kept
            feedv[i] = emitted[-1] if emitted else 0
            if not slot["sampling"] and not finishing[i]:
                # kept == a + 1 here (truncation implies finish):
                # partial accept leaves only the correction token
                # pending; full accept also leaves the never-consumed
                # k-th proposal.
                if a < k:
                    slot["pend"] = [emitted[-1]]
                else:
                    slot["pend"] = [
                        int(props_host[i, k - 1]), emitted[-1],
                    ]
                self._draft.pos[i] = (
                    self.pos[i] + 1 - len(slot["pend"])
                )
        if constrained:
            # Commit DFA states for rows continuing past the round —
            # greedy rows select the post-state column at their accept
            # length (the state after the round's LAST emitted token),
            # sampled rows advance one step by their draw. Pure UPLOAD
            # + device gather; finishing rows keep their state and are
            # reset by release below.
            sel = np.zeros((self.B,), np.int32)
            use_post = np.zeros((self.B,), bool)
            use_draw = np.zeros((self.B,), bool)
            for i, slot in enumerate(self.slots):
                if slot is None or not slot["cid"] or finishing[i]:
                    continue
                if slot["sampling"]:
                    use_draw[i] = True
                else:
                    use_post[i] = True
                    # analysis: ignore[host-sync-in-hot-loop] a_vec is
                    # host numpy (accept_lengths of the batched fetch)
                    sel[i] = int(a_vec[i])
            new_c = jnp.take_along_axis(
                post_states, jnp.asarray(sel)[:, None], 1
            )[:, 0]
            cst = jnp.where(jnp.asarray(use_post), new_c, sm.cstate)
            if draw is not None:
                cst = crt.advance_state(
                    crow0, cst, draw, jnp.asarray(use_draw)
                )
            sm.cstate = cst
        self._feed = jnp.asarray(feedv[:, None])
        self.spec_rounds_n += 1
        self.spec_proposed_n += proposed
        self.spec_accepted_n += accepted_draft
        self.spec_draft_tokens_n += draft_toks
        self.obs.spec_rounds.inc()
        if proposed:
            self.obs.spec_proposed.inc(proposed)
        if accepted_draft:
            self.obs.spec_accepted.inc(accepted_draft)
        if draft_toks:
            self.obs.spec_draft_tokens.inc(draft_toks)
        # Mean per-dispatch yield: a round is two dispatches.
        self.obs.tokens_per_dispatch.set(float(sum(accepted)) / 2.0)
        if self.on_token is not None:
            for t, i in window_drain_order(accepted, k + 1):
                slot = self.slots[i]
                self.on_token(
                    slot["rid"],
                    toks_host[i][t],
                    finishing[i] and t == accepted[i] - 1,
                )
        for i in range(self.B):
            if finishing[i]:
                self._finish(i)

    def _tick_spec_window(self) -> None:
        """W = decode_window speculative rounds in ONE host dispatch
        (_build_spec_window): the draft propose + target verify +
        accept test + pend recurrence all live inside the fused scan,
        so a window costs 1 dispatch and 1 batched sync where the
        unfused path costs 2W dispatches and W syncs. Greedy output
        is token-identical to spec_k=0 (and to decode_window=1
        speculation); stop sequences cut on drain with overshoot
        discarded, the _tick_window contract."""
        live = [s is not None for s in self.slots]
        if not any(live):
            return
        self._build()
        k, W = self.spec_k, self.decode_window
        sampling_rows = [
            s is not None and s["sampling"] for s in self.slots
        ]
        if not any(sampling_rows):
            mode = "argmax"
        elif any(self._sampler.row_sort):
            mode = "sort"
        else:
            mode = "nosort"
        constrained = any(self._sampler.row_constrained)
        prog = (
            self._build_spec_window_c(mode)
            if constrained
            else self._build_spec_window(mode)
        )
        # Round-0 seeds from host truth, exactly _tick_spec's: pend =
        # committed-but-unconsumed draft tokens, lane write head
        # pos + 1 - len(pend).
        feed2 = np.zeros((self.B, 2), np.int32)
        adv = np.zeros((self.B,), np.int32)
        dposm = np.zeros((self.B,), np.int32)
        for i, slot in enumerate(self.slots):
            if slot is None or slot["sampling"]:
                continue
            pend = slot["pend"]
            adv[i] = len(pend)
            feed2[i, 0] = pend[0]
            feed2[i, 1] = pend[-1]
            dposm[i] = self.pos[i] + 1 - len(pend)
        budget = [
            s["remaining"] if s is not None else 0
            for s in self.slots
        ]
        posm = np.where(live, self.pos, 0).astype(np.int32)
        sm = self._sampler
        # Same aliasing-copy rule as every tick: tables/adapter are
        # host-mutated by finish/admission while the dispatched window
        # may still be reading them.
        operands = (
            self.params, self.pool_k, self.pool_v,
            self._draft.ck, self._draft.cv, self._draft.params,
            jnp.asarray(self.tables.copy()), jnp.asarray(posm),
            jnp.asarray(dposm), self._feed, jnp.asarray(feed2),
            jnp.asarray(adv), jnp.asarray(live),
            jnp.asarray(sampling_rows), sm.keys, sm.temp, sm.topk,
            sm.topp, sm.minp, jnp.asarray(budget, jnp.int32),
            jnp.asarray(self.adapter.copy()),
        )
        died = fracs_a = None
        if constrained:
            (self.pool_k, self.pool_v, dk, dv, feed, feed2_o, adv_o,
             alive, keys, toks_a, kept_a, a_a, greedy_a, advu_a,
             cstate, died, fracs_a) = prog(
                *operands, sm.cid, sm.cstate, self._ctrans, self._cacc,
            )
            sm.cstate = cstate
        else:
            (self.pool_k, self.pool_v, dk, dv, feed, feed2_o, adv_o,
             alive, keys, toks_a, kept_a, a_a, greedy_a,
             advu_a) = prog(*operands)
        self._draft.ck, self._draft.cv = dk, dv
        self._feed = feed
        sm.keys = keys
        self.ticks += 1
        self.dispatches += 1
        n_live = sum(live)
        now = time.perf_counter()
        if self._last_tick_t is not None:
            self.obs.itl.observe(now - self._last_tick_t, n_live)
        self._last_tick_t = now
        self.obs.ticks.inc()
        self.obs.host_dispatches.inc()
        # W verify forwards' worth of collectives per dispatch (the
        # draft forward is replicated, no psums — _tick_spec's rule).
        self._account_psums(W)
        # The ONE batched sync per window: the [W, B, k+1] token
        # buffer plus the per-round kept/accept vectors — every piece
        # of drain bookkeeping reads these host copies.
        # analysis: ignore[host-sync-in-hot-loop] the ONE batched
        # [W, B, k+1] token transfer per fused spec window — up to
        # W*(k+1) tokens per slot amortize it (spec_window fixtures
        # pin the shape)
        toks_h = np.asarray(toks_a)
        # analysis: ignore[host-sync-in-hot-loop] per-round kept
        # counts, same per-window sync point (ready with the tokens)
        kept_h = np.asarray(kept_a)
        # analysis: ignore[host-sync-in-hot-loop] per-round accept
        # lengths, same batched per-window sync point
        a_h = np.asarray(a_a)
        # analysis: ignore[host-sync-in-hot-loop] per-round proposer
        # masks, same batched sync point
        greedy_h = np.asarray(greedy_a)
        # analysis: ignore[host-sync-in-hot-loop] per-round draft
        # catch-up counts, same batched sync point
        advu_h = np.asarray(advu_a)
        # analysis: ignore[host-sync-in-hot-loop] final liveness, same
        # batched sync point
        alive_h = np.asarray(alive)
        # analysis: ignore[host-sync-in-hot-loop] pend recurrence feed
        # pair, same batched sync point
        feed2_h = np.asarray(feed2_o)
        # analysis: ignore[host-sync-in-hot-loop] pend recurrence
        # advance, same batched sync point
        adv_h = np.asarray(adv_o)
        if constrained:
            # analysis: ignore[host-sync-in-hot-loop] dead-end flags,
            # same batched per-window sync point
            died_h = np.asarray(died)
            # analysis: ignore[host-sync-in-hot-loop] masked-fraction
            # buffer for obs, same batched per-window sync point
            fracs_h = np.asarray(fracs_a)
        # Verify-read accounting: the per-round mirror of _tick_spec's
        # (active rows read to pos_r + k; frozen rows sit at trash
        # position 0). Pure host python over the fetched counts.
        baseline = W * self.B * self.MB * self.bs
        if self.attention == "gathered":
            rows_read = baseline
        else:
            win = self.dec.cfg.window
            pos_l = posm.tolist()
            rows_read = 0
            for r in range(W):
                pe = [
                    p if kept_h[r][i] > 0 else 0
                    for i, p in enumerate(pos_l)
                ]
                if self.attention == "blockwise":
                    rows_read += (
                        self.B
                        * ((max(pe) + k) // self.bs + 1)
                        * self.bs
                    )
                else:  # pallas
                    rows_read += self.bs * sum(
                        (p + k) // self.bs
                        - (max(p - win + 1, 0) // self.bs
                           if win is not None else 0)
                        + 1
                        for p in pe
                    )
                pos_l = [
                    p + int(kept_h[r][i])
                    for i, p in enumerate(pos_l)
                ]
        self._account_kv_rows(rows_read, baseline)
        # Drain: per slot, walk the rounds in order; stop sequences
        # cut on the host (push_window per round) and discard the
        # overshoot the device kept generating — the _tick_window
        # contract. eos/budget freezes already happened on device.
        proposed = 0
        accepted_draft = 0
        draft_toks = 0
        rounds_run = 0
        kept_rounds: list[list[int]] = [[0] * self.B for _ in range(W)]
        total = [0] * self.B
        finishing = [False] * self.B
        stream_toks: list[list[list[int]] | None] = [None] * self.B
        for r in range(W):
            ran = False
            for i, slot in enumerate(self.slots):
                if slot is None:
                    continue
                if greedy_h[r][i]:
                    ran = True
                    proposed += k
                    a_r = int(a_h[r][i])
                    accepted_draft += a_r
                    draft_toks += int(advu_h[r][i]) + k - 1
                    self.obs.spec_acceptance.observe(a_r)
                n_r = int(kept_h[r][i])
                if n_r == 0:
                    continue
                row = [int(t) for t in toks_h[r][i][:n_r]]
                if finishing[i]:
                    row = []  # overshoot past a stop cut
                elif slot["stop"] is not None:
                    hit = slot["stop"].push_window(row)
                    if hit is not None:
                        row = row[:hit]
                        finishing[i] = True
                        self.obs.window_truncated.inc()
                kept_rounds[r][i] = len(row)
                total[i] += len(row)
                if stream_toks[i] is None:
                    stream_toks[i] = [[] for _ in range(W)]
                stream_toks[i][r] = row
            if ran:
                rounds_run += 1
        for i, slot in enumerate(self.slots):
            if slot is None or not constrained:
                continue
            if slot["cid"] and died_h[i] and not finishing[i]:
                # Dead-end DFA state mid-window: the device froze the
                # row with a forced eos — the slot's LAST kept token
                # (a stop cut would have discarded it as overshoot,
                # hence the finishing guard). Drop it so the output
                # ends at the last admissible token and the failure
                # surfaces as a per-request error, not a hang.
                for r in range(W - 1, -1, -1):
                    if kept_rounds[r][i]:
                        kept_rounds[r][i] -= 1
                        stream_toks[i][r].pop()
                        total[i] -= 1
                        break
                self.errors[slot["rid"]] = (
                    "constraint dead end: DFA state admits no token "
                    "and is not accepting"
                )
                self.constraint_dead_ends_n += 1
                self.obs.constrain_dead_ends.inc()
            if slot["cid"]:
                for r in range(W):
                    kr = kept_rounds[r][i]
                    self.constrained_tokens_n += kr
                    if kr:
                        self.obs.constrained_tokens.inc(kr)
                    for j in range(kr):
                        self.obs.constrain_masked_frac.observe(
                            float(fracs_h[r][i][j])
                        )
        for i, slot in enumerate(self.slots):
            if slot is None:
                continue
            n_i = total[i]
            slot["remaining"] -= n_i
            if finishing[i] or not alive_h[i]:
                slot["remaining"] = 0
            for row in stream_toks[i] or ():
                slot["out"] += row
            self.pos[i] += n_i
            finishing[i] = slot["remaining"] == 0
            self.obs.tokens_generated.inc(n_i)
            self.window_tokens += n_i
            if not slot["sampling"] and not finishing[i]:
                # Continuing greedy rows: reconstruct pend from the
                # device recurrence's final (feed2, adv) — host truth
                # for the next window's round-0 seed.
                av = int(adv_h[i])
                slot["pend"] = [
                    int(t) for t in feed2_h[i][2 - av:]
                ]
                self._draft.pos[i] = (
                    self.pos[i] + 1 - len(slot["pend"])
                )
        self.spec_rounds_n += rounds_run
        self.spec_proposed_n += proposed
        self.spec_accepted_n += accepted_draft
        self.spec_draft_tokens_n += draft_toks
        self.obs.spec_rounds.inc(rounds_run)
        if proposed:
            self.obs.spec_proposed.inc(proposed)
        if accepted_draft:
            self.obs.spec_accepted.inc(accepted_draft)
        if draft_toks:
            self.obs.spec_draft_tokens.inc(draft_toks)
        self.obs.tokens_per_dispatch.set(float(sum(total)))
        if self.on_token is not None:
            last_r = [
                max(
                    (r for r in range(W) if kept_rounds[r][i]),
                    default=0,
                )
                for i in range(self.B)
            ]
            for r in range(W):
                for t, i in window_drain_order(
                    kept_rounds[r], k + 1
                ):
                    slot = self.slots[i]
                    self.on_token(
                        slot["rid"],
                        stream_toks[i][r][t],
                        finishing[i]
                        and r == last_r[i]
                        and t == kept_rounds[r][i] - 1,
                    )
        for i in range(self.B):
            if finishing[i]:
                self._finish(i)

    def _tick_window(self) -> None:
        """One fused dispatch of up to decode_window tokens per live
        slot (_build_window); ONE batched host transfer drains the
        [B, K] token buffer (plus tiny valid-length/alive vectors when
        eos is configured)."""
        live = [s is not None for s in self.slots]
        if not any(live):
            return
        self._build()
        K = self.decode_window
        sampling = any(
            s is not None and s["sampling"] for s in self.slots
        )
        if not sampling:
            mode = "argmax"
        elif any(self._sampler.row_sort):
            mode = "sort"
        else:
            mode = "nosort"
        budget = [
            s["remaining"] if s is not None else 0
            for s in self.slots
        ]
        posm = np.where(live, self.pos, 0).astype(np.int32)
        sm = self._sampler
        constrained = any(sm.row_constrained)
        died = fracs = None
        # Same aliasing-copy rule as the K=1 tick: tables/adapter are
        # mutated by the host (finish/admission) while the dispatched
        # window may still be reading them.
        if constrained:
            window = self._build_window_c(mode)
            (self.pool_k, self.pool_v, feed, alive, keys, n_dev,
             toks, cstate, died, fracs) = window(
                self.params, self.pool_k, self.pool_v,
                jnp.asarray(self.tables.copy()), jnp.asarray(posm),
                self._feed, jnp.asarray(live), sm.keys, sm.temp,
                sm.topk, sm.topp, sm.minp,
                jnp.asarray(budget, jnp.int32),
                jnp.asarray(self.adapter.copy()),
                sm.cid, sm.cstate, self._ctrans, self._cacc,
            )
            sm.cstate = cstate
        else:
            window = self._build_window(mode)
            (self.pool_k, self.pool_v, feed, alive, keys, n_dev,
             toks) = window(
                self.params, self.pool_k, self.pool_v,
                jnp.asarray(self.tables.copy()), jnp.asarray(posm),
                self._feed, jnp.asarray(live), sm.keys, sm.temp,
                sm.topk, sm.topp, sm.minp,
                jnp.asarray(budget, jnp.int32),
                jnp.asarray(self.adapter.copy()),
            )
        self._feed = feed
        sm.keys = keys
        self.ticks += 1
        self.dispatches += 1
        n_live = sum(live)
        now = time.perf_counter()
        if self._last_tick_t is not None:
            self.obs.itl.observe(now - self._last_tick_t, n_live)
        self._last_tick_t = now
        self.obs.ticks.inc()
        self.obs.host_dispatches.inc()
        self._update_stall_fraction()
        # The fused window scans K sub-steps inside ONE sharded
        # program: K forwards' worth of collectives per dispatch.
        self._account_psums(K)
        need_toks = self.on_token is not None or any(
            s is not None and s["stop"] is not None
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
            # No eos: the device can only freeze rows on budget,
            # which the host already knows — no transfer needed.
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
        self._account_kv_rows_window(posm, emitted)
        self._drain_window(toks, toks_host, emitted, alive_host,
                           budget, died_host, fracs_host)

    def _probe_pp_layer_costs(self, num_blocks: int) -> list[float]:
        """Per-layer amortized step cost for pp_balance="probe"
        (parallel/pipeline.py::probe_latency methodology): each layer
        is wrapped in a throwaway single-layer stage with a 2-block
        pool and timed on a [1, 1] decode round. Boundary costs are
        attributed honestly — layer 0 carries the embedding, the last
        layer the final norm + head — so balance_stage_cuts sees the
        work a stage would actually run."""
        from defer_tpu.parallel.pipeline import probe_latency

        cfg = self.dec.cfg
        tab = jnp.zeros((1, self.MB), jnp.int32)
        pos = jnp.zeros((1,), jnp.int32)
        nk = jnp.ones((1,), jnp.int32)
        kf = jnp.zeros((1,), jnp.int32)
        ad = jnp.zeros((1,), jnp.int32)
        ids = jnp.zeros((1, 1), jnp.int32)
        act = jnp.zeros((1, 1, cfg.dim), self.dec.compute_dtype)
        costs = []
        for layer in range(cfg.num_layers):
            stage = _PPLocalStage(
                self.dec, self.params, layer, layer + 1,
                num_blocks=2,
                block_size=self.bs,
                attention=self.attention,
            )
            xin = ids if layer == 0 else act
            sample = probe_latency(
                stage.pp_dispatch, tab, pos, xin, nk, kf, ad, iters=3
            )
            costs.append(sample["amortized_s"])
        return costs

    def _build_pp_ctl(self, mode: str):
        """Jitted per-round controller for the pipelined decode loop:
        the sample/advance/freeze tail of ONE _build_window sub-step,
        lifted out of the stage programs so it runs once per
        (round, group) on the last stage's output. The freeze math is
        copied verbatim from the window body — same argmax/draw trio,
        same budget/eos gating, same pos/table zeroing — which is what
        pins pp greedy output token-identical to pp_stages=1."""
        from defer_tpu.utils.memo import cached_step

        eos = self.eos_id

        def build():
            def ctl(ll, keys, temp, topk, topp, minp, pos, n, active,
                    budget, tables):
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
                adv = active.astype(jnp.int32)
                pos = pos + adv
                n = n + adv
                alive = active & (n < budget)
                if eos is not None:
                    alive = alive & (nxt != eos)
                feed = nxt[:, None].astype(jnp.int32)
                pos_eff = jnp.where(alive, pos, 0)
                tab_eff = jnp.where(alive[:, None], tables, 0)
                return (
                    nxt, keys, pos, n, alive, feed, pos_eff, tab_eff,
                )

            return jax.jit(ctl)

        return cached_step(
            self.dec, ("paged_pp_ctl", mode, eos), build
        )

    def _tick_pp(self) -> None:
        """One PIPELINED decode window: decode_window rounds for each
        of M in-flight microbatch slot groups, chained through the S
        stages round-major (GPipe schedule). Every stage dispatch is
        asynchronous — while stage s computes group g's round, the
        host has already enqueued group g+1 on stage s-1 — so up to M
        chains overlap in flight and only the drain at the bottom
        synchronizes.

        Occupancy is MEASURED at the schedule level, which is
        placement-independent: dispatch (round k, group g) enters
        stage s at slot k*M_live + g + s, each stage is busy for
        `chains` of the span's `chains + S - 1` slots, and the bubble
        fraction published per window is 1 - mean occupancy =
        (S-1)/(K*M_live + S-1) — groups with no live slot at the
        window boundary are skipped, which is what makes the number
        measured rather than the closed form."""
        live = [s is not None for s in self.slots]
        if not any(live):
            return
        K = self.decode_window
        S = self.pp
        stages = self._pp_stage_objs
        sm = self._sampler
        sampling = any(
            s is not None and s["sampling"] for s in self.slots
        )
        if not sampling:
            mode = "argmax"
        elif any(sm.row_sort):
            mode = "sort"
        else:
            mode = "nosort"
        budget = [
            s["remaining"] if s is not None else 0
            for s in self.slots
        ]
        posm = np.where(live, self.pos, 0).astype(np.int32)
        ctl = self._build_pp_ctl(mode)
        put = stages[-1]._put if hasattr(stages[-1], "_put") else jnp.asarray
        groups = self._pp_groups
        Bg = len(groups[0])
        nk1 = jnp.ones((Bg,), jnp.int32)
        kf0 = jnp.zeros((Bg,), jnp.int32)
        # Per-group device state on the CONTROLLER placement (the last
        # stage's): the same aliasing-copy rule as _tick_window for
        # tables/adapter, the same host-side round-0 freeze masks the
        # window body computes from its initial `active`.
        st: list[dict | None] = [None] * len(groups)
        for g, idx in enumerate(groups):
            if not any(live[i] for i in idx):
                continue
            # analysis: ignore[host-sync-in-hot-loop] host index list
            # (python ints), no device buffer crosses here
            ia = np.asarray(idx)
            # analysis: ignore[host-sync-in-hot-loop] host bool list
            live_g = np.asarray([live[i] for i in idx])
            tab_g = self.tables[ia].copy()
            pos_g = posm[ia]
            st[g] = {
                "tables": put(tab_g),
                "tab_eff": put(np.where(live_g[:, None], tab_g, 0)),
                "pos": put(pos_g),
                "pos_eff": put(np.where(live_g, pos_g, 0)),
                "n": put(np.zeros(len(idx), np.int32)),
                "active": put(live_g),
                "budget": put(
                    # analysis: ignore[host-sync-in-hot-loop] host ints
                    np.asarray([budget[i] for i in idx], np.int32)
                ),
                "feed": put(self._feed[ia]),
                "keys": put(sm.keys[ia]),
                "temp": put(sm.temp[ia]),
                "topk": put(sm.topk[ia]),
                "topp": put(sm.topp[ia]),
                "minp": put(sm.minp[ia]),
                "adapter": put(self.adapter[ia].copy()),
                "toks": [],
            }
        disp = self.obs.pp_stage_dispatches
        chains = 0
        for _k in range(K):
            for g, state in enumerate(st):
                if state is None:
                    continue
                x = state["feed"]
                for s, stage in enumerate(stages):
                    x = stage.pp_dispatch(
                        state["tab_eff"], state["pos_eff"], x, nk1,
                        kf0, state["adapter"],
                    )
                    self.pp_stage_dispatch_n[s] += 1
                    disp[s].inc()
                chains += 1
                (nxt, keys, pos, n, alive, feed, pos_eff,
                 tab_eff) = ctl(
                    put(x[:, -1, :]), state["keys"], state["temp"],
                    state["topk"], state["topp"], state["minp"],
                    state["pos"], state["n"], state["active"],
                    state["budget"], state["tables"],
                )
                state.update(
                    keys=keys, pos=pos, n=n, active=alive, feed=feed,
                    pos_eff=pos_eff, tab_eff=tab_eff,
                )
                state["toks"].append(nxt)
        # Write the per-group sampler/feed state back to the full-B
        # vectors on their home device (async device-to-device puts).
        dev0 = jax.devices()[0]
        for g, state in enumerate(st):
            if state is None:
                continue
            ia = jnp.asarray(groups[g])
            self._feed = self._feed.at[ia].set(
                jax.device_put(state["feed"], dev0)
            )
            sm.keys = sm.keys.at[ia].set(
                jax.device_put(state["keys"], dev0)
            )
        self.ticks += 1
        self.dispatches += 1
        n_live = sum(live)
        now = time.perf_counter()
        if self._last_tick_t is not None:
            self.obs.itl.observe(now - self._last_tick_t, n_live)
        self._last_tick_t = now
        self.obs.ticks.inc()
        self.obs.host_dispatches.inc()
        # Every chain is one full forward spread over the S stages:
        # its collectives sum to the same 2L+2 the monolithic sharded
        # forward issues (psum mirror contract).
        self._account_psums(chains)
        occ, bubble = pp_schedule_occupancy(
            [chains] * S, chains + S - 1
        )
        self.pp_occupancy_last = occ
        self.pp_bubble_last = bubble
        self.obs.pp_bubble_fraction.set(bubble)
        for s, o in enumerate(occ):
            self.obs.pp_stage_occupancy[s].set(o)
        need_toks = self.on_token is not None or any(
            s is not None and s["stop"] is not None
            for s in self.slots
        )
        if self.eos_id is not None:
            emitted: list[int] = []
            alive_host: list[bool] = []
            for g, idx in enumerate(groups):
                if st[g] is None:
                    emitted += [0] * len(idx)
                    alive_host += [False] * len(idx)
                    continue
                # analysis: ignore[host-sync-in-hot-loop] one batched
                # per-WINDOW transfer of the group's valid-length /
                # alive vectors — K tokens amortize it, same waiver
                # as _tick_window
                emitted += np.asarray(st[g]["n"]).tolist()
                # analysis: ignore[host-sync-in-hot-loop] same
                # per-window sync point (ready with the vector above)
                act_g = np.asarray(st[g]["active"]).tolist()
                alive_host += [bool(a) for a in act_g]
        else:
            emitted = [min(b, K) for b in budget]
            alive_host = [b > K for b in budget]
        # Assemble the full-B [B, K] token buffer on the home device
        # (groups are contiguous ascending index ranges, so group
        # order IS slot order); skipped groups contribute zeros their
        # emitted=0 drain never reads.
        parts = []
        for g, state in enumerate(st):
            if state is None:
                parts.append(jnp.zeros((Bg, K), jnp.int32))
                continue
            parts.append(
                jax.device_put(
                    jnp.stack(state["toks"], axis=1), dev0
                ).astype(jnp.int32)
            )
        toks = jnp.concatenate(parts, axis=0)
        # analysis: ignore[host-sync-in-hot-loop] the ONE batched
        # [B, K] token transfer per window — only when a stream/stop
        # consumer exists, same waiver as _tick_window
        toks_host = np.asarray(toks).tolist() if need_toks else None
        self._account_kv_rows_window(posm, emitted)
        self._drain_window(toks, toks_host, emitted, alive_host,
                           budget)

    def close_pp(self) -> None:
        """Release pipeline-stage resources: transport-placed stages
        send their STOP frame so remote workers' serve loops exit
        (in-process stages are no-ops)."""
        for stage in self._pp_stage_objs:
            stage.close()

    def _account_kv_rows_window(self, posm, emitted) -> None:
        """Windowed K/V-row accounting: the exact host-side mirror of
        what each attention path read across the window's K sub-steps
        (same units/contract as the K=1 tick's accounting). A row
        active at sub-step t (t < emitted[i]) reads at depth
        posm[i] + t; frozen and idle rows sit at position 0 (trash
        block), exactly as the device's pos_eff zeroing makes them."""
        K = self.decode_window
        bs = self.bs
        baseline = K * self.B * self.MB * bs
        if self.attention == "gathered":
            rows_read = baseline
        else:
            # Pure-python mirror over host ints (posm/emitted are
            # already host-side — nothing here touches the device).
            pos_l = posm.tolist()
            win = self.dec.cfg.window
            rows_read = 0
            for t in range(K):
                pe = [
                    p + t if t < e else 0
                    for p, e in zip(pos_l, emitted)
                ]
                if self.attention == "blockwise":
                    rows_read += (
                        self.B * (max(pe) // bs + 1) * bs
                    )
                else:  # pallas
                    rows_read += bs * sum(
                        p // bs
                        - (max(p - win + 1, 0) // bs
                           if win is not None else 0)
                        + 1
                        for p in pe
                    )
        self._account_kv_rows(rows_read, baseline)

    def _drain_window(
        self, toks, toks_host, emitted, alive_host, budget,
        died_host=None, fracs_host=None,
    ) -> None:
        """Host-side window drain, per-token-equivalent to the K=1
        tick loop (flat-server _drain_window docstring has the
        contract): stop sequences truncate overshoot, budgets and
        finishes mirror the per-token bookkeeping, streaming fires in
        tick-major order, and block release (_finish) happens at the
        window boundary."""
        K = self.decode_window
        accepted = [0] * self.B
        finishing = [False] * self.B
        for i, slot in enumerate(self.slots):
            if slot is None:
                continue
            n_i = emitted[i]
            a_i = n_i
            stopped = False
            dead = bool(
                died_host is not None and died_host[i]
                and slot.get("cid")
            )
            if dead:
                # Dead-end DFA state mid-window: the device froze the
                # row with a FORCED eos (counted in n_i) — drop it, so
                # the output ends at the last admissible token and the
                # failure surfaces as a per-request error, not a hang.
                a_i = n_i - 1
            if slot["stop"] is not None:
                hit = slot["stop"].push_window(toks_host[i][:a_i])
                if hit is not None:
                    a_i, stopped = hit, True
            accepted[i] = a_i
            if a_i < min(budget[i], K):
                self.obs.window_truncated.inc()
            slot["remaining"] -= a_i
            if stopped or not alive_host[i]:
                # eos froze the row on device, a stop sequence cut it
                # on drain, or its budget ran out mid-window.
                slot["remaining"] = 0
            if dead:
                slot["remaining"] = 0
                self.errors[slot["rid"]] = (
                    "constraint dead end: DFA state admits no token "
                    "and is not accepting"
                )
                self.constraint_dead_ends_n += 1
                self.obs.constrain_dead_ends.inc()
            if slot.get("cid") and fracs_host is not None:
                self.constrained_tokens_n += a_i
                if a_i:
                    self.obs.constrained_tokens.inc(a_i)
                for fr in fracs_host[i][:a_i].tolist():
                    self.obs.constrain_masked_frac.observe(fr)
            if toks_host is not None:
                slot["out"] += toks_host[i][:a_i]
            elif a_i:
                # No consumer asked for the values: `_finish` reads
                # this slot's part of the window's buffer.
                slot["out"].append((toks, (i, slice(0, a_i))))
            self.pos[i] += a_i
            finishing[i] = slot["remaining"] == 0
            self.obs.tokens_generated.inc(a_i)
            self.window_tokens += a_i
        self.obs.tokens_per_dispatch.set(float(sum(accepted)))
        if self.on_token is not None:
            for t, i in window_drain_order(accepted, K):
                slot = self.slots[i]
                self.on_token(
                    slot["rid"],
                    toks_host[i][t],
                    finishing[i] and t == accepted[i] - 1,
                )
        for i in range(self.B):
            if finishing[i]:
                self._finish(i)

    def _emit_token(self, i: int, slot: dict, tok: int | tuple) -> None:
        """Record one emitted token in the slot and do the shared
        eos/streaming/finish bookkeeping (admission first-token and
        every tick): `tok` is the token's value on the host or, where
        neither eos nor streaming nor a stop sequence needed the
        transfer, where `_finish` will find it: (device array, index)."""
        slot["out"].append(tok)
        if isinstance(tok, tuple):
            tok = None
        self.obs.tokens_generated.inc()
        if (
            self.eos_id is not None
            and tok is not None
            and tok == self.eos_id
        ):
            slot["remaining"] = 0
        if (
            slot["stop"] is not None
            and tok is not None
            and slot["stop"].push(tok)
        ):
            slot["remaining"] = 0
        if self.on_token is not None:
            self.on_token(slot["rid"], tok, slot["remaining"] == 0)
        if slot["remaining"] == 0:
            self._finish(i)

    def _update_pool_gauges(self) -> None:
        self.obs.pool_blocks_free.set(len(self.free))
        self.obs.pool_blocks_used.set(self.blocks_in_use)
        if self.pool_state:
            self.obs.linear_state_slots_live.set(
                sum(s is not None for s in self.slots)
            )

    def _finish(self, i: int) -> None:
        slot = self.slots[i]
        with spans.span("paged.finish", rid=slot["rid"]) as sp:
            self.obs.requests_finished.inc()
            # Joined on the host, so no program is built whatever the
            # lengths: a slot's tokens are ints, but for those no
            # consumer needed before now. One transfer fetches each
            # such array (jax keeps the host copy, and the slots of a
            # tick share the array), one uploads the result.
            # analysis: ignore[host-sync-in-hot-loop] once per REQUEST,
            # of a prompt the device finished with at admission
            prompt = np.asarray(slot["prompt"])
            out: list[int] = []
            late = 0
            for tok in slot["out"]:
                if isinstance(tok, tuple):
                    arr, idx = tok
                    # analysis: ignore[host-sync-in-hot-loop] once per
                    # REQUEST, and only for tokens no consumer read as
                    # they were made: the wait the ticks did not make
                    got = np.asarray(arr)[idx].reshape(-1).tolist()
                    late += len(got)
                    out += got
                else:
                    out.append(tok)
            if late:
                self.obs.tokens_resolved_at_finish.inc(late)
            n_toks = sp.counts["tokens"] = len(out)
            # analysis: ignore[host-sync-in-hot-loop] a host int list
            ids = np.asarray(out, prompt.dtype)[None, :]
            self.done[slot["rid"]] = jax.device_put(
                np.concatenate([prompt, ids], axis=1), self.device
            )
            if self.radix is not None:
                # Shared blocks deref (parking at refcount 0 for later
                # revival); only privately owned blocks free immediately.
                # Released DEEPEST-FIRST so LRU eviction reclaims the
                # deep end of a chain before its shallow (more reusable,
                # and prerequisite-for-lookup) blocks.
                for blk in reversed(slot.get("shared", ())):
                    self.radix.release(blk)
            self.free.extend(slot["blocks"])
            self.tables[i] = 0
            self.pos[i] = 0
            self.adapter[i] = 0
            self.slots[i] = None
            if self._draft is not None:
                self._draft.release(i)
            # Release the slot's sampling policy row NOW, not at reuse —
            # a lingering row_sort would drag every later tick through the
            # sorting sampler (decode_server.SlotSampler.release).
            self._sampler.release(i)
            self._update_pool_gauges()
        if "submit_t" in slot:  # seated by the default admission
            spans.record(
                "paged.request", slot["submit_t"], time.perf_counter(),
                rid=slot["rid"], queue_s=slot["queue_s"],
                prompt_tokens=prompt.shape[1], tokens=n_toks,
            )


def serve_paged(
    dec: Any,
    params: dict,
    requests: list[tuple[jax.Array, int]],
    *,
    num_blocks: int,
    block_size: int = 16,
    max_batch: int = 4,
    eos_id: int | None = None,
    adapter_ids: list | None = None,
    prefix_ids: jax.Array | None = None,
    prefix_cache: bool = False,
    sampling: list | None = None,
    attention: str = "gathered",
    kv_dtype: str = "fp",
    spill_bytes: int = 0,
    decode_window: int = 1,
    spec_draft: Any = None,
    spec_params: dict | None = None,
    spec_k: int = 0,
    prefill_chunk: int | None = None,
    prefill_budget: int | None = None,
    prefill_lookahead: int = 2,
    mesh: Any = None,
    model_axis: str = "model",
    constraints: dict | None = None,
    pp_stages: int = 1,
    pp_inflight: int | None = None,
    pp_cuts: Any = None,
    pp_devices: Any = None,
    pp_remote: dict | None = None,
    pp_balance: str = "equal",
) -> tuple[list[jax.Array], dict]:
    """One-shot paged serving; returns (outputs in submission order,
    stats incl. peak pool usage). `adapter_ids` optionally assigns a
    LoRA adapter per request (parallel/lora.py::stack_adapters);
    `sampling` optionally assigns a SamplingParams per request;
    `attention` selects the decode attention path
    (PagedDecodeServer docstring / module docstring).

    `decode_window=K` fuses K decode sub-steps into one host dispatch
    (PagedDecodeServer docstring has the semantics); outputs stay
    token-identical to the default K=1. Stats then also carry
    `decode_window`, `host_dispatches` (decode dispatches issued) and
    `tokens_per_dispatch` (mean tokens accepted per dispatch — the
    dispatch-amortization win, approaching K * live slots).

    `spec_k=k` with `spec_draft`/`spec_params` turns on paged
    speculative decoding (PagedDecodeServer docstring): greedy
    outputs stay token-identical to `spec_k=0`; stats then also carry
    `spec_rounds` / `spec_proposed` / `spec_accepted` /
    `spec_acceptance` / `spec_draft_tokens`. `prefill_chunk=C`
    switches admission to the pool-native chunked prefill path.

    `prefill_budget=N` turns on STALL-FREE continuous batching
    (PagedDecodeServer docstring): admission prefill rides inside the
    decode dispatches, up to N prompt tokens per tick, token-identical
    greedy output to the default None. Stats always carry
    `prefill_budget`, `prefill_stall_ticks` (serialized-prefill
    dispatches issued while decode slots waited), `mixed_ticks`,
    `mixed_prefill_tokens`, and `decode_stall_fraction`.

    `mesh=` / `model_axis=` run the server tensor-parallel: weights
    and the KV block pool shard over the named mesh axis and every
    tick body runs under shard_map (PagedDecodeServer docstring has
    the layout). Greedy output is token-identical to `mesh=None`;
    stats then also carry `mesh_shape` and `tp_psums`.

    `kv_dtype="int8"` stores the pool quantized (PagedDecodeServer
    docstring: half the HBM bytes, bounded-logit-error accuracy
    contract); `spill_bytes=N` adds the host-RAM spill tier for
    evicted prefix blocks (needs prefix_cache=True). Stats carry
    `kv_dtype`, `pool_bytes` and the spill totals either way.

    `constraints={name: TokenDFA}` registers compiled grammars
    (defer_tpu/constrain/) that per-request SamplingParams can opt
    into via `constraint="name"`; stats then also carry
    `constrained_tokens` / `constraint_dead_ends`.

    `pp_stages=S` runs the server pipeline-parallel (PagedDecodeServer
    docstring: staged layer stack, per-stage KV pool slices, M
    in-flight microbatch groups). Greedy output is token-identical to
    `pp_stages=1`; stats then also carry `pp_stages` / `pp_inflight` /
    `pp_bubble_fraction` (measured, last window) /
    `pp_stage_occupancy` / `pp_stage_dispatches` /
    `pp_stage_pool_bytes`."""
    srv = PagedDecodeServer(
        dec,
        params,
        num_blocks=num_blocks,
        block_size=block_size,
        max_batch=max_batch,
        eos_id=eos_id,
        prefix_ids=prefix_ids,
        prefix_cache=prefix_cache,
        attention=attention,
        kv_dtype=kv_dtype,
        spill_bytes=spill_bytes,
        decode_window=decode_window,
        spec_draft=spec_draft,
        spec_params=spec_params,
        spec_k=spec_k,
        prefill_chunk=prefill_chunk,
        prefill_budget=prefill_budget,
        prefill_lookahead=prefill_lookahead,
        mesh=mesh,
        model_axis=model_axis,
        constraints=constraints,
        pp_stages=pp_stages,
        pp_inflight=pp_inflight,
        pp_cuts=pp_cuts,
        pp_devices=pp_devices,
        pp_remote=pp_remote,
        pp_balance=pp_balance,
    )
    aids = adapter_ids or [0] * len(requests)
    if len(aids) != len(requests):
        raise ValueError(
            f"adapter_ids has {len(aids)} entries for "
            f"{len(requests)} requests"
        )
    samps = sampling or [None] * len(requests)
    if len(samps) != len(requests):
        raise ValueError(
            f"sampling has {len(samps)} entries for "
            f"{len(requests)} requests"
        )
    rids = [
        srv.submit(p, s, adapter_id=a, sampling=sp)
        for (p, s), a, sp in zip(requests, aids, samps)
    ]
    done = srv.run()
    if srv.pp > 1:
        srv.close_pp()
    if srv._spill is not None:
        # Drain pending spill copies so the stats snapshot (and any
        # caller inspecting the store) sees a settled tier.
        srv._spill.flush()
    stats = ServerStats.snapshot(
        srv.obs.registry,
        ticks=srv.ticks,
        attention=attention,
        peak_blocks=srv.blocks_peak,
        pool_blocks=srv.num_blocks - 1,
        block_size=block_size,
        flat_equivalent_rows=max_batch * dec.cfg.max_len,
        shared_prefix_blocks=len(srv.shared_blocks),
        prefill_tokens_saved=srv.prefill_tokens_saved,
        cached_blocks=(
            srv.radix.cached_blocks if srv.radix is not None else 0
        ),
        decode_window=srv.decode_window,
        host_dispatches=srv.dispatches,
        tokens_per_dispatch=(
            srv.window_tokens / srv.dispatches if srv.dispatches else 0.0
        ),
        spec_k=srv.spec_k,
        spec_rounds=srv.spec_rounds_n,
        spec_proposed=srv.spec_proposed_n,
        spec_accepted=srv.spec_accepted_n,
        spec_acceptance=(
            srv.spec_accepted_n / srv.spec_proposed_n
            if srv.spec_proposed_n
            else 0.0
        ),
        spec_draft_tokens=srv.spec_draft_tokens_n,
        prefill_chunk=srv.prefill_chunk,
        prefill_budget=srv.prefill_budget,
        prefill_stall_ticks=srv.prefill_stall_ticks_n,
        mixed_ticks=srv.mixed_ticks_n,
        mixed_prefill_tokens=srv.mixed_prefill_tokens_n,
        decode_stall_fraction=srv.decode_stall_fraction_last,
        mesh_shape=srv.mesh_label,
        tp_psums=srv.tp_psums,
        kv_dtype=srv.kv_dtype,
        pool_bytes=srv.pool_bytes,
        spilled_blocks=(
            srv._spill.stored_blocks if srv._spill is not None else 0
        ),
        spill_hits=srv.spill_hits_n,
        spill_stored_bytes=(
            srv._spill.stored_bytes if srv._spill is not None else 0
        ),
        constrained_tokens=srv.constrained_tokens_n,
        constraint_dead_ends=srv.constraint_dead_ends_n,
        pp_stages=srv.pp,
        pp_inflight=srv._pp_inflight if srv.pp > 1 else 0,
        pp_bubble_fraction=srv.pp_bubble_last,
        pp_stage_occupancy=list(srv.pp_occupancy_last),
        pp_stage_dispatches=list(srv.pp_stage_dispatch_n),
        pp_stage_pool_bytes=list(srv.pp_stage_pool_bytes),
        pp_cut_starts=list(srv._pp_cut_starts),
    )
    return [done[r] for r in rids], stats
