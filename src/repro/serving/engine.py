"""Serving engine: continuous batching over a paged KV cache.

Requests enter a queue and are admitted to cache slots *individually*, the
moment a slot frees up — there is no wave barrier.  Each slot carries its
own write position, so a request prefilled at length 11 decodes next to
one at length 300 inside the same jitted dispatch, and a request that
finishes mid-stream hands its slot (and its KV pages) to the next pending
request while the others keep decoding.

Chunked prefill (§Perf, DESIGN.md §8b): with the paged cache, prompts are
prefilled in FIXED-SIZE chunks — a plan-derived multiple of the KV page
size, so chunk boundaries land on page boundaries — through ONE compiled
``prefill_chunk`` program whose offset/page-id operands are traced
scalars.  The compile count is therefore independent of the prompt-length
mix: a burst of 20 distinct lengths compiles one prefill program plus one
decode program, where the per-length path compiled 20.  Chunk *k* writes
its K/V into its pages and attends to chunks 0..k-1 through the same
pools the decode step appends to, and a half-prefilled request yields the
device between chunks: a token-budget scheduler hands each dispatch
either prefill chunks, a decode block, or both, so arrivals no longer
serialize behind whole-prompt prefills.  SSM/RWKV/hybrid configs (whose
recurrent state cannot yet resume mid-prompt) and the contiguous cache
fall back to whole-prompt prefill automatically.

Admission contract: an empty or over-long (``plen > max_len``) prompt is
FAILED at admission (``Request.failed`` + ``Request.error``) without ever
taking a slot or a page — it cannot strand the requests already decoding.
A slot abandoned MID-prefill (allocator failure between chunks) fails the
same way: its already-placed pages return to the allocator exactly once
(refcounted release — see ``kv_cache.assert_page_accounting``).

Prefix cache (DESIGN.md §10): in chunked mode the engine threads
admission through a radix-tree prefix walk (``serving/prefix_cache.py``)
— a request whose prompt shares page-aligned chunks with earlier traffic
claims the cached physical pages into its table row and prefills only
the divergent tail; on slot exit the pages stay cached in the tree until
memory pressure evicts them.  ``prefix_bootstrap=True`` additionally
claims partial tail pages and serves a fully-cached prompt through the
decode path alone (one dispatch to first token), copy-on-writing the
shared last page before the first append.  ``admission=`` picks the
queue order: FIFO (default), shortest-job-first, or
longest-cached-prefix-first.

Scheduler knobs: the chunked-prefill token budget is backlog-adaptive
(``_prefill_budget``), and ``adaptive_decode_block=True`` additionally
scales the decode scan length with the active-slot count — floored at
the static ``decode_block``, stepped in power-of-two multiples (bounded
compile count), pulled back by the ``decode_eff`` EMA when scan ticks
are being wasted.

Mesh-aware serving (DESIGN.md §9): constructed with ``mesh=``, the engine
resolves its StreamPlan against the mesh (per-stage sharding decisions),
creates the paged K/V pools ``kv_heads``-sharded over the model axis with
a replicated page table, replicates the weights onto the mesh, and traces
every dispatch under ``use_mesh`` so the plan-selected Pallas kernels run
inside ``shard_map`` — the same code path serves one device, the forced
8-virtual-device CPU mesh, and a real cluster, and greedy tokens match
the single-device engine.

Decode hot loop (§Perf):

  * The KV cache is PAGED (``kv_cache.PagedKVCache``): fixed-size pages,
    a ``[slots, max_pages]`` device page table, host-side free-list
    allocation.  Bytes-in-use is ``pages_used * page_bytes`` instead of
    the contiguous ``slots * max_len`` worst case; pages are allocated
    just ahead of each decode block and returned the moment a request
    retires.  ``paged=False`` keeps the PR-1 contiguous slot cache (same
    continuous scheduler) for A/B benchmarking.
  * Decode attention streams K/V pages through the page-table indirection
    in the ``paged_attention`` Pallas kernel when the StreamPlan selects
    it (``use_fused_kernels``); eager configs run the gather-pages
    reference path.  Either way the math bit-matches the contiguous
    eager decode.
  * The cache is DONATED through prefill placement and decode dispatches,
    so K/V updates happen in place; decode runs ``decode_block`` ticks
    per jitted dispatch as a ``lax.scan`` over ``decode_step`` with
    per-slot position/length vectors.
  * Slots that are idle — or parked mid-prefill with live pages — ride
    along in decode dispatches with their write position at the table
    extent, so ``paged_append`` routes their writes to the NULL page and
    a half-prefilled slot's K/V survives interleaved decode blocks; their
    outputs are discarded on the host.

Self-speculative decoding (DESIGN.md §11): with ``speculative=True`` the
decode scan is replaced by draft-then-verify.  Each pass drafts up to
``draft_len`` token guesses per slot from cheap host-side sources (the
prefix-cache radix tree via ``PrefixCache.suggest``, then n-gram
prompt-lookup over the slot's own history), stacks ``[pending, d1..dk]``
into a ``[slots, W]`` window, and scores every position with ONE paged
``verify_step`` dispatch.  A draft is accepted while it equals the
previous row's greedy argmax — acceptance can only ever keep tokens the
model itself would have produced, so greedy outputs are BIT-IDENTICAL to
the non-speculative engine; a repetitive stretch delivers up to
``draft_len + 1`` tokens per dispatch, a cold stretch still delivers one.
Rejected rows leave K/V garbage past the new write head; wholly-stale
pages roll back through ``PagedKVCache.rollback_extent`` (refcount-
checked: draft pages are freshly allocated and never tree-adopted, so
rollback can never free a shared prefix page).  Window widths come from
a <=3-rung ladder, so the verify program compiles at most three times.

Metrics count REAL work: ``generated`` is tokens actually delivered to
requests (padding slots and past-budget scan ticks excluded), ``ticks``
is the per-dispatch maximum of useful ticks, ``scan_ticks`` is what the
hardware executed — their ratio is the block-decode efficiency — and
``prefill_traces`` / ``decode_traces`` count jit RETRACES of the two
dispatch programs (a trace-time probe: the traced Python body bumps a
host counter), the compile-storm signal this engine exists to flatten.
"""

from __future__ import annotations

import os
import time
import warnings as _warnings
from collections import deque
from contextlib import ExitStack, nullcontext
from dataclasses import dataclass, field, replace
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs.base import ModelConfig
from ..distributed.context import use_mesh
from ..kernels.paged_attention import live_page_span
from ..models import (decode_step, init_cache, prefill, resolve_plan,
                      supports_chunked_prefill, supports_speculative,
                      verify_step)
from ..models import prefill_chunk as _model_prefill_chunk
from ..models.params import cache_leaf_kind, cache_leaf_name
from ..obs import (DISPATCH_DECODE, DISPATCH_PREFILL,
                   DISPATCH_PREFILL_CHUNK, DISPATCH_VERIFY, ENGINE_ADMIT,
                   ENGINE_CHUNK_STAGE, ENGINE_DECODE_ENQUEUE,
                   ENGINE_DECODE_PREP, ENGINE_GENERATE, ENGINE_HARVEST,
                   ENGINE_PREFILL_ENQUEUE, ENGINE_PREFIX_INSERT,
                   ENGINE_RETIRE, ENGINE_VERIFY_ENQUEUE, HostSpan,
                   MetricsView, NULL_RECORDER, Registry, REQ_ADMITTED,
                   REQ_FINISHED, REQ_FIRST_TOKEN, REQ_PREFILL_CHUNK,
                   REQ_QUEUED, REQ_REJECTED, SCHED_BUDGET, TRACE_DECODE,
                   TRACE_PREFILL, TRACE_VERIFY, TRACK_ENGINE, TRACK_SCHED,
                   WAIT_DECODE, WAIT_FIRST_TOKEN, WAIT_PREFILL, WAIT_VERIFY,
                   resolve_recorder, slot_track)
from .kv_cache import (NULL_PAGE, PagedKVCache, cdiv, place_prefill,
                       stage_chunk)
from .prefix_cache import PrefixCache

Tree = Any


@dataclass
class Request:
    rid: int
    prompt: np.ndarray              # [S] int32 (or embeds [S, D])
    max_new_tokens: int = 16
    out_tokens: List[int] = field(default_factory=list)
    done: bool = False
    failed: bool = False
    error: Optional[str] = None
    prefill_pos: int = 0            # prompt tokens already prefilled
    # Lifecycle stamps, all on the ENGINE's clock (``ServingEngine.clock``
    # — the injectable obs clock, ``time.perf_counter`` by default), so
    # request latencies and the trace's dispatch spans share one
    # timebase.  0.0 means "hasn't happened"; the derived properties
    # below return ``nan`` until their stamps exist and a finite value
    # forever after — an admission-REJECTED request still gets a real
    # ``finished_at`` (it failed AT a wall-clock time), so its
    # ``latency_s`` is finite while its ``ttft_s`` stays nan.
    submitted_at: float = 0.0
    admitted_at: float = 0.0
    first_token_at: float = 0.0
    finished_at: float = 0.0

    @property
    def ttft_s(self) -> float:
        """Time to first token; ``nan`` until a first token exists (never
        admitted, failed at admission, or still queued)."""
        if self.first_token_at <= 0.0 or self.submitted_at <= 0.0:
            return float("nan")
        return self.first_token_at - self.submitted_at

    @property
    def latency_s(self) -> float:
        """Submit-to-finish wall time; ``nan`` until the request finished
        (and for requests that never entered the engine)."""
        if self.finished_at <= 0.0 or self.submitted_at <= 0.0:
            return float("nan")
        return self.finished_at - self.submitted_at

    @property
    def queue_wait_s(self) -> float:
        """Submit-to-admission wait; ``nan`` until the request takes a
        slot (rejected requests never do)."""
        if self.admitted_at <= 0.0 or self.submitted_at <= 0.0:
            return float("nan")
        return self.admitted_at - self.submitted_at

    @property
    def tpot_s(self) -> float:
        """Time per output token AFTER the first (decode steady-state):
        ``(finished - first_token) / (n_tokens - 1)``.  ``nan`` until
        finished, and for requests that produced fewer than two tokens
        (a single token has no inter-token gap)."""
        n = len(self.out_tokens)
        if (n < 2 or self.finished_at <= 0.0
                or self.first_token_at <= 0.0):
            return float("nan")
        return (self.finished_at - self.first_token_at) / (n - 1)


def _ngram_continuation(hist: np.ndarray, k: int) -> List[int]:
    """Prompt-lookup drafting: find the most recent EARLIER occurrence of
    the history's trailing n-gram (n = 3, then 2) and return up to ``k``
    of the tokens that followed it.  Pure host work — one vectorized
    sliding-window compare per n."""
    n_tok = int(hist.shape[0])
    for n in (3, 2):
        if n_tok <= n:
            continue
        tail = hist[-n:]
        # Windows over hist[:-1] end strictly before the last token, so
        # the trailing n-gram can never match itself.
        win = np.lib.stride_tricks.sliding_window_view(hist[:-1], n)
        hits = np.nonzero((win == tail[None, :]).all(axis=1))[0]
        if hits.size:
            i = int(hits[-1])
            return [int(t) for t in hist[i + n:i + n + k]]
    return []


def _place_cache_slot(cache: Tree, fresh: Tree, slot: jax.Array) -> Tree:
    """Write a batch-1 prefill cache into one slot of the contiguous cache.

    Every leaf places at ``(0, slot, 0, ...)``: K/V leaves fill the slot's
    sequence prefix (an in-place ``dynamic_update_slice`` under donation),
    state leaves replace the slot row.  Leaf classification goes through
    the shared schema — an unregistered leaf raises instead of being
    silently whole-replaced.
    """
    def place(path, big, small):
        cache_leaf_kind(cache_leaf_name(path))      # validate: kv or state
        start = (jnp.int32(0), slot) + (jnp.int32(0),) * (big.ndim - 2)
        return lax.dynamic_update_slice(big, small.astype(big.dtype), start)
    return jax.tree_util.tree_map_with_path(place, cache, fresh)


class ServingEngine:
    """Continuously-batched greedy generation over a fixed slot count."""

    def __init__(self, cfg: ModelConfig, params: Tree, *,
                 batch_slots: int = 4, max_len: int = 256,
                 decode_block: int = 16, paged: bool = True,
                 page_size: Optional[int] = None,
                 prefill_chunk: Optional[int] = None,
                 chunked: Optional[bool] = None,
                 prefix_cache: Optional[bool] = None,
                 prefix_bootstrap: bool = False,
                 admission: str = "fifo",
                 adaptive_decode_block: bool = False,
                 speculative: bool = False, draft_len: int = 4,
                 quant: Optional[str] = None,
                 verify: Optional[str] = None,
                 autotune=None,
                 telemetry=None, clock=None,
                 mesh=None):
        # Quantized serving (DESIGN.md §14): ``quant=`` overrides the
        # config's QuantMode for this engine — the plan, kernel choices,
        # and paged pool dtypes all key off ``cfg.quant`` downstream.
        if quant is not None and quant != cfg.quant:
            cfg = replace(cfg, quant=quant)
        if cfg.kv_quant and not paged:
            raise ValueError("KV quantization requires the paged cache "
                             "(per-page scale pools ride next to the "
                             "page pools)")
        self.cfg = cfg
        self.mesh = mesh
        if admission not in ("fifo", "sjf", "prefix"):
            raise ValueError(f"unknown admission policy {admission!r} "
                             "(fifo | sjf | prefix)")
        self.admission = admission
        self.adaptive_decode_block = adaptive_decode_block
        if mesh is not None:
            # Replicate the weights onto the mesh's device set so every
            # dispatch (and the shard_maps inside) sees mesh-resident
            # inputs; the fused wrappers re-slice per the plan's claims.
            params = jax.device_put(
                params, jax.tree.map(lambda _: NamedSharding(mesh, P()),
                                     params))
        self.params = params
        self.slots = batch_slots
        self.max_len = max_len
        self.decode_block = max(1, decode_block)
        self.paged = paged
        # Trace-time probes: the traced bodies below bump these counters,
        # so they count PROGRAMS BUILT, not dispatches — the engine's
        # compile-storm signal.
        self._traces: Dict[str, int] = {"prefill": 0, "decode": 0,
                                        "verify": 0}
        # Telemetry (DESIGN.md §17): ``telemetry=`` is None/False (off,
        # zero-overhead NULL recorder), True (fresh Recorder), or a
        # Recorder instance; ``clock=`` injects the monotonic clock BOTH
        # the recorder and the Request lifecycle stamps use, so spans and
        # latencies share one timebase (and tests run deterministic).
        self.obs = resolve_recorder(telemetry, clock=clock)
        self.clock = (clock if clock is not None
                      else (self.obs.clock if self.obs.enabled
                            else time.perf_counter))
        # EMA of per-dispatch useful-tick fraction — the adaptive prefill
        # budget's decode-pressure signal (1.0 = every scan tick useful).
        self.decode_eff = 1.0

        # Measured-latency autotuning (DESIGN.md §16): ``autotune=`` is a
        # bool / table path / TuneTable / Tuner.  The resolved tuner is
        # installed (via contextvar, like the mesh) around every plan
        # resolution AND dispatch trace, so the model entry points —
        # which re-resolve plans at their own token counts — pick up
        # tuned block/page choices too.  Tune once at first start,
        # load-and-reuse thereafter: a warm table scores every candidate
        # from disk and performs zero measurements.
        from ..tuning.autotune import resolve_tuner, use_tuner
        self._use_tuner = use_tuner
        self.tuner = resolve_tuner(autotune, cfg)
        if self.tuner is not None:
            self.tuner.obs = self.obs
            for d in self.tuner.table.diagnostics:
                _warnings.warn(f"autotune table degraded: {d}")

        # One plan resolution drives both stream granularities: the KV
        # page size (decode) and the prefill chunk size (a multiple of
        # it) — resolved under the mesh so the plan carries the per-stage
        # sharding decisions (kept on ``self.plan``: the stage records the
        # sharded-serving tests assert against).  None when eager.
        with self._mesh_ctx():
            plan = resolve_plan(cfg, batch_slots, kv_len=max_len)
        self.plan = plan
        if page_size is None:
            # Page size = the StreamPlan's KV stream granule (the raw DSE
            # tile its paged-attention choice carries); 16 when eager.
            page_size = (plan.decode_page_size(16) if plan is not None
                         else 16)

        # Static verification (DESIGN.md §15): run the stream verifier
        # over the resolved plan + pool schema + dispatch effect
        # signatures BEFORE anything is traced.  strict (default) refuses
        # to build an engine whose plan carries error diagnostics; warn
        # reports and proceeds; off skips.  The plan records the outcome
        # (``summary()["verified"]``/``["diagnostics"]``).
        vmode = (verify if verify is not None
                 else os.environ.get("REPRO_VERIFY", "strict"))
        if vmode not in ("strict", "warn", "off"):
            raise ValueError(f"unknown verify mode {vmode!r} "
                             "(strict | warn | off)")
        self.verify_mode = vmode
        if vmode != "off" and plan is not None:
            from ..analysis import (PlanVerificationError, errors as
                                    _diag_errors, verify_plan)
            diags = verify_plan(
                plan, cfg, mesh=mesh,
                slots=batch_slots if paged else None,
                max_len=max_len if paged else None,
                page_size=min(page_size, max_len) if paged else None)
            errs = _diag_errors(diags)
            plan = plan.with_verification(
                not errs, tuple(str(d) for d in diags))
            self.plan = plan
            if errs:
                if vmode == "strict":
                    raise PlanVerificationError(diags)
                _warnings.warn("StreamPlan failed static verification: "
                               + "; ".join(str(d) for d in errs))

        if chunked is None:
            chunked = paged and supports_chunked_prefill(cfg)
        if chunked and not paged:
            raise ValueError("chunked prefill requires the paged cache "
                             "(chunks carry between dispatches in the "
                             "page pools)")
        if chunked and not supports_chunked_prefill(cfg):
            raise ValueError(
                f"config {cfg.name!r} does not support chunked prefill "
                "(SSM/RWKV state or mrope positions)")
        self.chunked = chunked

        # Self-speculative decoding (DESIGN.md §11): draft cheap guesses
        # on the host, score draft_len + 1 positions with one verify
        # dispatch, keep the longest prefix matching the model's own
        # greedy argmax.  Acceptance can only keep tokens greedy decode
        # would have produced, so outputs bit-match the plain engine.
        self.draft_len = int(draft_len)
        if speculative:
            if not paged:
                raise ValueError("speculative decoding requires the paged "
                                 "cache (rejection rolls back the slot's "
                                 "page-table extent)")
            if not supports_speculative(cfg):
                raise ValueError(
                    f"config {cfg.name!r} does not support speculative "
                    "decoding (recurrent state cannot roll back)")
            if self.draft_len < 1:
                raise ValueError("draft_len must be >= 1")
            if plan is not None:
                # The plan clamps the verify window to its KV stream
                # granule: a window wider than one page spans page
                # boundaries mid-row for no measured gain.
                self.draft_len = min(
                    self.draft_len, plan.verify_window(self.draft_len) - 1)
        self.speculative = bool(speculative)
        # Verify-window ladder: each distinct width W is one compiled
        # verify program, so per-pass widths snap UP to a <=3-rung ladder
        # instead of tracking the exact draft count (which would compile
        # once per distinct count).
        self._w_ladder = tuple(sorted(
            {2, self.draft_len // 2 + 1, self.draft_len + 1} - {0, 1}))
        # Tests flip this on to run the allocator's full accounting
        # audit after every rollback (churn soaks).
        self._debug_check_pages = False

        if paged:
            self.kv: Optional[PagedKVCache] = PagedKVCache(
                cfg, slots=batch_slots, max_len=max_len,
                page_size=page_size, mesh=mesh, obs=self.obs)
            self._slot_cache = self.kv.init_cache()
            # The window of the paged decode kernel's layers, for the
            # ``paged_attn_pages`` count: that of the first layer kind the
            # plan runs the kernel in; None when no layer runs it.
            self._kernel_window = next(
                (cfg.sliding_window if kind == "local_attn" else 0
                 for kind, lp in (plan.layers if plan is not None else ())
                 if lp.decode_attn.fused), None)

            def _prefill_into(p, batch, slot_cache, slot, pages):
                self._traces["prefill"] += 1
                self.obs.instant(TRACE_PREFILL, track=TRACK_ENGINE)
                logits, fresh = prefill(p, cfg, batch)
                placed = place_prefill(slot_cache, fresh, slot, pages,
                                       layout=cfg.kv_cache_layout)
                return (jnp.argmax(logits, axis=-1).astype(jnp.int32),
                        placed)

            def _decode_n(p, tok, cache, table, pos, lengths, cow_src,
                          cow_dst, block):
                self._traces["decode"] += 1
                self.obs.instant(TRACE_DECODE, track=TRACK_ENGINE)
                # Copy-on-write step (prefix bootstrap): slots whose next
                # append lands inside a shared page carry a (src, dst)
                # page pair; the shared page is duplicated onto the
                # private dst in every K/V pool BEFORE the scan — inside
                # the donated dispatch, so no extra host round trip.
                # Idle slots carry NULL pairs (the NULL page copied onto
                # itself).  ``table`` already points at dst.  Traced in
                # only when bootstrap can actually produce a COW — a
                # non-bootstrap engine must not pay the no-op page
                # gather/scatter on every decode dispatch.
                if prefix_bootstrap:
                    # Page-indexed leaves — K/V pools AND their per-page
                    # scale rows — copy together (dim 1 is pages on
                    # both); state rows are slot-indexed and skip.
                    def cow(path, leaf):
                        if cache_leaf_kind(cache_leaf_name(path)) == "state":
                            return leaf
                        return leaf.at[:, cow_dst].set(leaf[:, cow_src])

                    with jax.named_scope("cow"):
                        cache = jax.tree_util.tree_map_with_path(cow, cache)

                def tick(carry, _):
                    tok, cache, pos, lengths = carry
                    with jax.named_scope("tick"):
                        nt, _lg, cache = decode_step(
                            p, cfg, tok, cache, pos, lengths,
                            page_table=table)
                    return (nt, cache, pos + 1, lengths + 1), nt[:, 0]

                carry, toks = lax.scan(tick, (tok, cache, pos, lengths),
                                       None, length=block)
                return carry[0], carry[1], toks          # toks: [N, B]
        else:
            self.kv = None
            self._slot_cache = init_cache(cfg, batch_slots, max_len)

            def _prefill_into(p, batch, slot_cache, slot):
                self._traces["prefill"] += 1
                self.obs.instant(TRACE_PREFILL, track=TRACK_ENGINE)
                logits, fresh = prefill(p, cfg, batch)
                placed = _place_cache_slot(slot_cache, fresh, slot)
                return (jnp.argmax(logits, axis=-1).astype(jnp.int32),
                        placed)

            def _decode_n(p, tok, cache, pos, lengths, block):
                self._traces["decode"] += 1
                self.obs.instant(TRACE_DECODE, track=TRACK_ENGINE)

                def tick(carry, _):
                    tok, cache, pos, lengths = carry
                    with jax.named_scope("tick"):
                        nt, _lg, cache = decode_step(p, cfg, tok, cache, pos,
                                                     lengths)
                    return (nt, cache, pos + 1, lengths + 1), nt[:, 0]

                carry, toks = lax.scan(tick, (tok, cache, pos, lengths),
                                       None, length=block)
                return carry[0], carry[1], toks

        # Donate the slot cache through both dispatches: K/V page scatters
        # and state-row updates happen in place, not as full-pool copies.
        # The scan length is a STATIC arg so the adaptive decode block can
        # step it (each distinct value is one compiled program; the
        # power-of-two ladder bounds the count at three).
        self._prefill = jax.jit(_prefill_into, donate_argnums=(2,))
        self._decode = jax.jit(_decode_n, donate_argnums=(2,),
                               static_argnums=(8,) if paged else (5,))

        self._verify = None
        if self.speculative:
            def _verify_fwd(p, toks, cache, table, pos, lengths, cow_src,
                            cow_dst):
                self._traces["verify"] += 1
                self.obs.instant(TRACE_VERIFY, track=TRACK_ENGINE)
                # Same pre-scan COW as the decode dispatch: a bootstrap
                # slot's first append may land inside a shared page.
                if prefix_bootstrap:
                    def cow(path, leaf):
                        if cache_leaf_kind(cache_leaf_name(path)) == "state":
                            return leaf
                        return leaf.at[:, cow_dst].set(leaf[:, cow_src])

                    with jax.named_scope("cow"):
                        cache = jax.tree_util.tree_map_with_path(cow, cache)
                greedy, _lg, cache = verify_step(p, cfg, toks, cache, pos,
                                                 lengths, page_table=table)
                return greedy, cache

            # The window width W is baked in from ``toks.shape[1]``, so
            # each ladder rung is one compiled program (<=3 total) —
            # counted by the ``verify`` trace probe.
            self._verify = jax.jit(_verify_fwd, donate_argnums=(2,))

        if self.chunked:
            assert self.kv is not None
            ps = self.kv.page_size
            # Chunk size: the plan's prefill granule (attention block_q
            # rounded up to whole pages), page-aligned when overridden,
            # clamped to the slot's page-table extent.
            want = (prefill_chunk if prefill_chunk is not None
                    else (plan.prefill_chunk_size(ps) if plan is not None
                          else 4 * ps))
            want = cdiv(max(1, int(want)), ps) * ps
            self.chunk = max(ps, min(want, self.kv.extent))
            # The per-pass prefill token budget is adaptive — see
            # ``_prefill_budget`` (scaled by the decode backlog and the
            # measured ticks/scan_ticks block-decode efficiency).

            def _chunk_fwd(p, toks, slot_cache, row, cpages, off, last,
                           cow_src, cow_dst):
                self._traces["prefill"] += 1
                self.obs.instant(TRACE_PREFILL, track=TRACK_ENGINE)
                nt, _lg, placed = _model_prefill_chunk(
                    p, cfg, toks, slot_cache, row, cpages, off, last,
                    cow_src, cow_dst)
                return nt, placed

            self._prefill_chunk = jax.jit(_chunk_fwd, donate_argnums=(2,))
        else:
            self.chunk = 0
            self._prefill_chunk = None

        # Prefix cache: radix-tree page sharing over the paged pools
        # (DESIGN.md §10).  Defaults ON whenever chunked prefill runs —
        # the default chunk-aligned matching keeps greedy tokens
        # bit-identical to a cold engine, so sharing is a pure traffic
        # win.  ``prefix_bootstrap`` switches to page-granular matching
        # with the decode-path fast admission for fully-cached prompts.
        if prefix_cache is None:
            prefix_cache = self.chunked
        if prefix_cache and not self.chunked:
            raise ValueError("prefix_cache requires chunked prefill "
                             "(pages are shared at chunk granularity)")
        if prefix_bootstrap and not prefix_cache:
            raise ValueError("prefix_bootstrap requires prefix_cache")
        if admission == "prefix" and not prefix_cache:
            raise ValueError('admission="prefix" requires prefix_cache')
        self.prefix: Optional[PrefixCache] = None
        if prefix_cache:
            self.prefix = PrefixCache(self.kv, chunk=self.chunk,
                                      bootstrap=prefix_bootstrap,
                                      obs=self.obs)
        # Pending copy-on-write per slot: the LOGICAL page whose next
        # write must swap in a private copy (the physical src is read
        # from the table row at swap time — never cached here).
        self._cow: List[Optional[int]] = [None] * batch_slots

        # Reserved K/V bytes: pool size (paged) / worst-case slot rows
        # (contiguous) — the paged win is measured against bytes-IN-USE.
        self.kv_bytes_reserved = sum(
            leaf.nbytes for path, leaf in
            jax.tree_util.tree_flatten_with_path(self._slot_cache)[0]
            if cache_leaf_kind(cache_leaf_name(path)) in ("kv", "scale"))
        # Typed metric registry (DESIGN.md §17).  Every number the old
        # ad-hoc ``self.metrics`` dict carried is declared here with an
        # EXPLICIT lifetime — Counter (accumulates for the engine's whole
        # life), Gauge (point-in-time), Info (config/provenance string) —
        # plus the new latency histograms.  ``self.metrics`` stays a live
        # read-only Mapping over the lifetime view, so every existing
        # consumer (``dict(eng.metrics)``, key reads, counter deltas)
        # works unchanged; ``snapshot("last_generate")`` adds the
        # windowed view (``Registry.mark()`` at the top of ``generate``).
        reg = self.registry = Registry()
        for name, help in (
            ("dispatches", "decode+verify dispatches (not prefill)"),
            ("ticks", "useful decode scan ticks (max per dispatch)"),
            ("scan_ticks", "total decode scan ticks incl. wasted tail"),
            ("generated", "tokens delivered to requests"),
            ("prefills", "prompt prefills completed (incl. final chunk)"),
            ("prefill_chunks", "chunked-prefill dispatches"),
            ("rejected", "requests failed at admission or allocation"),
            ("prefill_traces", "prefill programs BUILT (trace probe)"),
            ("decode_traces", "decode programs BUILT (trace probe)"),
            ("verify_traces", "verify programs BUILT (trace probe)"),
            ("prefix_hit_pages", "prompt pages served from prefix cache"),
            ("prompt_pages", "prompt pages needed by admitted requests"),
            ("cow_copies", "copy-on-write page copies"),
            ("paged_attn_pages",
             "pages one layer's paged decode kernel streamed, over ticks "
             "and slots"),
            ("paged_attn_grid_pages",
             "scan ticks x slots x table pages: what a walk of every "
             "slot's whole table would stream"),
            ("prefix_bootstraps", "fully-cached prompts decode-bootstrapped"),
            ("prefix_evictions", "pages evicted from the prefix cache"),
            ("draft_tokens", "speculative draft tokens proposed"),
            ("accepted_tokens", "draft tokens accepted by verify"),
            ("spec_tokens", "tokens delivered by speculative dispatches"),
            ("verify_dispatches", "speculative verify dispatches"),
            ("rollbacks", "KV extent rollbacks after rejected drafts"),
            ("rollback_pages", "pages freed by rollbacks"),
            ("tune_hits", "tune-table lookups served"),
            ("tune_misses", "tune-table lookups missed"),
            ("tune_measured", "tuner measurement dispatches"),
            ("tune_pruned", "tuner candidates pruned by lint"),
        ):
            reg.counter(name, help)
        reg.gauge("decode_block", value=self.decode_block)
        reg.gauge("paged", value=int(paged))
        reg.gauge("chunked", value=int(self.chunked))
        reg.gauge("prefill_chunk", value=self.chunk)
        reg.gauge("page_size",
                  value=self.kv.page_size if self.kv else 0)
        reg.gauge("kv_bytes_reserved", value=self.kv_bytes_reserved)
        reg.gauge("kv_bytes_peak")
        reg.gauge("kv_bytes_cached")
        reg.info("quant", value=cfg.quant)
        reg.gauge("verified", value=int(bool(self.plan.verified))
                  if self.plan is not None else 0)
        reg.gauge("kv_itemsize_effective", value=(
            self.kv.kv_itemsize_effective if self.kv is not None
            else (2.0 if cfg.dtype == "bfloat16" else 4.0)))
        reg.gauge("sched_budget")
        reg.gauge("sharded", value=int(mesh is not None))
        reg.gauge("kv_shards", value=self.kv.kv_shards if self.kv else 1)
        reg.gauge("prefix_enabled", value=int(self.prefix is not None))
        reg.gauge("prefix_hit_rate")
        reg.gauge("prefix_cached_pages")
        reg.gauge("pages_in_use")
        reg.gauge("decode_block_last", value=self.decode_block)
        reg.gauge("speculative", value=int(self.speculative))
        reg.gauge("draft_len",
                  value=self.draft_len if self.speculative else 0)
        reg.gauge("accept_rate")
        reg.gauge("dispatches_per_token")
        # Plan provenance (DESIGN.md §16): where the plan's kernel
        # latencies came from, and what the tuner did to get them.
        reg.info("plan_source",
                 value=(self.plan.cost_source if self.plan is not None
                        else "analytic"))
        reg.gauge("autotuned", value=int(self.tuner is not None))
        reg.info("tune_table",
                 value=(self.tuner.table.path or ""
                        if self.tuner is not None else ""))
        reg.gauge("tune_entries")
        # Latency distributions (log-spaced buckets, exported with
        # p50/p90/p99): request-level TTFT / TPOT / queue wait, plus
        # per-dispatch wall times for each dispatch kind.
        reg.histogram("ttft_s", "time to first token")
        reg.histogram("tpot_s", "time per output token after the first")
        reg.histogram("queue_wait_s", "submit-to-admission wait")
        reg.histogram("chunk_latency_s",
                      "prefill-chunk enqueue wall (no read-back: the chunk "
                      "program's device time is in the profiler trace)")
        reg.histogram("prefill_dispatch_s",
                      "whole-prompt prefill dispatch wall")
        reg.histogram("decode_dispatch_s", "decode-block dispatch wall")
        reg.histogram("verify_dispatch_s", "verify dispatch wall")
        self.metrics = MetricsView(reg)
        self._refresh_tune_metrics()

    def _sync_counter(self, name: str, total: float) -> None:
        """Catch a lifetime counter up to an externally-maintained total
        (trace probes, prefix evictions, tune stats) — the delta lands in
        the current ``last_generate`` window."""
        c = self.registry[name]
        d = total - c.value()
        if d > 0:
            c.inc(d)

    def _refresh_tune_metrics(self) -> None:
        if self.tuner is None:
            return
        self._sync_counter("tune_hits", self.tuner.table.hits)
        self._sync_counter("tune_misses", self.tuner.table.misses)
        self._sync_counter("tune_measured", self.tuner.stats.measured)
        self._sync_counter("tune_pruned", self.tuner.stats.pruned)
        self.registry["tune_entries"].set(len(self.tuner.table))
        if self.plan is not None:
            self.registry["plan_source"].set(self.plan.cost_source)

    def snapshot(self, view: str = "lifetime") -> Dict[str, Any]:
        """Materialized metrics for ``view`` (``"lifetime"`` |
        ``"last_generate"``).  The lifetime view equals
        ``dict(self.metrics)``; the windowed view recomputes the derived
        rates from the WINDOW's counters (the stored gauges are lifetime
        rates — the conflation this method exists to fix)."""
        out = self.registry.snapshot(view)
        if view == "last_generate":
            reg = self.registry
            hits = reg["prefix_hit_pages"].value(view)
            out["prefix_hit_rate"] = (
                hits / max(reg["prompt_pages"].value(view), 1))
            out["accept_rate"] = (
                reg["accepted_tokens"].value(view)
                / max(reg["draft_tokens"].value(view), 1))
            out["dispatches_per_token"] = (
                reg["verify_dispatches"].value(view)
                / max(reg["spec_tokens"].value(view), 1))
        return out

    def _mesh_ctx(self):
        """Context installing the engine's mesh AND tuner for plan
        resolution and fused-wrapper shard_map dispatch (trace-time;
        no-op without either).  Every jitted call runs inside it so a
        first-call retrace always sees both."""
        stack = ExitStack()
        if self.mesh is not None:
            stack.enter_context(use_mesh(self.mesh))
        if self.tuner is not None:
            stack.enter_context(self._use_tuner(self.tuner))
        return stack

    def _span(self, name: str, **args) -> HostSpan:
        """A host phase of the engine: on the profiler's timeline always,
        on the recorder's engine track when telemetry is on."""
        return HostSpan(self.obs, self.clock, name, **args)

    # -------------------------------------------------------------- API
    def generate(self, prompts: List[np.ndarray],
                 max_new_tokens: int = 16) -> List[Request]:
        """Serve a list of prompts (any mix of lengths) to completion."""
        with self._span(ENGINE_GENERATE):
            return self._serve(prompts, max_new_tokens)

    def _serve(self, prompts: List[np.ndarray],
               max_new_tokens: int) -> List[Request]:
        self.registry.mark()        # open the ``last_generate`` window
        reqs = [Request(rid=i, prompt=np.asarray(p),
                        max_new_tokens=max_new_tokens,
                        submitted_at=self.clock())
                for i, p in enumerate(prompts)]
        if self.obs.enabled:
            for r in reqs:
                self.obs.instant(REQ_QUEUED, track=TRACK_SCHED,
                                 ts=r.submitted_at, rid=r.rid,
                                 plen=int(r.prompt.shape[0])
                                 if r.prompt.ndim >= 1 else 0)
        pending = deque(reqs)
        active: List[Optional[Request]] = [None] * self.slots
        decoding = [False] * self.slots     # False: idle or mid-prefill
        pos = np.zeros(self.slots, np.int32)        # == per-slot length
        tok = np.zeros((self.slots, 1), np.int32)

        while pending or any(r is not None for r in active):
            if pending:
                with self._span(ENGINE_ADMIT):
                    self._admit_pending(pending, active, decoding, pos, tok)
            if not any(r is not None for r in active):
                break                               # nothing admitted ran
            progressed = False
            if self.chunked:
                budget = self._prefill_budget(active, decoding)
                for s in range(self.slots):
                    r = active[s]
                    if r is None or decoding[s]:
                        continue
                    if progressed and budget < self.chunk:
                        break       # budget spent; the rest wait a pass
                    self._dispatch_chunk(s, r, active, decoding, pos, tok)
                    budget -= self.chunk
                    progressed = True
            if any(active[s] is not None and decoding[s]
                   for s in range(self.slots)):
                if self.speculative:
                    self._speculative_block(active, decoding, pos, tok)
                else:
                    self._decode_block(active, decoding, pos, tok)
                progressed = True
            if not progressed:                      # defensive: no work
                break
        reg = self.registry
        if self.kv is not None:
            reg["kv_bytes_peak"].max(self.kv.peak_bytes_in_use)
            reg["pages_in_use"].set(self.kv.pages_in_use)
        else:
            reg["kv_bytes_peak"].set(self.kv_bytes_reserved)
        if self.prefix is not None:
            # Derived-rate gauges keep their historical LIFETIME
            # semantics (hit pages over ALL prompt pages ever admitted);
            # ``snapshot("last_generate")`` recomputes them per window.
            reg["prefix_hit_rate"].set(
                reg["prefix_hit_pages"].value()
                / max(reg["prompt_pages"].value(), 1))
            self._sync_counter("prefix_evictions", self.prefix.evictions)
            reg["prefix_cached_pages"].set(self.kv.pages_cached)
            reg["kv_bytes_cached"].set(self.kv.bytes_cached)
        self._sync_counter("prefill_traces", self._traces["prefill"])
        self._sync_counter("decode_traces", self._traces["decode"])
        self._sync_counter("verify_traces", self._traces["verify"])
        if self.speculative:
            reg["accept_rate"].set(
                reg["accepted_tokens"].value()
                / max(reg["draft_tokens"].value(), 1))
            reg["dispatches_per_token"].set(
                reg["verify_dispatches"].value()
                / max(reg["spec_tokens"].value(), 1))
        self._refresh_tune_metrics()
        return reqs

    # ------------------------------------------------------- scheduling
    def _prefill_budget(self, active, decoding) -> int:
        """Adaptive prefill token budget for one scheduler pass.

        The static budget, ``max(chunk, slots * decode_block)``, spends
        the same share on prefill whether zero or all other slots are
        mid-decode.  Scale by the actual split instead: each slot waiting
        on prefill contributes one chunk of budget, and the decode
        backlog (slots mid-decode) contributes only the fraction the
        measured block-decode efficiency says decode is NOT using — a
        saturated decode stream (eff ~ 1) keeps prefill to the waiting
        slots' share, a draining one (eff -> 0) lends its slack to
        prompt ingestion.  Efficiency is an EMA over recent dispatches'
        useful-tick fraction (the cumulative ``ticks``/``scan_ticks``
        counters stay pure metrics), so the signal tracks the CURRENT
        split; a cold engine counts as fully efficient so TTFT behavior
        starts at the conservative split.  At least one chunk always
        advances (the dispatch loop's ``progressed`` guard), so prefill
        can't starve either.
        """
        waiting = sum(1 for s in range(self.slots)
                      if active[s] is not None and not decoding[s])
        if not waiting:
            self.registry["sched_budget"].set(0)
            return 0
        backlog = sum(1 for s in range(self.slots)
                      if active[s] is not None and decoding[s])
        # ``decode_eff`` is an EMA of per-dispatch useful-tick fraction
        # (not the lifetime ticks/scan_ticks ratio, which would stop
        # responding once enough history accumulated).
        slack = (1.0 - self.decode_eff) * backlog    # unused decode capacity
        share = min(float(self.slots), waiting + slack)
        budget = int(self.chunk * max(1.0, share))
        self.registry["sched_budget"].set(budget)
        if self.obs.enabled:
            self.obs.instant(SCHED_BUDGET, track=TRACK_SCHED,
                             budget=budget, waiting=waiting,
                             backlog=backlog,
                             decode_eff=round(self.decode_eff, 4))
        return budget

    def _next_request(self, pending, scores=None) -> Request:
        """Pop the next request per the admission policy.  ``fifo`` is
        arrival order; ``sjf`` picks the shortest prompt (classic
        shortest-job-first: small jobs stop queueing behind big ones);
        ``prefix`` picks the longest-cached-prefix prompt (its prefill is
        mostly free NOW, and serving it while its prefix is hot avoids
        re-computing it after eviction).  Ties fall back to arrival
        order.  ``scores`` is the per-admission-pass radix-walk memo —
        the tree only changes between scheduler passes, so one walk per
        request per pass suffices (not one per slot fill)."""
        if self.admission == "fifo" or len(pending) <= 1:
            return pending.popleft()
        if self.admission == "sjf":
            idx = min(range(len(pending)),
                      key=lambda i: (int(pending[i].prompt.shape[0]), i))
        else:                                       # "prefix"
            def score(i):
                r = pending[i]
                if r.rid not in scores:
                    scores[r.rid] = self.prefix.lookup_pages(r.prompt)
                return scores[r.rid]

            idx = max(range(len(pending)), key=lambda i: (score(i), -i))
        r = pending[idx]
        del pending[idx]
        return r

    def _validate(self, r: Request) -> Optional[str]:
        """Admission check: a bad prompt must fail HERE, not mid-dispatch
        where it would strand every active request with its pages held."""
        plen = int(r.prompt.shape[0]) if r.prompt.ndim >= 1 else 0
        if plen == 0:
            return "empty prompt"
        if plen > self.max_len:
            return f"prompt length {plen} exceeds max_len {self.max_len}"
        return None

    def _admit_pending(self, pending, active, decoding, pos, tok) -> None:
        """Fill every free slot from the queue — called between dispatches,
        so requests join mid-stream.  Invalid prompts are marked failed and
        skipped; the engine keeps serving.  Chunked mode only ASSIGNS the
        slot (prefill work is scheduled chunk-by-chunk); the fallback path
        prefills the whole prompt at its own length, as before."""
        scores: Dict[int, int] = {}
        for s in range(self.slots):
            while active[s] is None and pending:
                r = self._next_request(pending, scores)
                err = self._validate(r)
                if err is not None:
                    r.failed = True
                    r.error = err
                    r.done = True
                    # A rejected request failed AT a real wall-clock time:
                    # latency_s is finite, ttft_s stays nan (no token).
                    r.finished_at = self.clock()
                    self.registry["rejected"].inc()
                    if self.obs.enabled:
                        self.obs.instant(REQ_REJECTED, track=TRACK_SCHED,
                                         ts=r.finished_at, rid=r.rid,
                                         error=err)
                    continue
                self._stamp_admitted(r, s)
                if self.chunked:
                    r.prefill_pos = 0
                    self._cow[s] = None
                    if self.prefix is not None:
                        self._admit_prefix(s, r, active, decoding, pos,
                                           tok)
                        continue
                    active[s] = r
                    decoding[s] = False
                    continue
                self._admit(s, r, pos, tok)
                if (len(r.out_tokens) >= r.max_new_tokens
                        or pos[s] >= self.max_len):
                    self._retire(s, r, active, decoding, pos, tok)
                else:
                    active[s] = r
                    decoding[s] = True

    def _stamp_admitted(self, r: Request, slot: int) -> None:
        """Request takes a slot: stamp ``admitted_at`` on the engine
        clock, observe the queue wait, emit the lifecycle instant."""
        r.admitted_at = self.clock()
        self.registry["queue_wait_s"].observe(r.queue_wait_s)
        if self.obs.enabled:
            self.obs.instant(REQ_ADMITTED, track=slot_track(slot),
                             ts=r.admitted_at, rid=r.rid, slot=slot)

    def _stamp_first_token(self, r: Request, slot: int) -> None:
        """First output token exists: stamp it, observe TTFT, emit the
        lifecycle instant.  Call sites guard on ``first_token_at <= 0``
        where a slot can reach this more than once.  The profiler gets a
        ``req.first_token`` mark on both first-token paths; the recorder
        holds it as the instant."""
        with HostSpan(NULL_RECORDER, self.clock, REQ_FIRST_TOKEN):
            r.first_token_at = self.clock()
            self.registry["ttft_s"].observe(r.ttft_s)
        if self.obs.enabled:
            self.obs.instant(REQ_FIRST_TOKEN, track=slot_track(slot),
                             ts=r.first_token_at, rid=r.rid,
                             ttft_s=round(r.ttft_s, 6))

    def _admit_prefix(self, slot: int, r: Request, active, decoding, pos,
                      tok) -> None:
        """Chunked admission through the prefix walk: claim every cached
        prefix page into the slot's table row and resume prefill at the
        first non-cached chunk.  Under ``prefix_bootstrap`` a fully
        cached prompt (coverage >= plen - 1) skips prefill entirely — the
        final prompt token is fed through the decode path, whose first
        append copy-on-writes the shared tail page."""
        hit = self.prefix.claim(slot, r.prompt)
        r.prefill_pos = hit.prefill_start
        self._cow[slot] = hit.cow
        self.registry["prefix_hit_pages"].inc(hit.hit_pages)
        self.registry["prompt_pages"].inc(hit.prompt_pages)
        active[slot] = r
        if not hit.full:
            decoding[slot] = False
            return
        # Bootstrap fast path: TTFT = one decode dispatch.  The claimed
        # pages hold KV for tokens 0..plen-2; the decode step computes
        # (and appends, post-COW) the final prompt token's KV and emits
        # the first output token.
        plen = int(r.prompt.shape[0])
        self.prefix.insert(slot, r.prompt)      # re-stamp; nothing new
        r.prefill_pos = plen
        decoding[slot] = True
        pos[slot] = plen - 1
        tok[slot, 0] = int(r.prompt[-1])
        self.registry["prefix_bootstraps"].inc()
        self.registry["prefills"].inc()

    def _admit(self, slot: int, r: Request, pos, tok) -> None:
        """Whole-prompt prefill at the request's own length (fallback path:
        contiguous cache, or SSM/RWKV/mrope configs).  Compiles once per
        distinct prompt length."""
        plen = int(r.prompt.shape[0])
        if plen > self.max_len:                     # guarded by _validate
            raise ValueError(
                f"prompt length {plen} exceeds max_len {self.max_len}")
        batch = {"tokens": jnp.asarray(r.prompt)[None]}
        # Host-visible dispatch wall: the read-back synchronizes.
        with self._span(DISPATCH_PREFILL, slot=slot, rid=r.rid,
                        tokens=plen) as sp:
            with self._span(ENGINE_PREFILL_ENQUEUE), self._mesh_ctx():
                if self.kv is not None:
                    pages = jnp.asarray(self.kv.ensure(slot, plen))
                    next_tok, cache = self._prefill(
                        self.params, batch, self._slot_cache,
                        jnp.int32(slot), pages)
                else:
                    next_tok, cache = self._prefill(
                        self.params, batch, self._slot_cache,
                        jnp.int32(slot))
                # Reassign immediately after every donating dispatch: the
                # donated input buffer is deleted on accelerator backends,
                # and a mid-wave exception must not leave the engine
                # holding a dead reference.
                self._slot_cache = cache
            with self._span(WAIT_PREFILL):
                t = int(np.asarray(next_tok)[0, 0])
        self.registry["prefill_dispatch_s"].observe(sp.dur)
        if self.obs.enabled:
            self.obs.complete("prefill", sp.t0, sp.dur,
                              track=slot_track(slot), rid=r.rid, tokens=plen)
        r.out_tokens.append(t)
        self._stamp_first_token(r, slot)
        r.prefill_pos = plen
        pos[slot] = plen
        tok[slot, 0] = t
        self.registry["prefills"].inc()
        self.registry["generated"].inc()

    def _dispatch_chunk(self, slot: int, r: Request, active, decoding,
                        pos, tok) -> None:
        """One fixed-size prefill chunk through the single compiled
        ``prefill_chunk`` program; the final chunk emits the first token
        and flips the slot to decoding.  The first dispatch of a
        prefix-hit request starts at a NONZERO page-aligned offset
        against the pre-claimed table row."""
        assert self.kv is not None and self._prefill_chunk is not None
        c = self.chunk
        plen = int(r.prompt.shape[0])
        with self._span(ENGINE_CHUNK_STAGE):
            off = r.prefill_pos
            if self.prefix is not None and self._cow[slot] is None:
                # Catch-up walk: pages for our NEXT chunks may have
                # appeared since admission (a same-wave request computing
                # the shared prefix inserts as it completes) — claim them
                # and skip ahead.
                off, caught = self.prefix.extend_claim(slot, r.prompt, off)
                if caught:
                    r.prefill_pos = off
                    self.registry["prefix_hit_pages"].inc(caught)
            # Pages for the chunk's span (page-aligned by construction);
            # the portion of a final chunk past max_len maps to the NULL
            # page.  An allocator failure here (pool pressure with every
            # cached page still referenced) fails THIS request without
            # stranding the stream — its already-placed pages return
            # exactly once.
            try:
                self.kv.ensure(slot, min(off + c, self.max_len))
            except RuntimeError as e:
                r.failed = True
                r.error = str(e)
                self.registry["rejected"].inc()
                self._retire(slot, r, active, decoding, pos, tok)
                return
            row = self.kv.table_row(slot)
            toks, cpages, last = stage_chunk(r.prompt, off, c, row,
                                             self.kv.page_size)
        ci = off // c
        # The span times the enqueue only: the chunk program's device time
        # is in the profiler's trace (module ``jit__chunk_fwd``).
        with self._span(DISPATCH_PREFILL_CHUNK, slot=slot, rid=r.rid,
                        chunk=ci, off=off) as sp, self._mesh_ctx():
            # The COW operands ride as NULL here: the engine's matching
            # policies never hand a chunk a shared write target (default
            # mode restarts on fresh pages; bootstrap full hits COW on
            # the decode path).  The operands stay in the program for
            # API-level sub-chunk sharing (tests drive them; ROADMAP
            # names the bit-exact sub-chunk follow-on).
            next_tok, cache = self._prefill_chunk(
                self.params, jnp.asarray(toks)[None], self._slot_cache,
                jnp.asarray(row), jnp.asarray(cpages), jnp.int32(off),
                jnp.int32(last), jnp.int32(NULL_PAGE),
                jnp.int32(NULL_PAGE))
            self._slot_cache = cache
        self.registry["chunk_latency_s"].observe(sp.dur)
        if self.obs.enabled:
            self.obs.complete("prefill_chunk", sp.t0, sp.dur,
                              track=slot_track(slot), rid=r.rid, chunk=ci)
            self.obs.instant(REQ_PREFILL_CHUNK, track=slot_track(slot),
                             rid=r.rid, chunk=ci, off=off)
        r.prefill_pos = min(off + c, plen)
        self.registry["prefill_chunks"].inc()
        if r.prefill_pos < plen:
            return                                  # more chunks to go
        if self.prefix is not None:
            # Prefill done: the full prompt pages are final — index them
            # so concurrent and future requests share them.
            with self._span(ENGINE_PREFIX_INSERT):
                self.prefix.insert(slot, r.prompt)
        with self._span(WAIT_FIRST_TOKEN):
            t = int(np.asarray(next_tok)[0, 0])
        with self._span(ENGINE_HARVEST):
            r.out_tokens.append(t)
            self._stamp_first_token(r, slot)
            pos[slot] = plen
            tok[slot, 0] = t
            decoding[slot] = True
            self.registry["prefills"].inc()
            self.registry["generated"].inc()
            done = (len(r.out_tokens) >= r.max_new_tokens
                    or pos[slot] >= self.max_len)
        self._retire_all([slot] if done else [], active, decoding, pos, tok)

    def _retire(self, slot: int, r: Request, active, decoding, pos,
                tok) -> None:
        r.done = True
        r.finished_at = self.clock()
        if not r.failed:
            # Latency/TPOT only count completed requests; Histogram
            # ignores the nan a rejected or single-token request yields.
            self.registry["tpot_s"].observe(r.tpot_s)
        if self.obs.enabled:
            self.obs.instant(REQ_FINISHED, track=slot_track(slot),
                             ts=r.finished_at, rid=r.rid,
                             tokens=len(r.out_tokens), failed=r.failed)
        active[slot] = None
        decoding[slot] = False
        pos[slot] = 0
        tok[slot, 0] = 0
        self._cow[slot] = None
        if self.prefix is not None:
            # Slot exit: drop the tree references first (re-stamps the
            # prefix as most-recently-used), then release — exclusive
            # pages free, tree pages stay CACHED until eviction.
            self.prefix.release_slot(slot)
        if self.kv is not None:
            self.kv.release(slot)

    def _decode_block_size(self, n_active: int) -> int:
        """Scan ticks for the next decode dispatch.  Static by default;
        with ``adaptive_decode_block`` the block scales with the active-
        slot count — more slots decoding efficiently means each dispatch
        retires more real tokens, so a longer scan amortizes the fixed
        host round-trip further — floored at the static ``decode_block``
        and pulled back by the ``decode_eff`` EMA when ticks are being
        wasted (slots retiring mid-block).  Power-of-two steps capped at
        4x bound the compiled-program count at three."""
        if not self.adaptive_decode_block:
            return self.decode_block
        scale = n_active * max(self.decode_eff, 0.0)
        k = 0
        while k < 2 and (2 << k) <= scale:
            k += 1
        return self.decode_block << k

    def _decode_block(self, active, decoding, pos, tok) -> None:
        """One jitted dispatch: a block of scan ticks across all slots,
        each at its own position; harvest real tokens after."""
        with self._span(ENGINE_DECODE_PREP):
            runnable = [s for s in range(self.slots)
                        if active[s] is not None and decoding[s]]
            block = self._decode_block_size(len(runnable))
            self.registry["decode_block_last"].set(block)
            if self.kv is not None:
                runnable, cow_src, cow_dst = self._provision(
                    runnable, active, decoding, pos, tok,
                    lambda s: min(block, active[s].max_new_tokens
                                  - len(active[s].out_tokens)))
                if not runnable:
                    return
                # Idle slots AND slots parked mid-prefill ride along with
                # their write position at the table extent: paged_append
                # routes those writes to the NULL page, so a half-prefilled
                # slot's pages survive the decode blocks between its chunks.
                dpos = np.full(self.slots, self.kv.extent, np.int32)
                dlen = np.zeros(self.slots, np.int32)
                for s in runnable:
                    dpos[s] = pos[s]
                    dlen[s] = pos[s]
        with self._span(DISPATCH_DECODE, block=block,
                        slots=len(runnable)) as sp:
            with self._span(ENGINE_DECODE_ENQUEUE), self._mesh_ctx():
                if self.kv is not None:
                    next_tok, cache, toks = self._decode(
                        self.params, jnp.asarray(tok), self._slot_cache,
                        self.kv.page_table, jnp.asarray(dpos),
                        jnp.asarray(dlen), jnp.asarray(cow_src),
                        jnp.asarray(cow_dst), block)
                else:
                    next_tok, cache, toks = self._decode(
                        self.params, jnp.asarray(tok), self._slot_cache,
                        jnp.asarray(pos), jnp.asarray(pos), block)
                self._slot_cache = cache
            # The read-backs synchronize, so the parent span is the real
            # device+host block wall.
            with self._span(WAIT_DECODE):
                toks_np = np.asarray(toks)               # [N, slots]
                last_np = np.asarray(next_tok)           # [slots, 1]
        with self._span(ENGINE_HARVEST):
            self.registry["decode_dispatch_s"].observe(sp.dur)
            if self.obs.enabled:
                for s in runnable:
                    self.obs.complete("decode", sp.t0, sp.dur,
                                      track=slot_track(s),
                                      rid=active[s].rid, block=block)
            useful = 0
            finished = []
            for s in runnable:
                r = active[s]
                h = min(block,
                        r.max_new_tokens - len(r.out_tokens),
                        self.max_len - int(pos[s]))
                r.out_tokens.extend(int(t) for t in toks_np[:h, s])
                if r.out_tokens and r.first_token_at <= 0.0:
                    # Bootstrap-admitted slots emit their first token here.
                    self._stamp_first_token(r, s)
                useful = max(useful, h)
                self.registry["generated"].inc(h)
                pos[s] = min(int(pos[s]) + block, self.max_len)
                tok[s, 0] = last_np[s, 0]
                if (len(r.out_tokens) >= r.max_new_tokens
                        or pos[s] >= self.max_len):
                    finished.append(s)
            self.registry["dispatches"].inc()
            self.registry["ticks"].inc(useful)
            self.registry["scan_ticks"].inc(block)
            if self.kv is not None and self._kernel_window is not None:
                self._count_kernel_pages(dlen, block)
            self.decode_eff = (0.5 * self.decode_eff
                               + 0.5 * useful / block)
        self._retire_all(finished, active, decoding, pos, tok)

    def _count_kernel_pages(self, dlen: np.ndarray, block: int) -> None:
        """Count the pages the paged decode kernel streamed in a dispatch
        of ``block`` ticks, per layer: at tick t slot s attends through
        ``dlen[s] + t`` (its query offset), and streams the pages
        ``live_page_span`` gives it — the bound the kernel's own loop
        uses.  Beside it, what a walk of every slot's whole table would
        have streamed."""
        n_pages = self.kv.pages_per_slot
        q_off = dlen[None, :] + np.arange(block)[:, None]
        _, count = live_page_span(q_off, win=1, page_size=self.kv.page_size,
                                  n_pages=n_pages,
                                  window=self._kernel_window)
        self.registry["paged_attn_pages"].inc(int(count.sum()))
        self.registry["paged_attn_grid_pages"].inc(
            block * self.slots * n_pages)

    def _provision(self, runnable, active, decoding, pos, tok, ahead):
        """Resolve pending copy-on-write pairs and allocate the pages each
        runnable slot's next ``ahead(s)`` positions write.  Returns the
        slots still runnable and the per-slot ``(cow_src, cow_dst)``
        physical page pairs (NULL for the rest)."""
        # Prefix bootstrap: a slot whose next append lands inside a shared
        # page copies it onto a private one in the dispatch, before the
        # scan; the re-uploaded table already points at dst.
        cow_src = np.full(self.slots, NULL_PAGE, np.int32)
        cow_dst = np.full(self.slots, NULL_PAGE, np.int32)
        for s in list(runnable):
            r = active[s]
            try:
                if self._cow[s] is not None:
                    cow_src[s], cow_dst[s] = self.kv.cow_page(
                        s, self._cow[s])
                    self._cow[s] = None
                    self.registry["cow_copies"].inc()
                    # The slot's reference moved off the shared src:
                    # refresh its eviction entry.
                    self.prefix.page_released(int(cow_src[s]))
                # Allocate only what the request's remaining budget can
                # validly read back: positions past it write into
                # unallocated pages, which route to the NULL page, and
                # their outputs are discarded.
                self.kv.ensure(s, min(int(pos[s]) + ahead(s), self.max_len))
            except RuntimeError as e:
                # Pool pressure even after eviction — e.g. every page
                # referenced across slots while a bootstrap COW needs its
                # one transient extra page.  Fail THIS request (pages
                # returned exactly once via the refcounted release) and
                # keep the stream alive — same contract as the chunk path.
                r.failed = True
                r.error = str(e)
                self.registry["rejected"].inc()
                self._retire(s, r, active, decoding, pos, tok)
                cow_src[s] = cow_dst[s] = NULL_PAGE
        runnable = [s for s in runnable
                    if active[s] is not None and decoding[s]]
        return runnable, cow_src, cow_dst

    def _retire_all(self, slots, active, decoding, pos, tok) -> None:
        """Retire the slots a harvest finished, as one host phase, and
        refresh the pages-in-use gauge."""
        if slots:
            with self._span(ENGINE_RETIRE):
                for s in slots:
                    self._retire(s, active[s], active, decoding, pos, tok)
        if self.kv is not None:
            self.registry["pages_in_use"].set(self.kv.pages_in_use)

    # ------------------------------------------------ speculative decode
    def _draft(self, r: Request, limit: int) -> List[int]:
        """Host-side draft for one slot: up to ``min(draft_len, limit)``
        guesses for the tokens AFTER the pending one.  Sources, in
        order: the prefix-cache radix tree (what followed this history
        in earlier traffic — ``PrefixCache.suggest`` is read-only, so
        drafting never perturbs eviction order), then n-gram
        prompt-lookup (the history's trailing trigram/bigram matched
        backwards over the history itself).  Drafts are guesses — a
        wrong one costs its verify row, never correctness."""
        k = min(self.draft_len, limit)
        if k <= 0:
            return []
        hist = np.asarray(r.out_tokens, np.int32)
        if r.prompt.ndim == 1:                      # token prompts only
            hist = np.concatenate([r.prompt.astype(np.int32), hist])
        out: List[int] = []
        if self.prefix is not None:
            out = [int(t) for t in self.prefix.suggest(hist, k)]
        while len(out) < k:
            ext = _ngram_continuation(
                np.concatenate([hist, np.asarray(out, np.int32)]),
                k - len(out))
            if not ext:
                break
            out.extend(ext)
        return out[:k]

    def _speculative_block(self, active, decoding, pos, tok) -> None:
        """One draft-then-verify dispatch across all slots (DESIGN.md
        §11).  Stack ``[pending, d1..dk]`` per slot into a ``[slots, W]``
        window, score every position with ONE verify dispatch, accept
        the longest prefix of drafts matching the model's own greedy
        argmax, then roll the slot's KV extent back over the rejected
        tail.  W snaps up to the <=3-rung ladder; slots without drafts
        — and idle or parked mid-prefill slots — ride along on padding
        (their window writes route to the NULL page / their outputs are
        discarded, exactly like padded decode slots)."""
        assert self.kv is not None and self._verify is not None
        with self._span(ENGINE_DECODE_PREP):
            runnable = [s for s in range(self.slots)
                        if active[s] is not None and decoding[s]]
            drafts: Dict[int, List[int]] = {}
            caps: Dict[int, int] = {}
            need = 1
            for s in runnable:
                r = active[s]
                # A slot may deliver at most ``cap`` tokens this dispatch:
                # its remaining budget, clamped to max_len (positions past
                # max_len write to the NULL page and verify garbage).
                caps[s] = min(r.max_new_tokens - len(r.out_tokens),
                              self.max_len - int(pos[s]))
                drafts[s] = self._draft(r, caps[s] - 1)
                need = max(need, len(drafts[s]) + 1)
            w = next(x for x in self._w_ladder if x >= need)
            # COW resolution and page provisioning: same contract as the
            # decode block (allocator failure fails THIS request only).
            runnable, cow_src, cow_dst = self._provision(
                runnable, active, decoding, pos, tok, lambda s: w)
            if not runnable:
                return
            toks = np.zeros((self.slots, w), np.int32)
            dpos = np.full(self.slots, self.kv.extent, np.int32)
            dlen = np.zeros(self.slots, np.int32)
            for s in runnable:
                toks[s, 0] = tok[s, 0]
                d = drafts[s]
                toks[s, 1:1 + len(d)] = d
                dpos[s] = pos[s]
                dlen[s] = pos[s]
        with self._span(DISPATCH_VERIFY, window=w,
                        slots=len(runnable)) as sp:
            with self._span(ENGINE_VERIFY_ENQUEUE), self._mesh_ctx():
                greedy, cache = self._verify(
                    self.params, jnp.asarray(toks), self._slot_cache,
                    self.kv.page_table, jnp.asarray(dpos),
                    jnp.asarray(dlen), jnp.asarray(cow_src),
                    jnp.asarray(cow_dst))
                self._slot_cache = cache
            with self._span(WAIT_VERIFY):
                g = np.asarray(greedy)                   # [slots, W]
        with self._span(ENGINE_HARVEST):
            self.registry["verify_dispatch_s"].observe(sp.dur)
            if self.obs.enabled:
                for s in runnable:
                    self.obs.complete("verify", sp.t0, sp.dur,
                                      track=slot_track(s),
                                      rid=active[s].rid, window=w,
                                      drafts=len(drafts[s]))
            useful = 0
            filled = 0
            finished = []
            for s in runnable:
                r = active[s]
                d = drafts[s]
                cap = caps[s]
                # Row i's output is the model's next token after consuming
                # toks[s, :i+1]; draft i is accepted while it EQUALS the
                # previous row's output — i.e. while the window tracks
                # what plain greedy decode would have produced anyway.  (A
                # pad token that happens to match is accepted too: it IS
                # the correct greedy token.)
                a = 0
                while (a < w - 1 and a + 1 < cap
                       and int(toks[s, a + 1]) == int(g[s, a])):
                    a += 1
                delivered = a + 1                        # y0..ya
                r.out_tokens.extend(int(g[s, i]) for i in range(delivered))
                if r.first_token_at <= 0.0:
                    self._stamp_first_token(r, s)
                self.registry["generated"].inc(delivered)
                self.registry["spec_tokens"].inc(delivered)
                self.registry["draft_tokens"].inc(len(d))
                self.registry["accepted_tokens"].inc(min(a, len(d)))
                useful = max(useful, delivered)
                filled += delivered
                pos[s] = int(pos[s]) + delivered
                tok[s, 0] = int(g[s, a])
                # The verify window appended K/V at pos..pos+W-1; positions
                # past the new write head are stale.  Wholly-stale pages
                # are returned now (freshly allocated and exclusively
                # owned by construction — rollback_extent asserts it); the
                # stale tail INSIDE the kept last page is masked by length
                # and overwritten as the slot advances.
                dropped = self.kv.rollback_extent(s, int(pos[s]))
                if dropped:
                    self.registry["rollbacks"].inc()
                    self.registry["rollback_pages"].inc(dropped)
                if self._debug_check_pages:
                    self.kv.assert_page_accounting()
                if (len(r.out_tokens) >= r.max_new_tokens
                        or pos[s] >= self.max_len):
                    finished.append(s)
            self.registry["dispatches"].inc()
            self.registry["verify_dispatches"].inc()
            self.registry["ticks"].inc(useful)
            self.registry["scan_ticks"].inc(w)
            # The decode-pressure EMA counts ACCEPTED tokens per verify
            # row, not scan ticks — a rejected draft row is wasted
            # capacity exactly like a wasted scan tick.
            self.decode_eff = (0.5 * self.decode_eff
                               + 0.5 * filled / (w * len(runnable)))
        self._retire_all(finished, active, decoding, pos, tok)
