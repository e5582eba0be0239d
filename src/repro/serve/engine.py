"""Continuous-batching serving engine over the distributed striped KV cache.

The engine owns a fixed pool of ``num_slots`` cache rows, allocated ONCE at
construction.  Requests flow through ``serve/scheduler.py``:

  * **prefill**: an admitted request is right-padded to a bucket length and
    prefilled alone (batch=1) through a per-bucket jitted function that
    scatters the resulting cache row into its assigned slot — jit retraces
    are bounded by the number of buckets, not by batch composition.  With
    ``ServeConfig.prefill_chunk`` set, prompts instead stream into their
    slot in fixed-size chunks interleaved with decode (continuous prefill):
    one fixed-shape jitted chunk launch per tick, budgeted by
    ``ServeConfig.tick_token_budget``, so no tick scales with the longest
    pending prompt.
  * **decode**: ONE jitted step advances every slot per tick.  The cache
    carries a per-slot position vector ``pos: [B]`` (threaded through
    ``core/decode_attention.py``), so slots at arbitrary mixed depths decode
    together; per-token cross-device traffic stays O(B·H·D) (paper §3.7).
  * **retire**: per-slot EOS / max-token checks free the slot for the queue.

Because every decode op is batch-row-independent, a slot's tokens are exactly
what single-request generation would produce (MoE capacity is the one
documented exception: expert capacity couples rows by construction).
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import spans
from repro.configs.base import ModelConfig
from repro.core import dispatch
from repro.core.am import CommModel
from repro.models import transformer as tfm
from repro.parallel.context import ParallelCtx
from repro.serve.config import ServeConfig
from repro.serve.kv_pool import PageAllocator, PagedLayout, PoolExhausted
from repro.serve.scheduler import Request, RequestResult, Scheduler, default_buckets
from repro.serve.speculative import propose_ngram

__all__ = ["ServeEngine", "select_victim"]


def select_victim(slots, allocator, protect=()):
    """Preemption policy: pick the slot to evict when the page pool runs dry
    mid-decode.  Victims are ranked (1) slots whose pages nobody else maps
    first — evicting a prefix DONOR strands nothing (refcounts keep shared
    pages alive for the sharers) but frees fewer pages and forces the widest
    recompute blast radius, so donors go last; (2) youngest admission first
    (latest ``admit_tick``, then highest rid) — the oldest request always
    makes progress, which is what bounds recompute work and guarantees
    drain.  ``protect`` slots (the one being grown this tick) are exempt.
    Returns the slot index, or None when nothing is evictable."""
    cands = []
    for slot, req in enumerate(slots):
        if req is None or slot in protect:
            continue
        if allocator.slot_pages(slot) == 0:
            continue  # nothing to reclaim
        cands.append((
            allocator.slot_shares_pages(slot),  # donors last
            -(req.admit_tick if req.admit_tick is not None else -1),
            -req.rid,
            slot,
        ))
    if not cands:
        return None
    return min(cands)[3]


def _set_kv(cache, idx, value, keys):
    """``cache[key][:, *idx] = value`` for each of ``keys`` (K/V and scale
    stacks, indexed past their leading layer axis); out-of-range indices are
    dropped.  Jitted with the cache donated, so the write is in place."""
    out = dict(cache)
    for key in keys:
        out[key] = cache[key].at[(slice(None),) + tuple(idx)].set(value, mode="drop")
    return out


# engine totals each engine.tick span reports as its deltas (ServeEngine._counters)
_TICK_COUNTERS = ("prefill_launches", "pages_allocated", "cow_copies", "prefix_hit_pages",
                  "bt_uploads", "preemptions", "retraced")

# mid-prefill slots park their cache position past any capacity: the shared
# decode step still ticks their row, but every write guard (pos < n*m) drops
# the append, so a half-ingested prompt can never be corrupted by decode
_PARKED = 2**30


class ServeEngine:
    """Slot-based continuous-batching engine.

    All knobs arrive as ONE validated object: ``ServeEngine(cfg, params,
    ctx=ctx, serve=ServeConfig(...))``.  The pre-redesign kwarg form
    (``ServeEngine(cfg, params, ctx, max_seq=..., paged=...)``) still works
    through a deprecation shim that maps the old names onto ``ServeConfig``.

    ``generate(prompts, max_new_tokens)`` keeps the legacy static-batch API
    (greedy, exactly max_new_tokens per row) on top of the streaming path:
    ``submit()`` requests, ``step()`` ticks, ``run()`` to drain — the
    streaming calls return ``RequestResult`` (tokens + per-token tick
    stamps + TTFT + chunk count).

    With ``serve.prefill_chunk`` set the engine runs CONTINUOUS PREFILL:
    admitted prompts stream into their slot ``prefill_chunk`` tokens per
    tick (budgeted by ``serve.tick_token_budget``), interleaved with the
    decode batch, instead of monopolizing a tick with one bucket-sized
    launch.  A request starts decoding on the same tick its last chunk
    lands, so chunked serving is token-for-token AND tick-for-tick
    identical to one-shot prefill — only launch sizes change.
    """

    def __init__(
        self,
        cfg: ModelConfig,
        params,
        ctx: Optional[ParallelCtx] = None,
        *,
        serve: Optional[ServeConfig] = None,
        chaos=None,
        **legacy,
    ):
        if serve is not None and legacy:
            raise TypeError(
                f"pass serve=ServeConfig(...) or legacy kwargs, not both "
                f"(got both serve= and {sorted(legacy)})"
            )
        if serve is None:
            if legacy:
                warnings.warn(
                    "ServeEngine(cfg, params, ctx, max_seq=..., ...) is "
                    "deprecated; pass serve=ServeConfig(...) instead",
                    DeprecationWarning,
                    stacklevel=2,
                )
            serve = ServeConfig.from_legacy_kwargs(legacy)
        self.serve = serve
        self.cfg = cfg
        self.ctx = ctx or ParallelCtx()
        # flash-decode kernel variant: "auto" serves the paged cache with the
        # native paged kernel (block table read in-kernel) wherever Pallas
        # runs, the gather/band reference elsewhere; "native"/"gather" force
        if serve.decode_kernel != "auto":
            self.ctx = dataclasses.replace(self.ctx, decode_kernel=serve.decode_kernel)
        self.params = params
        self.max_seq = serve.max_seq
        self.cache_dtype = serve.cache_dtype
        self.num_slots = serve.num_slots
        self.eos_id = serve.eos_id
        self.pack_plan = serve.pack_plan
        n = self.ctx.sp_size
        if serve.max_seq % max(n, 1):
            raise ValueError(
                f"max_seq={serve.max_seq} must be divisible by sp_size={n}"
            )
        # continuous prefill: chunk size + per-tick token budget (None/None =
        # legacy one-shot bucketed prefill).  Chunks scatter by absolute
        # position, so unlike buckets they need no divisibility with n.
        self.prefill_chunk = serve.prefill_chunk
        self.tick_token_budget = serve.tick_token_budget
        if self.prefill_chunk is not None and (
            cfg.ssm is not None or cfg.encoder_layers or cfg.frontend is not None
        ):
            raise ValueError(
                "continuous prefill serves attention-only decoder archs "
                "(SSM state / encoder / frontend inputs have no chunk-append)"
            )
        # speculative decode: verify spec_k tokens per slot per tick through
        # the chunk-attention machinery; greedy accept/reject keeps tokens
        # identical to vanilla decode, only the per-tick commit count changes
        self.spec_k = serve.spec_k
        self.spec_draft = serve.spec_draft
        self.spec_max_misses = serve.spec_max_misses
        self._spec_on = serve.spec_k >= 2 and serve.spec_draft != "off"
        if self._spec_on and (
            cfg.ssm is not None or cfg.encoder_layers or cfg.frontend is not None
        ):
            raise ValueError(
                "speculative decode rides the chunk-attention verify path: "
                "attention-only decoder archs (no SSM / encoder / frontend)"
            )
        # paged KV: slot rows virtualize over a refcounted physical page pool
        # (serve/kv_pool.py) — memory follows allocated pages, and identical
        # prompt prefixes share pages across requests
        self.paged = serve.paged
        # quantized pool storage: int8/fp8 pages + per-(token, kv-head) scale
        # side tables riding the block table's physical indexing
        self.kv_dtype = serve.kv_dtype
        self._quantized = serve.kv_dtype != "fp"
        self.dequant_fallbacks = 0  # quantized ticks served by the gather ref
        # the decode kernel "auto" resolved to on this platform: "native"
        # (paged Pallas kernel), "gather" (paged reference) or "band" (dense)
        self.decode_kernel = dispatch._resolve_decode_kernel(
            getattr(self.ctx, "decode_kernel", "auto"), paged=serve.paged
        )
        self.allocator: Optional[PageAllocator] = None
        if serve.paged:
            if cfg.ssm is not None or cfg.encoder_layers:
                raise ValueError(
                    "the paged KV cache serves attention-only decoder archs "
                    "(SSM state / encoder cross-K/V have no page structure)"
                )
            layout = PagedLayout.for_engine(
                serve.max_seq, max(n, 1), serve.num_slots,
                page_size=serve.page_size, num_pages=serve.num_pages,
            )
            self.allocator = PageAllocator(
                layout, quantized=self._quantized,
                oversubscribe=serve.oversubscribe,
            )
        # SSD's recurrent state has no pad-correction: prefill exactly
        exact = cfg.ssm is not None
        buckets = (
            tuple(serve.prefill_buckets)
            if serve.prefill_buckets
            else default_buckets(serve.max_seq, n)
        )
        if any(b % max(n, 1) for b in buckets) and not exact:
            raise ValueError(f"buckets {buckets} must be multiples of sp_size={n}")
        self.scheduler = Scheduler(
            self.num_slots, buckets, self.max_seq, exact=exact, multiple=n,
            chunk=cfg.ssm.chunk if exact else None, allocator=self.allocator,
            prefill_chunk=self.prefill_chunk,
            tick_token_budget=self.tick_token_budget,
        )
        # packed prefill: several same-tick admissions share one row under a
        # document mask (attention-only decoder archs; SSD state and per-row
        # frontend/encoder side inputs do not pack)
        self.pack_max = max(1, serve.pack_max)
        self._can_pack = (
            serve.pack_prefill
            and cfg.ssm is None
            and not cfg.encoder_layers
            and cfg.frontend is None
        )
        # the declarative attention plan this engine serves under (the
        # prefill path resolves its backend/tile through this via dispatch)
        self.attn_plan = dispatch.plan_from_ctx(
            self.ctx, causal=True, layout=cfg.causal_layout
        )
        # THE cache: allocated once here, threaded through prefill inserts
        # and decode steps for the engine's whole lifetime
        self._cache = tfm.init_cache(
            cfg, self.num_slots, self.max_seq, dtype=self.cache_dtype, ctx=self.ctx,
            paged=self.allocator.layout if self.allocator else None,
            kv_dtype=serve.kv_dtype,
        )
        self._cache = {k: self._place(k, v) for k, v in self._cache.items()}
        self._cur = np.zeros((self.num_slots, 1), np.int32)  # last token per slot
        self._depth = np.zeros((self.num_slots,), np.int64)  # host view of pos
        # per-slot consecutive zero-accept verify ticks (speculative decode:
        # at spec_max_misses the slot stops drafting; reset on accept/admit)
        self._spec_misses = np.zeros((self.num_slots,), np.int64)
        self._shared_len = np.zeros((self.num_slots,), np.int64)  # paged prefix
        self._bt_version = -1  # device block table staleness marker
        self.bt_uploads = 0  # device block-table uploads (version-gated:
        # ticks whose appends stay inside a page re-upload nothing)
        self._tick = 0
        self._finished: Dict[int, RequestResult] = {}
        # jit bookkeeping: trace counters tick at TRACE time only, so tests
        # can assert the retrace count is bounded by the bucket set
        self._prefill_fns: Dict[int, object] = {}
        self.prefill_trace_counts: Dict[int, int] = {}
        self.decode_trace_count = 0
        self.chunk_trace_count = 0
        self.verify_trace_count = 0
        # launch accounting (every call, not just traces): the pack planner's
        # padded-prefill cost is launches x bucket tokens
        self.prefill_launches = 0
        self.prefill_launch_tokens = 0
        self.chunk_launches = 0
        # speculative decode accounting (engine-wide; per-request twins live
        # on Request/RequestResult)
        self.verify_launches = 0
        self.spec_proposed = 0
        self.spec_accepted = 0
        # per-tick token series: PROMPT tokens ingested vs tokens GENERATED
        # (kept separate so a prefill-heavy tick cannot inflate decode
        # tokens/s — serve_bench reports both)
        self.tick_prefill_tokens: List[int] = []
        self.tick_decode_tokens: List[int] = []
        # debug logit capture (set BEFORE the first tick; read at trace time):
        # records every generated token's full logits row per rid so the
        # distributed quant check can bound per-token error vs an fp engine
        self.capture_logits = False
        self.debug_logits: Dict[int, List[np.ndarray]] = {}
        # robustness: oversubscribed preemption + lifecycle + fault guards
        self.nan_guard = serve.nan_guard
        self.health_every = serve.health_every
        self.chaos = chaos  # testing/chaos.py injector (None in production)
        self.preemptions = 0  # mid-decode evictions (pool pressure)
        self.recompute_tokens = 0  # tokens re-ingested for preempted requests
        self.cancelled = 0
        self.deadline_expired = 0
        self.numeric_errors = 0
        self.rejected_requests = 0
        self.health_sweeps = 0
        self.chaos_dropped_grants = 0
        # every jitted step that returns the cache DONATES the one it is
        # given: the pool is updated in place (the model carries its K/V
        # stacks whole through the layer loop), so a tick neither copies
        # the pool nor holds two of them.  The engine always replaces
        # self._cache with the returned cache; nothing keeps the old one.
        self._decode = jax.jit(self._decode_traced, donate_argnums=1)
        self._copy_pages = jax.jit(self._copy_pages_traced, donate_argnums=0)
        self._chunk_step = jax.jit(self._chunk_traced, donate_argnums=1)
        self._verify = jax.jit(self._verify_traced, donate_argnums=1)
        self._set_kv = jax.jit(_set_kv, static_argnames="keys", donate_argnums=0)

    # -- jitted paths -------------------------------------------------------

    def _decode_traced(self, params, cache, tokens):
        self.decode_trace_count += 1  # python side effect: trace-time only
        nxt, cache, logits = tfm.decode_step(
            params, cache, tokens, self.cfg, self.ctx
        )
        # per-slot finiteness bit for the NaN/Inf guard: reduced in-graph so
        # the host transfer is [B] bools, not the full logits
        ok = jnp.all(jnp.isfinite(logits), axis=(1, 2))
        return nxt, cache, logits, ok

    def _chunk_traced(self, params, cache, tokens, starts, lens, wstarts, pos_set):
        """Continuous prefill: append one [num_slots, prefill_chunk] chunk
        batch into the live cache — fixed operand shapes, so ONE trace serves
        every tick regardless of which slots have chunk work."""
        self.chunk_trace_count += 1  # python side effect: trace-time only
        batch = {
            "tokens": tokens,
            "starts": starts,
            "lens": lens,
            "write_starts": wstarts,
            "pos_set": pos_set,
        }
        logits, cache = tfm.prefill_chunk(params, self.cfg, self.ctx, batch, cache)
        first = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [B]
        ok = jnp.all(jnp.isfinite(logits), axis=1)  # NaN guard (final chunks)
        if self.capture_logits:
            return cache, first, logits, ok
        return cache, first, ok

    def _verify_traced(self, params, cache, tokens, starts, lens):
        """Speculative verify: ONE fixed-shape [num_slots, spec_k] banded
        chunk launch scores every row's current token + draft, commits the
        longest accepted prefix in-graph (pos advances by the commit count),
        and returns the per-position greedy outputs.  lens=1 rows are
        exactly a vanilla one-token decode tick riding the same launch;
        lens=0 rows write nothing and keep their pos."""
        self.verify_trace_count += 1  # python side effect: trace-time only
        batch = {
            "tokens": tokens,
            "starts": starts,
            "lens": lens,
            # verify appends everything it scores: write start == band start
            "write_starts": starts,
        }
        y, commit, cache, logits = tfm.verify_step(
            params, self.cfg, self.ctx, batch, cache, return_logits=True,
        )
        # finiteness over the whole [K, V] block; only the reduced [B] bit
        # leaves the graph unless logits capture is on (XLA drops the rest)
        ok = jnp.all(jnp.isfinite(logits), axis=(1, 2))
        if self.capture_logits:
            return y, commit, cache, logits, ok
        return y, commit, cache, ok

    def _copy_pages_traced(self, cache, src, dst):
        """Copy-on-write: physical page src[i] -> dst[i] in every layer's
        pool.  Pad entries carry dst == num_pages, which the scatter drops;
        fixed [num_slots] operand shapes keep this a single trace."""
        out = dict(cache)
        # quantized pools copy the scale tables in lockstep with the pages:
        # a CoW'd page with stale scales would dequantize garbage
        for key in ("k", "v", "k_scale", "v_scale"):
            if key not in cache:
                continue
            pool = cache[key]  # [L, num_pages, n*ps, Hkv, D] (scales: no D)
            out[key] = pool.at[:, dst].set(pool[:, src], mode="drop")
        return out

    def _place(self, key: str, value):
        """Put a cache leaf where the jitted steps return it: attention K/V
        (and their scales) sharded on the sequence axis along dim 2, every
        other leaf replicated.  A leaf placed otherwise has another type on
        the mesh, and the next step would trace again."""
        if self.ctx.mesh is None:
            return jax.tree.map(jnp.asarray, value)
        seq_sharded = key in ("k", "v", "k_scale", "v_scale")
        spec = P(None, None, self.ctx.sp_axis) if seq_sharded else P()
        return jax.device_put(value, NamedSharding(self.ctx.mesh, spec))

    def _sync_block_table(self):
        """Upload the allocator's block table when it moved since last sync."""
        if self.allocator is None or self.allocator.version == self._bt_version:
            return
        with spans.span("engine.bt_upload"):
            self._cache = dict(self._cache)
            self._cache["bt"] = self._place("bt", self.allocator.device_table(self.num_slots))
            self._bt_version = self.allocator.version
            self.bt_uploads += 1

    def _aux_inputs(self, batch_size: int) -> Dict:
        """Frontend stub inputs (audio frames / vision patches)."""
        extra = {}
        cfg = self.cfg
        if cfg.frontend == "audio_stub":
            extra["frames"] = jnp.zeros(
                (batch_size, cfg.encoder_seq, cfg.frontend_dim), jnp.float32
            )
        if cfg.frontend == "vision_stub":
            extra["patches"] = jnp.zeros(
                (batch_size, cfg.num_patches, cfg.frontend_dim), jnp.float32
            )
        return extra

    def _get_prefill(self, bucket: int):
        """Jitted (prefill into a fresh row + scatter into slot) per bucket."""
        if bucket in self._prefill_fns:
            return self._prefill_fns[bucket]
        cfg, ctx = self.cfg, self.ctx
        n = ctx.sp_size
        if self.attn_plan.autotune and n > 1:
            # resolve the (a, b) tile + schedules for this bucket geometry
            # through the on-disk plan cache BEFORE tracing, so repeated
            # serve launches skip the simulator entirely.  The key must match
            # what dispatch computes at trace time: activations inherit the
            # PARAM dtype (q flows from the embedding), not the cache dtype.
            act_dtype = jax.tree.leaves(self.params)[0].dtype
            dispatch.plan_schedules(
                self.attn_plan,
                CommModel(
                    seq=bucket,
                    hidden=cfg.num_heads * cfg.hd,
                    n=n,
                    kv_hidden=cfg.num_kv_heads * cfg.hd,
                    bytes_per_elem=jnp.dtype(act_dtype).itemsize,
                    batch=1,
                ),
            )
        if n > 1 and cfg.causal_layout == "striped":
            from repro.core.tiling import stripe_permutation

            perm = np.asarray(stripe_permutation(bucket, n))
        else:
            perm = np.arange(bucket)
        positions = jnp.asarray(perm, jnp.int32)
        self.prefill_trace_counts.setdefault(bucket, 0)

        def fn(params, cache, tokens, length, slot, shared_len):
            self.prefill_trace_counts[bucket] += 1  # trace-time only
            # striping is the serving analogue of the data pipeline's §3.7
            # permutation: token at index j carries true position perm[j]
            toks = tokens[:, perm]
            batch = {
                "tokens": toks,
                "positions": positions,
                "length": jnp.reshape(length, (1,)),
                **self._aux_inputs(1),
            }
            if self.paged:
                # the pool IS the cache: K/V scatter through slot's block-
                # table row; positions below shared_len stay with their owner
                batch["slot"] = slot
                batch["shared_len"] = shared_len
                logits, cache = tfm.prefill(params, cfg, ctx, batch, cache)
                first = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [1,1]
                if self.capture_logits:
                    return cache, first, logits[0, 0]
                return cache, first
            row = tfm.init_cache(cfg, 1, self.max_seq, dtype=self.cache_dtype, ctx=ctx)
            logits, row = tfm.prefill(params, cfg, ctx, batch, row)
            first = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [1,1]

            def insert(big, small):
                ax = 1 if big.ndim > 1 else 0  # pos is [B]; all else [L,B,...]
                return lax.dynamic_update_slice_in_dim(
                    big, small.astype(big.dtype), slot, axis=ax
                )

            merged = jax.tree.map(insert, cache, row)
            if self.capture_logits:
                return merged, first, logits[0, 0]
            return merged, first

        jitted = jax.jit(fn, donate_argnums=1)
        self._prefill_fns[bucket] = jitted
        return jitted

    def _get_prefill_packed(self, bucket: int, k: int):
        """Jitted packed prefill for ``k`` documents sharing one ``bucket``
        row — scatters each document's K/V into its own slot.  Trace count
        keys are (bucket, k): retraces stay bounded by buckets x pack sizes,
        independent of the actual prompt-length mix."""
        key = (bucket, k)
        if key in self._prefill_fns:
            return self._prefill_fns[key]
        cfg, ctx = self.cfg, self.ctx
        n = ctx.sp_size
        if self.attn_plan.autotune and n > 1:
            # pre-resolve the segment-masked plan for this bucket geometry
            # through the on-disk cache (mask signature is part of the key)
            from repro.core.masking import MaskSpec

            act_dtype = jax.tree.leaves(self.params)[0].dtype
            plan = dispatch.plan_from_ctx(
                ctx, mask=MaskSpec.segment(window=cfg.window), layout=cfg.causal_layout
            )
            dispatch.plan_schedules(
                plan,
                CommModel(
                    seq=bucket,
                    hidden=cfg.num_heads * cfg.hd,
                    n=n,
                    kv_hidden=cfg.num_kv_heads * cfg.hd,
                    bytes_per_elem=jnp.dtype(act_dtype).itemsize,
                    batch=1,
                ),
            )
        if n > 1 and cfg.causal_layout == "striped":
            from repro.core.tiling import stripe_permutation

            perm = np.asarray(stripe_permutation(bucket, n))
        else:
            perm = np.arange(bucket)
        perm_j = jnp.asarray(perm)
        self.prefill_trace_counts.setdefault(key, 0)

        def fn(params, cache, tokens, doc_lens, slots, shared_lens):
            self.prefill_trace_counts[key] += 1  # trace-time only
            j = jnp.arange(bucket, dtype=jnp.int32)
            cum = jnp.cumsum(doc_lens)
            starts = jnp.concatenate([jnp.zeros((1,), jnp.int32), cum[:-1]])
            seg = jnp.sum(j[:, None] >= starts[None, :], axis=1).astype(jnp.int32) - 1
            pad = j >= cum[-1]
            seg = jnp.where(pad, jnp.int32(k), seg)  # pads match nothing real
            positions = j - starts[jnp.clip(seg, 0, k - 1)]
            batch = {
                "tokens": tokens[:, perm],  # §3.7 stripe, as in the data pipeline
                "positions": positions[perm_j],
                "segments": seg[perm_j],
                "doc_lens": doc_lens,
                "slots": slots,
            }
            if self.paged:
                batch["shared_lens"] = shared_lens
            logits, cache = tfm.prefill_packed(params, cfg, ctx, batch, cache)
            first = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [k]
            if self.capture_logits:
                return cache, first, logits
            return cache, first

        jitted = jax.jit(fn, donate_argnums=1)
        self._prefill_fns[key] = jitted
        return jitted

    # -- streaming API ------------------------------------------------------

    def submit(
        self, prompt: np.ndarray, max_new_tokens: int = 16, arrival_tick: int = 0,
        *, deadline_ticks: Optional[int] = None, priority: int = 0,
    ) -> int:
        """Queue one request; returns its rid.  ``arrival_tick`` defers
        admission until the engine clock reaches it (trace replay).
        ``deadline_ticks`` retires the request (status ``"deadline"``, partial
        tokens kept) once that many ticks pass from arrival; higher
        ``priority`` admits first (FIFO within a level)."""
        req = self.scheduler.submit(
            prompt, max_new_tokens, arrival_tick,
            deadline_ticks=deadline_ticks, priority=priority,
        )
        req.queued_since = spans.now()
        return req.rid

    @property
    def has_work(self) -> bool:
        return self.scheduler.has_work

    def _finish(self, slot: int, status: str = "ok") -> RequestResult:
        req = self.scheduler.retire(slot, self._tick, status=status)
        freed: List[int] = []
        if self.allocator is not None:
            # drop the slot's page references; pages shared with live slots
            # survive until their last reader retires
            freed = self.allocator.free_slot(slot)
        if status == "numeric_error":
            self._scrub_numeric(slot, freed)
        result = RequestResult.from_request(req)
        self._finished[req.rid] = result
        return result

    def _scrub_numeric(self, slot: int, freed: List[int]) -> None:
        """Zero a numeric_error slot's K/V (quantized: also its scales)
        before the data can be re-read.  Stale FINITE garbage in freed pages
        is harmless — band-masked or overwritten before the band reaches it
        — but non-finite garbage is not: additive ``-inf`` mask bias keeps
        NaN NaN, so one retired slot's NaN could leak into other slots'
        scores through FREE-entry clamped page reads.  Shared pages (ref
        still > 0) are left alone: their content is live prefix data.  The
        write runs in place through a donating jit; the page list pads to
        ``max_pages`` with an out-of-range id (dropped), one trace for all."""
        keys = tuple(k for k in ("k", "v", "k_scale", "v_scale") if k in self._cache)
        if self.allocator is not None:
            if not freed:
                return
            lay = self.allocator.layout
            idx = np.full((max(lay.max_pages, len(freed)),), lay.num_pages, np.int32)
            idx[: len(freed)] = freed
        else:
            idx = np.asarray([slot], np.int32)
        self._cache = self._set_kv(self._cache, (jnp.asarray(idx),), 0, keys=keys)

    def _finish_queued(self, req: Request) -> RequestResult:
        """Terminal path for a request that never held a slot this time
        around (cancelled / expired / rejected while queued).  A previously
        preempted request may still carry generated tokens — they ride along
        on the result."""
        req.finish_tick = self._tick
        result = RequestResult.from_request(req)
        self._finished[req.rid] = result
        return result

    def cancel(self, rid: int) -> Optional[RequestResult]:
        """Cancel a live request (queued or mid-flight).  Frees its slot and
        pages immediately; partial tokens are kept on the result (status
        ``"cancelled"``).  Returns the result, or None if the rid is not in
        flight (already finished or unknown)."""
        req = self.scheduler.cancel_queued(rid)
        if req is not None:
            self.cancelled += 1
            return self._finish_queued(req)
        req = self.scheduler.find(rid)
        if req is None or req.slot is None:
            return None
        self.cancelled += 1
        return self._finish(req.slot, status="cancelled")

    # -- robustness: preemption, fault guards, health -----------------------

    def _do_preempt(self, slot: int) -> List[int]:
        """Evict ``slot`` back to the queue under pool pressure: free its
        pages (refcounts keep prefix sharers' pages alive) and reset its
        ingest cursor so admission recomputes prompt + generated through
        continuous prefill.  Returns the physical pages whose refcount hit
        zero (the caller scrubs pending CoW copies against them)."""
        freed = self.allocator.free_slot(slot)
        req = self.scheduler.preempt(slot)
        req.preemptions += 1
        req.recompute_tokens += req.context_len
        self.preemptions += 1
        self.recompute_tokens += req.context_len
        self._shared_len[slot] = 0
        # park the stale row: paged writes already drop through the FREE
        # block-table row, parking additionally drops the pos-guard writes
        # and mirrors the mid-prefill convention
        self._cache = dict(self._cache)
        self._cache["pos"] = self._cache["pos"].at[slot].set(_PARKED)
        return freed

    def _preempt_for(self, protect) -> Optional[List[int]]:
        """Pick and evict one victim; None when nothing is evictable (the
        caller re-raises the pool exhaustion)."""
        if self.prefill_chunk is None:
            return None  # recompute rides continuous prefill only
        victim = select_victim(self.scheduler.slots, self.allocator, protect)
        if victim is None:
            return None
        return self._do_preempt(victim)

    def _ensure_append_robust(self, slot: int, pos: int, copies) -> None:
        """``ensure_append`` with preempt-and-retry: on pool exhaustion evict
        victims until the append fits (or nothing is left to evict).  Pending
        CoW copies whose destination page was freed by a preemption are
        scrubbed — the requester is gone, and the page may be re-issued
        within this same ensure phase."""
        while True:
            try:
                cp = self.allocator.ensure_append(slot, pos)
                if cp is not None:
                    copies.append(cp)
                return
            except PoolExhausted:
                freed = self._preempt_for(protect={slot})
                if freed is None:
                    raise
                drop = set(freed)
                copies[:] = [(s, d) for (s, d) in copies if d not in drop]

    def _ensure_span_robust(self, slot: int, start: int, count: int, copies) -> None:
        """``ensure_span`` with preempt-and-retry (speculative verify)."""
        chunk = self.allocator.layout.chunk
        if count <= 0:
            return
        for lp in range(start // chunk, (start + count - 1) // chunk + 1):
            if lp >= self.allocator.layout.max_pages:
                break
            self._ensure_append_robust(slot, max(start, lp * chunk), copies)

    def poison_slot_cache(self, slot: int) -> None:
        """Fault injection (testing/chaos.py): overwrite part of ``slot``'s
        resident K with NaN so its next attention pass produces non-finite
        logits — exercising the REAL in-graph guard path.  Batch rows are
        independent, so only this slot's stream is affected.  Quantized
        pools poison the f32 scale table (int8 codes cannot hold NaN)."""
        self._cache = dict(self._cache)
        if self.allocator is not None:
            held = self.allocator.slot_pages(slot)
            if held == 0:
                return
            pid = int(self.allocator.block_table[slot, 0])
            key = "k_scale" if "k_scale" in self._cache else "k"
            pool = self._cache[key]  # [L, num_pages, n*ps, ...]
            self._cache[key] = pool.at[:, pid, 0].set(jnp.nan)
        else:
            key = "k_scale" if "k_scale" in self._cache else "k"
            row = self._cache[key]  # [L, B, cap, ...]
            self._cache[key] = row.at[:, slot, 0].set(jnp.nan)

    def health(self) -> Dict[str, object]:
        """Invariant sweep: allocator refcounts/free list/scale lockstep plus
        engine-level slot cross-checks.  Raises on any violation; returns a
        summary dict when healthy.  Runs automatically every
        ``ServeConfig.health_every`` ticks."""
        self.health_sweeps += 1
        problems: List[str] = []
        if self.allocator is not None:
            problems += self.allocator.check_invariants()
            # every page-holding allocator slot must be a live scheduler slot
            for slot in self.allocator._slot_pages:
                if not (0 <= slot < self.num_slots):
                    problems.append(f"allocator holds pages for bad slot {slot}")
                elif self.scheduler.slots[slot] is None:
                    problems.append(
                        f"orphaned slot {slot}: holds "
                        f"{self.allocator.slot_pages(slot)} pages but no request"
                    )
            # ... and every ADMITTED paged request must hold pages (a request
            # still queued holds none; mid-prefill and decoding both do)
            for slot, req in enumerate(self.scheduler.slots):
                if req is not None and self.allocator.slot_pages(slot) == 0:
                    problems.append(
                        f"slot {slot} (rid {req.rid}) active without pages"
                    )
        if problems:
            raise RuntimeError(
                "engine.health() invariant sweep failed:\n  " + "\n  ".join(problems)
            )
        out = {
            "ok": True,
            "tick": self._tick,
            "active_slots": len(self.scheduler.active_slots()),
            "queued": self.scheduler.pending,
        }
        if self.allocator is not None:
            out.update(
                pages_in_use=self.allocator.pages_in_use,
                pages_reserved=self.allocator.pages_reserved,
                scale_entries_in_use=self.allocator.scale_entries_in_use,
            )
        return out

    def _req_done(self, req: Request, tok: int) -> bool:
        if self.eos_id is not None and tok == self.eos_id:
            return True
        return len(req.generated) >= req.max_new_tokens

    def _alloc_pages(self, slot: int, req: Request) -> int:
        """Paged admission: claim (or prefix-share) the slot's pages and sync
        the device block table BEFORE the prefill trace reads it.  A resumed
        (previously preempted) request allocates for its CONTEXT — prompt +
        generated — and only its REMAINING token budget.  Returns the
        shared-prefix length the scatter must skip."""
        alloc = self.allocator.alloc_slot(
            slot, req.context, req.remaining_new_tokens
        )
        return alloc.shared_len

    def _alloc_pages_robust(self, slot: int, req: Request) -> int:
        """Admission alloc with preempt-and-retry: under oversubscription the
        admission check only guaranteed PROMPT pages + margin, so a burst of
        same-tick admissions (or a chaos squeeze) can still find the free
        list short.  ``alloc_slot`` unwinds atomically on failure, so each
        retry starts from a clean slate."""
        while True:
            try:
                return self._alloc_pages(slot, req)
            except PoolExhausted:
                if self._preempt_for(protect={slot}) is None:
                    raise

    def _resident_shared_len(self, slot: int, shared: int) -> int:
        """Shared-prefix tokens whose CONTENT is already resident.

        Continuous prefill admits a sharer while its prefix donor may still
        be mid-chunk-ingestion: the shared pages are booked but their data
        hasn't been written, and a chunk that attended them would bake zeros
        into its deeper-layer KV writes.  Cap the credit at every
        mid-prefill donor's written watermark (page-aligned); the sharer
        recomputes and rewrites the rest of the prefix itself — identical
        values into the same physical pages, so the donor's own later
        writes are idempotent.  One-shot mode never needs this: a donor's
        full prefill launch always precedes a later sharer's admission."""
        lay = self.allocator.layout
        mine = {
            int(p) for p in self.allocator.block_table[slot, : lay.pages_for(shared)]
        }
        for s2, r2 in enumerate(self.scheduler.slots):
            if s2 == slot or r2 is None or r2.prefill_pos >= r2.ingest_len:
                continue
            if self.allocator.slot_pages(s2) == 0:
                continue  # admitted this tick, pages not allocated yet
            theirs = self.allocator.block_table[s2, : lay.pages_for(r2.ingest_len)]
            if mine & {int(p) for p in theirs}:
                shared = min(shared, (r2.prefill_pos // lay.chunk) * lay.chunk)
        return shared

    def _prefill_single(self, slot: int, req: Request) -> int:
        """Legacy one-row-per-request prefill (exact/frontend archs)."""
        bucket = self.scheduler.bucket_for(len(req.prompt))
        with spans.span("engine.prefill", bucket=bucket, k=1, tokens=len(req.prompt),
                        rids=str(req.rid)):
            self.prefill_launches += 1
            self.prefill_launch_tokens += bucket
            toks = np.zeros((1, bucket), np.int32)
            toks[0, : len(req.prompt)] = req.prompt
            shared = self._alloc_pages(slot, req) if self.paged else 0
            self._sync_block_table()
            fn = self._get_prefill(bucket)
            out = fn(
                self.params,
                self._cache,
                jnp.asarray(toks),
                jnp.asarray(len(req.prompt), jnp.int32),
                jnp.asarray(slot, jnp.int32),
                jnp.asarray(shared, jnp.int32),
            )
            if self.capture_logits:
                self._cache, first, row = out
            else:
                self._cache, first = out
            with spans.span("engine.prefill.wait"):
                tok = int(np.asarray(first)[0, 0])
            if self.capture_logits:
                self.debug_logits.setdefault(req.rid, []).append(np.asarray(row))
            self._depth[slot] = len(req.prompt)
            return tok

    def _prefill_group(self, group) -> List[int]:
        """Packed prefill: the group's prompts concatenate into one bucket
        row under a document mask; each document's K/V lands in its own
        slot.  Returns the first generated token per request."""
        lens = [len(req.prompt) for _, req in group]
        bucket = self.scheduler.bucket_for(sum(lens))
        k = len(group)
        with spans.span("engine.prefill", bucket=bucket, k=k, tokens=sum(lens),
                        rids=" ".join(str(req.rid) for _, req in group)):
            self.prefill_launches += 1
            self.prefill_launch_tokens += bucket
            toks = np.zeros((1, bucket), np.int32)
            off = 0
            for (_, req), ln in zip(group, lens):
                toks[0, off : off + ln] = req.prompt
                off += ln
            shared = [
                self._alloc_pages(slot, req) if self.paged else 0 for slot, req in group
            ]
            self._sync_block_table()
            fn = self._get_prefill_packed(bucket, k)
            out = fn(
                self.params,
                self._cache,
                jnp.asarray(toks),
                jnp.asarray(lens, jnp.int32),
                jnp.asarray([slot for slot, _ in group], jnp.int32),
                jnp.asarray(shared, jnp.int32),
            )
            if self.capture_logits:
                self._cache, firsts, rows = out
            else:
                self._cache, firsts = out
            with spans.span("engine.prefill.wait"):
                firsts_np = np.asarray(firsts)
            if self.capture_logits:
                rows_np = np.asarray(rows)
                for d, (_, req) in enumerate(group):
                    self.debug_logits.setdefault(req.rid, []).append(rows_np[d])
            for (slot, req), ln in zip(group, lens):
                self._depth[slot] = ln
            return [int(t) for t in firsts_np]

    def _record_first_token(self, slot: int, req: Request, tok: int, finished) -> None:
        """First generated token off prefill logits (one-shot or final
        chunk): same-tick bookkeeping shared by both ingestion modes.  For a
        RESUMED (preempted) request this is the first token past the
        recomputed context — TTFT keeps the original first-token tick."""
        req.generated.append(tok)
        req.token_ticks.append(self._tick)
        if req.first_token_tick is None:
            req.first_token_tick = self._tick
        self._cur[slot, 0] = tok
        if self._req_done(req, tok):
            finished.append(self._finish(slot))

    def _run_chunks(self, plan, finished) -> int:
        """Launch this tick's chunk plan as ONE fixed-shape [num_slots, C]
        jitted call; rows without work carry lens=0 (nothing written).  Rows
        whose LAST chunk this is get their cache position un-parked to the
        prompt length and sample their first token from the returned logits —
        the same tick a one-shot prefill would have.  Returns prompt tokens
        ingested."""
        C = self.prefill_chunk
        B = self.num_slots
        tokens = np.zeros((B, C), np.int32)
        starts = np.zeros((B,), np.int32)
        lens = np.zeros((B,), np.int32)
        wstarts = np.zeros((B,), np.int32)
        pos_set = np.full((B,), -1, np.int32)
        finishing = []
        total = 0
        for slot, req, start, take in plan:
            ctx_toks = req.context  # prompt + generated (recompute on resume)
            tokens[slot, :take] = ctx_toks[start : start + take]
            starts[slot] = start
            lens[slot] = take
            wstarts[slot] = self._shared_len[slot]  # skip resident shared prefix
            if req.first_chunk_tick is None:
                req.first_chunk_tick = self._tick
            req.prefill_pos = start + take
            req.chunks += 1
            total += take
            if req.prefill_pos >= req.ingest_len:
                pos_set[slot] = req.ingest_len
                finishing.append((slot, req))
        self.chunk_launches += 1
        self._sync_block_table()  # paged: admission allocated this plan's pages
        out = self._chunk_step(
            self.params, self._cache, jnp.asarray(tokens), jnp.asarray(starts),
            jnp.asarray(lens), jnp.asarray(wstarts), jnp.asarray(pos_set),
        )
        if self.capture_logits:
            self._cache, first, logits, ok = out
        else:
            self._cache, first, ok = out
        with spans.span("engine.chunk.wait"):
            first_np = np.asarray(first)
        logits_np = np.asarray(logits) if self.capture_logits else None
        ok_np = np.asarray(ok)
        n_first = 0
        for slot, req in finishing:
            if self.nan_guard and not bool(ok_np[slot]):
                self.numeric_errors += 1
                finished.append(self._finish(slot, status="numeric_error"))
                continue
            self._depth[slot] = req.ingest_len
            if logits_np is not None:
                self.debug_logits.setdefault(req.rid, []).append(logits_np[slot])
            self._record_first_token(slot, req, int(first_np[slot]), finished)
            n_first += 1
        return total, n_first

    def _apply_copies(self, copies) -> None:
        """Run queued CoW page copies through the jitted scatter (fixed
        [num_slots] operand shape; pad rows carry dst == num_pages which the
        scatter drops).  Batches of more than num_slots copies launch in
        waves."""
        if not copies:
            return
        npages = self.allocator.layout.num_pages
        for off in range(0, len(copies), self.num_slots):
            wave = copies[off : off + self.num_slots]
            src = np.zeros((self.num_slots,), np.int32)
            dst = np.full((self.num_slots,), npages, np.int32)  # dropped
            for i, (s, d) in enumerate(wave):
                src[i], dst[i] = s, d
            self._cache = self._copy_pages(
                self._cache, jnp.asarray(src), jnp.asarray(dst)
            )

    def _vanilla_decode_tick(self, decodable, finished) -> int:
        """One plain decode launch over every decodable slot; returns tokens
        generated this tick."""
        if self.paged:
            # make every decodable slot's write position appendable:
            # allocate tail pages on chunk boundaries, CoW shared tails.
            # Under oversubscription (or a chaos squeeze) an allocation may
            # find the pool dry — preempt victims and retry; a preempted
            # slot drops out of this tick's decodable set
            with spans.span("engine.pages"):
                copies = []
                for slot in decodable:
                    if self.scheduler.slots[slot] is None:
                        continue  # preempted by an earlier slot's ensure
                    self._ensure_append_robust(slot, int(self._depth[slot]), copies)
                decodable = [s for s in decodable if self.scheduler.slots[s] is not None]
                self._apply_copies(copies)
            self._sync_block_table()
            if not decodable:
                return 0
        if self._quantized and self.decode_kernel != "native":
            self.dequant_fallbacks += 1  # gather-path dequant served this tick
        with spans.span("engine.decode"):
            nxt, self._cache, logits, ok = self._decode(
                self.params, self._cache, jnp.asarray(self._cur)
            )
            with spans.span("engine.decode.wait"):
                nxt_np = np.asarray(nxt)
            with spans.span("engine.decode.post"):
                ok_np = np.asarray(ok)
                logits_np = np.asarray(logits) if self.capture_logits else None
                tokens = 0
                for slot in decodable:
                    req = self.scheduler.slots[slot]
                    if self.nan_guard and not bool(ok_np[slot]):
                        # non-finite logits: retire ONLY this slot; every other row's
                        # token came off the same launch and is bitwise what it would
                        # have been (batch rows are independent)
                        self.numeric_errors += 1
                        finished.append(self._finish(slot, status="numeric_error"))
                        continue
                    self._depth[slot] += 1
                    tok = int(nxt_np[slot, 0])
                    if logits_np is not None:
                        self.debug_logits.setdefault(req.rid, []).append(logits_np[slot, 0])
                    req.generated.append(tok)
                    req.token_ticks.append(self._tick)
                    tokens += 1
                    self._cur[slot, 0] = tok
                    if self._req_done(req, tok):
                        finished.append(self._finish(slot))
                return tokens

    def _spec_decode_tick(self, decodable, finished, prefill_tokens) -> int:
        """Speculative tick: draft per slot (prompt-lookup n-gram), verify
        every decodable row's current token + granted draft in ONE
        [num_slots, spec_k] banded launch, commit the longest accepted
        prefix.  Token stream is identical to vanilla greedy decode; only
        the commit count per tick changes.  Falls back to the plain decode
        launch when no slot has a granted draft (cold history, drafting
        suspended after ``spec_max_misses`` dry ticks, or no leftover tick
        budget) — so low-acceptance traffic degrades to baseline, not
        below it.  Returns tokens generated this tick."""
        with spans.span("engine.draft"):
            drafts = {}
            for slot in decodable:
                if self.spec_max_misses is not None:
                    m = self._spec_misses[slot]
                    period = 16 * self.spec_max_misses
                    if m >= self.spec_max_misses:
                        # tripped: suspend drafting until the next global probe
                        # boundary (negative counter counts the cooldown down).
                        # Aligning every slot's wake-up to tick % period == 0
                        # batches probes into ONE shared verify launch — a verify
                        # tick costs the whole batch, so staggered per-slot
                        # probes would each bill a full launch for one row.
                        self._spec_misses[slot] = -(period - self._tick % period)
                        continue
                    if m < 0:
                        # cooldown lands on max_misses-1: ONE missed probe
                        # re-trips immediately, a fully-accepted probe
                        # re-enables drafting outright
                        self._spec_misses[slot] = (
                            self.spec_max_misses - 1 if m == -1 else m + 1
                        )
                        continue
                req = self.scheduler.slots[slot]
                # cap so the furthest write position stays inside the slot's
                # reserved capacity: at most max_new_tokens positions past prompt
                rem = req.max_new_tokens - len(req.generated)
                k_cap = min(self.spec_k, rem)
                if k_cap < 2:
                    continue
                d = propose_ngram(req.prompt, req.generated, k_cap - 1)
                if d:
                    drafts[slot] = d
            # draft tokens only spend LEFTOVER tick budget: decode rows and chunk
            # tokens were planned first, so the PR6 TTFT bound is untouched
            granted = self.scheduler.plan_spec(drafts, len(decodable), prefill_tokens)
            granted = {s: d for s, d in granted.items() if d}
        if not granted:
            return self._vanilla_decode_tick(decodable, finished)
        K = self.spec_k
        B = self.num_slots
        tokens = np.zeros((B, K), np.int32)
        starts = np.zeros((B,), np.int32)
        lens = np.zeros((B,), np.int32)
        for slot in decodable:
            d = granted.get(slot, [])
            tokens[slot, 0] = self._cur[slot, 0]
            tokens[slot, 1 : 1 + len(d)] = d
            starts[slot] = self._depth[slot]
            lens[slot] = 1 + len(d)
        if self.paged:
            with spans.span("engine.pages"):
                copies = []
                for slot in decodable:
                    if self.scheduler.slots[slot] is None:
                        continue  # preempted by an earlier slot's ensure
                    self._ensure_span_robust(
                        slot, int(self._depth[slot]), int(lens[slot]), copies
                    )
                live = [s for s in decodable if self.scheduler.slots[s] is not None]
                if len(live) < len(decodable):
                    for s in decodable:
                        if self.scheduler.slots[s] is None:
                            lens[s] = 0  # preempted rows write/commit nothing
                    decodable = live
                self._apply_copies(copies)
            self._sync_block_table()
            if not decodable:
                return 0
        with spans.span("engine.verify"):
            for slot in decodable:
                d = granted.get(slot, [])
                if d:
                    req = self.scheduler.slots[slot]
                    req.spec_proposed += len(d)
                    self.spec_proposed += len(d)
            self.verify_launches += 1
            if self._quantized and self.decode_kernel != "native":
                self.dequant_fallbacks += 1  # gather-path dequant served this tick
            out = self._verify(
                self.params,
                self._cache,
                jnp.asarray(tokens),
                jnp.asarray(starts),
                jnp.asarray(lens),
            )
            if self.capture_logits:
                y, commit, self._cache, v_logits, ok = out
            else:
                y, commit, self._cache, ok = out
            with spans.span("engine.verify.wait"):
                y_np = np.asarray(y)
            with spans.span("engine.verify.post"):
                logits_np = np.asarray(v_logits) if self.capture_logits else None
                commit_np = np.asarray(commit)
                ok_np = np.asarray(ok)
                generated = 0
                for slot in decodable:
                    req = self.scheduler.slots[slot]
                    if self.nan_guard and not bool(ok_np[slot]):
                        # non-finite verify logits: commit nothing for this slot,
                        # retire it alone (other rows commit bitwise-unchanged)
                        self.numeric_errors += 1
                        finished.append(self._finish(slot, status="numeric_error"))
                        continue
                    committed = int(commit_np[slot])
                    drafted = int(lens[slot]) - 1
                    if drafted:
                        accepted = committed - 1  # draft tokens that matched greedy
                        req.spec_accepted += accepted
                        self.spec_accepted += accepted
                        # a MISS is any verify tick with a rejection: the accept
                        # distribution is bimodal (a live loop verifies fully, a
                        # cold history verifies ~nothing), so full-accept cleanly
                        # splits the regimes — and partial-accept ticks barely pay
                        # for the batch-wide verify launch anyway
                        if accepted == drafted:
                            self._spec_misses[slot] = 0
                        else:
                            self._spec_misses[slot] += 1
                    self._depth[slot] += committed
                    done = False
                    for i in range(committed):
                        tok = int(y_np[slot, i])
                        if logits_np is not None:
                            self.debug_logits.setdefault(req.rid, []).append(
                                logits_np[slot, i]
                            )
                        req.generated.append(tok)
                        req.token_ticks.append(self._tick)  # same tick: all one launch
                        generated += 1
                        self._cur[slot, 0] = tok
                        if self._req_done(req, tok):
                            # EOS (or cap) mid-commit: later accepted tokens are
                            # discarded; their cache writes sit past the final depth
                            # and are band-invisible / freed by the rollback below
                            self._depth[slot] -= committed - (i + 1)
                            done = True
                            finished.append(self._finish(slot))
                            break
                    if done:
                        continue
                    if self.paged and drafted:
                        # free pages the verify wrote past the accepted prefix —
                        # sharers never see them (append pages are never registered
                        # for prefix sharing), but held rejected pages would leak
                        # capacity until retirement.  No device sync here: every
                        # launch site re-syncs the block table before launching.
                        self.allocator.rollback(slot, int(self._depth[slot]))
                return generated

    def step(self) -> List[RequestResult]:
        """One engine tick: admission, prompt ingestion, then one jitted
        decode over every decodable slot.  Returns requests finished this
        tick (as ``RequestResult``).

        Legacy mode ingests each admission in ONE bucketed prefill launch
        (same-tick admissions PACK into shared rows under a document mask).
        Continuous mode (``serve.prefill_chunk``) parks newly admitted slots
        past cache capacity and streams their prompt in ``prefill_chunk``-
        token chunks under ``serve.tick_token_budget``; a slot joins the
        decode batch the same tick its last chunk lands.

        Under a profiler session the tick is an ``engine.tick`` span carrying
        its counters, with a span per phase (``repro.spans``)."""
        with spans.span("engine.tick") as tick:
            if not tick.recording:
                return self._step()[0]
            t, before = self._tick, self._counters()
            finished, admitted, decodable = self._step()
            tick.set(
                tick=t, admitted=admitted, decodable=decodable,
                prefill_tokens=self.tick_prefill_tokens[-1],
                decode_tokens=self.tick_decode_tokens[-1], finished=len(finished),
                **{k: b - a for k, a, b in zip(_TICK_COUNTERS, before, self._counters())},
            )
            return finished

    def _counters(self):
        """The engine totals that ``engine.tick`` reports as deltas
        (``_TICK_COUNTERS``)."""
        a = self.allocator
        return (
            self.prefill_launches + self.chunk_launches,
            a.fresh_allocs if a else 0,
            a.cow_copies if a else 0,
            a.shared_hits if a else 0,
            self.bt_uploads,
            self.preemptions,
            self.decode_trace_count + self.chunk_trace_count + self.verify_trace_count
            + sum(self.prefill_trace_counts.values()),
        )

    def _step(self):
        """The tick; returns (finished results, admitted, decode batch size)."""
        finished: List[RequestResult] = []
        prefill_tokens = 0
        decode_tokens = 0
        with spans.span("engine.admit"):
            # 0. fault injection (testing only) + lifecycle expiry
            if self.chaos is not None:
                self.chaos.on_tick(self)
            for req in self.scheduler.take_expired(self._tick):
                self.deadline_expired += 1
                finished.append(self._finish_queued(req))
            for slot, req in enumerate(self.scheduler.slots):
                if (
                    req is not None
                    and req.deadline_ticks is not None
                    and self._tick - req.arrival_tick >= req.deadline_ticks
                ):
                    self.deadline_expired += 1
                    finished.append(self._finish(slot, status="deadline"))
            # 1. admission + prompt ingestion
            assigned = self.scheduler.admit(self._tick)
            for req in self.scheduler.take_rejected():
                self.rejected_requests += 1
                finished.append(self._finish_queued(req))
            for slot, req in assigned:
                self._spec_misses[slot] = 0  # fresh request: drafting re-enabled
                if req.queued_since is not None:
                    spans.record("request.queued", req.queued_since, rid=req.rid,
                                 prompt_len=len(req.prompt))
                    req.queued_since = None
            if self.prefill_chunk is not None:
                for slot, req in assigned:
                    shared = 0
                    if self.paged:
                        try:
                            shared = self._alloc_pages_robust(slot, req)
                        except PoolExhausted:
                            # nothing evictable (fresh squeeze / lone giant):
                            # hand the slot back and retry on a later tick
                            self.scheduler.preempt(slot)
                            continue
                    if shared:
                        shared = self._resident_shared_len(slot, shared)
                    self._shared_len[slot] = shared
                    # fully-shared chunks never launch, but the LAST context token
                    # always runs forward — its logits seed the first decode
                    req.prefill_pos = min(shared, req.ingest_len - 1)
                if assigned:
                    # park mid-prefill rows so the shared decode's writes drop
                    idx = jnp.asarray([slot for slot, _ in assigned], jnp.int32)
                    self._cache = dict(self._cache)
                    self._cache["pos"] = self._cache["pos"].at[idx].set(_PARKED)
                decodable = [
                    s
                    for s in self.scheduler.active_slots()
                    if self.scheduler.slots[s].prefill_pos
                    >= self.scheduler.slots[s].ingest_len
                ]
                plan = self.scheduler.plan_chunks(len(decodable))
                if plan and self.chaos is not None and self.chaos.drop_grants(self._tick):
                    # injected scheduler fault: this tick's chunk grants vanish;
                    # progress resumes next tick (the head-of-line guarantee is
                    # per-plan, so a dropped plan only delays, never deadlocks)
                    self.chaos_dropped_grants += len(plan)
                    plan = []
            elif self._can_pack:
                groups = self.scheduler.pack_groups(
                    assigned, pack_max=self.pack_max, plan=self.pack_plan
                )
            else:
                groups = [[x] for x in assigned]
        if self.prefill_chunk is not None:
            if plan:
                with spans.span("engine.chunk", tokens=sum(p[3] for p in plan),
                                rids=" ".join(str(p[1].rid) for p in plan)):
                    ingested, n_first = self._run_chunks(plan, finished)
                prefill_tokens += ingested
                decode_tokens += n_first  # first tokens off final-chunk logits
                # final chunks join the decode batch this same tick
                decodable = [
                    s
                    for s in self.scheduler.active_slots()
                    if self.scheduler.slots[s].prefill_pos
                    >= self.scheduler.slots[s].ingest_len
                ]
        else:
            for group in groups:
                if self._can_pack:
                    firsts = self._prefill_group(group)
                else:
                    firsts = [self._prefill_single(slot, req) for slot, req in group]
                for tok, (slot, req) in zip(firsts, group):
                    req.prefill_pos = len(req.prompt)
                    req.chunks = 1
                    req.first_chunk_tick = self._tick
                    prefill_tokens += len(req.prompt)
                    decode_tokens += 1  # first token off the prefill logits
                    self._record_first_token(slot, req, tok, finished)
            decodable = self.scheduler.active_slots()
        # 2. one decode step over every decodable slot (mixed depths via
        # pos: [B]; mid-prefill rows ride along parked, writes dropped).
        # Speculative mode turns the decode launch into a [slots, spec_k]
        # verify launch whenever any slot has a granted draft.
        if decodable:
            if self._spec_on:
                decode_tokens += self._spec_decode_tick(
                    decodable, finished, prefill_tokens
                )
            else:
                decode_tokens += self._vanilla_decode_tick(decodable, finished)
        self.tick_prefill_tokens.append(prefill_tokens)
        self.tick_decode_tokens.append(decode_tokens)
        self._tick += 1
        if self.health_every and self._tick % self.health_every == 0:
            with spans.span("engine.health"):
                self.health()  # raises on any invariant violation
        return finished, len(assigned), len(decodable)

    def run(self) -> Dict[int, RequestResult]:
        """Drain the queue; returns {rid: RequestResult}."""
        while self.has_work:
            self.step()
        return dict(self._finished)

    def tick_stats(self) -> Dict[str, object]:
        """Per-tick token series: prompt tokens ingested (one-shot prefill or
        chunk launches) vs tokens generated, kept separate so prefill ticks
        cannot inflate decode tokens/s."""
        return {
            "ticks": self._tick,
            "prefill_tokens": list(self.tick_prefill_tokens),
            "decode_tokens": list(self.tick_decode_tokens),
        }

    def kv_cache_stats(self) -> Dict[str, float]:
        """Attention-cache memory accounting (bench / capacity planning).
        Dense: bytes are fixed at ``num_slots x max_seq``.  Paged: resident
        bytes follow the allocator's peak page usage, and the allocator's
        sharing/CoW counters ride along."""
        cfg = self.cfg
        spec = {
            "spec_proposed": float(self.spec_proposed),
            "spec_accepted": float(self.spec_accepted),
            "spec_accept_rate": (
                self.spec_accepted / self.spec_proposed if self.spec_proposed else 0.0
            ),
            "verify_launches": float(self.verify_launches),
            # robustness counters (ISSUE 10): ride along on every branch so
            # serve_bench / launch summaries need no allocator special-casing
            "preemptions": float(self.preemptions),
            "recompute_tokens": float(self.recompute_tokens),
            "cancelled": float(self.cancelled),
            "deadline_expired": float(self.deadline_expired),
            "numeric_errors": float(self.numeric_errors),
            "rejected_requests": float(self.rejected_requests),
            "health_sweeps": float(self.health_sweeps),
            "chaos_dropped_grants": float(self.chaos_dropped_grants),
        }
        if cfg.family == "ssm":
            return {"cache_bytes": 0.0, **spec}
        L = cfg.num_layers
        # the POOL's storage width, not cache_dtype: a quantized pool stores
        # int8/fp8 elements with f32 scales accounted separately below
        itemsize = jnp.dtype(self._cache["k"].dtype).itemsize
        hkv = self._cache["k"].shape[-2]
        elem = self._cache["k"].shape[-1] + self._cache["v"].shape[-1]  # dk + dv
        per_tok = L * hkv * elem * itemsize
        # per-(token, kv-head) scale entries: one f32 each for K and V
        scale_per_tok = (
            L * hkv * 2 * jnp.dtype(self._cache["k_scale"].dtype).itemsize
            if "k_scale" in self._cache else 0
        )
        if self.allocator is None:
            return {
                "paged": 0,
                "cache_bytes": float(self.num_slots * self.max_seq * per_tok),
                # dense rollback frees nothing: rejected positions are simply
                # band-invisible and get rewritten in place
                "spec_rolled_back_pages": 0.0,
                **spec,
            }
        lay = self.allocator.layout
        stats = self.allocator.stats()
        return {
            "paged": 1,
            "page_size": lay.page_size,
            "chunk_tokens": lay.chunk,
            "num_pages": lay.num_pages,
            # pool reservation (what init_cache actually allocated) ...
            "cache_bytes": float(lay.num_pages * lay.chunk * per_tok),
            # ... vs what the workload actually touched
            "peak_page_bytes": float(stats["peak_in_use"] * lay.chunk * per_tok),
            "bt_uploads": float(self.bt_uploads),
            # quantized pool: scale-table reservation + gather-ref fallbacks
            "scale_table_bytes": float(lay.num_pages * lay.chunk * scale_per_tok),
            "dequant_fallbacks": float(self.dequant_fallbacks),
            **{k: float(v) for k, v in stats.items()},
            **spec,
        }

    # -- legacy static-batch API --------------------------------------------

    def generate(self, prompts: np.ndarray, max_new_tokens: int = 16) -> np.ndarray:
        """prompts: [B, S0] int32.  Greedy decoding; returns [B,
        max_new_tokens].  A thin wrapper over the streaming path: B requests
        arrive at once and are served by the slot pool (in waves when B >
        num_slots).  The striped prompt permutation (§3.7) happens inside the
        bucketed prefill."""
        prompts = np.asarray(prompts, np.int32)
        rids = [self.submit(prompts[i], max_new_tokens, self._tick) for i in range(len(prompts))]
        self.run()
        out = []
        for rid in rids:
            row = self._finished.pop(rid).generated[:max_new_tokens]
            row = row + [self.eos_id or 0] * (max_new_tokens - len(row))
            out.append(row)
        return np.asarray(out, np.int32)
