"""Serving engine: prefill/decode-separated step loop (DESIGN.md §7) behind
the streaming generation API (DESIGN.md §10), with shared-prefix KV reuse,
batched bucketed prefill (DESIGN.md §11), an optional paged KV layout —
``plan.kv_paging='paged'`` routes the slot cache through the refcounted
block pool of ``serving/block_pool.py``: byte-budgeted admission, prefix
blocks attached by reference, copy-on-write ``n>1`` forks, bit-identical
streams (DESIGN.md §15) — and prefill-only encode traffic
(DESIGN.md §14) — classify/embed/score requests that resolve in the step
that admits them, either on a mode='encoder' plan (bidirectional int4 BERT,
per-row length masking keeps bucket padding bit-exact) or interleaved with
decode traffic on a generation engine (task='score' = prompt
log-likelihood).

Two-phase execution over a deployed model (``repro.deploy.DeployedModel``, or
a raw params tree plus its ``ExecutionPlan``):

* **prefill** — admissions are grouped by (bucket, cached-prefix) and each
  group runs as ONE batch-N forward (``plan.prefill_batch`` caps N; N pads to
  a power of two so the compile-key space stays (bucket, n)). With
  ``plan.prefix_cache`` enabled, the longest cached block-aligned prefix is
  scattered into the slot — quantized codes + scales copy directly — and
  only the suffix is computed, block-chunked so the rows a cold run attends
  to are bit-equal to the rows a hit copies out of the cache.
* **decode** — one token per step for every occupied slot, batched across the
  slot table with per-slot cache cursors (kv_cache.SlotKVCache).

Both phases sample through ONE jitted step: the legacy per-batch ``argmax``
is the ``temperature=0`` case of ``api.sample_batch``, which threads per-slot
(seed, step, temperature, top_k, top_p) vectors alongside the decode state so
a request's tokens are a function of (prompt, seed) only — never of which
other requests share the batch (or the prefill group).

``engine_step()`` is the public pump: one admit → prefill → batched-decode
round, returning the ``(rid, token)`` pairs it emitted (``TokenStream``
handles are fed from inside it). ``run_until_drained`` is a loop over it and
raises when ``max_steps`` strands work. ``cancel(rid)`` frees a queued entry
or an occupied slot (KV state reset) mid-flight; every slotted exit funnels
through one finalize helper, so cancel and complete truncate output
identically.

Everything configuration-shaped — segments, kernel selection, KV precision,
prefill mode, decode dtype, default sampling, prefix/batch prefill knobs —
comes from the plan; the engine itself only owns slots, max_len and the step
loop. Families without a {'k','v','len'} decode cache (xlstm, hybrid,
encdec) run ``prefill_mode='token'``: the seed semantics with a shared
cursor, now guarded against cursor exhaustion (admission is refused until
the cursor fits the request; an idle engine resets its state instead of
silently clamping KV writes past max_len).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..deploy import DeployedModel, ExecutionPlan
from ..kernels.kv_pack import kv_buffer_keys, kv_row_bytes
from ..models import api as model_api
from ..models.bert import bert_encode, bert_pool
from .api import (GenerationRequest, SamplingParams, TokenStream,
                  sample_batch, sample_seed, sample_token)
from .block_pool import BlockPool, PagedKVCache, blocks_needed
from .clock import SYSTEM_CLOCK, Clock
from .encoder import EncodeHandle, EncodeRequest
from .kv_cache import SlotKVCache
from .metrics import ServeMetrics
from .prefix_cache import PREFIX_BLOCK, PrefixCache
from .scheduler import Request, Scheduler, group_admits  # noqa: F401 (compat)


def _bucket_for(plen: int, max_len: int, min_bucket: int = 8) -> int:
    b = min_bucket
    while b < plen:
        b *= 2
    return min(b, max_len)


def _pow2_ceil(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length()


class ServingEngine:
    """Continuous-batching engine over the deployed quantized model.

    ``model`` is a :class:`DeployedModel` (plan included), or a raw params
    tree with ``plan`` passed explicitly. ``max_queue`` bounds the pending
    queue (``submit`` raises :class:`QueueFullError` past it).
    """

    def __init__(self, model, plan: Optional[ExecutionPlan] = None, *,
                 slots: int = 8, max_len: int = 512,
                 max_queue: Optional[int] = None,
                 metrics: Optional[ServeMetrics] = None,
                 clock: Clock = SYSTEM_CLOCK,
                 tenant: Optional[str] = None,
                 kv_budget_bytes: Optional[int] = None,
                 warmup: bool = False):
        if isinstance(model, DeployedModel):
            if plan is not None and plan != model.plan:
                raise ValueError(
                    "pass either a DeployedModel (plan included) or raw "
                    "params + plan, not a conflicting pair")
            params, plan = model.params, model.plan
        else:
            params = model
            if plan is None:
                raise TypeError("raw params need an ExecutionPlan; build one "
                                "with repro.deploy.ExecutionPlan.build")
        self.plan = plan
        self.cfg = cfg = plan.cfg
        self.segments = segments = plan.segments
        # tensor-parallel serving (DESIGN.md §16): a tp>1 plan owns a
        # ("model",) mesh; weights/KV are partitioned over it. deploy()
        # already places DeployedModel params, so re-placing is a no-op
        # there — this covers the raw params + plan constructor form.
        self.mesh = plan.make_mesh()
        if self.mesh is not None:
            from ..distributed.sharding import (place_serving,
                                                serving_param_specs)
            params = place_serving(params, self.mesh,
                                   serving_param_specs(params))
        self.params = params
        self.slots = slots
        self.max_len = max_len
        self.mode = plan.mode                 # "decode" | "encoder"
        self.tenant = tenant                  # metrics label (DESIGN.md §14)
        self.dtype = plan.jnp_dtype           # the ONE serving decode dtype
        self.kv_bits = plan.kv_bits
        self.prefill_mode = plan.prefill_mode
        self.prefill_batch = max(1, plan.prefill_batch)
        self.default_sampling = (plan.default_sampling
                                 if plan.default_sampling is not None
                                 else SamplingParams())
        # ONE clock for the whole serving stack (DESIGN.md §12): deadline
        # shedding, TTFT/queue-wait stamps, and step timings all read it, so
        # injecting a VirtualClock makes every timing path deterministic.
        self.clock = clock
        self.scheduler = Scheduler(slots, max_queue=max_queue, clock=clock)
        self.metrics = (metrics if metrics is not None
                        else ServeMetrics(clock=clock))
        self.generated: list[list[int]] = [[] for _ in range(slots)]
        self._streams: dict = {}              # rid -> TokenStream|EncodeHandle
        self._events: list[tuple[int, int]] = []
        # per-step work counters, reset by engine_step: the multi-tenant
        # deficit accounting and the virtual-cost model read them after
        # each pump (DESIGN.md §14).
        self.last_step_tokens = 0
        self.last_step_encode_tokens = 0
        # per-slot sampling state, threaded into the jitted step alongside
        # the decode state (DESIGN.md §10): seed/temperature/top_k/top_p are
        # set at admit; the step index is the slot's generated-token count.
        self._seed = np.zeros(slots, np.int32)
        self._temp = np.zeros(slots, np.float32)
        self._topk = np.zeros(slots, np.int32)
        self._topp = np.ones(slots, np.float32)

        self.prefix_cache: Optional[PrefixCache] = None
        self._prefix_refs: dict[int, tuple] = {}   # rid -> pinned block keys
        self._encode_fns: dict[tuple, callable] = {}
        # paged KV layout (DESIGN.md §15): plan.kv_paging='paged' routes the
        # slot cache through the refcounted block pool
        self.paged = plan.kv_paging == "paged"
        self.pool: Optional[BlockPool] = None
        self._prefix_on = False    # paged-mode prefix registry switch
        self._reserved = 0         # blocks reserved within one admit round
        self._next_fork = 0        # fork-group ids for n>1 fanout
        if kv_budget_bytes is not None and not self.paged:
            raise ValueError(
                "kv_budget_bytes applies to kv_paging='paged' plans only "
                "(the dense layout preallocates slots*max_len rows)")
        if self.mode == "encoder":
            # prefill-only: no KV retained across steps, no decode state —
            # every request resolves inside the step that admits it.
            self.kv = None
            self.state = None
        elif self.prefill_mode == "chunked":
            self.state = None
            self._prefill_fns: dict[tuple, callable] = {}
            self._chunk_fns: dict[tuple, callable] = {}
            if self.paged:
                if max_len % PREFIX_BLOCK:
                    raise ValueError(
                        f"kv_paging='paged' needs max_len % {PREFIX_BLOCK} "
                        f"== 0 (block granularity), got {max_len}")
                block_bytes = PREFIX_BLOCK * cfg.num_layers * kv_row_bytes(
                    cfg.num_kv_heads, cfg.hd, self.kv_bits,
                    fp_bytes=jnp.dtype(self.dtype).itemsize)
                if kv_budget_bytes is None:
                    # dense-equivalent default: exactly the bytes the dense
                    # layout would preallocate, so flipping kv_paging alone
                    # changes the layout, never the capacity
                    kv_budget_bytes = (slots * (max_len // PREFIX_BLOCK)
                                       * block_bytes)
                self.pool = BlockPool(cfg, kv_budget_bytes, dtype=self.dtype,
                                      kv_bits=self.kv_bits, mesh=self.mesh)
                self.kv = PagedKVCache(self.pool, slots, max_len)
                # plan.prefix_cache > 0 switches prefix reuse on; the BYTE
                # value is absorbed by the pool budget (the registry shares
                # the pool's blocks instead of owning a second store)
                self._prefix_on = plan.prefix_cache > 0
            else:
                self.kv = SlotKVCache.from_plan(plan, slots, max_len,
                                                mesh=self.mesh)
                if plan.prefix_cache:
                    self.prefix_cache = PrefixCache(plan.prefix_cache)
        else:
            self.kv = None
            self.state = self._place_state(plan.decode_state(slots, max_len))
            self.pos = np.zeros(slots, np.int32)   # per-slot prompt cursor
            self._cursor = 0   # host mirror of the SHARED token-mode cursor

        def step(params, state, tokens, seeds, steps, temps, top_ks, top_ps):
            logits, new_state, _, _ = model_api.forward(
                params, cfg, segments, state=state, tokens=tokens)
            toks = sample_batch(logits[:, -1], seeds, steps, temps,
                                top_ks, top_ps)
            return toks, new_state

        self._step = jax.jit(step, donate_argnums=(1,))
        self._sample1 = jax.jit(sample_token)   # prefill's first token
        if warmup:
            self._warmup()

    def _place_state(self, state):
        """Partition a freshly allocated decode state over the tp mesh
        (no-op at tp=1)."""
        if self.mesh is None:
            return state
        from ..distributed.sharding import place_serving, serving_state_specs
        return place_serving(state, self.mesh,
                             serving_state_specs(state, self.mesh))

    def _warmup(self) -> None:
        """Pre-populate the (bucket, n) compile-key caches before traffic
        arrives (DESIGN.md §16): every prefill/encode bucket on the ladder
        (8, 16, ... max_len doubling) times every power-of-two group size up
        to ``prefill_batch``, plus the decode step. Each jitted function is
        actually CALLED on throwaway zeros — ``lower().compile()`` would not
        populate the pjit call cache — and the decode step is warmed against
        a THROWAWAY state, never the live (donated) cache. Nothing is
        recorded in metrics: the first *real* step's latency then shows the
        steady-state cost, which is exactly what the first-vs-steady metric
        split exists to surface."""
        buckets, b = [], 8
        while b < self.max_len:
            buckets.append(b)
            b *= 2
        buckets.append(self.max_len)
        ns, n = [], 1
        while n <= self.prefill_batch:
            ns.append(n)
            n *= 2
        if self.mode == "encoder":
            for bucket in buckets:
                for n in ns:
                    self._encode_fn(bucket, n)(
                        self.params, jnp.zeros((n, bucket), jnp.int32),
                        jnp.ones(n, jnp.int32))
            return
        if self.prefill_mode != "chunked":
            # token mode: one compile key — the batched step itself; warmed
            # below with the throwaway state
            state = self._place_state(
                self.plan.decode_state(self.slots, self.max_len))
        else:
            for bucket in buckets:
                for n in ns:
                    self._prefill_fn(bucket, n)(
                        self.params, jnp.zeros((n, bucket), jnp.int32))
            if (self.paged and self._prefix_on) \
                    or self.prefix_cache is not None:
                B = self.pool.block if self.paged else self.prefix_cache.block
                for bucket in buckets:
                    S = -(-bucket // B) * B
                    for n in ns:
                        self._chunk_fn(S, n)(
                            self.params, self.plan.decode_state(n, S),
                            jnp.zeros((n, B), jnp.int32))
            if self.paged:
                # the live decode input IS a gathered view; gathering the
                # (empty, sentinel-clamped) tables warms both the gather and
                # the step on exactly the avals decode will present
                state = self.kv.gather_state()
            else:
                state = self._place_state(self.plan.decode_state(
                    self.slots, self.max_len, per_slot_len=True))
        self._step(self.params, state, jnp.zeros((self.slots, 1), jnp.int32),
                   self._seed, self._gen_steps(), self._temp, self._topk,
                   self._topp)

    # ------------------------------------------------------------------ API
    def submit(self, req: GenerationRequest, *,
               on_token: Optional[Callable[[int, int], None]] = None
               ) -> TokenStream:
        """Validate + enqueue; returns the request's :class:`TokenStream`
        (iterate it, or pass ``on_token`` for the callback form). Malformed
        requests are rejected HERE, for both prefill modes — by decode time
        the bad prompt would have been scattered into the cache (or indexed
        at [-1]) already.

        ``sampling.n > 1`` fans out into ``n`` independent child requests
        (sample ``i`` decodes with seed ``api.sample_seed(seed, i)``) and
        returns a LIST of ``n`` streams instead of one. On a paged engine
        the children share the prompt's KV blocks copy-on-write; on a dense
        engine they expand into plain slots — the streams are identical
        either way. A ``QueueFullError`` mid-fanout propagates; children
        already enqueued stay queued (cancel them by rid if unwanted)."""
        if self.mode == "encoder":
            raise ValueError(
                "this engine serves a mode='encoder' plan: no decode loop "
                "exists; submit EncodeRequests via submit_encode")
        self.scheduler.assign_id(req)      # so rejections carry a real rid
        plen = len(req.prompt)
        if plen <= 0:
            raise ValueError(f"request {req.rid}: empty prompt")
        if plen + req.max_new_tokens > self.max_len and \
                self.cfg.family != "xlstm":
            # past max_len the cache writes clamp or drop silently — decode
            # would keep emitting tokens that cannot see recent context.
            # (xlstm state is recurrent: no positional cache to overflow.
            # Token mode's shared cursor additionally gates ADMISSION on the
            # live cursor — see _token_fits — so steady-state slot refills
            # can no longer walk the cursor past max_len.)
            raise ValueError(
                f"request {req.rid}: prompt ({plen}) + max_new_tokens "
                f"({req.max_new_tokens}) exceeds engine max_len "
                f"({self.max_len})")
        if self.paged:
            need = blocks_needed(plen, req.max_new_tokens)
            if need > self.pool.num_blocks:
                raise ValueError(
                    f"request {req.rid}: needs {need} KV blocks but the "
                    f"pool budget holds {self.pool.num_blocks} total — "
                    "raise kv_budget_bytes or shrink the request")
        req.sampling = SamplingParams.resolve(
            req.sampling if req.sampling is not None
            else self.default_sampling)
        sp = req.sampling
        if sp.n > 1:
            gid = self._next_fork
            self._next_fork += 1
            streams = []
            for i in range(sp.n):
                child = dataclasses.replace(
                    req,
                    sampling=dataclasses.replace(
                        sp, n=1, seed=sample_seed(sp.seed, i)),
                    rid=-1, out=None, finish_reason=None)
                child.fork_group = gid
                child.sample_index = i
                streams.append(self.submit(child, on_token=on_token))
            return streams
        stream = TokenStream(self, req, on_token=on_token)
        self._streams[req.rid] = stream
        try:
            self.scheduler.submit(req)     # may raise QueueFullError
        except Exception:
            self._streams.pop(req.rid, None)
            raise
        return stream

    def submit_encode(self, req: EncodeRequest, *,
                      on_result: Optional[Callable[[int, object], None]] = None
                      ) -> EncodeHandle:
        """Enqueue a prefill-only request (DESIGN.md §14). Shares the
        scheduler — priority heap, bounded queue, deadline shed, cancel —
        with generation traffic; the result lands on the returned
        :class:`EncodeHandle`. Task support is family-shaped: an encoder
        plan serves classify/embed/score from its heads, while a decode
        engine serves ``score`` only (prompt log-likelihood through the
        same batched bucketed prefill path)."""
        self.scheduler.assign_id(req)      # so rejections carry a real rid
        plen = len(req.tokens)
        if plen <= 0:
            raise ValueError(f"request {req.rid}: empty input")
        if plen > self.max_len:
            raise ValueError(
                f"request {req.rid}: input ({plen}) exceeds engine max_len "
                f"({self.max_len})")
        if self.mode == "encoder":
            needs = ("classifier",) if req.task in ("classify", "score") \
                else ("pooler",)
            for head in needs:
                if head not in self.params:
                    raise ValueError(
                        f"request {req.rid}: task={req.task!r} needs a "
                        f"{head!r} head the deployed artifact does not have")
        else:
            if self.prefill_mode != "chunked":
                raise ValueError(
                    f"request {req.rid}: token-mode engines feed prompts "
                    "through a shared cursor and cannot serve prefill-only "
                    "requests")
            if req.task != "score":
                raise ValueError(
                    f"request {req.rid}: a decoder artifact serves only "
                    f"task='score' (prompt log-likelihood), got "
                    f"{req.task!r}")
        handle = EncodeHandle(self, req, on_result=on_result)
        self._streams[req.rid] = handle
        try:
            self.scheduler.submit(req)     # may raise QueueFullError
        except Exception:
            self._streams.pop(req.rid, None)
            raise
        return handle

    def cancel(self, rid: int) -> bool:
        """Cancel a queued or mid-flight request. An occupied slot is freed
        immediately — its KV rows are zeroed and its cursor rewound — so the
        next ``engine_step`` can admit queued work into it. Tokens already
        generated stay on ``req.out`` (truncated to ``max_new_tokens``, like
        every other exit); ``finish_reason`` becomes ``'cancelled'``.
        Returns False when ``rid`` is unknown or already finished."""
        req = self.scheduler.cancel(rid)
        if req is not None:                      # still queued: never ran
            self._finalize_unslotted(req, "cancelled")
            return True
        for s, req in enumerate(self.scheduler.active):
            if req is not None and req.rid == rid:
                self._finalize_slotted(s, req, "cancelled")
                if self.kv is not None:
                    self.kv.reset_slot(s)        # free the KV state now
                return True
        return False

    def pop_done(self) -> list[GenerationRequest]:
        """Drain completed requests (see ``Scheduler.pop_done``)."""
        return self.scheduler.pop_done()

    @property
    def done(self) -> list[GenerationRequest]:
        return self.scheduler.done

    @property
    def queue(self):
        return self.scheduler.queue

    @property
    def active(self):
        return self.scheduler.active

    def run_until_drained(self, max_steps: int = 10000) -> int:
        """Pump ``engine_step`` until no work remains; raises RuntimeError
        instead of silently stranding requests when ``max_steps`` hits."""
        steps = 0
        while self.scheduler.has_work:
            if steps >= max_steps:
                q = self.scheduler.queue_depth
                a = self.scheduler.num_active
                raise RuntimeError(
                    f"run_until_drained: hit max_steps={max_steps} with "
                    f"{q + a} request(s) stranded ({q} queued, {a} active)")
            self.engine_step()
            steps += 1
        return steps

    def engine_step(self) -> list[tuple[int, int]]:
        """The public pump: one admit → prefill → batched-decode round.
        Returns the ``(rid, token)`` pairs emitted this step (streams and
        callbacks are fed from inside)."""
        with self.metrics.span("serve/step"):
            self._events = []
            self.last_step_tokens = 0
            self.last_step_encode_tokens = 0
            if self.mode == "encoder":
                self._encoder_step()
            elif self.prefill_mode == "chunked":
                self._chunked_step()
            else:
                self._token_step()
            for req in self.scheduler.pop_shed():
                self._finalize_unslotted(req, "shed")
            if self.paged:
                self.metrics.update_kv(self.pool.stats())
        return self._events

    # ------------------------------------------------------------ lifecycle
    def _admit(self, fits: Optional[Callable] = None
               ) -> list[tuple[int, "GenerationRequest"]]:
        """Scheduler admit + per-slot sampling-state install + queue-wait
        metric. Clears the slot's stale token tally up front, so a cancel
        landing between admission and prefill cannot report the previous
        occupant's tokens."""
        with self.metrics.span("serve/admit"):
            placed = self.scheduler.admit(fits=fits)
            for s, req in placed:
                self.generated[s] = []
                sp = getattr(req, "sampling", None)  # EncodeRequests: none
                if sp is not None:
                    self._seed[s] = np.int32(sp.seed & 0x7FFFFFFF)
                    self._temp[s] = sp.temperature
                    self._topk[s] = sp.top_k
                    self._topp[s] = sp.top_p
                if req.queue_wait_s is not None:
                    self.metrics.record_wait("queue_wait", req.queue_wait_s,
                                             tenant=self.tenant)
        return placed

    def _emit(self, req: GenerationRequest, token: int) -> None:
        if req.first_token_t is None:
            req.first_token_t = self.clock()
            if req.ttft_s is not None:
                self.metrics.record_wait("ttft", req.ttft_s,
                                         tenant=self.tenant)
        stream = self._streams.get(req.rid)
        if stream is not None:
            stream._push(token)
        self._events.append((req.rid, token))

    def _close_stream(self, req: GenerationRequest) -> None:
        stream = self._streams.pop(req.rid, None)
        if stream is not None:
            stream._finish()

    def _release_prefix(self, req: GenerationRequest) -> None:
        keys = self._prefix_refs.pop(req.rid, None)
        if keys and self.prefix_cache is not None:
            self.prefix_cache.release(keys)

    def _finalize_unslotted(self, req, reason: str) -> None:
        """Finish a request that never occupied a slot (queued-cancel or
        deadline shed): empty output, straight to done."""
        if isinstance(req, EncodeRequest):
            req.result = None
        else:
            req.out = np.zeros(0, np.int32)
        req.finish_reason = reason
        req.finish_t = self.clock()
        self.scheduler.done.append(req)
        self._release_prefix(req)
        self._close_stream(req)

    def _finalize_slotted(self, slot: int, req, reason: str) -> None:
        """The ONE exit path for slotted requests (length/stop/cancel):
        output truncated to the request's own ``max_new_tokens``, slot
        returned to the scheduler, prefix pins released, stream closed.
        Encode requests hold a slot only within the step that admits them;
        their result (set by ``_encode_group``, None if cancelled first)
        rides on the request itself."""
        if not isinstance(req, EncodeRequest):
            req.out = np.array(self.generated[slot][:req.max_new_tokens],
                               np.int32)
        if self.paged:
            # drop every block reference the request holds (shared blocks
            # survive under their other holders / the prefix registry)
            self.kv.release_slot(slot)
        req.finish_reason = reason
        req.finish_t = self.clock()
        self.scheduler.complete(slot)
        self._release_prefix(req)
        self._close_stream(req)

    def _maybe_complete(self, slot: int, req: GenerationRequest) -> None:
        toks = self.generated[slot]
        if toks and toks[-1] in req.stop_tokens:
            self._finalize_slotted(slot, req, "stop")  # stop token stays
        elif len(toks) >= req.max_new_tokens:
            self._finalize_slotted(slot, req, "length")

    # ------------------------------------------------------------- chunked
    def _prefill_fn(self, bucket: int, n: int):
        """Batch-n full-prompt forward on an fp scratch cache, compiled once
        per (bucket, n) — n is the power-of-two padded group size."""
        fn = self._prefill_fns.get((bucket, n))
        if fn is None:
            cfg, segments, plan = self.cfg, self.segments, self.plan

            def pf(params, tokens):
                # prefill always runs on the fp cache regardless of
                # plan.kv_bits; quantization happens on slot insert
                st = plan.decode_state(n, bucket, kv_bits=16)
                logits, st2, _, _ = model_api.forward(
                    params, cfg, segments, state=st, tokens=tokens)
                return logits, st2

            fn = self._prefill_fns[(bucket, n)] = jax.jit(pf)
        return fn

    def _chunk_fn(self, scratch_len: int, n: int):
        """One prefix-block forward over the plan-precision scratch cache
        (DESIGN.md §11), compiled once per (scratch_len, n) — scratch_len is
        the bucket rounded up to the block grid, so the key space matches
        the bucket ladder. Suffix tokens attend the quantized rows of every
        EARLIER block (exactly what a prefix hit restores) and fp rows
        within their own block; the new block's rows quantize on append via
        models/transformer.write_new_kv."""
        fn = self._chunk_fns.get((scratch_len, n))
        if fn is None:
            cfg, segments = self.cfg, self.segments

            def cf(params, state, tokens):
                logits, st2, _, _ = model_api.forward(
                    params, cfg, segments, state=state, tokens=tokens)
                return logits, st2

            fn = self._chunk_fns[(scratch_len, n)] = jax.jit(
                cf, donate_argnums=(1,))
        return fn

    def _sample_first(self, logits_row, slot: int) -> int:
        with self.metrics.span("serve/sample/readback"):
            return int(np.asarray(self._sample1(
                logits_row, self._seed[slot], np.int32(0), self._temp[slot],
                self._topk[slot], self._topp[slot])))

    def _emit_first_tokens(self, group, firsts) -> None:
        for (s, req), first in zip(group, firsts):
            if self.scheduler.active[s] is not req:
                continue   # an earlier emit's callback cancelled it
            self.generated[s] = [first]
            self._emit(req, first)
            if self.scheduler.active[s] is req:   # ... or a self-cancel
                self._maybe_complete(s, req)

    def _prefill_admitted(self, placed) -> None:
        """Group this round's admissions and prefill each group in one
        forward. The group key is (bucket, prefix-hit length, prefix block
        keys): same-bucket requests sharing a cached prefix (or sharing
        none) batch together; ``prefill_batch`` caps the group size."""
        jobs = []
        for s, req in placed:
            plen = len(req.prompt)
            bucket = _bucket_for(plen, self.max_len)
            m, keys = 0, ()
            if self.paged:
                self.kv.open_slot(s, req.rid)
                if self._prefix_on:
                    # registry hit: attach resident blocks BY REFERENCE —
                    # refcount++ pins them, no row copy ever happens
                    m, ids = self.pool.match(req.prompt)
                    if m:
                        self.pool.attach(req.rid, ids)
                        self.kv.extend_table(s, ids)
                    keys = tuple(ids)
                    self.metrics.record_prefix(m, plen)
            elif self.prefix_cache is not None:
                m, keys = self.prefix_cache.match(req.prompt)
                self._prefix_refs[req.rid] = keys
                self.metrics.record_prefix(m, plen)
            jobs.append((s, req, bucket, m, keys))
        groups = group_admits(jobs, key_fn=lambda j: (j[2], j[3], j[4]),
                              max_batch=self.prefill_batch)
        blocks_path = (self._prefix_on if self.paged
                       else self.prefix_cache is not None)
        for (bucket, m, keys), members in groups:
            group = [(s, req) for s, req, *_ in members
                     if self.scheduler.active[s] is req]
            if not group:      # cancelled by a callback mid-round
                continue
            if blocks_path:
                self._prefill_group_blocks(bucket, m, keys, group)
            else:
                self._prefill_group(bucket, group)

    def _prefill_group(self, bucket: int, group) -> None:
        """One batch-n fp forward covering every request in ``group``; each
        request's first token samples from its own logits row and its KV
        rows scatter (quantize-on-insert) into its own slot."""
        n = _pow2_ceil(len(group))
        span = self.metrics.span
        with span("serve/prefill/pack"):
            toks = np.zeros((n, bucket), np.int32)
            for i, (s, req) in enumerate(group):
                toks[i, :len(req.prompt)] = req.prompt
            t0 = self.clock()
            toks = jnp.asarray(toks)
        with span("serve/prefill/dispatch"):
            logits, pstate = self._prefill_fn(bucket, n)(self.params, toks)
        firsts = []
        total = 0
        fork_leaders: dict = {}
        # the first tokens' blocking reads (serve/sample/readback inside)
        # and the slot inserts
        with span("serve/prefill/readback"):
            for i, (s, req) in enumerate(group):
                plen = len(req.prompt)
                total += plen
                firsts.append(self._sample_first(logits[i, plen - 1], s))
                if self.paged:
                    self._paged_insert_fp(s, req, pstate, i, fork_leaders)
                else:
                    self.kv.reset_slot(s)
                    self.kv.insert_prefill(s, pstate, plen, bucket, row=i)
        self.metrics.record("prefill", self.clock() - t0, total,
                            tenant=self.tenant)
        self.last_step_tokens += total
        self._emit_first_tokens(group, firsts)

    def _prefill_group_blocks(self, bucket: int, m: int, keys, group) -> None:
        """Prefix-reuse prefill (DESIGN.md §11): restore the ``m`` cached
        prefix tokens (codes + scales copy straight into the scratch cache,
        no requantization) and compute only the suffix, one prefix block per
        forward so hit and cold runs attend bit-identical rows.

        Serves both layouts: dense restores host rows from the PrefixCache
        store; paged gathers the resident pool blocks on device (same
        values — the pool's blocks hold exactly the rows a dense publish
        would have copied out)."""
        B = self.pool.block if self.paged else self.prefix_cache.block
        n = _pow2_ceil(len(group))
        span = self.metrics.span
        t0 = self.clock()
        with span("serve/prefill/pack"):
            # scratch capacity on the BLOCK grid: a bucket capped at a
            # non-multiple-of-B max_len would make the last chunk's write
            # run past the buffer, where dynamic_update_slice clamps the
            # start and silently overwrites real rows with padding. Rounding
            # up keeps every chunk write in-bounds; the slot insert below
            # copies only the first min(S, max_len) rows back out.
            S = -(-bucket // B) * B
            state = self.plan.decode_state(n, S)
            if m:
                if self.paged:
                    rows = self.pool.gather_rows(list(keys))
                else:
                    rows = {key: jnp.asarray(val) for key, val
                            in self.prefix_cache.gather(keys).items()}
                state = {key: (val if key == "len" else
                               val.at[:, :, :m].set(rows[key][:, None]))
                         for key, val in state.items()}
                state["len"] = jnp.asarray(m, jnp.int32)
            max_plen = max(len(req.prompt) for _, req in group)
            n_chunks = -(-(max_plen - m) // B)
            toks = np.zeros((n, n_chunks * B), np.int32)
            for i, (s, req) in enumerate(group):
                toks[i, :len(req.prompt) - m] = req.prompt[m:]
        first_logits = [None] * len(group)
        with span("serve/prefill/dispatch"):
            fn = self._chunk_fn(S, n)
            for c in range(n_chunks):
                logits, state = fn(self.params, state,
                                   jnp.asarray(toks[:, c * B:(c + 1) * B]))
                for i, (s, req) in enumerate(group):
                    ci, pi = divmod(len(req.prompt) - 1 - m, B)
                    if ci == c:    # this chunk holds the request's last token
                        first_logits[i] = logits[i, pi]
        firsts = []
        total = 0
        copy = min(S, self.max_len)     # slot rows past plen stay masked
        fork_leaders: dict = {}
        with span("serve/prefill/readback"):
            for i, (s, req) in enumerate(group):
                plen = len(req.prompt)
                total += plen - m
                firsts.append(self._sample_first(first_logits[i], s))
                if self.paged:
                    self._paged_insert_state(s, req, state, i, m,
                                             fork_leaders)
                    self._paged_publish(req)
                else:
                    self.kv.reset_slot(s)
                    self.kv.insert_rows(s, state, plen, copy, row=i)
                    self._publish_prefix(req, m, state, i)
        self.metrics.record("prefill", self.clock() - t0, total,
                            tenant=self.tenant)
        self.last_step_tokens += total
        self._emit_first_tokens(group, firsts)

    def _publish_prefix(self, req: GenerationRequest, m: int, state,
                        row: int) -> None:
        """Insert the request's newly computed full blocks into the prefix
        cache (lazy device→host copy: hits never pay it)."""
        plen = len(req.prompt)
        upto = (plen // self.prefix_cache.block) * self.prefix_cache.block
        if upto <= m:
            return
        buf_keys = kv_buffer_keys(self.kv.kv_bits)
        host: dict = {}

        def rows_for_block(lo, hi):
            if not host:
                host.update({key: np.asarray(state[key][:, row])
                             for key in buf_keys})
            return {key: host[key][:, lo:hi].copy() for key in buf_keys}

        self.prefix_cache.insert(req.prompt, upto, rows_for_block)

    # --------------------------------------------------------------- paged
    def _paged_fits(self, req) -> bool:
        """Admission predicate (DESIGN.md §15): a request admits only if
        its WORST-CASE block need — every prompt + generated token, whole
        blocks — fits in free + evictable pool blocks, minus what this
        round's earlier admissions already reserved. Prefix hits only ever
        reduce the blocks actually allocated, so a reservation can never be
        exceeded. Encode requests retain no KV and always fit."""
        if isinstance(req, EncodeRequest):
            return True
        need = blocks_needed(len(req.prompt), req.max_new_tokens)
        if self.pool.available() - self._reserved < need:
            return False
        self._reserved += need
        return True

    def _fork_share(self, slot: int, req, fork_leaders: dict, lo: int,
                    nb_full: int) -> int:
        """Copy-on-write fork bookkeeping for one prefill-group member.

        The first member of a fork group in this prefill group is the
        leader (recorded); later members attach the leader's FULL prompt
        blocks ``[lo, nb_full)`` by reference and only write their own tail
        block + decode blocks — prompt KV is stored once per group, decode
        divergence stays private. (Fork members split across prefill groups
        fall back to private blocks here; with the prefix registry on they
        still converge to shared blocks via ``match`` on later arrivals.)
        Returns the first block index this member must WRITE itself."""
        if req.fork_group is None:
            return lo
        leader = fork_leaders.get(req.fork_group)
        if leader is None or leader[1] != len(req.prompt):
            fork_leaders[req.fork_group] = (slot, len(req.prompt))
            return lo
        share = self.kv.block_ids(leader[0])[lo:nb_full]
        if not share:
            return lo
        self.pool.attach(req.rid, share)
        self.kv.extend_table(slot, share)
        self.pool.cow_forks += 1
        return nb_full

    def _paged_insert_fp(self, slot: int, req, pstate, row: int,
                         fork_leaders: dict) -> None:
        """Paged analogue of ``insert_prefill``: allocate the request's
        worst-case block need up front (admission already reserved it) and
        write the prompt blocks from the fp prefill row, quantize-on-insert
        at kv_bits < 16. Decode blocks are allocated NOW, written later by
        ``append_from`` — a request can never run out of KV mid-decode."""
        B = self.pool.block
        plen = len(req.prompt)
        nb_full, nb_fill = plen // B, -(-plen // B)
        start = self._fork_share(slot, req, fork_leaders, 0, nb_full)
        own = self.pool.alloc(req.rid,
                              blocks_needed(plen, req.max_new_tokens) - start)
        self.kv.extend_table(slot, own)
        write_n = nb_fill - start
        if write_n:
            self.kv.write_fp_blocks(own[:write_n], pstate, row, start,
                                    write_n)
        self.kv.set_length(slot, plen)

    def _paged_insert_state(self, slot: int, req, state, row: int, m: int,
                            fork_leaders: dict) -> None:
        """Paged analogue of ``insert_rows`` (the prefix-chunked path):
        blocks ``[0, m/B)`` are already attached by reference, so only the
        computed-suffix blocks copy out of the plan-precision scratch —
        same precision, no requantization."""
        B = self.pool.block
        plen = len(req.prompt)
        nb_full, nb_fill = plen // B, -(-plen // B)
        start = self._fork_share(slot, req, fork_leaders, m // B, nb_full)
        own = self.pool.alloc(req.rid,
                              blocks_needed(plen, req.max_new_tokens) - start)
        self.kv.extend_table(slot, own)
        write_n = nb_fill - start
        if write_n:
            self.kv.write_state_blocks(own[:write_n], state, row, start * B,
                                       write_n)
        self.kv.set_length(slot, plen)

    def _paged_publish(self, req) -> None:
        """Register the request's full prompt blocks in the pool's prefix
        registry (pure bookkeeping — the blocks ARE the cache; no device→
        host copy, the dense path's lazy-copy publish disappears)."""
        if not self._prefix_on:
            return
        plen = len(req.prompt)
        upto = (plen // self.pool.block) * self.pool.block
        if upto:
            self.pool.publish(req.rid, req.prompt, upto)

    # -------------------------------------------------------------- encode
    def _encode_fn(self, bucket: int, n: int):
        """Batch-n prefill-only forward, compiled once per (bucket, n) —
        the same compile-key space as ``_prefill_fn``. Encoder plans run the
        bidirectional stack with per-row length masking (bucket padding
        stays bit-exact, see serving/encoder.py) and return every head the
        artifact carries; decode plans return the prompt log-likelihood
        (causal attention, so padded tails are free) as ``score``."""
        fn = self._encode_fns.get((bucket, n))
        if fn is None:
            cfg, segments, plan = self.cfg, self.segments, self.plan
            if self.mode == "encoder":
                has_cls = "classifier" in self.params

                def ef(params, tokens, lengths):
                    h, _ = bert_encode(params, cfg, segments, tokens,
                                       lengths=lengths)
                    out = {"embed": bert_pool(params, h)}
                    if has_cls:
                        logits = (out["embed"] @ params["classifier"]["w"]
                                  + params["classifier"]["b"])
                        logp = jax.nn.log_softmax(
                            logits.astype(jnp.float32), axis=-1)
                        out["classify"] = logits
                        # relevance score: positive-class log-probability
                        out["score"] = (logp[:, 1] if logits.shape[-1] >= 2
                                        else logp[:, 0])
                    return out
            else:
                def ef(params, tokens, lengths):
                    st = plan.decode_state(n, bucket, kv_bits=16)
                    logits, _, _, _ = model_api.forward(
                        params, cfg, segments, state=st, tokens=tokens)
                    logp = jax.nn.log_softmax(
                        logits.astype(jnp.float32), axis=-1)
                    ll = jnp.take_along_axis(
                        logp[:, :-1], tokens[:, 1:, None], -1)[..., 0]
                    mask = (jnp.arange(bucket - 1)[None, :] + 1
                            < lengths[:, None])
                    return {"score": jnp.sum(jnp.where(mask, ll, 0.0),
                                             axis=1)}

            fn = self._encode_fns[(bucket, n)] = jax.jit(ef)
        return fn

    def _encode_admitted(self, placed) -> None:
        """Group this round's encode admissions by bucket and run each
        group as one forward (``prefill_batch`` caps the group size, n pads
        to a power of two — the PR-5 grouping, reused verbatim)."""
        jobs = [(s, req, _bucket_for(len(req.tokens), self.max_len))
                for s, req in placed]
        groups = group_admits(jobs, key_fn=lambda j: j[2],
                              max_batch=self.prefill_batch)
        for bucket, members in groups:
            group = [(s, req) for s, req, _ in members
                     if self.scheduler.active[s] is req]
            if not group:      # cancelled by a callback mid-round
                continue
            self._encode_group(bucket, group)

    def _encode_group(self, bucket: int, group) -> None:
        """One batched forward; every request resolves (and frees its slot)
        before this returns — encode requests never outlive their step."""
        n = _pow2_ceil(len(group))
        total = sum(len(req.tokens) for _, req in group)
        span = self.metrics.span
        with span("serve/encode/group", bucket=bucket, rows=n, useful=total):
            with span("serve/encode/pack"):
                toks = np.zeros((n, bucket), np.int32)
                lens = np.ones(n, np.int32)  # padding rows: length-1, masked
                for i, (s, req) in enumerate(group):
                    toks[i, :len(req.tokens)] = req.tokens
                    lens[i] = len(req.tokens)
                t0 = self.clock()
                toks, lens = jnp.asarray(toks), jnp.asarray(lens)
            with span("serve/encode/dispatch"):
                out = self._encode_fn(bucket, n)(self.params, toks, lens)
            with span("serve/encode/readback"):   # the host blocks here
                # only the heads the group asked for, their copies started
                # together: each blocking copy is a round trip to the device
                tasks = {req.task for _, req in group}
                out = jax.device_get({t: out[t] for t in tasks})
            self.metrics.record("encode", self.clock() - t0, total,
                                tenant=self.tenant)
            self.metrics.count("encode_arrays_read", len(out))
            self.metrics.count("encode_tokens_useful", total)
            self.metrics.count("encode_tokens_computed", n * bucket)
            self.last_step_encode_tokens += total
            self.last_step_tokens += total
            with span("serve/encode/finalize"):
                for i, (s, req) in enumerate(group):
                    if self.scheduler.active[s] is not req:
                        continue   # an earlier on_result callback cancelled
                    req.result = out[req.task][i]
                    self._finalize_slotted(s, req, "done")

    def _encoder_step(self) -> None:
        """mode='encoder': the whole step is admit + batched encode — there
        is no decode phase and no KV to carry forward."""
        placed = self._admit()
        if placed:
            self._encode_admitted(placed)

    def _gen_steps(self) -> np.ndarray:
        """Per-slot index of the NEXT generated token (the sampling step fed
        to ``fold_in``), so token i of a request always draws from the same
        key regardless of batch composition."""
        return np.array([len(self.generated[s]) for s in range(self.slots)],
                        np.int32)

    def _chunked_step(self) -> None:
        fits = None
        if self.paged:
            # ONE byte budget drives admission: reservations are per-round
            # (prefill below turns them into real allocations)
            self._reserved = 0
            fits = self._paged_fits
        placed = self._admit(fits=fits)
        if placed:
            # encode and generation traffic arrive through one admit round:
            # encode jobs resolve immediately (freeing their slots), then
            # the generation jobs prefill and join the decode batch below.
            enc = [(s, r) for s, r in placed if isinstance(r, EncodeRequest)]
            gen = [(s, r) for s, r in placed
                   if not isinstance(r, EncodeRequest)]
            if enc:
                self._encode_admitted(enc)
            if gen:
                self._prefill_admitted(gen)
        active = self.scheduler.active_slots()
        if not active:
            return
        toks = np.zeros((self.slots, 1), np.int32)
        for s in active:
            toks[s, 0] = self.generated[s][-1]
        t0 = self.clock()
        with self.metrics.span("serve/decode/dispatch"):
            if self.paged:
                # block-table indirection for the jnp reference path: gather
                # a dense-shaped view and feed the SAME jitted step the dense
                # layout compiled — garbage rows from table padding are
                # masked to exact zeros inside the attention (DESIGN.md §15),
                # so the streams stay bit-identical. The step writes each
                # slot's new row into the (donated) view; append_from
                # scatters it back to the pool block its table maps that
                # position to.
                state = self.kv.gather_state()
                next_tok, new_state = self._step(
                    self.params, state, jnp.asarray(toks),
                    self._seed, self._gen_steps(), self._temp, self._topk,
                    self._topp)
                self.kv.append_from(new_state, active)
            else:
                next_tok, self.kv.state = self._step(
                    self.params, self.kv.state, jnp.asarray(toks),
                    self._seed, self._gen_steps(), self._temp, self._topk,
                    self._topp)
        with self.metrics.span("serve/decode/readback"):
            next_tok = np.asarray(next_tok)
        self.metrics.record("decode", self.clock() - t0, len(active),
                            tenant=self.tenant)
        self.last_step_tokens += len(active)
        for s in active:
            req = self.scheduler.active[s]
            if req is None:    # freed mid-step by an on_token cancel()
                continue
            self.generated[s].append(int(next_tok[s]))
            self._emit(req, int(next_tok[s]))
            if self.scheduler.active[s] is req:   # ... or a self-cancel
                self._maybe_complete(s, req)

    # --------------------------------------------------------------- token
    def _token_fits(self, req: GenerationRequest) -> bool:
        """Token mode shares ONE cache cursor across slots: a request
        admitted at cursor c consumes positions [c, c + plen + max_new), so
        it fits iff that span ends inside max_len."""
        return (self._cursor + len(req.prompt) + req.max_new_tokens
                <= self.max_len)

    def _token_step(self) -> None:
        """Seed semantics: prompts fed one token per batched step (global
        cache cursor; used by families without a KV slot cache). The shared
        cursor only advances — so admission is gated on the LIVE cursor
        (submit's per-request check is necessary, not sufficient), and an
        idle engine resets its decode state instead of admitting work whose
        KV writes would silently clamp past max_len."""
        fits = None
        if self.cfg.family != "xlstm":   # recurrent state: nothing to exhaust
            fits = self._token_fits
            head = self.scheduler.peek()
            if (head is not None and self.scheduler.num_active == 0
                    and self._cursor > 0 and not fits(head)):
                # drained but the cursor is spent: fresh state, cursor 0.
                # submit() guarantees every queued request fits from there.
                self.state = self._place_state(
                    self.plan.decode_state(self.slots, self.max_len))
                self._cursor = 0
        for s, _req in self._admit(fits=fits):
            self.pos[s] = 0
        active = self.scheduler.active_slots()
        if not active:
            return
        if self.cfg.family != "xlstm" and self._cursor >= self.max_len:
            raise RuntimeError(
                f"token-mode cache cursor exhausted mid-flight (cursor "
                f"{self._cursor} >= max_len {self.max_len}) with "
                f"{len(active)} active request(s) — admission gating "
                "should have prevented this")
        toks = np.zeros((self.slots, 1), np.int32)
        for s in active:
            req = self.scheduler.active[s]
            if self.pos[s] < len(req.prompt):      # still feeding the prompt
                toks[s, 0] = req.prompt[self.pos[s]]
            else:                                  # submit() bans empty
                toks[s, 0] = self.generated[s][-1]  # prompts: always filled
        t0 = self.clock()
        with self.metrics.span("serve/decode/dispatch"):
            next_tok, self.state = self._step(
                self.params, self.state, jnp.asarray(toks),
                self._seed, self._gen_steps(), self._temp, self._topk,
                self._topp)
        with self.metrics.span("serve/decode/readback"):
            next_tok = np.asarray(next_tok)
        self._cursor += 1
        # a slot emits a generated token this step once it has consumed its
        # last prompt token, i.e. pos >= plen - 1 before the increment
        n_decoding = sum(
            self.pos[s] >= len(self.scheduler.active[s].prompt) - 1
            for s in active)
        self.metrics.record("decode", self.clock() - t0, n_decoding,
                            tenant=self.tenant)
        self.last_step_tokens += len(active)
        for s in active:
            req = self.scheduler.active[s]
            if req is None:    # freed mid-step by an on_token cancel()
                continue
            self.pos[s] += 1
            if self.pos[s] >= len(req.prompt):
                self.generated[s].append(int(next_tok[s]))
                self._emit(req, int(next_tok[s]))
                if self.scheduler.active[s] is req:   # ... or a self-cancel
                    self._maybe_complete(s, req)
