"""Token-level continuous-batching decode lane (docs/SERVING.md
"Decode lane").

PR 6's engine continuously batches fixed-shape forward passes; real
`generate()` traffic is autoregressive.  This module is the decode lane
on top: a step-level scheduler that admits/evicts SEQUENCES PER DECODE
STEP over a paged KV pool (serving/kv_pool.py), with a prefill/decode
phase split so long prompts chunk through a separate fixed-shape
prefill executable and never stall the running decode step.

The engine serves any model that hands it a decode-lane declaration
(serving/lane.py: the cache rows a token leaves in each layer, and the
builders of the two programs below) from its config's ``decode_lane()``:
models/gpt.py (a K and a V row, dense paged attention) and models/glm.py
(a latent row and an indexer key, learned sparse attention, held
experts) go through the same engine, scheduler and pool.

Execution model — exactly TWO compiled signatures in steady state:

  prefill chunk   the lane's ``build_prefill_chunk`` — [1, C] tokens of
                  ONE sequence, its cache rows written into whole pool
                  pages, attention over the sequence's prefix through
                  its page table.  A P-token prompt is ceil(P/C) calls.
  decode step     the lane's ``build_decode_step`` — [pool_slots]
                  one-token rows (batch-slot dim padded to the pool
                  size), scatter-write + read against the page tables,
                  greedy argmax out.

Every feed shape is static, so after the first prefill chunk and the
first decode step NOTHING recompiles (the benchmark's decode cells
count compiles inside the window), and per-token latency is
independent of prompt length (prefill cost is paid in the prefill
phase, the decode step touches only page tables).

Scheduling loop (one scheduler thread per engine):

  1. run ONE prefill chunk of the oldest queued sequence (if any) —
     chunk granularity is the no-stall knob: between two chunks of a
     long prompt every running sequence still gets its decode steps
  2. admit prefill-complete sequences into free decode slots
  3. run ONE decode step over the active slots; finished sequences
     (eos / max_new_tokens) resolve their futures and free pages+slot

Every iteration is one ``turn`` span (observability/profiling.py, lane
``decode``) whose children name where the host's time goes:
``prefill.pages``, ``prefill.feed_build``, ``prefill.run``, ``admit``,
``decode.pages``, ``decode.feed_build``, ``decode.run``, ``emit`` (which
also books the pool's bytes in use by declared row tensor and its pages
by cache kind); a lane with window layers gives their dead pages back
in a second ``prefill.pages`` / ``decode.pages`` span right after the
run that moved the sequence on; the
executor's own phases are children of the two ``*.run`` spans, which also
feed ``pt_decode_step_seconds`` and ``pt_decode_phase_seconds_total``.

Eviction under pool pressure: when a page allocation fails (of any
cache kind), the YOUNGEST other live sequence is evicted — its pages of
every kind return to the pool,
its request re-queues for re-prefill of prompt + already-generated
tokens (greedy decode is deterministic, so the replay reproduces the
same stream; the generated prefix is kept, not re-decoded).  Booked on
``pt_decode_evictions_total``.

Restart story: the two executables ride the executor's warm cache
(FLAGS_compile_cache_dir) and, with FLAGS_aot_cache_dir set, the AOT
serialization path (fluid/aot_cache.py) — a restarted replica's
`warmup()` deserializes ready-to-run executables and performs ZERO
compiles before its first request.
"""

from __future__ import annotations

import collections
import concurrent.futures
import threading
import time

import numpy as np

from paddle_tpu.observability import profiling as _profiling
from paddle_tpu.observability import reqtrace as _reqtrace

from .errors import PoolExhaustedError, ServingOverloadError
from . import lane as _lane
from .kv_pool import FREED_WHY, KVPool, TRASH_PAGE

__all__ = ["DecodeEngine", "DecodeRequest"]


# ---------------------------------------------------------------------------
# metrics (lazy idempotent registration — the observability contract)
# ---------------------------------------------------------------------------


def _m_tokens():
    from paddle_tpu import observability as obs

    return obs.counter(
        "pt_decode_tokens_total",
        "Tokens generated by the decode lane (one per active slot per "
        "decode step, plus each prefill's seed token)",
        labels=("engine",))


def _m_step_seconds():
    from paddle_tpu import observability as obs

    return obs.histogram(
        "pt_decode_step_seconds",
        "Wall time of one decode step — the per-token latency of every "
        "sequence active in it (fixed-shape executable: independent of "
        "prompt length after prefill)", labels=("engine",))


def _m_phase_seconds():
    from paddle_tpu import observability as obs

    return obs.counter(
        "pt_decode_phase_seconds_total",
        "Wall seconds by decode-lane phase (prefill vs decode) — the "
        "phase split that tells a prompt-bound fleet from a "
        "decode-bound one", labels=("engine", "phase"))


def _m_prefill_chunks():
    from paddle_tpu import observability as obs

    return obs.counter(
        "pt_decode_prefill_chunks_total",
        "Prefill chunk executions (a P-token prompt is "
        "ceil(P/chunk) of these)", labels=("engine",))


def _m_turns():
    from paddle_tpu import observability as obs

    return obs.counter(
        "pt_decode_turns_total",
        "Scheduler turns run (<=1 prefill chunk, admissions, <=1 decode "
        "step each)", labels=("engine",))


def _m_turn_seconds():
    from paddle_tpu import observability as obs

    return obs.counter(
        "pt_decode_turn_seconds_total",
        "Wall seconds of scheduler turns by part: prefill_run and "
        "decode_run are the two blocking executor runs (the *.run "
        "spans), sched is the rest of the turn — pages, feed building, "
        "admission, token emission", labels=("engine", "part"))


def _m_queue_wait():
    from paddle_tpu import observability as obs

    return obs.histogram(
        "pt_decode_queue_wait_seconds",
        "Arrival to the start of the request's first prefill chunk",
        labels=("engine",))


def _m_ttft():
    from paddle_tpu import observability as obs

    return obs.histogram(
        "pt_decode_ttft_seconds",
        "Arrival to the request's first token (the prefill's seed "
        "token; resumed requests have none)", labels=("engine",))


def _m_token_gap():
    from paddle_tpu import observability as obs

    return obs.histogram(
        "pt_decode_token_gap_seconds",
        "Gap between two consecutive tokens of one request, as the "
        "scheduler appends them", labels=("engine",))


def _m_slot_occupancy():
    from paddle_tpu import observability as obs

    return obs.gauge(
        "pt_decode_slot_occupancy",
        "Active decode slots / pool_slots after the last step — "
        "sustained < 1.0 with a non-empty queue names admission, not "
        "the device", labels=("engine",))


def _m_pages_in_use():
    from paddle_tpu import observability as obs

    return obs.gauge(
        "pt_decode_kv_pages_in_use",
        "KV pool pages currently allocated to live sequences",
        labels=("engine",))


def _m_kind_pages_in_use():
    from paddle_tpu import observability as obs

    return obs.gauge(
        "pt_kv_pages_in_use",
        "KV pool pages currently allocated to live sequences, by cache "
        "kind (full / window<W>)", labels=("engine", "kind"))


def _m_pages_alloc():
    from paddle_tpu import observability as obs

    return obs.counter(
        "pt_kv_pages_alloc_total",
        "KV pool pages handed to sequences, by cache kind",
        labels=("engine", "kind"))


def _m_pages_freed():
    from paddle_tpu import observability as obs

    return obs.counter(
        "pt_kv_pages_freed_total",
        "KV pool pages given back, by cache kind and why: window (they "
        "fell wholly below a live sequence's window), end (the request "
        "finished) or evict", labels=("engine", "kind", "why"))


def _m_cache_bytes():
    from paddle_tpu import observability as obs

    return obs.gauge(
        "pt_decode_cache_bytes",
        "Device bytes the pages in use hold, by declared cache row "
        "tensor (k / v; latent / index), over every layer",
        labels=("engine", "row"))


def _m_evictions():
    from paddle_tpu import observability as obs

    return obs.counter(
        "pt_decode_evictions_total",
        "Sequences evicted under KV-pool pressure (pages freed, request "
        "re-queued for re-prefill of its prompt + generated prefix)",
        labels=("engine",))


def _m_queue_depth():
    from paddle_tpu import observability as obs

    return obs.gauge(
        "pt_decode_queue_depth",
        "Requests waiting for prefill in the decode lane",
        labels=("engine",))


def _m_rejected():
    # the SAME family the continuous-batch engine owns (engine.py is
    # the one registration site): one admission-rejection surface
    # across both serving lanes, with the decode engine's name in the
    # model label
    from .engine import _m_rejected as _engine_rejected

    return _engine_rejected()


def _engine_max_tenants():
    from .engine import _MAX_TENANT_LABELS

    return _MAX_TENANT_LABELS


# ---------------------------------------------------------------------------
# request / sequence state
# ---------------------------------------------------------------------------


class DecodeRequest:
    """One generate() call: prompt tokens in, generated tokens out
    (greedy; the future resolves to a list[int] of generated ids,
    including the eos token when one stops the sequence)."""

    __slots__ = ("prompt", "max_new_tokens", "eos_id", "tenant", "future",
                 "seq_id", "generated", "prefilled", "t_arrival",
                 "span", "t_first", "t_admit", "token_times")

    def __init__(self, prompt, max_new_tokens, eos_id, tenant):
        self.prompt = [int(t) for t in prompt]
        self.max_new_tokens = int(max_new_tokens)
        self.eos_id = None if eos_id is None else int(eos_id)
        self.tenant = tenant
        self.future = concurrent.futures.Future()
        self.seq_id = None
        self.span = None     # serve span (observability.reqtrace)
        self.t_first = None  # first-token time — TTFT = t_first - t_arrival
        self.generated = []      # greedy stream; [-1] is the pending
        # token (sampled, not yet written to the pool)
        self.prefilled = 0       # tokens whose K/V sit in the pool
        self.t_arrival = time.monotonic()  # observability: allow
        self.t_admit = None  # start of the first prefill chunk
        # monotonic seconds, one per element of `generated` (a resumed
        # request's replayed prefix carries its arrival time)
        self.token_times = []

    @property
    def written_target(self):
        """Tokens that must be in the pool before decode can proceed:
        the prompt plus every generated token except the pending one."""
        return len(self.prompt) + max(len(self.generated) - 1, 0)

    def tokens_to_write(self):
        return self.prompt + self.generated[:-1] if self.generated \
            else self.prompt

    def done(self):
        return bool(self.generated) and (
            len(self.generated) >= self.max_new_tokens
            or (self.eos_id is not None
                and self.generated[-1] == self.eos_id))


# ---------------------------------------------------------------------------
# engine
# ---------------------------------------------------------------------------


class DecodeEngine:
    """Continuous-batching greedy decode over a model's parameters.

    ``cfg`` is the model's config object; its ``decode_lane()`` returns
    the decode-lane declaration (serving/lane.py) the engine builds its
    pool and its two programs from.  ``scope`` must already hold the
    model parameters (train in-process,
    or `fluid.io`-load a checkpoint into it); the engine installs the
    pool vars beside them and builds its two programs against the same
    names, so the decode lane and the training/whole-sequence lanes
    share one set of weights.

    Sizing: ``pool_slots`` concurrent decoding sequences;
    ``max_len`` >= prompt + max_new_tokens per request (defaults to
    the lane's max_position); ``prefill_chunk`` and ``pool_dtype``
    default to the lane's; ``num_pages`` defaults to every slot at full
    length (+1 trash) — shrink it to exercise eviction, the lane stays
    correct (requests replay deterministically).  A lane that declares
    window layers (``lane.layer_windows``) gets a pool tensor size a
    cache kind: ``num_pages`` is the ``full`` kind's, a window kind has
    ``pool_slots x lane.window_pages_per_seq`` pages (+1 trash) and never
    more than ``num_pages``."""

    def __init__(self, cfg, *, scope=None, place=None, pool_slots=4,
                 page_size=16, prefill_chunk=None, max_len=None,
                 num_pages=None, max_queue=None, pool_dtype=None,
                 attn_force=None, name="decode", auto_start=True,
                 tenant_quota=None, drain_on_sigterm=True,
                 int8_weights=False):
        from paddle_tpu import fluid
        from paddle_tpu.fluid import flags as _flags
        from paddle_tpu.fluid.executor import global_scope

        lane = cfg.decode_lane()
        if pool_dtype is None:
            # FLAGS_int8_kv_cache picks the dual-int8 pool
            # (docs/KERNELS.md "int8 KV"); explicit pool_dtype wins
            pool_dtype = ("int8" if _flags.flag("int8_kv_cache")
                          else lane.pool_dtype)
        self.cfg = cfg
        self.lane = lane
        self.name = name
        self.scope = scope if scope is not None else global_scope()
        self.pool_slots = int(pool_slots)
        page_size = int(page_size)
        max_len = int(max_len if max_len is not None else lane.max_position)
        if max_len > lane.max_position:
            raise ValueError(
                f"max_len {max_len} exceeds the model's max_position "
                f"{lane.max_position}")
        max_pages = -(-max_len // page_size)
        if prefill_chunk is None:
            prefill_chunk = lane.prefill_chunk
        if prefill_chunk is None:
            prefill_chunk = min(max(page_size, 32), max_len)
            prefill_chunk -= prefill_chunk % page_size
            # page_size > max_len rounds the derived chunk down to 0 —
            # floor at one whole page (a 0-token chunk never advances
            # prefill: the scheduler would livelock); the valid tail
            # is trash-padded like any short final chunk
            prefill_chunk = max(prefill_chunk, page_size)
        prefill_chunk = int(prefill_chunk)
        if prefill_chunk <= 0 or prefill_chunk % page_size:
            raise ValueError(
                f"prefill_chunk {prefill_chunk} must be a positive "
                f"multiple of page_size {page_size} (chunks cover "
                f"whole pool pages)")
        if num_pages is None:
            num_pages = self.pool_slots * max_pages + 1
        self.max_len = max_len
        self.prefill_chunk = prefill_chunk
        self._max_queue = int(_flags.flag("serving_max_queue")
                              if max_queue is None else max_queue)
        rows = lane.cache_rows(pool_dtype)
        windows = lane.layer_windows
        self.pool = KVPool(
            lane.num_layers, rows, num_pages, page_size, max_pages,
            layer_windows=windows, window_pages={
                _lane.kind_name(w): self.pool_slots
                * _lane.window_pages_per_seq(w, prefill_chunk, page_size) + 1
                for w in set(windows or ()) if w is not None})
        self.pool.install(self.scope)
        if windows is not None:  # the builders size each layer by its kind
            num_pages = self.pool.pages_by_kind()
        if pool_dtype == "int8":
            # book the modeled HBM saving of the dual-int8 pool vs the
            # fp32 pool it replaces — the counter the int8-KV claim is
            # proven against (docs/OBSERVABILITY.md)
            from paddle_tpu.kernels import primitives as _prims

            fp32 = KVPool(lane.num_layers, lane.cache_rows("float32"),
                          self.pool.num_pages, page_size, max_pages)
            _prims.book_bytes_saved(
                "kv_cache",
                fp32.modeled_bytes() - self.pool.modeled_bytes())
        # the lane's device counters: on the device, fetched by no step
        self._counters_seen = {}
        self._install_device_counters()

        # two programs, two fixed-shape executables — built once, against
        # the SAME parameter names the training lanes use
        dec_prog, dec_start = fluid.Program(), fluid.Program()
        with fluid.program_guard(dec_prog, dec_start), \
                fluid.unique_name.guard():
            self._dec_feeds, dec_tok, _ = lane.build_decode_step(
                self.pool_slots, num_pages, page_size, max_pages,
                pool_dtype=pool_dtype, attn_force=attn_force)
        pf_prog, pf_start = fluid.Program(), fluid.Program()
        with fluid.program_guard(pf_prog, pf_start), \
                fluid.unique_name.guard():
            self._pf_feeds, pf_tok, _ = lane.build_prefill_chunk(
                prefill_chunk, num_pages, page_size, max_pages,
                pool_dtype=pool_dtype, attn_force=attn_force)
        self._dec_prog, self._dec_fetch = dec_prog, dec_tok.name
        self._pf_prog, self._pf_fetch = pf_prog, pf_tok.name
        # what a device trace calls the two executables:
        # jit_decode_step / jit_prefill_chunk
        dec_prog.name, pf_prog.name = "decode_step", "prefill_chunk"
        if int8_weights:
            # opt-in dual-int8 WEIGHT storage (docs/KERNELS.md): both
            # programs route their matmul weights through
            # dequantize_weight_storage, then the scope's fp32 arrays are
            # quantized ONCE and dropped.  Destructive to the scope —
            # only engage on a scope no training lane shares.
            from paddle_tpu import passes as _passes
            from paddle_tpu.passes.int8_weights import \
                quantize_scope_weights as _quantize_scope_weights

            ctx = _passes.PassContext(lane="serving")
            mgr = _passes.PassManager(["int8_weight_storage"])
            mgr.run(dec_prog, ctx)
            mgr.run(pf_prog, ctx)
            claimed = [set(op.output("Out")[0]
                           for op in p.global_block().ops
                           if op.type == "dequantize_weight_storage")
                       for p in (dec_prog, pf_prog)]
            if claimed[0] != claimed[1]:
                raise RuntimeError(
                    f"int8_weights: decode and prefill programs claimed "
                    f"different weight sets "
                    f"({sorted(claimed[0] ^ claimed[1])}) — the shared "
                    f"scope cannot satisfy both")
            _quantize_scope_weights(self.scope, dec_prog)
        self._exe = fluid.Executor(place)

        self._queue = collections.deque()   # prefill-pending, FIFO
        self._ready = collections.deque()   # prefill done, need a slot
        self._slots = [None] * self.pool_slots
        self._live_order = []               # admission order (evict LIFO)
        self._cv = threading.Condition()
        self._thread = None
        self._closed = False
        self._failed = None  # scheduler-killing exception, if any
        # per-tenant admission quota (FLAGS_serving_tenant_quota): max
        # LIVE requests one tenant may hold; 0 = unlimited
        self._tenant_quota = int(_flags.flag("serving_tenant_quota")
                                 if tenant_quota is None else tenant_quota)
        # graceful drain (elastic.DrainHandler): admission stops, queued
        # futures fail typed, in-flight sequences decode to completion
        self._draining = False
        self._drained = threading.Event()
        self._drain_on_sigterm = bool(drain_on_sigterm)
        # serializes device dispatch (the PR-6 Engine _exec_lock
        # convention): a user-thread warmup() racing the scheduler's
        # step would run two Executor.run calls over the same scope's
        # DONATED pool buffers — use-after-donate / silent corruption
        self._exec_lock = threading.Lock()
        self._next_seq = 0
        self._steps = 0
        self._turns = 0
        # seconds inside the *.run spans of the turn under way
        self._turn_run_s = 0.0
        self._tokens = 0
        self._evictions = 0
        # tenant labels this lane has minted on the cross-lane
        # pt_serve_requests_total family (the engine.py 64-label cap)
        self._tenants_seen = set()
        self._bind_metrics()
        from . import status as _status

        _status.track_decode_engine(self)
        if auto_start:
            self.start()

    def _bind_metrics(self):
        from paddle_tpu import observability as obs

        from .engine import _m_latency, _m_requests

        self._metrics_epoch = obs.REGISTRY.epoch
        e = self.name
        self._tok_ctr = _m_tokens().labels(engine=e)
        self._step_hist = _m_step_seconds().labels(engine=e)
        self._phase = {p: _m_phase_seconds().labels(engine=e, phase=p)
                       for p in ("prefill", "decode")}
        self._chunks = _m_prefill_chunks().labels(engine=e)
        self._occupancy = _m_slot_occupancy().labels(engine=e)
        self._pages_gauge = _m_pages_in_use().labels(engine=e)
        self._evict_ctr = _m_evictions().labels(engine=e)
        self._depth = _m_queue_depth().labels(engine=e)
        self._turn_ctr = _m_turns().labels(engine=e)
        self._turn_part = {
            p: _m_turn_seconds().labels(engine=e, part=p)
            for p in ("sched", "prefill_run", "decode_run")}
        self._queue_wait = _m_queue_wait().labels(engine=e)
        self._ttft = _m_ttft().labels(engine=e)
        self._token_gap = _m_token_gap().labels(engine=e)
        self._cache_bytes = {
            row: _m_cache_bytes().labels(engine=e, row=row.name)
            for row in self.pool.rows}
        kinds = self.pool.kinds
        self._kind_pages = {
            k: _m_kind_pages_in_use().labels(engine=e, kind=k)
            for k in kinds}
        self._kind_alloc = {
            k: _m_pages_alloc().labels(engine=e, kind=k) for k in kinds}
        self._kind_freed = {
            (k, why): _m_pages_freed().labels(engine=e, kind=k, why=why)
            for k in kinds for why in FREED_WHY}
        # the pool's running totals as last booked on those counters
        self._kind_booked = {
            k: {"alloc_total": 0, "freed": dict.fromkeys(FREED_WHY, 0)}
            for k in kinds}
        # the SAME cross-lane families engine.py owns: end-to-end decode
        # request latency (submit -> future resolve, exemplar-bearing)
        # and admitted-request counts, with this engine's name as model
        self._req_lat = _m_latency().labels(model=e)
        self._requests_family = _m_requests()

    def _check_metrics_epoch(self):
        from paddle_tpu import observability as obs

        if self._metrics_epoch != obs.REGISTRY.epoch:
            self._bind_metrics()

    # -- public API ---------------------------------------------------------

    def submit(self, prompt, max_new_tokens, eos_id=None,
               tenant="default"):
        """Enqueue one greedy generation; returns a Future resolving to
        the generated token ids (list[int])."""
        return self.submit_request(prompt, max_new_tokens, eos_id=eos_id,
                                   tenant=tenant).future

    def submit_request(self, prompt, max_new_tokens, eos_id=None,
                       tenant="default", prefix=None):
        """`submit` returning the `DecodeRequest` itself (the router's
        surface: it needs the request's live `generated` progress to
        fail a victim over, not just the future).

        ``prefix`` seeds the generated stream with tokens an earlier
        incarnation already emitted: the request re-prefills
        prompt + prefix[:-1] through the eviction-replay path and
        resumes decoding from prefix[-1] — greedy decode is
        deterministic, so the resumed stream is token-exact with the
        uninterrupted one (docs/SERVING.md "Resilience").
        ``max_new_tokens`` stays the ORIGINAL budget: the prefix counts
        toward it, so a resumed request finishes at the same total
        length."""
        self._check_metrics_epoch()
        prompt = list(prompt)
        if not prompt:
            raise ValueError("decode: empty prompt")
        if int(max_new_tokens) < 1:
            raise ValueError(
                f"decode: max_new_tokens must be >= 1, got "
                f"{max_new_tokens}")
        total = len(prompt) + int(max_new_tokens)
        if total > self.max_len:
            raise ValueError(
                f"decode: prompt ({len(prompt)}) + max_new_tokens "
                f"({max_new_tokens}) = {total} exceeds the engine's "
                f"max_len {self.max_len} — raise max_len or split the "
                f"request")
        # normalize at the edge (the engine.py lane's convention): the
        # quota scan and the metric label must see ONE spelling — an
        # int-tenant caller must not bypass its own quota
        tenant = str(tenant)
        req = DecodeRequest(prompt, max_new_tokens, eos_id, tenant)
        if prefix:
            prefix = [int(t) for t in prefix]
            if len(prefix) > int(max_new_tokens):
                raise ValueError(
                    f"decode: resume prefix ({len(prefix)}) exceeds "
                    f"max_new_tokens ({max_new_tokens})")
            req.generated = prefix
            req.token_times = [req.t_arrival] * len(prefix)
            if req.done():
                # the victim died with its stream already complete:
                # nothing to decode — resolve without spending a prefill
                req.future.set_result(list(req.generated))
                return req
        with self._cv:
            if self._closed:
                raise self._reject(
                    "closed", f"decode engine {self.name!r} is closed")
            if self._failed is not None:
                # the scheduler thread died on this exception — a
                # queued future would never be processed (silent hang);
                # fail typed at the admission edge instead
                raise self._reject(
                    "scheduler_failed",
                    f"decode engine {self.name!r} scheduler died: "
                    f"{self._failed!r} — close and recreate the engine")
            if self._draining:
                raise self._reject(
                    "draining",
                    f"decode engine {self.name!r} is draining (graceful "
                    f"preemption) — resubmit to another replica")
            if len(self._queue) >= self._max_queue:
                raise self._reject(
                    "overload",
                    f"decode engine {self.name!r}: queue at admission "
                    f"limit ({self._max_queue}) — retry with backoff")
            if self._tenant_quota > 0:
                # live = queued + prefill-done + decoding: the whole
                # footprint this tenant holds in the lane
                live = sum(
                    1 for r in (*self._queue, *self._ready,
                                *(s for s in self._slots
                                  if s is not None))
                    if r.tenant == tenant)
                if live >= self._tenant_quota:
                    raise self._reject(
                        "tenant_quota",
                        f"decode engine {self.name!r}: tenant "
                        f"{tenant!r} holds {live} live requests, at "
                        f"FLAGS_serving_tenant_quota="
                        f"{self._tenant_quota} — retry with backoff "
                        f"(per-tenant pressure, not engine overload)")
            req.span = self._serve_span(req, tenant)
            if tenant not in self._tenants_seen and \
                    len(self._tenants_seen) >= _engine_max_tenants():
                tenant = "__other__"
            self._tenants_seen.add(tenant)
            self._queue.append(req)
            self._depth.set(len(self._queue))
            self._cv.notify_all()
        self._requests_family.labels(model=self.name, tenant=tenant).inc()
        return req

    def _serve_span(self, req, tenant):
        """Engine-side serve span for one admitted decode request (the
        engine.py convention): a router/frontend caller carries its span
        in via reqtrace.attach() on the submit edge; a direct caller
        becomes its own trace root.  Finishes with the future."""
        parent = _reqtrace.current_span()
        attrs = {"engine": self.name, "tenant": tenant,
                 "prompt_tokens": len(req.prompt),
                 "max_new_tokens": req.max_new_tokens,
                 "resumed": bool(req.generated)}
        if parent is not None:
            span = _reqtrace.start_span(f"serve:{self.name}",
                                        kind="serve", parent=parent,
                                        attrs=attrs)
        else:
            span = _reqtrace.start_request(f"serve:{self.name}",
                                           kind="serve", attrs=attrs)
        if span is not None:
            req.future.add_done_callback(
                lambda f, s=span: _reqtrace.finish_future(s, f))
        return span

    def _reject(self, reason, msg):
        """Book + build one typed admission rejection (the same
        pt_serve_rejected_total{model,reason} surface the continuous-
        batch engine uses, with this engine's name as the model)."""
        _m_rejected().labels(model=self.name, reason=reason).inc()
        return ServingOverloadError(msg, reason=reason)

    def generate(self, prompts, max_new_tokens, eos_id=None,
                 timeout=None):
        """Blocking convenience: submit every prompt, wait for all.
        Returns list[list[int]] of generated ids."""
        futs = [self.submit(p, max_new_tokens, eos_id=eos_id)
                for p in prompts]
        return [f.result(timeout=timeout) for f in futs]

    def _warm_prefill_args(self):
        trash = np.zeros(self.prefill_chunk // self.pool.page_size, np.int32)
        return dict(
            tokens=[0], pos0=0, seq_id=None,
            write_pages={k: trash for k in self.pool.kinds}, valid=1)

    def warmup(self):
        """Compile (or AOT-load) both executables outside the request
        path: one all-inactive decode step + one trash-page prefill
        chunk.  Writes land only on the pool's trash page.  Returns the
        number of executables warmed (2)."""
        self._run_prefill_feed(**self._warm_prefill_args(), warm=True)
        self._run_decode_feed([], warm=True)
        # the warm chunk's one trash-page token is no traffic
        self._install_device_counters()
        return 2

    def lower(self, sharding=None):
        """AOT-lower the two executables :meth:`warmup` compiles, without
        running them: ``[prefill_chunk, decode_step]`` (Executor.lower —
        ``sharding`` over a topology device compiles them chip-free)."""
        return [
            self._exe.lower(self._pf_prog,
                            self._prefill_feed(**self._warm_prefill_args()),
                            [self._pf_fetch], scope=self.scope,
                            sharding=sharding),
            self._exe.lower(self._dec_prog, self._decode_feed([]),
                            [self._dec_fetch], scope=self.scope,
                            sharding=sharding),
        ]

    def drain(self, timeout=None):
        """Graceful drain (the decode lane's half of the
        `elastic.DrainHandler` contract): stop admission — new submits
        reject typed with ``reason="draining"`` — fail every QUEUED and
        prefill-pending future typed (their pool pages return), and let
        the sequences already IN decode slots run to completion instead
        of dying mid-batch.  Blocks up to `timeout` seconds for the
        in-flight work to finish (None = return immediately).  The
        scheduler thread stays alive — the process snapshots/LEAVEs
        before the DrainHandler re-delivers the signal — and the flush
        itself runs ON the scheduler thread, preserving its single-
        threaded ownership of the pool and slot state.  Idempotent."""
        with self._cv:
            if self._closed:
                return True
            self._draining = True
            self._cv.notify_all()
        if timeout is not None:
            return self._drained.wait(timeout=timeout)
        return True

    def close(self):
        with self._cv:
            if self._closed:
                return
            self._closed = True
            self._drained.set()  # a close supersedes any pending drain
            self._cv.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=30)
        leftovers = []
        with self._cv:
            leftovers.extend(self._queue)
            self._queue.clear()
            leftovers.extend(self._ready)
            self._ready.clear()
            leftovers.extend(s for s in self._slots if s is not None)
            self._slots = [None] * self.pool_slots
        for req in leftovers:
            if req.future.set_running_or_notify_cancel():
                req.future.set_exception(ServingOverloadError(
                    f"decode engine {self.name!r} closed before the "
                    f"request finished", reason="closed"))
        from . import status as _status

        _status.untrack_decode_engine(self)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def start(self):
        with self._cv:
            if self._thread is not None or self._closed:
                return self
            self._thread = threading.Thread(
                target=self._scheduler_loop, daemon=True,
                name=f"pt-decode-{self.name}")
            self._thread.start()
        return self

    # -- scheduler ----------------------------------------------------------

    def _scheduler_loop(self):
        while True:
            with self._cv:
                while (not self._closed and not self._queue
                       and not self._ready
                       and all(s is None for s in self._slots)):
                    if self._draining:
                        # nothing in flight: the drain is complete — the
                        # scheduler idles here until close()
                        self._drained.set()
                    # deliberately unbounded: close()/submit() notify
                    self._cv.wait()  # resilience: allow
                if self._closed:
                    return
            try:
                self._step_once()
            except BaseException as e:  # resilience: allow — fanned out
                # an executor failure must fail the live requests, not
                # kill the scheduler thread silently
                self._fail_all(e)
                if isinstance(e, (KeyboardInterrupt, SystemExit)):
                    raise
                return

    def _flush_for_drain(self):
        """Scheduler-thread half of drain(): fail queued + prefill-done
        futures typed, free any pool pages a mid-prefill victim held,
        and leave slot-admitted sequences running.  Runs on the
        scheduler thread — the only owner of pool/slot state."""
        with self._cv:
            victims = list(self._queue) + list(self._ready)
            self._queue.clear()
            self._ready.clear()
            self._depth.set(0)
        for req in victims:
            if req.seq_id is not None:
                # a mid-prefill victim holds pool pages: return them
                if req in self._live_order:
                    self._live_order.remove(req)
                self.pool.free_seq(req.seq_id)
                req.seq_id = None
                req.prefilled = 0
            if req.future.set_running_or_notify_cancel():
                req.future.set_exception(self._reject(
                    "draining",
                    f"decode engine {self.name!r} drained before this "
                    f"request started decoding — resubmit to another "
                    f"replica"))
        if all(s is None for s in self._slots):
            self._drained.set()

    def _fail_all(self, exc):
        with self._cv:
            self._failed = exc  # latch: submit() rejects typed from now on
            reqs = list(self._queue) + list(self._ready) + [
                s for s in self._slots if s is not None]
            self._queue.clear()
            self._ready.clear()
            self._slots = [None] * self.pool_slots
        for req in reqs:
            if req.future.set_running_or_notify_cancel():
                req.future.set_exception(exc)

    def _step_once(self):
        """One scheduler iteration: <=1 prefill chunk, admissions, <=1
        decode step — the interleave that keeps long prompts from
        stalling running sequences.  A requested drain (drain() call,
        or the process DrainHandler's SIGTERM with drain_on_sigterm)
        flushes queued work typed FIRST, so only already-running
        sequences consume further device steps."""
        self._check_metrics_epoch()
        self._turns += 1
        self._turn_run_s = 0.0
        with _profiling.span("turn", "decode", number=self._turns) as turn:
            if not self._draining and self._drain_on_sigterm:
                from paddle_tpu.distributed import elastic

                if elastic.drain_requested():
                    with self._cv:
                        self._draining = True
            if self._draining:
                self._flush_for_drain()
            self._prefill_one_chunk()
            with _profiling.span("admit", "decode"):
                self._admit_ready()
            self._decode_step()
            with _profiling.span("emit", "decode"):
                self._occupancy.set(
                    sum(s is not None for s in self._slots)
                    / self.pool_slots)
                self._pages_gauge.set(self.pool.pages_in_use())
                # bytes in use by declared row tensor and pages by cache
                # kind: part of `emit`, so that the turn's children stay
                # the eight the benchmark's idle split knows
                # (readers/host_gap.py)
                self._book_pool()
                with self._cv:
                    self._depth.set(len(self._queue))
        self._turn_ctr.inc()
        self._turn_part["sched"].inc(
            max(turn.seconds - self._turn_run_s, 0.0))

    def _book_pool(self):
        """The pool's state onto the gauges, and what its running totals
        gained since the last booking onto the page counters."""
        kinds = self.pool.kind_stats()
        in_use = {k: st["pages_in_use"] for k, st in kinds.items()}
        for row, gauge in self._cache_bytes.items():
            gauge.set(self.pool.row_bytes(row, in_use))
        for k, st in kinds.items():
            booked = self._kind_booked[k]
            self._kind_pages[k].set(st["pages_in_use"])
            self._kind_alloc[k].inc(st["alloc_total"]
                                    - booked["alloc_total"])
            for why, n in st["freed"].items():
                self._kind_freed[k, why].inc(n - booked["freed"][why])
            self._kind_booked[k] = st

    # -- eviction -----------------------------------------------------------

    def _evict_one(self, protect):
        """Free the YOUNGEST live sequence other than `protect`; its
        request re-queues (front — it already waited) for re-prefill of
        prompt + generated prefix.  Returns False when nobody else is
        evictable."""
        for req in reversed(self._live_order):
            if req is protect:
                continue
            self._live_order.remove(req)
            self.pool.free_seq(req.seq_id, why="evict")
            req.seq_id = None
            req.prefilled = 0
            for i, s in enumerate(self._slots):
                if s is req:
                    self._slots[i] = None
            with self._cv:
                if req in self._ready:
                    self._ready.remove(req)
                # a victim still mid-prefill is ALREADY queued (requests
                # stay on the queue until their prefill completes) —
                # re-queueing it would double it
                if req not in self._queue:
                    self._queue.appendleft(req)
            self._evictions += 1
            self._evict_ctr.inc()
            return True
        return False

    def _ensure_pages(self, req, n_tokens):
        while True:
            try:
                return self.pool.ensure_capacity(req.seq_id, n_tokens)
            except PoolExhaustedError:
                if not self._evict_one(protect=req):
                    raise  # sized below one sequence — constructor bug

    # -- prefill ------------------------------------------------------------

    def _prefill_one_chunk(self):
        with self._cv:
            req = self._queue[0] if self._queue else None
        if req is None:
            return
        with _profiling.span("prefill.pages", "decode"):
            if req.t_admit is None:
                req.t_admit = time.monotonic()  # observability: allow — latency anchor
                self._queue_wait.observe(
                    max(req.t_admit - req.t_arrival, 0.0))
            if req.seq_id is None:
                req.seq_id = self._next_seq
                self._next_seq += 1
                self.pool.open_seq(req.seq_id)
                self._live_order.append(req)
            tokens = req.tokens_to_write()
            total = len(tokens)
            ctx_len = req.prefilled
            valid = min(self.prefill_chunk, total - ctx_len)
            self._ensure_pages(req, ctx_len + valid)
            pgs = self.pool.page_size
            first_lp = ctx_len // pgs
            write_pages = {}
            for kind in self.pool.kinds:
                table = self.pool.table(req.seq_id, kind)
                pages = np.full(self.prefill_chunk // pgs, TRASH_PAGE,
                                np.int32)
                for j in range(len(pages)):
                    lp = first_lp + j
                    if lp < len(table) and lp * pgs < ctx_len + valid:
                        pages[j] = table[lp]
                write_pages[kind] = pages
        next_tok = self._run_prefill_feed(
            tokens=tokens[ctx_len:ctx_len + valid], pos0=ctx_len,
            seq_id=req.seq_id, write_pages=write_pages, valid=valid)
        req.prefilled = ctx_len + valid
        self._release_below_window("prefill.pages", [(req, req.prefilled)])
        with _profiling.span("emit", "decode"):
            if req.prefilled == total:
                if not req.generated:
                    # fresh prompt: the prefill's argmax seeds the
                    # stream.  First token exists now: TTFT anchor
                    # (resumed requests arrive with a prefix, so theirs
                    # stays None — a replay is not a first-token
                    # experience)
                    req.t_first = time.monotonic()  # observability: allow — latency anchor
                    self._emit_token(req, int(next_tok), req.t_first)
                    self._ttft.observe(
                        max(req.t_first - req.t_arrival, 0.0))
                with self._cv:
                    # remove by identity, not popleft: an eviction during
                    # _ensure_pages may have re-queued a victim AHEAD of us
                    if req in self._queue:
                        self._queue.remove(req)
                    self._ready.append(req)

    def _emit_token(self, req, token, now):
        """Append one token to the request's stream, where every token
        is appended: `generated` and `token_times` grow together."""
        req.generated.append(token)
        if req.token_times:
            self._token_gap.observe(max(now - req.token_times[-1], 0.0))
        req.token_times.append(now)
        self._tok_ctr.inc()
        self._tokens += 1

    def _release_below_window(self, span, moved):
        """After a run moved sequences on (``moved``: (request, tokens it
        now holds in the pool)): window layers' pages that no later
        query of theirs can see go back to the pool, under one of the
        scheduler's own span names."""
        if self.lane.layer_windows is None:
            return
        with _profiling.span(span, "decode"):
            for req, length in moved:
                self.pool.release(req.seq_id, length)

    def _prefill_feed(self, tokens, pos0, seq_id, write_pages, valid):
        """One chunk's feed: a page table and the chunk's write pages a
        cache kind (``lane.kind_feed``: the ``full`` kind under the plain
        names)."""
        c = self.prefill_chunk
        tok = np.zeros((1, c), np.int64)
        tok[0, :len(tokens)] = tokens
        pos = np.minimum(pos0 + np.arange(c, dtype=np.int64),
                         self.lane.max_position - 1)[None, :]
        feed = {"pf_tok": tok, "pf_pos": pos}
        for kind in self.pool.kinds:
            feed[_lane.kind_feed("pf_page_table", kind)] = \
                self.pool.padded_table(seq_id, kind)[None, :].astype(np.int32)
            feed[_lane.kind_feed("pf_write_pages", kind)] = \
                write_pages[kind].astype(np.int32)
        feed["pf_qstart"] = np.asarray([pos0], np.int32)
        feed["pf_last_idx"] = np.asarray([max(valid - 1, 0)], np.int64)
        return feed

    def _run_prefill_feed(self, tokens, pos0, seq_id, write_pages,
                          valid, warm=False):
        with _profiling.span("prefill.feed_build", "decode"):
            feed = self._prefill_feed(tokens, pos0, seq_id, write_pages,
                                      valid)
        with self._exec_lock:
            with _profiling.span("prefill.run", "decode") as run:
                (out,) = self._exe.run(self._pf_prog, feed=feed,
                                       fetch_list=[self._pf_fetch],
                                       scope=self.scope)
        if not warm:
            self._phase["prefill"].inc(run.seconds)
            self._turn_part["prefill_run"].inc(run.seconds)
            self._turn_run_s += run.seconds
            self._chunks.inc()
        return int(np.asarray(out).reshape(-1)[0])

    # -- decode -------------------------------------------------------------

    def _admit_ready(self):
        with self._cv:
            ready = self._ready
            for i in range(self.pool_slots):
                if self._slots[i] is None and ready:
                    self._slots[i] = ready.popleft()

    def _decode_step(self):
        with _profiling.span("decode.pages", "decode"):
            active = [(i, s) for i, s in enumerate(self._slots)
                      if s is not None]
            # a request satisfiable by prefill alone (max_new_tokens=1,
            # or eos as the seed token) finishes without a decode step
            for i, req in list(active):
                if req.done():
                    self._finish(i, req)
            active = [(i, s) for i, s in enumerate(self._slots)
                      if s is not None]
            for i, req in active:
                # an EARLIER iteration's eviction may have already
                # removed this request from its slot — touching its freed
                # seq would re-allocate pages for a sequence that
                # re-queued
                if self._slots[i] is not req:
                    continue
                self._ensure_pages(req, req.written_target + 1)
            # re-read: _ensure_pages may have evicted some of `active`
            active = [(i, s) for i, s in enumerate(self._slots)
                      if s is not None]
        if not active:
            return
        # deterministic mid-decode death (`replica_kill:` rules): raise
        # BEFORE the step executes, so step n's tokens are never emitted
        # — the scheduler loop fans the error to every live future and
        # the router's failover resumes from the last COMPLETED step
        from paddle_tpu.distributed import fault_injection as _fault

        _fault.on_replica_step(self.name, self._steps + 1)
        # one shared decode-step span: every active request links to it
        # (fan-in), so a request's trace names the exact steps it rode
        bspan = _reqtrace.start_batch(
            f"decode_step:{self.name}",
            attrs={"engine": self.name, "step": self._steps + 1,
                   "n_active": len(active)})
        try:
            next_toks = self._run_decode_feed(active)
        except BaseException as e:
            if bspan is not None:
                bspan.finish("error", error=e)
            raise
        with _profiling.span("emit", "decode"):
            if bspan is not None:
                for _, req in active:
                    if req.span is not None:
                        req.span.link(bspan)
                bspan.finish("ok")
            now = time.monotonic()  # observability: allow — latency anchor
            now_done = []
            for i, req in active:
                self._emit_token(req, int(next_toks[i]), now)
                if req.done():
                    now_done.append((i, req))
            for i, req in now_done:
                self._finish(i, req)
        self._release_below_window(
            "decode.pages", [(req, req.written_target) for i, req in active
                             if self._slots[i] is req])

    def _decode_feed(self, active):
        """One step's feed: a page table and the slots' write pages a
        cache kind (``lane.kind_feed``)."""
        ps, pgs = self.pool_slots, self.pool.page_size
        tok = np.zeros((ps, 1), np.int64)
        pos = np.zeros((ps, 1), np.int64)
        woff = np.zeros(ps, np.int32)
        for i, req in active:
            p = req.written_target  # the pending token's position
            tok[i, 0] = req.generated[-1]
            pos[i, 0] = p
            woff[i] = p % pgs
        feed = {"dec_tok": tok, "dec_pos": pos}
        for kind in self.pool.kinds:
            table = np.tile(self.pool.padded_table(None, kind), (ps, 1))
            wpage = np.zeros(ps, np.int32)
            for i, req in active:
                table[i] = self.pool.padded_table(req.seq_id, kind)
                wpage[i] = table[i][pos[i, 0] // pgs]
            feed[_lane.kind_feed("dec_page_table", kind)] = \
                table.astype(np.int32)
            feed[_lane.kind_feed("dec_write_page", kind)] = wpage
        feed["dec_write_off"] = woff
        return feed

    def _run_decode_feed(self, active, warm=False):
        with _profiling.span("decode.feed_build", "decode"):
            feed = self._decode_feed(active)
        with self._exec_lock:
            with _profiling.span("decode.run", "decode") as run:
                (out,) = self._exe.run(self._dec_prog, feed=feed,
                                       fetch_list=[self._dec_fetch],
                                       scope=self.scope)
        if not warm:
            self._phase["decode"].inc(run.seconds)
            self._step_hist.observe(run.seconds)
            self._turn_part["decode_run"].inc(run.seconds)
            self._turn_run_s += run.seconds
            self._steps += 1
        return np.asarray(out).reshape(-1)

    def _install_device_counters(self):
        """The lane's device counters beside the pool: zeros on the
        device (kept if already there with the declared length); what
        they hold now is what the next booking counts from."""
        import jax.numpy as jnp

        for name, length in self.lane.device_counters:
            cur = self.scope.get(name)
            if cur is None or tuple(np.shape(cur)) != (length,):
                self.scope.set(name, jnp.zeros((length,), jnp.int32))
            self._counters_seen[name] = np.asarray(
                self.scope.get(name)).astype(np.int64)

    def book_device_counters(self):
        """Read the lane's device counters and hand what each gained
        since the last reading to the lane's ``book_counters``.  Only a
        caller that asks pays for it (a trace reader, a test): the
        programs add to the counters in place and no step fetches them,
        so this read waits for the step under way."""
        if not self.lane.device_counters:
            return
        with self._exec_lock:
            now = {name: np.asarray(self.scope.get(name)).astype(np.int64)
                   for name, _ in self.lane.device_counters}
        # int32 on the device: a difference survives a wrap
        gained = {name: (now[name] - self._counters_seen[name]) % (1 << 32)
                  for name in now}
        self._counters_seen = now
        self.lane.book_counters(self.name, gained)

    def _finish(self, slot, req):
        self._slots[slot] = None
        if req in self._live_order:
            self._live_order.remove(req)
        self.pool.free_seq(req.seq_id)
        now = time.monotonic()  # observability: allow — latency anchor
        n = len(req.generated)
        if req.span is not None:
            # per-request serving quality, derived from the span tree:
            # TTFT (arrival -> first token) and TPOT (steady-state
            # seconds per subsequent token).  Resumed requests have no
            # t_first (the replayed prefix is not a first token) — their
            # trace carries tokens only.  Set BEFORE set_result: the
            # future callback finishes the span.
            req.span.set_attr("tokens", n)
            if req.t_first is not None:
                req.span.set_attr(
                    "ttft_s", max(req.t_first - req.t_arrival, 0.0))
                if n > 1:
                    req.span.set_attr(
                        "tpot_s", max(now - req.t_first, 0.0) / (n - 1))
        if req.future.set_running_or_notify_cancel():
            req.future.set_result(list(req.generated))
            # end-to-end decode latency with the trace id as exemplar —
            # the cross-lane pt_serve_request_latency_seconds family
            self._req_lat.observe(
                max(now - req.t_arrival, 0.0),
                exemplar=(req.span.trace_id if req.span is not None
                          else None))

    # -- introspection ------------------------------------------------------

    def healthy(self):
        """Liveness-probe surface (the router's per-replica check):
        True while the scheduler thread is alive and the engine can
        still accept work.  False on close/drain/scheduler death —
        exactly the states a router must route around."""
        with self._cv:
            if self._closed or self._draining or self._failed is not None:
                return False
            thread = self._thread
        # not-yet-started (auto_start=False) counts as healthy: submits
        # queue and the scheduler picks them up at start()
        return thread is None or thread.is_alive()

    def load(self):
        """Dispatch-cost estimate for least-loaded routing: live
        sequences (queued + prefill-done + decoding) in this lane."""
        with self._cv:
            return (len(self._queue) + len(self._ready)
                    + sum(s is not None for s in self._slots))

    def stats(self):
        """The /servez decode section for this engine."""
        with self._cv:
            depth = len(self._queue)
            ready = len(self._ready)
            active = sum(s is not None for s in self._slots)
        return {
            "engine": self.name,
            "pool_slots": self.pool_slots,
            "active_slots": active,
            "queue_depth": depth,
            "ready": ready,
            "draining": self._draining,
            "tenant_quota": self._tenant_quota,
            "failed": repr(self._failed) if self._failed else None,
            "prefill_chunk": self.prefill_chunk,
            "max_len": self.max_len,
            "steps": self._steps,
            "tokens": self._tokens,
            "evictions": self._evictions,
            "kv_pool": self.pool.stats(),
        }
