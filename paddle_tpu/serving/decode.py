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

A lane may also declare an image encoder (serving/lane.py
``ImageEncoder``: a vision tower and its projector; models/kimi_vl.py).
A request may then carry images, whose encoded rows stand at the prompt
positions that hold the model's placeholder id.  The encoder runs INSIDE
the turn loop, one image at most a turn, just ahead of the first prefill
chunk that needs the image's rows (a chunk that needs a second image
waits a turn; the decode step of the turn runs all the same).  The rows
never leave the device: the encoder's program writes them into the
engine's one row-staging var (as large as the largest declared image
and one chunk, whatever the number of requests), the chunk's program
reads them from there by an index a position, nothing is fetched and
nothing is put, and a place is written over as soon as the chunk that
held its position has run.  An evicted request replays from token 0 and
its images are encoded again.  One executable an image shape, every
declared shape compiled by ``warmup()``.

A lane may declare state a SEQUENCE owns in some layers (serving/lane.py
``SeqState``: a linear-attention layer's recurrent state;
models/olmo_hybrid.py).  The pool then has the kind ``state`` beside its
page kinds: one block a sequence from ``open_seq`` to ``free_seq``.  A
prefilling sequence has a seq_id and no slot, so the programs find the
state by BLOCK INDEX, one piece more in each executable's feed
(``pf_state_block`` [1], ``dec_state_block`` [slots]; inactive slots and
warm-up name the trash block); the chunk whose ``pf_qstart`` is 0 reads
the block as zeros inside the program, so a block goes from one sequence
to the next without a host write, the state is carried from chunk to
chunk and from the last chunk into the decode steps, and an eviction
replays from token 0 as it always did.  A pool out of state blocks
raises what a pool out of pages raises, and the scheduler evicts.

Execution model — exactly TWO compiled signatures in steady state (and
one more an image shape where the lane declares an encoder):

  prefill chunk   the lane's ``build_prefill_chunk`` — [1, C] tokens of
                  ONE sequence, its cache rows written into whole pool
                  pages, attention over the sequence's prefix through
                  its page table.  A P-token prompt is ceil(P/C) calls.
  decode step     the lane's ``build_decode_step`` — [pool_slots]
                  one-token rows (batch-slot dim padded to the pool
                  size), scatter-write + read against the page tables,
                  greedy argmax out.

Each of the two takes ONE feed, an int32 buffer of a static length that
holds every scheduling table of the run (tokens, positions, page tables,
write pages, offsets, state blocks, the staged-row index) and is sliced
apart inside the program: one host transfer a run.  ``serving/lane.py``
owns the layout (``decode_layout`` / ``prefill_layout``); ``_decode_feed``
and ``_prefill_feed`` below build the tables and hand them to its
fillers by role.  So after the first prefill chunk and the
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
``encode.feed_build``, ``encode.run`` (an encoder run, where the lane has
one: dispatched and not waited for, so its span is the host's side only),
``decode.pages``, ``decode.feed_build``, ``decode.run``, ``emit`` (which
also books the pool's bytes in use by declared row tensor and its pages
by cache kind); a lane with window layers gives their dead pages back
in a second ``prefill.pages`` / ``decode.pages`` span right after the
run that moved the sequence on; the
executor's own phases are children of the two ``*.run`` spans, which also
feed ``pt_decode_step_seconds`` and ``pt_decode_phase_seconds_total``.

Which runs block (docs/SERVING.md "Which runs block" has the whole of
it).  A ``*.run`` span is a run that ends in a blocking fetch: the decode
step always, a prefill chunk only where its token is read, which is a
fresh prompt's LAST chunk (``final``).  Every other chunk is ENQUEUED, not
waited for: the same program and fetch list with ``return_numpy=False``
and no ``prefill.run`` span, so the executor's ``lookup`` .. ``fetch_sync``
lie directly under the turn, and admission, the step's feed and its
dispatch happen while the chunk runs (the device runs programs in
dispatch order and the pool's buffers chain through the scope).  The
engine keeps the outputs of at most two such chunks (``_in_flight``, each
with its ordinal in the in-flight ledger) and waits for them before a
third goes out, in the drain's flush, when the scheduler fails and when
it closes: each such wait is a ``prefill.await`` span with the note
``done#<ordinal>``.  Booked on ``pt_decode_prefill_unawaited_total``.

What the device holds (observability/profiling.py "in-flight ledger").
Every run's ``dispatch`` span carries ``jit_prefill_chunk#<ordinal>`` /
``jit_decode_step#<ordinal>``, and every wait marks what it proved
finished: the two ``*.run`` spans' ``fetch_wait`` (the executor's) and
``prefill.await`` (the scheduler's own).  From the wait on the newest
program until the next dispatch the chip stands empty, and the turn's
spans book those seconds on ``pt_device_starved_seconds_total{under}``.

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


def _m_prefill_head_runs():
    from paddle_tpu import observability as obs

    return obs.counter(
        "pt_decode_prefill_head_runs_total",
        "Prefill chunk executions that ran the vocabulary head (a fresh "
        "prompt's final chunk; every other chunk skips it)",
        labels=("engine",))


def _m_prefill_unawaited():
    from paddle_tpu import observability as obs

    return obs.counter(
        "pt_decode_prefill_unawaited_total",
        "Prefill chunk executions enqueued and not waited for: nobody "
        "reads their token (every chunk but a fresh prompt's final one), "
        "so the turn goes on while they run", labels=("engine",))


def _m_encoder_runs():
    from paddle_tpu import observability as obs

    return obs.counter(
        "pt_decode_encoder_runs_total",
        "Image-encoder executions inside the scheduler's turn loop, by "
        "image shape (one image a run; a replay after an eviction "
        "encodes again)", labels=("engine", "shape"))


def _m_prompt_tokens():
    from paddle_tpu import observability as obs

    return obs.counter(
        "pt_decode_prompt_tokens_total",
        "Prompt positions of admitted requests by what stands there: "
        "image (a row of an image encoder's output) or text (a token's "
        "embedding)", labels=("engine", "kind"))


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
        "decode_run are the host's time in the two executor runs (the "
        "blocking *.run spans; of a chunk that is not waited for, its "
        "enqueue), sched is the rest of the turn — pages, feed building, "
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
        "kind (full / window<W>; state: per-sequence state blocks)",
        labels=("engine", "kind"))


def _m_kind_bytes_in_use():
    from paddle_tpu import observability as obs

    return obs.gauge(
        "pt_kv_bytes_in_use",
        "Device bytes the pages (state: blocks) in use hold, by cache "
        "kind, each kind at its own rows' widths over its own layers",
        labels=("engine", "kind"))


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
        "tensor (k / v; latent / index), over every layer; for a lane "
        "with per-sequence state also the bytes the blocks in use hold, "
        "by declared state tensor",
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
                 "span", "t_first", "t_admit", "token_times", "images",
                 "image_spans")

    def __init__(self, prompt, max_new_tokens, eos_id, tenant, images=(),
                 image_spans=()):
        self.prompt = [int(t) for t in prompt]
        # the request's images as their encoder takes them
        # (lane.PreparedImage) and, for each, (first prompt position,
        # rows): where its rows stand
        self.images = list(images)
        self.image_spans = list(image_spans)
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
    more than ``num_pages``.  A lane that declares per-sequence state
    (``lane.seq_state``) gets ``state_blocks`` blocks of every state
    tensor, the trash block included: by default one a slot and one for
    the sequence that prefills (``pool_slots + 2``) — shrink it to
    exercise eviction."""

    def __init__(self, cfg, *, scope=None, place=None, pool_slots=4,
                 page_size=16, prefill_chunk=None, max_len=None,
                 num_pages=None, max_queue=None, pool_dtype=None,
                 attn_force=None, name="decode", auto_start=True,
                 tenant_quota=None, drain_on_sigterm=True,
                 int8_weights=False, state_blocks=None):
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
        state_kw = {}
        if lane.seq_state:
            if state_blocks is None:
                state_blocks = self.pool_slots + 2
            state_kw = {"seq_state": lane.seq_state,
                        "state_layers": lane.state_layers,
                        "state_blocks": int(state_blocks)}
        self.pool = KVPool(
            lane.num_layers, rows, num_pages, page_size, max_pages,
            layer_windows=windows, window_pages={
                _lane.kind_name(w): self.pool_slots
                * _lane.window_pages_per_seq(w, prefill_chunk, page_size) + 1
                for w in set(windows or ()) if w is not None}, **state_kw)
        self.pool.install(self.scope)
        # the builders of a lane with state size its tensors by this
        build_kw = ({"state_blocks": self.pool.state_blocks}
                    if lane.seq_state else {})
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

        # a lane with an image encoder: the one row-staging var beside the
        # pool, and the chunk's feed that indexes it
        self._enc = lane.encoder
        self._attn_force = attn_force
        self._encoders = {}     # image shape -> (program, feed names)
        chunk_kw = {}
        if self._enc is not None:
            self._max_image_rows = max(self._enc.rows_of(s)
                                       for s in self._enc.shapes)
            self._staging_rows = self._max_image_rows + prefill_chunk
            chunk_kw["image_rows"] = self._staging_rows
            self._install_row_staging()
        self._reset_staging()
        self._staged_live_max = 0

        # two programs, two fixed-shape executables — built once, against
        # the SAME parameter names the training lanes use
        dec_prog, dec_start = fluid.Program(), fluid.Program()
        with fluid.program_guard(dec_prog, dec_start), \
                fluid.unique_name.guard():
            self._dec_layout, dec_tok, _ = lane.build_decode_step(
                self.pool_slots, num_pages, page_size, max_pages,
                pool_dtype=pool_dtype, attn_force=attn_force, **build_kw)
        pf_prog, pf_start = fluid.Program(), fluid.Program()
        with fluid.program_guard(pf_prog, pf_start), \
                fluid.unique_name.guard():
            self._pf_layout, pf_tok, _ = lane.build_prefill_chunk(
                prefill_chunk, num_pages, page_size, max_pages,
                pool_dtype=pool_dtype, attn_force=attn_force, **chunk_kw,
                **build_kw)
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
        # (in-flight ledger ordinal, outputs) of the chunks enqueued and
        # not waited for, oldest first (never more than two); the
        # scheduler thread's alone
        self._in_flight = collections.deque()
        self._next_seq = 0
        self._steps = 0
        self._turns = 0
        # seconds inside the executor's runs of the turn under way
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
                       for p in ("prefill", "decode", "encode")}
        self._prompt_tokens = {
            k: _m_prompt_tokens().labels(engine=e, kind=k)
            for k in ("image", "text")}
        self._chunks = _m_prefill_chunks().labels(engine=e)
        self._head_runs = _m_prefill_head_runs().labels(engine=e)
        self._unawaited = _m_prefill_unawaited().labels(engine=e)
        self._occupancy = _m_slot_occupancy().labels(engine=e)
        self._pages_gauge = _m_pages_in_use().labels(engine=e)
        self._evict_ctr = _m_evictions().labels(engine=e)
        self._depth = _m_queue_depth().labels(engine=e)
        self._turn_ctr = _m_turns().labels(engine=e)
        self._turn_part = {
            p: _m_turn_seconds().labels(engine=e, part=p)
            for p in ("sched", "prefill_run", "decode_run", "encode_run")}
        self._queue_wait = _m_queue_wait().labels(engine=e)
        self._ttft = _m_ttft().labels(engine=e)
        self._token_gap = _m_token_gap().labels(engine=e)
        # a gauge a row NAME: rows declared by kind (lane.py) may carry
        # one name at a width a kind, and their bytes add up
        self._cache_bytes = {
            name: (_m_cache_bytes().labels(engine=e, row=name),
                   [r for r in self.pool.rows if r.name == name])
            for name in dict.fromkeys(r.name for r in self.pool.rows)}
        self._state_bytes = {
            st: _m_cache_bytes().labels(engine=e, row=st.name)
            for st in self.pool.seq_state}
        kinds = list(self.pool.kind_stats())  # the state kind with them
        self._kind_pages = {
            k: _m_kind_pages_in_use().labels(engine=e, kind=k)
            for k in kinds}
        self._kind_bytes = {
            k: _m_kind_bytes_in_use().labels(engine=e, kind=k)
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
               tenant="default", images=None):
        """Enqueue one greedy generation; returns a Future resolving to
        the generated token ids (list[int])."""
        return self.submit_request(prompt, max_new_tokens, eos_id=eos_id,
                                   tenant=tenant, images=images).future

    def _place_images(self, prompt, images):
        """The request's images as their encoder takes them, and where
        each one's rows stand: the prompt's placeholder ids, in order,
        one unbroken run an image, checked against the images' row
        counts here, at admission."""
        enc = self._enc
        if enc is None:
            if images:
                raise ValueError(
                    f"decode engine {self.name!r}: the model's decode lane "
                    f"declares no image encoder, the request carries "
                    f"{len(images)} image(s)")
            return [], []
        images = [im if isinstance(im, _lane.PreparedImage)
                  else enc.prepare(im) for im in images or ()]
        held = np.flatnonzero(np.asarray(prompt) == enc.placeholder_id)
        rows = sum(im.rows for im in images)
        if len(held) != rows:
            raise ValueError(
                f"decode: the prompt holds {len(held)} placeholder ids "
                f"({enc.placeholder_id}), its {len(images)} image(s) give "
                f"{rows} rows")
        spans, at = [], 0
        for k, im in enumerate(images):
            if im.rows > self._max_image_rows:
                raise ValueError(
                    f"decode: image {k} of shape {tuple(im.shape)} has "
                    f"{im.rows} rows, the largest declared shape "
                    f"{self._max_image_rows}")
            run = held[at:at + im.rows]
            if int(run[-1]) - int(run[0]) != im.rows - 1:
                raise ValueError(
                    f"decode: image {k}'s {im.rows} placeholder ids are "
                    f"not one unbroken run of the prompt")
            spans.append((int(run[0]), im.rows))
            at += im.rows
        return images, spans

    def submit_request(self, prompt, max_new_tokens, eos_id=None,
                       tenant="default", prefix=None, images=None):
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
        length.

        ``images`` (a lane with an image encoder): the request's images,
        in the order their rows stand in the prompt, each as the lane's
        ``ImageEncoder.prepare`` takes it or already prepared
        (``lane.PreparedImage``)."""
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
        images, spans = self._place_images(prompt, images)
        req = DecodeRequest(prompt, max_new_tokens, eos_id, tenant, images,
                            spans)
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
        image_rows = sum(n for _, n in spans)
        self._prompt_tokens["image"].inc(image_rows)
        self._prompt_tokens["text"].inc(len(prompt) - image_rows)
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
                 timeout=None, images=None):
        """Blocking convenience: submit every prompt, wait for all.
        Returns list[list[int]] of generated ids.  ``images``: per
        prompt, its images (or None)."""
        images = images if images is not None else [None] * len(prompts)
        futs = [self.submit(p, max_new_tokens, eos_id=eos_id, images=im)
                for p, im in zip(prompts, images)]
        return [f.result(timeout=timeout) for f in futs]

    def _warm_prefill_args(self):
        trash = np.zeros(self.prefill_chunk // self.pool.page_size, np.int32)
        return dict(
            tokens=[0], pos0=0, seq_id=None,
            write_pages={k: trash for k in self.pool.kinds}, valid=1,
            final=True)

    def warmup(self):
        """Compile (or AOT-load) every executable outside the request
        path: one all-inactive decode step, one trash-page prefill
        chunk and, where the lane declares an image encoder, one run of
        every declared image shape over zeros.  Writes land only on the
        pool's trash page and in the row staging, which no request owns
        yet.  Returns the number of executables warmed (2, and one a
        declared image shape)."""
        self._run_prefill_feed(**self._warm_prefill_args(), warm=True)
        self._run_decode_feed([], warm=True)
        shapes = self._enc.shapes if self._enc is not None else ()
        for shape in shapes:
            self._run_encoder_feed(self._warm_image(shape), 0, warm=True)
        # the warm chunk's one trash-page token is no traffic
        self._install_device_counters()
        return 2 + len(shapes)

    def lower(self, sharding=None):
        """AOT-lower the executables :meth:`warmup` compiles, without
        running them: ``[prefill_chunk, decode_step]`` and then one
        encoder a declared image shape (Executor.lower — ``sharding``
        over a topology device compiles them chip-free)."""
        encoders = []
        for shape in (self._enc.shapes if self._enc is not None else ()):
            prog, _ = self._encoder_for(shape)
            encoders.append(self._exe.lower(
                prog, self._encoder_feed(self._warm_image(shape), 0), [],
                scope=self.scope, sharding=sharding))
        return [
            self._exe.lower(self._pf_prog,
                            self._prefill_feed(**self._warm_prefill_args()),
                            [self._pf_fetch], scope=self.scope,
                            sharding=sharding),
            self._exe.lower(self._dec_prog, self._decode_feed([]),
                            [self._dec_fetch], scope=self.scope,
                            sharding=sharding),
        ] + encoders

    def drain(self, timeout=None):
        """Graceful drain (the decode lane's half of the
        `elastic.DrainHandler` contract): stop admission — new submits
        reject typed with ``reason="draining"`` — fail every QUEUED and
        prefill-pending future typed (their pool pages return), and let
        the sequences already IN decode slots run to completion instead
        of dying mid-batch.  Blocks up to `timeout` seconds for the
        in-flight work to finish (None = return immediately): the
        sequences in decode slots, and a prefill chunk enqueued and not
        waited for (the flush waits for it first).  The
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
        if self._thread is None or not self._thread.is_alive():
            # a running scheduler settles its chunks as it leaves
            self._settle_chunks()
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
                closed = self._closed
            if closed:
                self._settle_chunks()  # closed means nothing in flight
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
        self._await_chunks()  # drained means nothing in flight
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
                self._drop_staging_of(req)
            if req.future.set_running_or_notify_cancel():
                req.future.set_exception(self._reject(
                    "draining",
                    f"decode engine {self.name!r} drained before this "
                    f"request started decoding — resubmit to another "
                    f"replica"))
        if all(s is None for s in self._slots):
            self._drained.set()

    def _fail_all(self, exc):
        self._settle_chunks()
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
        for gauge, rows in self._cache_bytes.values():
            gauge.set(sum(self.pool.row_bytes(row, in_use) for row in rows))
        for st, gauge in self._state_bytes.items():
            gauge.set(self.pool.state_bytes(st, in_use[_lane.STATE]))
        for k, st in kinds.items():
            booked = self._kind_booked[k]
            self._kind_pages[k].set(st["pages_in_use"])
            self._kind_bytes[k].set(
                self.pool.kind_bytes(k, st["pages_in_use"]))
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
            self._drop_staging_of(req)  # the replay encodes again
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

    def _open_seq(self, req):
        """Open the request's sequence in the pool; a pool out of state
        blocks evicts as a pool out of pages does."""
        while True:
            try:
                return self.pool.open_seq(req.seq_id)
            except PoolExhaustedError:
                if not self._evict_one(protect=req):
                    raise  # fewer state blocks than one sequence needs

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
                self._open_seq(req)
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
        row_idx = None
        if req.image_spans:
            row_idx = self._stage_image_rows(req, ctx_len, valid)
            if row_idx is None:
                return  # the chunk needs one more image: the next turn's
        # the one chunk whose next token is read, and so the one that is
        # waited for: a fresh prompt's last (a resumed request replays
        # tokens it already has)
        seeds = ctx_len + valid == total and not req.generated
        next_tok = self._run_prefill_feed(
            tokens=tokens[ctx_len:ctx_len + valid], pos0=ctx_len,
            seq_id=req.seq_id, write_pages=write_pages, valid=valid,
            final=seeds, row_idx=row_idx)
        req.prefilled = ctx_len + valid
        self._release_below_window("prefill.pages", [(req, req.prefilled)])
        with _profiling.span("emit", "decode"):
            if req.prefilled == total:
                if seeds:
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

    # -- image rows -----------------------------------------------------------

    def _install_row_staging(self):
        """The row-staging var beside the pool: zeros on the device (kept
        if already there in this shape)."""
        import jax.numpy as jnp

        shape = (self._staging_rows, 1, self._enc.row_width)
        cur = self.scope.get(_lane.ROW_STAGING)
        if cur is None or tuple(np.shape(cur)) != shape:
            self.scope.set(_lane.ROW_STAGING, jnp.zeros(shape, jnp.float32))

    def _reset_staging(self):
        """Nothing staged: whose rows the staging holds (one request's,
        the one whose prompt is being prefilled), where each staged
        image's first row lies, and where the next image goes."""
        self._staged_for = None
        self._staged = {}        # image number -> place of its first row
        self._staging_head = 0

    def _drop_staging_of(self, req):
        if self._staged_for is req:
            self._reset_staging()

    def _encoder_for(self, shape):
        """The shape's encoder program, built (and its prepare program
        run, once) the first time the shape is asked for."""
        from paddle_tpu import fluid

        shape = tuple(shape)
        if shape not in self._encoders:
            prog, start = fluid.Program(), fluid.Program()
            with fluid.program_guard(prog, start), \
                    fluid.unique_name.guard():
                feeds, prepare = self._enc.build(
                    *shape, self._staging_rows,
                    attn_force=self._attn_force)
            # what a device trace calls it: jit_vision_encoder
            prog.name = "vision_encoder"
            if prepare is not None:
                with self._exec_lock:
                    self._exe.run(prepare, feed={}, fetch_list=[],
                                  scope=self.scope)
            self._encoders[shape] = (prog, feeds)
        return self._encoders[shape]

    def _warm_image(self, shape):
        """An image of ``shape`` that is all zeros, as its encoder takes
        it (the shapes of one prepared image of the shape's first run)."""
        prog, feeds = self._encoder_for(shape)
        block = prog.global_block()
        return _lane.PreparedImage(
            tuple(shape),
            {n: np.zeros(tuple(block.var(n).shape), block.var(n).dtype)
             for n in feeds if n != self._enc.places_feed},
            self._enc.rows_of(shape))

    def _encoder_feed(self, image, place):
        feed = dict(image.feeds)
        feed[self._enc.places_feed] = (
            (place + np.arange(image.rows)) % self._staging_rows
        ).astype(np.int32)
        return feed

    def _run_encoder_feed(self, image, place, warm=False):
        """One image through its shape's encoder, its rows written into
        the staging from ``place`` on (around its end).  Dispatched, not
        waited for: the chunk that reads the rows is ordered behind it
        on the device."""
        prog, _ = self._encoder_for(image.shape)
        with _profiling.span("encode.feed_build", "decode"):
            feed = self._encoder_feed(image, place)
        with self._exec_lock:
            with _profiling.span("encode.run", "decode") as run:
                self._exe.run(prog, feed=feed, fetch_list=[],
                              scope=self.scope)
        if not warm:
            self._phase["encode"].inc(run.seconds)
            self._turn_part["encode_run"].inc(run.seconds)
            self._turn_run_s += run.seconds
            _m_encoder_runs().labels(
                engine=self.name,
                shape="x".join(map(str, image.shape))).inc()

    def _stage_image_rows(self, req, ctx_len, valid):
        """The chunk's row index [1, C] (the staged row that stands at
        each position, -1 at a token's), after running the encoder for
        the first image the chunk needs and the staging lacks: one image
        a turn.  None where the chunk needs yet another: no chunk this
        turn.

        The staging is a ring that one request's images enter whole, in
        prompt order, each where the last one ended; a row is dead once
        the chunk that held its position has run.  When an image is
        encoded, every live row before it lies inside the chunk under
        way, so the largest image and one chunk of rows always
        suffice."""
        if self._staged_for is not req:
            self._reset_staging()
            self._staged_for = req
        end = ctx_len + valid
        spans, size = req.image_spans, self._staging_rows
        for k in [k for k in self._staged
                  if spans[k][0] + spans[k][1] <= ctx_len]:
            del self._staged[k]  # its last chunk has run
        needed = [k for k, (s, n) in enumerate(spans)
                  if s < end and s + n > ctx_len]
        missing = [k for k in needed if k not in self._staged]
        if missing:
            k = missing[0]
            live = sum(n - min(max(ctx_len - s, 0), n)
                       for s, n in (spans[j] for j in self._staged))
            rows = spans[k][1]
            if live + rows > size:
                raise RuntimeError(
                    f"decode engine {self.name!r}: {live} live staged rows "
                    f"and an image of {rows} do not fit the row staging "
                    f"of {size}")
            self._staged_live_max = max(self._staged_live_max, live + rows)
            self._run_encoder_feed(req.images[k], self._staging_head)
            self._staged[k] = self._staging_head
            self._staging_head = (self._staging_head + rows) % size
            if len(missing) > 1:
                return None
        idx = np.full((1, self.prefill_chunk), -1, np.int32)
        for k in needed:
            s, n = spans[k]
            lo, hi = max(s, ctx_len), min(s + n, end)
            idx[0, lo - ctx_len:hi - ctx_len] = (
                self._staged[k] + np.arange(lo - s, hi - s)) % size
        return idx

    def _prefill_feed(self, tokens, pos0, seq_id, write_pages, valid,
                      final, row_idx=None):
        """One chunk's feed (``lane.prefill_feed`` packs it into the one
        buffer): a page table and the chunk's write pages a cache kind;
        for a lane with an image encoder also the staged row a position
        (-1 throughout for a chunk of tokens)."""
        c = self.prefill_chunk
        tok = np.zeros((1, c), np.int64)
        tok[0, :len(tokens)] = tokens
        pos = np.minimum(pos0 + np.arange(c, dtype=np.int64),
                         self.lane.max_position - 1)[None, :]
        kinds = self.pool.kinds
        block = (np.asarray([self.pool.state_block(seq_id)], np.int32)
                 if self.lane.seq_state else None)
        if self._enc is not None:
            row_idx = (self._enc.index_feed,
                       np.full((1, c), -1, np.int32) if row_idx is None
                       else row_idx)
        return _lane.prefill_feed(
            tok, pos,
            {k: self.pool.padded_table(seq_id, k)[None, :].astype(np.int32)
             for k in kinds},
            {k: write_pages[k].astype(np.int32) for k in kinds},
            np.asarray([pos0], np.int32),
            np.asarray([max(valid - 1, 0)], np.int64),
            np.asarray([final], np.int32), block, row_idx)

    def _run_prefill_feed(self, tokens, pos0, seq_id, write_pages,
                          valid, final, warm=False, row_idx=None):
        """One chunk through the prefill executable.  Where ``final``
        (the head ran: somebody reads the token) the run is waited for
        and its next token returned.  Any other chunk is enqueued and the
        caller goes on at once, None returned: what follows it on the
        device is ordered behind it."""
        with _profiling.span("prefill.feed_build", "decode"):
            feed = self._prefill_feed(tokens, pos0, seq_id, write_pages,
                                      valid, final, row_idx)
        if final:
            with self._exec_lock:
                with _profiling.span("prefill.run", "decode") as run:
                    (out,) = self._exe.run(self._pf_prog, feed=feed,
                                           fetch_list=[self._pf_fetch],
                                           scope=self.scope)
            seconds = run.seconds
            token = int(np.asarray(out).reshape(-1)[0])
        else:
            # no `*.run` span: that name says the run ends in a blocking
            # fetch.  The host's time here is the enqueue and, in a turn
            # with no blocking step, the wait that holds it to two chunks
            # (the clock pair stays: `prefill.await` is the wait alone,
            # and the three counters below take wait + enqueue)
            t0 = time.perf_counter()  # observability: allow
            self._await_chunks(keep=1)
            with self._exec_lock:
                (out,) = self._exe.run(self._pf_prog, feed=feed,
                                       fetch_list=[self._pf_fetch],
                                       scope=self.scope, return_numpy=False)
                self._in_flight.append((self._exe.ordinal, out))
            seconds = time.perf_counter() - t0  # observability: allow
            token = None
        if not warm:
            self._phase["prefill"].inc(seconds)
            self._turn_part["prefill_run"].inc(seconds)
            self._turn_run_s += seconds
            self._chunks.inc()
            if final:
                self._head_runs.inc()
                self._await_chunks()  # finished before this one: no wait
            else:
                self._unawaited.inc()
        return token

    def _await_chunks(self, keep=0):
        """Wait for the chunks enqueued and not waited for, all but the
        newest ``keep``; a device error of one of them is raised here.
        Each wait is a ``prefill.await`` span that marks its chunk done
        in the in-flight ledger (a chunk the step behind it has already
        proved finished costs the span and moves nothing)."""
        import jax

        while len(self._in_flight) > keep:
            ordinal, out = self._in_flight.popleft()
            with _profiling.span("prefill.await", "decode") as wait:
                jax.block_until_ready(out)
                wait.note = _profiling.done(ordinal)

    def _settle_chunks(self):
        """`_await_chunks` where the engine is failing or closing
        already: nothing is left in flight, and no error is raised."""
        while self._in_flight:
            try:
                self._await_chunks()
            except Exception:
                continue  # the engine is going down on an error of its own

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
        """One step's feed (``lane.decode_feed`` packs it into the one
        buffer): a page table and the slots' write pages a cache kind."""
        ps, pgs = self.pool_slots, self.pool.page_size
        tok = np.zeros((ps, 1), np.int64)
        pos = np.zeros((ps, 1), np.int64)
        woff = np.zeros(ps, np.int32)
        for i, req in active:
            p = req.written_target  # the pending token's position
            tok[i, 0] = req.generated[-1]
            pos[i, 0] = p
            woff[i] = p % pgs
        tables, wpages = {}, {}
        for kind in self.pool.kinds:
            table = np.tile(self.pool.padded_table(None, kind), (ps, 1))
            wpage = np.zeros(ps, np.int32)
            for i, req in active:
                table[i] = self.pool.padded_table(req.seq_id, kind)
                wpage[i] = table[i][pos[i, 0] // pgs]
            tables[kind], wpages[kind] = table.astype(np.int32), wpage
        blocks = None
        if self.lane.seq_state:
            blocks = np.full(ps, TRASH_PAGE, np.int32)
            for i, req in active:
                blocks[i] = self.pool.state_block(req.seq_id)
        return _lane.decode_feed(tok, pos, tables, wpages, woff, blocks)

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
            self._await_chunks()  # finished before the step: no wait
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
            # a lane with an image encoder: the row staging's size and
            # the most live rows it ever held (else None)
            "image_rows": None if self._enc is None else {
                "staging_rows": self._staging_rows,
                "largest_image_rows": self._max_image_rows,
                "live_max": self._staged_live_max,
                "shapes_built": sorted(self._encoders)},
        }
