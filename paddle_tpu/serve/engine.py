"""Continuous-batching serving engine over the paged KV block pool.

The PagedAttention/vLLM (Kwon et al., 2023) + Orca iteration-level
scheduling (Yu et al., 2022) design, adapted to this repo's single-jit
decode architecture: ``models/generation.py`` gives you ONE batched
``generate`` call; this engine gives you a *server* — concurrent
streams that arrive, decode and finish independently while sharing one
fixed-shape compiled decode step and one paged KV pool.

Scheduling policy (the contract the tests pin):

- **Admission: FIFO.** ``submit()`` validates loudly (a request whose
  ``prompt + max_new_tokens`` exceeds ``max_seq_len``, or whose KV
  working set can never fit the pool, raises ``ValueError`` at submit
  time — it could never run) and appends to the queue. Each ``step()``
  admits from the queue head into free decode slots while the pool has
  blocks for the prompt; the head blocks the line (no skip-ahead), so
  admission order is completion-independent.
- **Continuous batching.** A finished stream frees its slot and blocks
  at the step it finishes; the next queued request prefills into that
  slot on the following ``step()`` while the other streams keep
  decoding — there is no batch barrier.
- **Eviction (preemption): youngest-first.** When a growing stream
  needs a KV block and the pool is empty, the most recently admitted
  active stream is evicted (a stream that is itself the youngest
  self-preempts): its blocks return to the pool and the request is
  re-queued at the FRONT with its generated tokens intact (on
  re-admission it re-prefills prompt+generated — vLLM's recompute
  strategy). The oldest stream is never a victim, so it always runs
  to completion and the engine cannot livelock.
- **One persistent compiled decode step.** Slot state (tokens, lengths,
  block tables, active mask, temperatures) rides as jit *data* at fixed
  ``[max_slots, ...]`` shapes, so admission/finish/preemption churn
  never retraces: ``serve.decode_traces`` stays at 1 for the life of
  the engine (the e2e test asserts exactly that). Prefill compiles once
  per power-of-two length bucket.
- **One decode program in flight ahead of the host.** A steady
  ``step()`` dispatches program N+1 BEFORE it reads program N's tokens:
  N+1 takes its input tokens from N's output as it lies on the device,
  and everything else it needs is known without them (each active
  stream's length grows by one; a stream that reaches
  ``max_new_tokens`` at N is left out of N+1; the block N+1 writes into
  is allocated a token ahead). The host then blocks on N, emits its
  tokens, and the next ``step()`` admits and dispatches N+2 while N+1
  runs. Only an ``eos`` hit is not known ahead: a stream that hits it
  at N still has ONE row in N+1, whose token the host throws away (the
  row is written into a block the stream held when N+1 was dispatched;
  every program is chained to the one before through the donated pool,
  so whoever is handed that block later writes and reads it after N+1).
  A prefill's first token is sampled on the host as ever, but its
  logits are read only after the step's decode program is out, so the
  new stream joins the program after. What must see the tokens first
  reads the program in flight and then runs as before
  (``serve.pipeline_drains``): a preemption, a fused burst, a step with
  nothing to decode. There is no other order: with nothing in flight
  the same code is the sequential loop.
- **A dispatch sends the device only what changed.** A decode program
  hands back the next program's slot state (its tokens, every active
  length one on, the masks) and the sampling key split once, and both
  stay on the device: while the same streams decode, a step builds and
  uploads nothing for them. The block tables (and rings) and the
  temperatures are kept there from their last upload and go up again
  only once the scheduler has written them. A step on which a stream
  came, went or got its first token, and the first after a drain or a
  burst, sends the host's mirrors afresh, which stay whole throughout
  (``serve.decode_uploads{what=state|tables|temps}`` over
  ``serve.decode_steps``: how often each went up).

What a decoder layer IS belongs to the model families: every compiled
step here runs ``models/decoder_stack.stack_layers`` over the view the
model hands over (``decode_view()``: arrays and one ``LayerSpec`` a
layer), the function ``generate()``'s dense-cache forward runs too, so
engine streams and ``generate()`` cannot drift. This module keeps the
CACHE and the SCHEDULE: it hands the stack ``write_kv`` (new K/V rows
into the paged pool, ``ops/pallas/kv_write``) and ``attn`` (through
``ops/pallas/paged_attention.paged_attention_decode`` — the decode-
specialized Pallas kernel on TPU, its jnp gather reference on CPU — or,
in a cold prefill, causal attention within the prompt), and for the
layers that keep another kind of cache ``ssm`` and ``mla``.

Telemetry: the ``serve.`` metric subsystem (claimed in
``observability.metrics.CLAIMED_SUBSYSTEMS``, label discipline audited
by ``tools/lint_registry.py``): queue depth, TTFT, tokens/sec,
preemptions, pool occupancy, batch fill ratio, per-step timings.

Per-request attribution rides on top of the aggregates:
``ServeEngine(trace=True)`` (or ``PADDLE_TPU_TRACE=1``) attaches an
``observability.tracing.ServeTracer`` whose host-side hooks — called
only from the scheduler path, never inside a compiled step, so
``serve.decode_traces`` stays at 1 — grow a span tree on every request
(queue -> prefill -> decode -> preempt -> resume -> recompute).
``ServeEngine(slo=[...])`` (or ``PADDLE_TPU_SLO``) adds an
``observability.slo.SloMonitor`` evaluated at every step boundary.
Both, plus all request timestamps, read the injectable ``clock``
(default ``time.perf_counter``) so load tests can run on a fake clock.

Always on, under the engine's ``name`` in ``observability.tracing``'s
bounded rings (they outlive the engine, as the registry's series do):
one STEP RECORD a ``step()`` — begin, end and the seconds by phase
(``admit``, ``prefill``, ``ensure_blocks``, ``dispatch``, ``wait``,
``emit`` and the ``other`` that is left: they sum to the step) — and one
REQUEST RECORD a request (id, submit, admit, first token, finish, the
warm-up flag). Counts (tokens, preemptions, programs dispatched) are NOT
copied into the records: the registry's counters hold them, and the
spans carry them as attributes. Each phase is an ``observability.span`` (``serve.step``
> ``serve.admit`` > ``serve.prefill``, ``serve.ensure_blocks``,
``serve.decode.dispatch`` / ``.wait`` / ``.emit``) with ONE clock pair,
which also lands on the host plane of any live profiler trace; the
records, the ``serve.*_seconds`` histograms and the tracer's decode
steps are all fed from that one measurement.
"""
from __future__ import annotations

import collections
import operator
import os
from functools import partial
import time
from dataclasses import dataclass, field
from typing import Deque, List, Optional

import numpy as np

from .. import observability as obs
from ..core.tensor import Tensor
from ..models import decoder_stack as _stack
from ..models import generation as _gen
from .pool import BlockPool, PoolExhaustedError
from .prefix import PrefixCache

__all__ = ["ServeEngine", "Request", "PoolExhaustedError"]

# --- serve. metric subsystem (prefix claimed in CLAIMED_SUBSYSTEMS) ----
_M_QUEUE_DEPTH = obs.gauge(
    "serve.queue_depth", "requests waiting for a decode slot")
_M_POOL_OCCUPANCY = obs.gauge(
    "serve.pool_occupancy", "fraction of KV pool blocks allocated")
_M_PAGES_LIVE = obs.counter(
    "serve.paged_pages_live", "pages the active streams hold at a decode "
    "step (ceil(length / block_size), the step's own token counted), "
    "summed over steps: what paged_decode walks")
_M_PAGES_TABLE = obs.counter(
    "serve.paged_pages_table", "entries of the full layers' block table "
    "(max_slots x its width), summed over decode steps: what a walk of "
    "the whole table would visit")
_M_SSM_ROWS_LIVE = obs.counter(
    "serve.ssm_rows_live", "state rows a decode step moves on: its live "
    "slots x the model's state-space layers, summed over steps (what "
    "ssm_decode walks)")
_M_SSM_ROWS_TABLE = obs.counter(
    "serve.ssm_rows_table", "max_slots x state-space layers, summed over "
    "decode steps: what a walk of every slot's state would visit")
_M_SSM_PREFILL_TOKENS = obs.counter(
    "serve.ssm_prefill_tokens", "prompt tokens a prefill's chunked scan "
    "went over (re-prefills after a preemption count again)")
_M_SSM_STATE_BYTES = obs.gauge(
    "serve.ssm_state_bytes", "bytes the recurrent state and convolution "
    "tails of all slots and state-space layers hold on the device")
_M_LATENT_BYTES = obs.gauge(
    "serve.latent_cache_bytes", "bytes the latent layers' pools hold on the "
    "device (one row a token and layer, padded to whole lane tiles)")
_M_LATENT_ROWS = obs.counter(
    "serve.latent_rows_written", "latent rows written into the pools: a "
    "prefill's tokens and a decode step's rows, times the latent layers")
_M_MLA_CTX = obs.counter(
    "serve.mla_ctx_tokens", "sum over decode steps of the decoding "
    "streams' lengths, the step's own token counted: the rows mla_decode "
    "reads in each latent layer")
_M_MLA_PAGES = obs.counter(
    "serve.mla_pages_computed", "sum over decode steps and decoding "
    "streams of the pages mla_decode's two products run over in each "
    "latent layer (ops/pallas/mla_decode.pages_computed of the same "
    "lengths): x block_size / serve.mla_ctx_tokens is what the kernel "
    "computes for a row it needs, 1 plus half a page a stream at best")
_M_BATCH_FILL = obs.gauge(
    "serve.batch_fill", "active streams / max_slots at the last step")
_M_TOKENS_PER_SEC = obs.gauge(
    "serve.tokens_per_sec", "aggregate generated tokens/sec over run()")
_M_ADMITTED = obs.counter(
    "serve.requests_admitted", "requests scheduled into a decode slot "
    "(re-admissions after preemption count again)")
_M_FINISHED = obs.counter(
    "serve.requests_finished", "requests completed, by reason "
    "(eos / max_new_tokens)")
_M_REJECTED = obs.counter(
    "serve.requests_rejected", "submissions refused at validation, by "
    "reason")
_M_PREEMPTIONS = obs.counter(
    "serve.preemptions", "streams evicted mid-decode, by reason")
_M_STALLS = obs.counter(
    "serve.admission_stalls", "scheduler passes where the queue head "
    "could not be admitted, by reason")
_M_TOKENS = obs.counter(
    "serve.tokens_generated", "tokens emitted across all streams")
_M_DECODE_STEPS = obs.counter(
    "serve.decode_steps", "batched decode steps dispatched")
_M_DECODE_OVERLAPPED = obs.counter(
    "serve.decode_overlapped", "decode programs dispatched while the one "
    "before was still unread: the host's work of that step ran behind "
    "the device (over serve.decode_steps: how often the pipeline holds)")
_M_DECODE_UPLOADS = obs.counter(
    "serve.decode_uploads", "arrays a decode dispatch sent up because the "
    "device's copy no longer said what the host's does, by what (state: "
    "the slots' tokens, lengths and masks; tables: the block tables and "
    "rings; temps: the temperatures); 1 - this over serve.decode_steps is "
    "the share of programs that found the device's copy good")
_M_PIPELINE_DRAINS = obs.counter(
    "serve.pipeline_drains", "times the decode program in flight was "
    "read before the next could be dispatched, by reason (preempt: the "
    "pool ran dry; burst: a fused burst follows; idle: no stream had a "
    "token to decode)")
_M_DECODE_TRACES = obs.counter(
    "serve.decode_traces", "times the persistent decode step was "
    "traced — slot churn must keep this at 1 per engine")
_M_PREFILL_TRACES = obs.counter(
    "serve.prefill_traces", "prefill compiles, by length bucket")
_M_KV_WRITE_TRACES = obs.counter(
    "serve.kv_write_traces", "compiled programs by the path their K/V "
    "rows take into the pool (ops/pallas/kv_write.py: rows, blocks or "
    "reference), one count a traced program")
_M_TTFT = obs.histogram(
    "serve.ttft_seconds", "submit -> first generated token wall time "
    "(queue wait included)")
_M_REQUEST_SECONDS = obs.histogram(
    "serve.request_seconds", "submit -> finish wall time per request")
_M_DECODE_SECONDS = obs.histogram(
    "serve.decode_step_seconds", "wall time of one batched decode step")
_M_PREFILL_SECONDS = obs.histogram(
    "serve.prefill_seconds", "wall time of one prefill call")
_M_PREFIX_HITS = obs.counter(
    "serve.prefix_hits", "admissions that mounted shared KV blocks "
    "from the prefix cache")
_M_PREFIX_BLOCKS = obs.counter(
    "serve.prefix_blocks_shared", "full KV blocks mounted read-only "
    "from the prefix cache at admission — prefill was skipped for "
    "those tokens")
_M_COW = obs.counter(
    "serve.cow_copies", "copy-on-write block duplications where a "
    "stream diverged inside a shared prefix block")
_M_BURST_TOKENS = obs.counter(
    "serve.burst_tokens", "tokens generated inside fused multi-step "
    "decode bursts (the on-chip lax.scan path)")
_M_MOE_ROUTED = obs.counter(
    "serve.moe_tokens_routed", "decoded tokens that passed a sparse "
    "layer's router (tokens x sparse layers)")
_M_MOE_HELD = obs.counter(
    "serve.moe_assignments_held", "of those tokens' top-k assignments, "
    "the ones that fell on an expert this engine holds")
_M_MOE_HELD_GROUP = obs.counter(
    "serve.moe_tokens_to_held_group", "of the tokens routed by a "
    "group-limited router, those whose kept groups include one this "
    "engine holds experts of (tokens x sparse layers)")
_M_MOE_MAX = obs.counter(
    "serve.moe_expert_tokens_max", "a decode step's largest held "
    "expert's tokens, summed over steps, by sparse layer")
_M_MOE_SUM = obs.counter(
    "serve.moe_expert_tokens_sum", "a decode step's tokens on all held "
    "experts, summed over steps, by sparse layer")
_M_HOST_RT = obs.counter(
    "serve.host_roundtrips", "host->device decode dispatches — one "
    "per burst, so decode_burst=N cuts this ~N x per token")

QUEUED = "QUEUED"
RUNNING = "RUNNING"
FINISHED = "FINISHED"

#: what a step record's seconds are split into; ``other`` is the rest of
#: the step (gauges, SLO and health hooks, the clock reads themselves)
STEP_PHASES = ("admit", "prefill", "ensure_blocks", "dispatch", "wait",
               "emit", "other")
#: orders a step record's ``spans``, each ``(phase, start, end)``
_span_start = operator.itemgetter(1)


@dataclass
class Request:
    """One stream: prompt in, tokens out, scheduling state in between."""

    id: int
    prompt: np.ndarray                     # [t0] int32
    max_new_tokens: int
    eos_token_id: Optional[int] = None
    temperature: float = 0.0               # 0.0 = greedy
    submit_time: float = 0.0
    admit_time: Optional[float] = None     # FIRST admission into a slot
    first_token_time: Optional[float] = None
    finish_time: Optional[float] = None
    finish_reason: Optional[str] = None
    state: str = QUEUED
    ids: List[int] = field(default_factory=list)   # prompt + generated
    blocks: List[int] = field(default_factory=list)
    # the slot's ring in the window layers' pool (engines with such layers)
    window_blocks: List[int] = field(default_factory=list)
    slot: Optional[int] = None
    admit_seq: int = -1                    # recency rank for eviction
    preemptions: int = 0
    warmup: bool = False                   # excluded from TTFT telemetry
    # prefix-cache bookkeeping: trie registration cursor, how many full
    # blocks of ids are already covered by the trie, and sharing stats
    # (blocks mounted from the cache at the LAST admission; tokens this
    # request actually prefilled across all admissions — suffix-only
    # when the cache hit)
    prefix_node: Optional[object] = field(default=None, repr=False)
    registered_upto: int = 0
    shared_blocks: int = 0
    prefilled_tokens: int = 0
    # span tree (observability.tracing.RequestTrace) when the engine
    # runs with tracing enabled; None otherwise
    trace: Optional[object] = field(default=None, repr=False)
    # this request's entry in the engine's request ring (always on),
    # filled in as the request moves
    record: Optional[dict] = field(default=None, repr=False)

    @property
    def n_prompt(self) -> int:
        return len(self.prompt)

    @property
    def n_generated(self) -> int:
        return len(self.ids) - self.n_prompt

    @property
    def output_ids(self) -> List[int]:
        """Generated tokens only (prompt excluded)."""
        return self.ids[self.n_prompt:]

    @property
    def ttft(self) -> Optional[float]:
        if self.first_token_time is None:
            return None
        return self.first_token_time - self.submit_time


@dataclass
class _Program:
    """One dispatched decode program whose tokens the host has not read."""

    nxt: object                  # [max_slots] tokens, on the device
    sizes: object                # the sparse layers' group sizes, flat
    reqs: List[Optional[Request]]   # by slot: whose row it computes
    record: dict                 # its entry-to-be in the ring of programs
    state: object                # the slot state it leaves, on the device
    active: np.ndarray           # [max_slots] bool: the rows it computes


class ServeEngine:
    """Continuous-batching server over a paged KV pool (module docstring
    has the admission/eviction contract). Any family whose
    ``decode_view()`` gives one ``LayerSpec`` a layer with FFN kinds that
    work row by row: the layers themselves are
    ``models/decoder_stack.py``'s, and what the engine reads of a layer
    is its spec (the mixer's kind, the window), never its family.

    Four kinds of per-layer cache: a full-attention layer's pool is the
    block table the docstring describes (``num_blocks`` counts its
    blocks); a sliding-window layer keeps a RING of ``ceil(window /
    block_size) + 1`` blocks a slot in a pool of its own
    (``window_pool``), taken with the slot and given back with it,
    whatever the stream's length; a state-space (``mamba2``) layer keeps
    one row a slot of a recurrent state (``heads x dh x n`` numbers, in
    ``ops/pallas/ssm_decode.state_shape``'s layout) and of its
    convolution's last inputs ``[taps - 1, max_slots, channels]``
    (the taps lead, so that the channels fill the lanes and no tile is
    padded), in the model's type, of a fixed size whatever the stream's
    length. A slot brings its row: it is overwritten by the prompt's
    prefill (a chunked scan from a zero state), moved on in place by
    each decode program for the rows that decode, left as it lies when
    the stream goes (a preempted stream's is rebuilt from its tokens).
    Neither a ring nor a recurrent state can be shared, so
    ``prefix_cache`` is refused for such models. A latent-attention
    (``mla``) layer keeps ONE pool of one row a token, ``[1, num_blocks,
    block_size, lanes]`` (the row ``[c | k_pe]`` padded with zeros to whole
    128-lane tiles: ``ops/mla.py:row_lanes``), under the full layers'
    block table and everything the scheduler does with it: a latent block
    is admitted, shared by the prefix cache, copied on write, preempted
    and released like any block. A prompt attends with its own rows
    expanded to per-head keys and values (the flash forward, a query-key
    head of ``nope + rope`` and a value head of ``v``); every row that is
    read from the pool is read as it lies, by ``ops/pallas/mla_decode``
    with the expansion absorbed into the query.

    Usage::

        eng = ServeEngine(model, max_slots=4, block_size=32,
                          num_blocks=64, max_seq_len=256)
        r1 = eng.submit(prompt_ids, max_new_tokens=32, eos_token_id=2)
        r2 = eng.submit(other_ids, max_new_tokens=64)
        eng.run()                      # or step() from your own loop
        print(r1.output_ids, r1.ttft)
    """

    def __init__(self, model, *, max_slots: int = 4, block_size: int = 32,
                 num_blocks: int = 64, max_seq_len: int = 256,
                 seed: int = 0, name: str = "default",
                 attention_backend: str = "auto", clock=None,
                 trace=None, slo=None, prefix_cache=None,
                 decode_burst=None):
        """``clock`` is a zero-arg callable returning seconds (default
        ``time.perf_counter``) — every request timestamp, tracer span
        and SLO window reads it, so tests inject a fake. ``trace`` is
        True/False, a ready ``ServeTracer``, or None to read
        ``PADDLE_TPU_TRACE``. ``slo`` is a rule list (``SloRule``/
        dicts/JSON), a ready ``SloMonitor``, or None to read
        ``PADDLE_TPU_SLO``. ``prefix_cache`` is True/False or None to
        read ``PADDLE_TPU_PREFIX_CACHE`` (cross-request KV block
        sharing — see ``serve/prefix.py``). ``decode_burst`` is the
        max number of decode steps fused into one on-chip ``lax.scan``
        dispatch (None reads ``PADDLE_TPU_DECODE_BURST``, default 1 =
        the PR-14 one-roundtrip-per-token loop)."""
        import jax

        p = _gen._decode_family(model)
        self._specs = p["specs"]
        if not all(_stack.FFN_KINDS[s.ffn].per_row for s in self._specs):
            # (a capacity computed over the call's tokens makes a stream
            # depend on what it is batched with)
            kinds = sorted({s.ffn for s in self._specs
                            if not _stack.FFN_KINDS[s.ffn].per_row})
            raise NotImplementedError(
                f"ServeEngine batches streams that do not know of each "
                f"other, so every FFN kind has to work row by row; "
                f"{type(model).__name__} has {kinds} (a capacity "
                f"computed over a call's tokens): such a model decodes "
                f"on the dense path, through generate()")
        #: the layers whose FFN is sparse (their group sizes come back
        #: from a decode step in this order)
        self._sparse = [i for i, s in enumerate(self._specs)
                        if s.ffn == "moe"]
        windows = {s.window for s in self._specs if s.window is not None}
        if len(windows) > 1:
            raise NotImplementedError(
                f"one window size an engine, got {sorted(windows)}")
        #: the sliding layers' window, or None where every layer is full
        self.window = windows.pop() if windows else None
        #: the state-space layers (each keeps a state row a slot)
        self._mamba = [i for i, s in enumerate(self._specs)
                       if s.mixer == "mamba2"]
        #: the latent-attention layers (each keeps one pool of rows)
        self._mla = [i for i, s in enumerate(self._specs)
                     if s.mixer == "mla"]
        max_pos = p.get("max_positions")
        if max_pos is not None and max_seq_len > max_pos:
            raise ValueError(
                f"max_seq_len ({max_seq_len}) exceeds the model's "
                f"learned position table (max_position_embeddings="
                f"{max_pos})")
        if max_slots < 1:
            raise ValueError(
                f"max_slots must be >= 1, got {max_slots} — with no "
                f"decode slot nothing can ever be admitted and every "
                f"driver loop would spin forever")
        self.name = str(name)
        self.max_slots = int(max_slots)
        self.block_size = int(block_size)
        self.max_seq_len = int(max_seq_len)
        self._clock = clock if clock is not None else time.perf_counter
        self.max_blocks_per_seq = -(-self.max_seq_len // self.block_size)
        self.pool = BlockPool(num_blocks, block_size)
        #: blocks of a slot's ring, and the window layers' pool
        self.ring_blocks = (0 if self.window is None
                            else -(-self.window // self.block_size) + 1)
        self.window_pool = (None if self.window is None else BlockPool(
            self.max_slots * self.ring_blocks, block_size))
        from ..ops.pallas.paged_attention import resolve_backend

        #: the attention path every compiled step of this engine takes:
        #: "kernel" (Mosaic-compiled), "interpret" or "reference"
        self.attention_backend = resolve_backend(attention_backend)

        self._static = {k: v for k, v in p.items()
                        if not hasattr(v, "dtype")
                        and not isinstance(v, list)}
        self._arrays = {k: v for k, v in p.items() if k not in self._static}
        self._nh, self._nkv, self._dh = p["nh"], p["nkv"], p["dh"]
        #: what the scores are multiplied by
        self._scale = p.get("attn_scale", self._dh ** -0.5)
        self._dtype = p["embed"].dtype
        #: K/V heads that share a pool row's 128 lanes. The kernels move
        #: whole lane tiles (Mosaic refuses a slice of 64 of 128 lanes,
        #: and a pool padded to them would be twice its bytes), so heads
        #: narrower than a tile lie side by side, ``pack`` of them a row:
        #: the pool is ``[kvh / pack, blocks, block_size, pack * dh]``,
        #: a new row is its heads reshaped, and a query is laid into its
        #: key-value head's lanes with zeros in the others (so that the
        #: product over the row is its own head's) and reads its head's
        #: lanes of the result. The jnp reference has no tiles to fill.
        lanes = 128
        pack = lanes // self._dh if lanes % self._dh == 0 else 1
        self._pack = pack if (self.attention_backend != "reference"
                              and self._nkv % pack == 0) else 1
        import jax.numpy as jnp

        def cache_of(spec):
            if spec.mixer == "mamba2":
                from ..ops.pallas.ssm_decode import state_shape

                st = p["ssm"]
                return (jnp.zeros((st["taps"] - 1, self.max_slots,
                                   st["channels"]), self._dtype),
                        jnp.zeros(state_shape(self.max_slots, st["heads"],
                                              st["dh"], st["n"]),
                                  self._dtype))
            if spec.mixer == "mla":
                from ..ops.mla import row_lanes

                return (jnp.zeros((1, self.pool.num_blocks, self.block_size,
                                   row_lanes(p["mla"])), self._dtype),)
            pool = self.pool if spec.window is None else self.window_pool
            shape = (self._nkv // self._pack, pool.num_blocks,
                     self.block_size, self._dh * self._pack)
            return jnp.zeros(shape, self._dtype), jnp.zeros(shape,
                                                            self._dtype)

        #: one tuple a layer: (K, V) pools, (tail, state) by slot, or
        #: the one pool of latent rows
        self._caches = [cache_of(s) for s in self._specs]
        if self._mla:
            _M_LATENT_BYTES.set(
                sum(a.nbytes for i in self._mla for a in self._caches[i]),
                engine=self.name)
        if self._mamba:
            _M_SSM_STATE_BYTES.set(
                sum(a.nbytes for i in self._mamba for a in self._caches[i]),
                engine=self.name)

        # host-side slot state (jit DATA — shapes never change)
        self._slots: List[Optional[Request]] = [None] * self.max_slots
        self._tables = np.zeros(
            (self.max_slots, self.max_blocks_per_seq), np.int32)
        # a slot's ring of window-layer blocks (no column where no layer
        # has a window: the compiled steps then take the full table alone)
        self._rings = np.zeros((self.max_slots, self.ring_blocks), np.int32)
        self._lens = np.zeros(self.max_slots, np.int32)
        self._tokens = np.zeros(self.max_slots, np.int32)
        self._temps = np.zeros(self.max_slots, np.float32)
        # per-slot eos ids (-1 = none) ride into the fused burst so eos
        # latching can happen inside the scan
        self._eos = np.full(self.max_slots, -1, np.int32)

        if prefix_cache is None:
            prefix_cache = os.environ.get(
                "PADDLE_TPU_PREFIX_CACHE", "").strip().lower() in (
                    "1", "true", "yes", "on")
        if prefix_cache and (self.window is not None or self._mamba):
            raise NotImplementedError(
                "prefix_cache with sliding-window layers: a slot's ring "
                "of window blocks is overwritten as the stream grows and "
                "cannot be shared between streams" if not self._mamba else
                "prefix_cache with state-space layers: a slot's recurrent "
                "state is overwritten at every token and holds no copy of "
                "what it was at a block's end, so a prefix's blocks "
                "cannot be mounted without the state that goes with them")
        self._prefix: Optional[PrefixCache] = (
            PrefixCache(self.block_size) if prefix_cache else None)
        if decode_burst is None:
            decode_burst = int(
                os.environ.get("PADDLE_TPU_DECODE_BURST", "").strip()
                or 1)
        if int(decode_burst) < 1:
            raise ValueError(
                f"decode_burst must be >= 1, got {decode_burst}")
        self.decode_burst = int(decode_burst)
        # pow2 burst lengths actually dispatched — each is one compiled
        # scan, so serve.decode_traces == len(burst_lens_used) in burst
        # mode (the bounded-trace contract the tests pin)
        self.burst_lens_used: set = set()

        self.queue: Deque[Request] = collections.deque()
        self.finished: List[Request] = []
        self.decode_traces = 0
        self.prefill_traces = 0
        self._next_id = 0
        self._admit_counter = 0
        # lifetime totals the step-boundary SLO evaluation differences
        self._n_tokens = 0
        self._n_preempts = 0
        self._n_steps = 0
        self._step_ring = obs.tracing.ring(self.name, "steps")
        self._request_ring = obs.tracing.ring(self.name, "requests")
        self._program_ring = obs.tracing.ring(self.name, "programs")
        # seconds by phase of the step under way, and its phases' spans
        # as they ran (``_phase``)
        self._secs = dict.fromkeys(STEP_PHASES, 0.0)
        self._spans: List[tuple] = []
        self._key = jax.random.PRNGKey(seed)
        self._rng = np.random.default_rng(seed)
        #: the decode program dispatched and not yet read, if any
        self._inflight: Optional[_Program] = None
        #: prefills whose first token is still to be read: (request,
        #: logits on the device, the program's record-to-be)
        self._first_tokens: List[tuple] = []
        # what a program takes for "the tokens before" with none in flight
        self._no_tokens = jnp.zeros(self.max_slots, jnp.int32)
        # by what (tables, temps): the host arrays as a decode dispatch
        # last sent them, and the device's copies (``_resident``)
        self._sent: dict = {}
        # the caches are DONATED (argument 1 after the bound self):
        # the engine replaces self._caches with the returned pool every
        # call, so in-place aliasing is safe — and without it every
        # decode tick would COPY the entire pool (≈1 GB/token at the
        # 10-layer/96x128-block bf16 serving shape)
        self._decode_fn = jax.jit(self._decode_impl, donate_argnums=(1,))
        self._prefill_fn = jax.jit(self._prefill_impl,
                                   donate_argnums=(1,))
        # prefix-cache companions: suffix prefill (attends through the
        # block table so suffix tokens see the shared resident prefix)
        # and the copy-on-write block duplication; fused decode burst
        # (n static -> one trace per pow2 burst length)
        self._suffix_prefill_fn = jax.jit(self._suffix_prefill_impl,
                                          donate_argnums=(1,))
        self._cow_fn = jax.jit(self._cow_impl, donate_argnums=(0,))
        self._burst_fn = jax.jit(self._burst_impl, static_argnums=(0,),
                                 donate_argnums=(2,))

        # request-lifecycle tracing + SLO guardrails (both host-side
        # scheduler-path bookkeeping; the compiled steps never see them)
        from ..observability import slo as _slo_mod
        from ..observability import tracing as _tracing_mod

        if trace is None:
            trace = _tracing_mod.trace_enabled_from_env()
        if isinstance(trace, _tracing_mod.ServeTracer):
            self.tracer: Optional[_tracing_mod.ServeTracer] = trace
        elif trace:
            self.tracer = _tracing_mod.ServeTracer(
                self.name, self._clock, max_slots=self.max_slots)
        else:
            self.tracer = None
        if slo is None:
            slo = _slo_mod.rules_from_env() or None
        if isinstance(slo, _slo_mod.SloMonitor):
            self.slo: Optional[_slo_mod.SloMonitor] = slo
        elif slo:
            self.slo = _slo_mod.SloMonitor(
                slo, engine=self.name, clock=self._clock,
                exemplars=(self.tracer.exemplars if self.tracer
                           else None))
        else:
            self.slo = None

    # -- submission --------------------------------------------------------
    def submit(self, prompt, max_new_tokens: int = 32,
               eos_token_id: Optional[int] = None,
               temperature: float = 0.0,
               warmup: bool = False) -> Request:
        """Validate and enqueue one stream (FIFO). Raises ``ValueError``
        for requests that could NEVER run — too long for
        ``max_seq_len``, or a KV working set larger than the whole pool
        — instead of failing later with a corrupted gather. A request
        that merely has to WAIT for blocks is queued, not refused.
        ``warmup`` marks a compile-warming request whose TTFT (which
        bills the XLA compile, not serving latency) must stay out of
        the ``serve.ttft_seconds`` histogram."""
        if isinstance(prompt, Tensor):
            prompt = np.asarray(prompt._value)
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size == 0:
            _M_REJECTED.inc(engine=self.name, reason="empty_prompt")
            raise ValueError("submit: prompt is empty")
        if max_new_tokens < 1:
            _M_REJECTED.inc(engine=self.name, reason="bad_max_new_tokens")
            raise ValueError(
                f"submit: max_new_tokens must be >= 1, got "
                f"{max_new_tokens}")
        total = prompt.size + int(max_new_tokens)
        if total > self.max_seq_len:
            _M_REJECTED.inc(engine=self.name, reason="too_long")
            raise ValueError(
                f"submit: prompt ({prompt.size}) + max_new_tokens "
                f"({max_new_tokens}) = {total} exceeds the engine's "
                f"max_seq_len ({self.max_seq_len})")
        # the last generated token is emitted but never written back,
        # so the KV working set is total - 1 positions
        need = self.pool.blocks_for_tokens(total - 1)
        if need > self.pool.num_blocks or (
                self.window_pool is not None
                and self.ring_blocks > self.window_pool.num_blocks):
            _M_REJECTED.inc(engine=self.name, reason="pool_too_small")
            raise ValueError(
                f"submit: request needs {need} KV blocks "
                f"(block_size={self.block_size}) but the whole pool is "
                f"{self.pool.num_blocks} — it can never be admitted"
                + ("" if self.window_pool is None else
                   f" (and a ring of {self.ring_blocks} of the window "
                   f"pool's {self.window_pool.num_blocks})"))
        req = Request(
            id=self._next_id, prompt=prompt,
            max_new_tokens=int(max_new_tokens),
            eos_token_id=(None if eos_token_id is None
                          else int(eos_token_id)),
            temperature=float(temperature),
            submit_time=self._clock(),
            ids=[int(t) for t in prompt], warmup=bool(warmup))
        self._next_id += 1
        self.queue.append(req)
        req.record = {
            "id": req.id, "submit": req.submit_time, "admit": None,
            "first_token": None, "finish": None, "warmup": req.warmup}
        self._request_ring.append(req.record)
        if self.tracer is not None and not req.warmup:
            self.tracer.on_submit(req)
        _M_QUEUE_DEPTH.set(len(self.queue), engine=self.name)
        return req

    # -- engine loop -------------------------------------------------------
    @property
    def n_active(self) -> int:
        """Streams currently holding a decode slot."""
        return sum(1 for r in self._slots if r is not None)

    @property
    def has_work(self) -> bool:
        """True while anything is queued or decoding, or a dispatched
        program's tokens are still to be read."""
        return (bool(self.queue) or self._inflight is not None
                or any(r is not None for r in self._slots))

    def step(self) -> int:
        """One scheduler iteration: admit from the queue into free
        slots (their prefills are dispatched), dispatch ONE batched
        decode step for every stream that has a token to decode, read
        the step dispatched before it and retire the streams that
        finish, then read the new streams' first tokens. Returns the
        number of streams that held a slot this step."""
        serving_real_work = self.slo is not None and any(
            not r.warmup for r in self._live_requests())
        tok0, pre0 = self._n_tokens, self._n_preempts
        secs = self._secs = dict.fromkeys(STEP_PHASES, 0.0)
        spans = self._spans = []
        with self._span("serve.step", step=self._n_steps,
                        queued=len(self.queue)) as whole:
            with self._span("serve.admit") as sp:
                sp.note(admitted=self._admit())
            self._phase("admit", sp)
            secs["admit"] -= secs["prefill"]
            n_active = self.n_active
            if self.decode_burst > 1:
                # a burst carries its streams' tokens on from the host's
                self._sync("burst")
                if self.n_active:
                    self._decode_burst_once()
            else:
                self._decode_once()
            self._read_first_tokens()
            whole.note(n_active=n_active)
            _M_QUEUE_DEPTH.set(len(self.queue), engine=self.name)
            _M_POOL_OCCUPANCY.set(round(self.pool.occupancy, 4),
                                  engine=self.name)
            if self.window_pool is not None:
                for kind, pool in (("full", self.pool),
                                   ("window", self.window_pool)):
                    _M_POOL_OCCUPANCY.set(round(pool.occupancy, 4),
                                          engine=self.name, kind=kind)
            if self._mamba:
                _M_POOL_OCCUPANCY.set(
                    round(self.n_active / self.max_slots, 4),
                    engine=self.name, kind="state")
            _M_BATCH_FILL.set(round(n_active / self.max_slots, 4),
                              engine=self.name)
            if serving_real_work:
                # step-boundary SLO evaluation — skipped while the only
                # work is compile-warming (whose throughput/TTFT would
                # bill XLA, not serving)
                self.slo.on_step(tokens=self._n_tokens - tok0,
                                 preemptions=self._n_preempts - pre0,
                                 now=self._clock())
            obs.health.maybe_on_step(self._clock())
        secs["other"] = whole.seconds - sum(secs.values())
        # a span is put down as it closes: an admission after the
        # prefills inside it
        spans.sort(key=_span_start)
        self._step_ring.append(
            {"step": self._n_steps, "begin": whole.start, "end": whole.end,
             "seconds": secs, "spans": spans})
        self._n_steps += 1
        return n_active

    def _span(self, name: str, **attrs):
        """A phase of the step, on the engine's clock."""
        return obs.span(name, clock=self._clock, **attrs)

    def _phase(self, phase: str, sp):
        """Put a closed span down to ``phase`` (one of ``STEP_PHASES``) of
        the step under way: its seconds and its interval. Outside any
        step, as nothing of the engine's runs, it would add to a record
        that nobody reads."""
        self._secs[phase] += sp.seconds
        self._spans.append((phase, sp.start, sp.end))

    def _live_requests(self):
        for r in self.queue:
            yield r
        for r in self._slots:
            if r is not None:
                yield r

    def run(self, max_steps: int = 100_000) -> List[Request]:
        """Drive :meth:`step` until queue and slots drain; returns the
        finished requests. Sets ``serve.tokens_per_sec`` over the run."""
        t0 = self._clock()
        tok0 = sum(r.n_generated for r in self.finished)
        steps = 0
        while self.has_work:
            self.step()
            steps += 1
            if steps > max_steps:
                raise RuntimeError(
                    f"run(): exceeded max_steps={max_steps} with "
                    f"{len(self.queue)} queued and "
                    f"{sum(1 for r in self._slots if r)} active — "
                    f"scheduler is not making progress")
        dt = self._clock() - t0
        n_tok = sum(r.n_generated for r in self.finished) - tok0
        if dt > 0 and n_tok:
            _M_TOKENS_PER_SEC.set(round(n_tok / dt, 2), engine=self.name)
        return self.finished

    # -- scheduling --------------------------------------------------------
    def _table_args(self, slot: Optional[int] = None) -> tuple:
        """The block tables as a compiled step takes them: the full
        layers' table and, where layers have a window, the rings; one
        slot's rows of each for a prefill, and then, where layers keep a
        recurrent state, the slot itself."""
        import jax.numpy as jnp

        # a private numpy copy made NOW goes up, never the live table: on
        # the CPU ``jnp.asarray`` may alias an aligned numpy buffer, and
        # ``jnp.array`` is a program that reads the (aliased) buffer when
        # the device gets to it, while the scheduler writes these tables
        # in place between steps: a slot cleared straight after its
        # prefill went out (a preemption) sent that prefill's rows
        # through a zeroed table into another stream's block
        hosts = self._host_tables()
        if slot is not None:
            hosts = tuple(a[slot] for a in hosts)
        tables = tuple(jnp.asarray(a.copy()) for a in hosts)
        if self._mamba and slot is not None:
            # the row of the state arrays a prefill writes
            tables += (jnp.int32(slot),)
        return tables

    def _host_tables(self) -> tuple:
        """The scheduler's own block table and, where layers have a
        window, rings (numpy, written in place)."""
        return ((self._tables,) if self.window is None
                else (self._tables, self._rings))

    def _resident(self, what: str, *hosts) -> tuple:
        """The device's copies of the numpy arrays ``hosts`` for a decode
        dispatch: those of the last upload while the host's still say
        what was sent, fresh ones (and one count of
        ``serve.decode_uploads{what=}``) once they differ. What was sent
        is kept and compared, microseconds for these sizes, so no writer
        of the tables has to remember to say so; and it is that private
        copy which goes up, for ``_table_args``'s reason: the device's
        copy must not follow the scheduler's later writes."""
        import jax.numpy as jnp

        kept = self._sent.get(what)
        if kept is None or not all(np.array_equal(h, s)
                                   for h, s in zip(hosts, kept[0])):
            sent = tuple(h.copy() for h in hosts)
            kept = self._sent[what] = (sent,
                                       tuple(jnp.asarray(c) for c in sent))
            _M_DECODE_UPLOADS.inc(engine=self.name, what=what)
        return kept[1]

    def _free_slot(self) -> Optional[int]:
        for i, r in enumerate(self._slots):
            if r is None:
                return i
        return None

    def _admit(self):
        """FIFO admission from the queue head into free slots. With the
        prefix cache on, the queue head's prompt is longest-prefix
        matched against resident full blocks first: matched blocks are
        acquired (refcount +1) and mounted directly into the block
        table, and prefill runs only on the unshared suffix — the TTFT
        win. A prompt whose EVERY full block matches still recomputes
        its last token (the logits source) into a copy-on-write
        duplicate of the final matched block, so no stream ever writes
        KV that another stream reads."""
        bs = self.block_size
        admitted = 0
        while self.queue:
            slot = self._free_slot()
            if slot is None:
                _M_STALLS.inc(engine=self.name, reason="no_free_slot")
                break
            req = self.queue[0]
            # resumed streams re-prefill prompt+generated minus the
            # pending last token; fresh streams prefill the prompt.
            # COPY either way: _prefill appends the first sampled token
            # to req.ids, and an aliased list would inflate the slot
            # length by one (skipping a cache slot + shifting rope)
            prefill_ids = list(req.ids[:-1] if req.n_generated > 0
                               else req.ids)
            n_pre = len(prefill_ids)
            matched: List[int] = []
            cow = False
            if self._prefix is not None:
                matched = self._prefix.match(prefill_ids)
                # a full-prompt match (every token in matched full
                # blocks) must still produce the last token's logits:
                # recompute it into a CoW copy of the last block
                cow = bool(matched) and len(matched) * bs >= n_pre
            read_only = matched[:-1] if cow else matched
            if read_only:
                self.pool.acquire(read_only)
                self._prefix.note_acquired(read_only)
            need = self.pool.blocks_for_tokens(n_pre) - len(read_only)
            evictable = (self._prefix.evictable_blocks
                         if self._prefix is not None else 0)
            if need > self.pool.free_blocks + evictable or (
                    self.window_pool is not None
                    and self.ring_blocks > self.window_pool.free_blocks):
                # head-of-line blocking is the FIFO contract: later
                # (smaller) requests do NOT jump a starving head. Put
                # the acquired prefix references back (registered
                # blocks park in the cached state, still matchable)
                if read_only:
                    self._prefix.note_cached(
                        self.pool.release(read_only, retain=read_only))
                _M_STALLS.inc(engine=self.name, reason="no_free_blocks")
                break
            self.queue.popleft()
            admitted += 1
            fresh = self._alloc_blocks(need)
            req.blocks = list(read_only) + fresh
            if self.window_pool is not None:
                req.window_blocks = self.window_pool.alloc(self.ring_blocks)
                self._rings[slot] = req.window_blocks
            req.shared_blocks = len(read_only)
            if cow:
                # fresh[0] sits at the divergence position: duplicate
                # the shared block's K/V so the recomputed last token
                # writes into private pages
                self._caches = self._cow_fn(
                    self._caches, np.int32(matched[-1]),
                    np.int32(fresh[0]))
                _M_COW.inc(engine=self.name)
            if read_only or cow:
                _M_PREFIX_HITS.inc(engine=self.name)
                _M_PREFIX_BLOCKS.inc(len(read_only), engine=self.name)
            if self._prefix is not None:
                req.prefix_node = self._prefix.node_for(prefill_ids)
                req.registered_upto = len(matched)
            req.slot = slot
            req.state = RUNNING
            req.admit_seq = self._admit_counter
            self._admit_counter += 1
            self._slots[slot] = req
            row = np.zeros(self.max_blocks_per_seq, np.int32)
            row[:len(req.blocks)] = req.blocks
            self._tables[slot] = row
            now = self._clock()
            if req.admit_time is None:
                req.admit_time = req.record["admit"] = now
            if self.tracer is not None:
                self.tracer.on_admit(req, slot,
                                     resumed=req.n_generated > 0, t=now)
            # shared tokens are resident KV the suffix attends to but
            # never recomputes; under CoW the suffix is the last token
            start = (n_pre - 1) if cow else len(read_only) * bs
            self._prefill(req, prefill_ids, start=start)
            _M_ADMITTED.inc(engine=self.name)
            self._lens[slot] = n_pre
            # a resumed stream's pending token; a fresh stream's comes
            # with its first token (_read_first_tokens)
            self._tokens[slot] = req.ids[-1]
            self._temps[slot] = req.temperature
            self._eos[slot] = (-1 if req.eos_token_id is None
                               else req.eos_token_id)
        return admitted

    def _alloc_blocks(self, n: int) -> List[int]:
        """Pool alloc with prefix-cache eviction backing it: when the
        free list runs short, reclaim LRU refcount-0 cached blocks
        first (their KV is resident only speculatively); referenced
        blocks are never touched. Raises ``PoolExhaustedError`` when
        even eviction cannot cover ``n``."""
        if self._prefix is not None and n > self.pool.free_blocks:
            self._prefix.evict(self.pool, n - self.pool.free_blocks)
        return self.pool.alloc(n)

    def _prefill(self, req: Request, prefill_ids: List[int],
                 start: int = 0):
        """Dispatch the prefill of this stream's KV. ``start`` tokens are
        already resident (mounted from the prefix cache), so only the
        suffix ``prefill_ids[start:]`` is computed — through the block
        table, where each suffix row attends to the shared prefix it
        never recomputed. ``start == 0`` is the cold path (in-prompt
        causal attention, the PR-14 kernel). A fresh stream's logits
        stay on the device until ``_read_first_tokens``: the step's
        decode program goes out before the host blocks on them."""
        import jax.numpy as jnp

        suffix = prefill_ids[start:]
        n = len(suffix)
        bucket = self._bucket(n)
        if self.tracer is not None:
            self.tracer.on_prefill(req, bucket=bucket, tokens=n)
        with self._span("serve.prefill", request=req.id, bucket=bucket,
                        tokens=n) as sp:
            padded = np.zeros((1, bucket), np.int32)
            padded[0, :n] = suffix
            req.prefilled_tokens += n
            if self._mamba:
                _M_SSM_PREFILL_TOKENS.inc(n, engine=self.name)
            if self._mla:
                _M_LATENT_ROWS.inc(n * len(self._mla), engine=self.name)
            if start == 0:
                self._caches, logits = self._prefill_fn(
                    self._arrays, self._caches, jnp.asarray(padded),
                    jnp.int32(n), self._table_args(req.slot))
            else:
                self._caches, logits = self._suffix_prefill_fn(
                    self._arrays, self._caches, jnp.asarray(padded),
                    jnp.int32(n), jnp.int32(start),
                    *self._table_args(req.slot))
            # the blocks this prefill fills are matchable from here on:
            # whoever mounts them runs after it (a later request of this
            # very admission pass shares them, as it always could)
            self._register_full_blocks(req, written=len(prefill_ids))
        self._phase("prefill", sp)
        record = {"kind": "prefill", "request": req.id, "bucket": bucket,
                  "tokens": n, "step": self._n_steps, "dispatch": sp.start,
                  "dispatched": sp.end, "read": None, "tokens_at": None}
        if req.n_generated == 0:
            # fresh stream: its FIRST token comes from these logits
            self._first_tokens.append((req, logits, record))
            return
        # resumed streams already hold their pending token, the logits
        # are discarded: nobody reads this program's result
        self._program_ring.append(record)
        _M_PREFILL_SECONDS.observe(sp.seconds, engine=self.name)
        if self.tracer is not None:
            self.tracer.on_decode_begin(req)

    def _read_first_tokens(self):
        """Block on the logits of the prefills dispatched since the last
        call, in admission order, and sample each fresh stream's first
        token on the host (this is the TTFT moment). A second
        ``serve.prefill`` span a prompt: the wait for its program."""
        pending, self._first_tokens = self._first_tokens, []
        for req, logits, record in pending:
            with self._span("serve.prefill", request=req.id,
                            first_token=True) as sp:
                tok = self._sample_host(np.asarray(logits),
                                        req.temperature)
                now = self._clock()
                req.first_token_time = req.record["first_token"] = now
                if not req.warmup:
                    _M_TTFT.observe(now - req.submit_time,
                                    engine=self.name)
                    if self.slo is not None:
                        self.slo.observe_ttft(now - req.submit_time,
                                              now=now)
                if self.tracer is not None:
                    self.tracer.on_first_token(req, now)
                slot = req.slot
                self._append_token(req, tok)
                if req.state is not FINISHED:
                    self._tokens[slot] = tok
            _M_PREFILL_SECONDS.observe(sp.end - record["dispatch"],
                                       engine=self.name)
            self._phase("prefill", sp)
            record.update(read=sp.start, tokens_at=sp.end)
            self._program_ring.append(record)
            if self.tracer is not None and req.state is not FINISHED:
                self.tracer.on_decode_begin(req)

    def _sample_host(self, logits: np.ndarray, temperature: float) -> int:
        """First-token sampling (host-side; decode steps sample on
        device). Greedy at temperature 0."""
        if temperature <= 0.0:
            return int(np.argmax(logits))
        z = logits.astype(np.float64) / max(temperature, 1e-6)
        z -= z.max()
        prob = np.exp(z)
        prob /= prob.sum()
        return int(self._rng.choice(logits.shape[0], p=prob))

    def _register_full_blocks(self, req: Request,
                              written: Optional[int] = None):
        """Register every newly-FULL block of this stream in the prefix
        trie so later prompts can share it. ``written`` positions hold
        their K/V once the programs dispatched so far have run:
        ``len(ids) - 1`` for a decoding stream (the pending last token
        is emitted but not yet written), the prefilled ids for a prompt
        just dispatched. A chunk another stream registered first wins
        and this stream's block simply stays private."""
        if self._prefix is None or req.prefix_node is None:
            return
        bs = self.block_size
        full = (len(req.ids) - 1 if written is None else written) // bs
        while req.registered_upto < full:
            b = req.registered_upto
            req.prefix_node = self._prefix.register(
                req.prefix_node, req.ids[b * bs:(b + 1) * bs],
                req.blocks[b])
            req.registered_upto += 1

    def _release_blocks(self, req: Request):
        """Drop this stream's references. Trie-registered blocks whose
        refcount hits 0 are RETAINED in the pool's cached state (their
        KV stays matchable — this is what makes preemption recompute
        and repeat system prompts nearly free); everything else returns
        to the free list."""
        if self._prefix is not None:
            retain = [b for b in req.blocks
                      if self._prefix.is_registered(b)]
            self._prefix.note_cached(
                self.pool.release(req.blocks, retain=retain))
        else:
            self.pool.free(req.blocks)
        req.blocks = []
        if req.window_blocks:
            self.window_pool.free(req.window_blocks)
            req.window_blocks = []

    def _append_token(self, req: Request, tok: int,
                      now: Optional[float] = None):
        """``now`` carries the in-scan step-boundary timestamp when the
        token was produced inside a fused burst (interpolated between
        the burst's host dispatch and return); None = read the clock."""
        req.ids.append(int(tok))
        self._n_tokens += 1
        _M_TOKENS.inc(engine=self.name)
        self._register_full_blocks(req)
        if req.eos_token_id is not None and tok == req.eos_token_id:
            self._finish(req, "eos", now=now)
        elif req.n_generated >= req.max_new_tokens:
            self._finish(req, "max_new_tokens", now=now)

    def _finish(self, req: Request, reason: str,
                now: Optional[float] = None):
        self._release_blocks(req)
        if req.slot is not None:
            self._clear_slot(req.slot)
        req.slot = None
        req.state = FINISHED
        req.finish_reason = reason
        req.finish_time = self._clock() if now is None else now
        req.record["finish"] = req.finish_time
        self.finished.append(req)
        _M_FINISHED.inc(engine=self.name, reason=reason)
        _M_REQUEST_SECONDS.observe(req.finish_time - req.submit_time,
                                   engine=self.name)
        if self.tracer is not None:
            self.tracer.on_finish(req)

    def _clear_slot(self, slot: int):
        self._slots[slot] = None
        self._tables[slot] = 0
        self._rings[slot] = 0
        self._lens[slot] = 0
        self._tokens[slot] = 0
        self._temps[slot] = 0.0
        self._eos[slot] = -1

    def _preempt_youngest(self) -> Request:
        """Evict the most recently admitted active stream; its blocks
        return to the pool and the request goes back to the FRONT of
        the queue (re-prefill of prompt+generated on re-admission).
        The OLDEST stream is therefore never a victim and always runs
        to completion — the no-livelock guarantee."""
        victims = [r for r in self._slots if r is not None]
        victim = max(victims, key=lambda r: r.admit_seq)
        self._release_blocks(victim)
        self._clear_slot(victim.slot)
        victim.slot = None
        victim.state = QUEUED
        victim.preemptions += 1
        self._n_preempts += 1
        self.queue.appendleft(victim)
        _M_PREEMPTIONS.inc(engine=self.name, reason="pool_exhausted")
        if self.tracer is not None:
            self.tracer.on_preempt(victim)
        return victim

    def _decodable(self) -> List[Optional[Request]]:
        """By slot, the stream whose row the next decode program
        computes, or None: it has its first token, and the tokens it has
        and the one a program in flight is making leave it one more to
        make (an ``eos`` among them is not known yet: that row is
        wasted)."""
        flying = self._inflight.reqs if self._inflight is not None else None
        rows: List[Optional[Request]] = []
        for slot, r in enumerate(self._slots):
            if r is not None and r.n_generated > 0:
                unread = flying is not None and flying[slot] is r
                if r.n_generated + unread < r.max_new_tokens:
                    rows.append(r)
                    continue
            rows.append(None)
        return rows

    def _ensure_blocks(self, lookahead: int = 1):
        """Every stream of the next decode program needs the block its
        next token writes into; allocate at block boundaries, evicting
        youngest-first when the pool runs dry (a stream that is ITSELF
        the youngest self-preempts back to the queue rather than
        evicting an older one). This is the full layers' table alone: a
        slot's ring of window blocks came with the slot and never grows.
        ``_lens`` counts the rows of programs dispatched, read or not,
        so with a program in flight this is one token ahead of what the
        host has seen. A preemption needs the victim's tokens whole:
        whatever is unread is read first, and the pass starts again.

        ``lookahead > 1`` (the fused-burst path) pre-allocates enough
        blocks for the next ``lookahead`` tokens so a stream one token
        shy of a block edge doesn't collapse the whole batch's burst to
        one step. Only the MUST-HAVE block (the one the very next token
        writes into) is worth preempting for — when the pool can't fund
        the extra lookahead blocks the burst just shrinks via
        ``_pick_burst_len``'s capacity term."""
        for req in sorted((r for r in self._decodable() if r is not None),
                          key=lambda r: r.admit_seq):
            if req.slot is None:
                continue          # evicted by an older stream this pass
            la = max(1, min(lookahead,
                            req.max_new_tokens - req.n_generated))
            bi = int(self._lens[req.slot]) // self.block_size
            target = (int(self._lens[req.slot]) + la - 1) // self.block_size
            while target >= len(req.blocks):
                try:
                    new = self._alloc_blocks(1)
                except PoolExhaustedError:
                    if len(req.blocks) > bi:
                        break     # next token covered; burst shrinks
                    if self._inflight is not None or self._first_tokens:
                        self._sync("preempt")
                        return self._ensure_blocks(lookahead)
                    if self._preempt_youngest() is req:
                        break     # req went back to the queue itself
                    continue
                req.blocks.extend(new)
                self._tables[req.slot, len(req.blocks) - 1] = new[0]

    def _sync(self, reason: str):
        """Read whatever is dispatched and unread, the decode program in
        flight and the new streams' first tokens: after it the host
        holds every token, as the sequential loop did at this point."""
        prog, self._inflight = self._inflight, None
        if prog is not None:
            _M_PIPELINE_DRAINS.inc(engine=self.name, reason=reason)
            self._read_decode(prog)
        self._read_first_tokens()

    def _decode_once(self):
        """Dispatch the next decode program, THEN read the one before
        it: the host's work of a step runs while the device runs the
        program dispatched a step ago."""
        rows = self._ensure_blocks_timed()
        prev = self._inflight
        if any(r is not None for r in rows):
            self._inflight = self._dispatch_decode(rows, prev)
        elif prev is not None:    # nothing to queue behind it
            self._inflight = None
            _M_PIPELINE_DRAINS.inc(engine=self.name, reason="idle")
        if prev is not None:
            self._read_decode(prev)

    def _dispatch_decode(self, rows, prev: Optional[_Program]) -> _Program:
        """One decode program for ``rows`` (by slot, the stream or
        None), handed only what the device does not hold already. The
        slot state: a program leaves the next one's on the device
        (its tokens, each length one on, its rows as both masks), and
        where ``rows`` are ``prev``'s, stream for stream, that IS this
        program's state: nothing is built and nothing goes up. Any
        other step (a stream came, went, or got its first token; nothing
        in flight after a drain or a burst) sends the host's mirrors
        afresh: a row whose last token ``prev`` is still making takes it
        from ``prev``'s output, the others the host's. Tables and
        temperatures go up when they were written (``_resident``); the
        sampling key lives on the device and the program splits it."""
        import jax.numpy as jnp

        with self._span("serve.decode.dispatch", burst=1) as dispatch:
            if prev is not None and all(
                    r is was for r, was in zip(rows, prev.reqs)):
                state, active = prev.state, prev.active
            else:
                active = np.array([r is not None for r in rows], bool)
                fed = np.array([r is not None and prev is not None
                                and prev.reqs[slot] is r
                                for slot, r in enumerate(rows)], bool)
                state = jnp.asarray(np.stack(
                    [self._tokens, self._lens, active, fed],
                    dtype=np.int32))
                _M_DECODE_UPLOADS.inc(engine=self.name, what="state")
            nxt, sizes, self._caches, state, self._key = self._decode_fn(
                self._arrays, self._caches,
                self._no_tokens if prev is None else prev.nxt, state,
                self._resident("tables", *self._host_tables()),
                *self._resident("temps", self._temps), self._key)
            for out in (nxt, sizes):
                out.copy_to_host_async()
            self._lens[active] += 1
        if prev is not None:
            _M_DECODE_OVERLAPPED.inc(engine=self.name)
        return _Program(nxt, sizes, list(rows),
                        self._dispatched(dispatch, active, prev is not None),
                        state, active)

    def _dispatched(self, dispatch, active, overlapped: bool,
                    n: int = 1) -> dict:
        """Count one dispatch of ``n`` decode ticks over the rows
        ``active``; returns the program's record, which ``_decode_done``
        completes and rings once its tokens are on the host."""
        self._phase("dispatch", dispatch)
        _M_DECODE_STEPS.inc(n, engine=self.name)
        _M_HOST_RT.inc(engine=self.name)
        return {"kind": "decode", "step": self._n_steps, "read_step": None,
                "rows": int(np.count_nonzero(active)),
                "dispatch": dispatch.start, "dispatched": dispatch.end,
                "read": None, "tokens": None, "overlapped": overlapped}

    def _read_decode(self, prog: _Program):
        """Block on a dispatched program's tokens and emit them. A row
        whose stream has left its slot since the dispatch (``eos`` at
        the program before) is thrown away."""
        with self._span("serve.decode.wait") as wait:
            nxt = np.asarray(prog.nxt)
        with self._span("serve.decode.emit") as emit:
            self._count_moe(np.asarray(prog.sizes),
                            sum(r is not None for r in prog.reqs))
            tok0 = self._n_tokens
            for slot, req in enumerate(prog.reqs):
                if req is None or self._slots[slot] is not req:
                    continue
                self._append_token(req, int(nxt[slot]))
                if req.state is not FINISHED:
                    self._tokens[slot] = req.ids[-1]
            emit.note(tokens=self._n_tokens - tok0)
        self._decode_done(prog.record, wait, emit)

    def _count_moe(self, sizes: np.ndarray, n_tokens: int):
        """Feed the ``serve.moe_*`` counters from the held experts' group
        sizes of each sparse layer, which a decode program hands back
        with its tokens, so a program late (nothing there for a dense
        model)."""
        if not sizes.size:
            return
        sizes = sizes.reshape(len(self._sparse), -1)
        count = self._static["moe"]["count"]
        if sizes.shape[1] > count:
            # a group-limited router's last number (models/exaone_moe.py)
            _M_MOE_HELD_GROUP.inc(int(sizes[:, count:].sum()),
                                  engine=self.name)
            sizes = sizes[:, :count]
        _M_MOE_ROUTED.inc(n_tokens * len(self._sparse), engine=self.name)
        _M_MOE_HELD.inc(int(sizes.sum()), engine=self.name)
        for layer, row in zip(self._sparse, sizes):
            _M_MOE_MAX.inc(int(row.max()), engine=self.name, layer=layer)
            _M_MOE_SUM.inc(int(row.sum()), engine=self.name, layer=layer)

    def _ensure_blocks_timed(self, lookahead: int = 1) -> list:
        """``_ensure_blocks`` with any preemption it causes, as one
        phase of the step; returns the rows it leaves to decode (by
        slot, the stream or None)."""
        pre0 = self._n_preempts
        with self._span("serve.ensure_blocks") as sp:
            self._ensure_blocks(lookahead)
            sp.note(preemptions=self._n_preempts - pre0)
        self._phase("ensure_blocks", sp)
        rows = self._decodable()
        active = np.array([r is not None for r in rows], bool)
        if active.any():          # a decode step follows
            _M_PAGES_LIVE.inc(int((self._lens[active] // self.block_size
                                   + 1).sum()), engine=self.name)
            _M_PAGES_TABLE.inc(self._tables.size, engine=self.name)
            if self._mamba:
                _M_SSM_ROWS_LIVE.inc(int(active.sum()) * len(self._mamba),
                                     engine=self.name)
                _M_SSM_ROWS_TABLE.inc(self.max_slots * len(self._mamba),
                                      engine=self.name)
            if self._mla:
                _M_LATENT_ROWS.inc(int(active.sum()) * len(self._mla),
                                   engine=self.name)
                from ..ops.pallas.mla_decode import pages_computed

                ctx = self._lens[active] + 1
                _M_MLA_CTX.inc(int(ctx.sum()), engine=self.name)
                _M_MLA_PAGES.inc(int(pages_computed(
                    ctx, self.block_size, self.max_blocks_per_seq).sum()),
                    engine=self.name)
        return rows

    def _decode_done(self, record: dict, wait, emit, n: int = 1):
        """Feed everything that reads one decode program's times, from
        the start of its dispatch to its tokens on the host (the step
        after, for a program that another was dispatched behind): the
        step record, the program's own record, the histogram and the
        tracer's engine lane."""
        self._phase("wait", wait)
        self._phase("emit", emit)
        record.update(read_step=self._n_steps, read=wait.start,
                      tokens=wait.end)
        self._program_ring.append(record)
        start = record["dispatch"]
        _M_DECODE_SECONDS.observe(wait.end - start, engine=self.name)
        if self.tracer is not None:
            # active_after = runnable slots LEFT BEHIND by this step —
            # the gap to the next program only counts as host-side stall
            # (PTL404) when someone was still waiting to decode, and it
            # is a gap only if that program's dispatch began after this
            # one's tokens were read (one queued behind starts before)
            self.tracer.on_decode_step(start, wait.end,
                                       active_after=self.n_active,
                                       queued=len(self.queue), tokens=n)

    def _pick_burst_len(self) -> int:
        """Adaptive burst length: never cross a block boundary (the
        scheduler allocates blocks host-side) or any stream's
        max-length mid-burst, then round DOWN to a power of two so the
        number of compiled scans stays bounded at one per pow2 bucket
        (``serve.decode_traces == len(burst_lens_used)``)."""
        n = self.decode_burst
        for slot, req in enumerate(self._slots):
            if req is None:
                continue
            cap = len(req.blocks) * self.block_size - int(
                self._lens[slot])
            n = min(n, cap, req.max_new_tokens - req.n_generated)
        n = max(1, n)
        return 1 << (n.bit_length() - 1)

    def _decode_burst_once(self):
        """One scheduler pass's worth of decode as a fused burst: N
        decode ticks execute as ONE compiled ``lax.scan`` dispatch that
        never leaves the chip (sampling, eos latching and length
        advance all in-scan), then the host replays the emitted token
        matrix through the normal finish/registration bookkeeping.
        Per-token timestamps are the in-scan step boundaries
        (interpolated across the dispatch window, indexed by the
        per-slot emit counts carried out of the scan) — NOT the
        burst-end host time, so TTFT/latency attribution matches the
        unbursted engine to within one step."""
        import jax
        import jax.numpy as jnp

        active_np = np.array([r is not None for r in
                              self._ensure_blocks_timed(self.decode_burst)])
        if not active_np.any():
            return                # everyone was preempted away
        n = self._pick_burst_len()
        self.burst_lens_used.add(n)
        with self._span("serve.decode.dispatch", burst=n) as dispatch:
            # pre-split the SAME per-step key schedule the unbursted
            # loop draws, so burst=N and burst=1 sample identical streams
            subs = []
            for _ in range(n):
                self._key, sub = jax.random.split(self._key)
                subs.append(sub)
            ys, emitted, self._caches = self._burst_fn(
                n, self._arrays, self._caches,
                jnp.asarray(self._tokens), jnp.asarray(self._lens),
                jnp.asarray(active_np), self._table_args(),
                jnp.asarray(self._temps), jnp.asarray(self._eos),
                jnp.stack(subs))
        record = self._dispatched(dispatch, active_np, False, n)
        record["ticks"] = n
        with self._span("serve.decode.wait") as wait:
            ys = np.asarray(ys)
            emitted = np.asarray(emitted)
        with self._span("serve.decode.emit") as emit:
            t0 = dispatch.start
            per_step = (wait.end - t0) / n
            n_emitted = 0
            for slot, req in enumerate(self._slots):
                if req is None:
                    continue
                for j in range(int(emitted[slot])):
                    self._lens[slot] += 1
                    n_emitted += 1
                    self._append_token(req, int(ys[j, slot]),
                                       now=t0 + per_step * (j + 1))
                    if req.state is FINISHED:
                        break
                if req.state is not FINISHED:
                    self._tokens[slot] = req.ids[-1]
            emit.note(tokens=n_emitted)
        _M_BURST_TOKENS.inc(n_emitted, engine=self.name)
        self._decode_done(record, wait, emit, n)

    def warm_burst(self, n: int):
        """Compile the ``n``-step fused burst against idle slot state
        (every row inactive: KV writes fence off the pool, outputs are
        discarded) so serving traffic never pays the XLA compile."""
        import jax
        import jax.numpy as jnp

        keys = jax.random.split(jax.random.PRNGKey(0), n)
        _, _, self._caches = self._burst_fn(
            int(n), self._arrays, self._caches,
            jnp.asarray(self._tokens), jnp.asarray(self._lens),
            jnp.zeros(self.max_slots, bool), self._table_args(),
            jnp.asarray(self._temps), jnp.asarray(self._eos), keys)

    # -- compiled steps ----------------------------------------------------
    def _bucket(self, n: int) -> int:
        """The pow2 length bucket an ``n``-token prefill is padded to."""
        return min(max(8, 1 << (n - 1).bit_length()), self.max_seq_len)

    def lowered(self, prompt_lens=(), *, suffix_lens=(), bursts=(),
                cow=False, device=None) -> dict:
        """The jax ``Lowered`` decode step (``"decode"``), the cold
        prefill program of the bucket of each of ``prompt_lens``
        (``"prefill.<bucket>"``), the suffix prefill of each of
        ``suffix_lens`` (``"suffix_prefill.<bucket>"``), the fused burst
        of each length in ``bursts`` (``"burst.<n>"``) and, with ``cow``,
        the copy-on-write (``"cow"``), lowered from the engine's own arrays
        as ``StaticFunction.lowered()`` lowers from its call's: for
        ``.as_text()`` / ``.compile().as_text()``, whose ``op_name``
        metadata carries the scopes ``profiler.scope_seconds`` joins a
        trace with. ``device`` lowers for that device in place of the
        arrays' own: a compile-only TPU of
        ``jax.experimental.topologies``, whose compiler then says what
        the chip would run. Lowering re-traces (``decode_traces`` and
        ``prefill_traces`` count it) but runs nothing and donates
        nothing."""
        import jax
        import jax.numpy as jnp

        sharding = (None if device is None
                    else jax.sharding.SingleDeviceSharding(device))

        def avals(*args):
            return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
                a.shape, a.dtype,
                sharding=sharding or getattr(a, "sharding", None)), args)

        state = (self._arrays, self._caches)
        slots = (jnp.asarray(self._tokens), jnp.asarray(self._lens),
                 jnp.zeros(self.max_slots, bool),
                 self._table_args(), jnp.asarray(self._temps))
        row = jnp.asarray(self._tables[0])
        out = {"decode": self._decode_fn.lower(*avals(
            *state, self._no_tokens,
            jnp.zeros((4, self.max_slots), jnp.int32), *slots[3:],
            self._key))}
        for b in sorted({self._bucket(int(n)) for n in prompt_lens}):
            out[f"prefill.{b}"] = self._prefill_fn.lower(*avals(
                *state, jnp.zeros((1, b), jnp.int32), jnp.int32(1),
                self._table_args(0)))
        for b in sorted({self._bucket(int(n)) for n in suffix_lens}):
            out[f"suffix_prefill.{b}"] = self._suffix_prefill_fn.lower(
                *avals(*state, jnp.zeros((1, b), jnp.int32), jnp.int32(1),
                       jnp.int32(1), row))
        for n in sorted({int(n) for n in bursts}):
            out[f"burst.{n}"] = self._burst_fn.lower(n, *avals(
                *state, *slots, jnp.asarray(self._eos),
                jax.random.split(self._key, n)))
        if cow:
            out["cow"] = self._cow_fn.lower(*avals(
                self._caches, jnp.int32(0), jnp.int32(0)))
        return out

    def _scatter_kv(self, slots, fresh, _i, spec, kc, vc, k_new, v_new):
        """Write per-row K/V ([rows, kvh, dh]) into the pool at flat
        slot ids, in place and in the pool's own layout (out-of-range
        ids drop — that is how inactive slots and pad rows are fenced
        off the pool). With ``slots`` and ``fresh`` bound it is the
        ``write_kv`` the decoder stack is handed. ``slots`` gives the
        ids by the layer's kind of cache (``_full_slots`` /
        ``_ring_slots``). ``fresh``: the rows start their stream (a cold
        prefill), so they go in whole blocks; of a window layer's only
        the ring's last blocks are written, ``slots["window"]`` then
        being (first row, rows, their ids)."""
        from jax import lax

        from ..ops.pallas.kv_write import kv_write

        if self._pack > 1:
            k_new, v_new = (a.reshape(-1, kc.shape[0], kc.shape[3])
                            for a in (k_new, v_new))
        if spec.window is None:
            safe_slot = slots["full"]
        elif fresh:
            start, rows, safe_slot = slots["window"]
            k_new = lax.dynamic_slice_in_dim(k_new, start, rows, axis=0)
            v_new = lax.dynamic_slice_in_dim(v_new, start, rows, axis=0)
        else:
            safe_slot = slots["window"]
        kw = dict(slots=safe_slot, rows_start_blocks=fresh,
                  backend=self.attention_backend)
        return kv_write(kc, k_new, **kw), kv_write(vc, v_new, **kw)

    def _paged_attn(self, q, kc, vc, lengths, tables, **window):
        """``paged_attention_decode`` of ``q`` [rows, nh, dh] over a
        layer's pool, as [rows, nh * dh]; where heads are packed
        (``_pack``) each query goes in, and its result comes out, through
        its key-value head's lanes of the pool's row."""
        import jax.numpy as jnp

        from ..ops.pallas.paged_attention import paged_attention_decode

        rows, nh, dh = q.shape
        if self._pack > 1:
            # head h reads key-value head h // group, which lies in
            # lanes [(kv % pack) * dh, +dh) of row kv // pack
            at = (np.arange(nh) // (nh // self._nkv)) % self._pack
            mine = jnp.asarray(at[:, None] == np.arange(self._pack),
                               q.dtype)[None, :, :, None]   # [1,nh,pack,1]
            q = (q[:, :, None, :] * mine).reshape(rows, nh, -1)
        out = paged_attention_decode(
            q, kc, vc, lengths, tables, sm_scale=self._scale,
            backend=self.attention_backend, **window)
        if self._pack > 1:
            out = jnp.sum(out.reshape(rows, nh, self._pack, dh) * mine,
                          axis=2)
        return out.reshape(rows, nh * dh)

    def _scatter_latent(self, slots, fresh, pool, latent):
        """Write the rows ``latent`` ``[rows, rank + rope]`` into a latent
        layer's pool at ``slots["full"]``, padded with zeros to the pool
        row's lanes: ``_scatter_kv`` for the one pool of a ``mla`` layer."""
        import jax.numpy as jnp

        from ..ops.pallas.kv_write import kv_write

        pad = pool.shape[-1] - latent.shape[-1]
        rows = jnp.pad(latent, ((0, 0), (0, pad)))[:, None, :]
        return kv_write(pool, rows, slots=slots["full"],
                        rows_start_blocks=fresh,
                        backend=self.attention_backend)

    def _latent_attn(self, i, lp, q_nope, q_pe, pool, lengths, tables):
        """Attention of the rows' queries over a latent layer's pool as it
        lies, ``[rows, nh * v]``: the expansion's key half folded into the
        query, ``mla_decode`` over the rows, its value half applied to the
        result. No cached token is expanded."""
        import jax

        from ..ops import mla as _mla
        from ..ops.pallas.mla_decode import mla_decode

        st = self._static["mla"]
        with jax.named_scope(f"layer{i}/mla/absorb_q"):
            q = _mla.absorb_q(lp, st, q_nope, q_pe, pool.shape[-1])
        with jax.named_scope(f"layer{i}/mla/attn"):
            out = mla_decode(q, pool, lengths, tables, dv=st["rank"],
                             sm_scale=self._scale,
                             backend=self.attention_backend)
        with jax.named_scope(f"layer{i}/mla/absorb_o"):
            return _mla.absorb_o(lp, st, out)

    def _mla_cached(self, slots, lengths, tables):
        """The ``mla`` closure of the steps whose rows attend through the
        block table (a decode step, a suffix prefill)."""
        import jax

        def mla(i, _spec, lp, q_nope, q_pe, latent, cache):
            with jax.named_scope(f"layer{i}/mla/scatter_latent"):
                pool = self._scatter_latent(slots, False, cache[0], latent)
            return self._latent_attn(i, lp, q_nope, q_pe, pool, lengths,
                                     tables), (pool,)

        return mla

    def _full_slots(self, table, positions, written):
        """Flat pool slot of each position through a full layer's block
        table (``table`` [rows, blocks] or one row); rows not
        ``written`` get an id past the pool, which drops them."""
        import jax.numpy as jnp

        bs = self.block_size
        bi = jnp.clip(positions // bs, 0, self.max_blocks_per_seq - 1)
        phys = (jnp.take(table, bi) if table.ndim == 1 else
                jnp.take_along_axis(table, bi[:, None], axis=1)[:, 0])
        return jnp.where(written, phys * bs + positions % bs,
                         self.pool.num_blocks * bs)

    def _ring_slots(self, ring, positions, written):
        """The same through a slot's ring: position ``p`` lies in entry
        ``(p // block_size) % ring_blocks``."""
        import jax.numpy as jnp

        bs = self.block_size
        ri = (positions // bs) % self.ring_blocks
        phys = (jnp.take(ring, ri) if ring.ndim == 1 else
                jnp.take_along_axis(ring, ri[:, None], axis=1)[:, 0])
        return jnp.where(written, phys * bs + positions % bs,
                         self.window_pool.num_blocks * bs)

    def _count_kv_write(self, rows: int, fresh: bool = False):
        """One count a traced program, by the path its K/V rows take
        (executes at TRACE time only)."""
        from ..ops.pallas.kv_write import kv_write_path

        _M_KV_WRITE_TRACES.inc(engine=self.name, path=kv_write_path(
            rows, self.block_size, rows_start_blocks=fresh,
            backend=self.attention_backend))

    def _decode_impl(self, arrays, caches, prev, state, tables, temps,
                     key):
        """ONE batched decode tick over every slot: write each active
        stream's pending token into its KV block, attend through the
        block tables (decode-specialized paged attention), project,
        sample. Shapes are fixed at [max_slots, ...]; slot churn is
        data, so this traces exactly once per engine (asserted via
        ``serve.decode_traces``). The caches are DONATED: the pool
        updates in place instead of being copied per token. ``state``
        is the slots' tokens, lengths, active mask and ``fed`` mask in
        one int32 array; a ``fed`` row's token is ``prev``'s, the output
        of the program before as it lies on the device, which the host
        may not have read yet. The program hands back the state of the
        one after it (its tokens, each active length one on, every
        active row ``fed``), which stays on the device for as long as
        the same streams decode, and ``key`` split once: the host's
        ``jax.random.split`` chain, a link a program. A model with
        sparse layers hands their held experts' group sizes back beside
        the tokens (none for a dense model)."""
        import jax
        import jax.numpy as jnp

        # executes at TRACE time only — the flatness counter the e2e
        # continuous-batching test pins at 1
        self.decode_traces += 1
        _M_DECODE_TRACES.inc(engine=self.name)
        tokens, lens, active, fed = state
        key, sub = jax.random.split(key)
        nxt, new_caches, moe_sizes = self._decode_core(
            caches, jnp.where(fed != 0, prev, tokens), lens, active != 0,
            tables, temps, sub, arrays=arrays)
        sizes = (jnp.stack(moe_sizes).reshape(-1).astype(jnp.int32)
                 if moe_sizes else jnp.zeros(0, jnp.int32))
        state = jnp.stack([nxt.astype(jnp.int32), lens + active, active,
                           active])
        return nxt, sizes, new_caches, state, key

    def _decode_core(self, caches, tokens, lens, active, tables, temps,
                     key, *, arrays=None, p=None):
        """The decode-tick math, shared VERBATIM by the single-step jit
        and every tick of the fused burst scan — op-for-op identity is
        what makes burst=N token-for-token equal to burst=1."""
        import jax
        import jax.numpy as jnp

        if p is None:
            p = {**arrays, **self._static}
        b = self.max_slots
        nh = self._nh
        table, *ring = tables

        with jax.named_scope("embed"):
            pos = lens.astype(jnp.int32)
            x, rope = _stack.embed(p, tokens, pos, self.max_seq_len)
        lengths = jnp.where(active, pos + 1, 0)
        slots = {"full": self._full_slots(table, pos, active)}  # OOB drops
        if ring:
            slots["window"] = self._ring_slots(ring[0], pos, active)
            starts = jnp.maximum(lengths - self.window, 0)

        def attn(_i, spec, q, _k, _v, kc, vc):
            if spec.window is None:
                return self._paged_attn(q, kc, vc, lengths, table)
            return self._paged_attn(q, kc, vc, lengths, ring[0],
                                    starts=starts, ring=True)

        def ssm(i, _spec, lp, xbc, dt, cache):
            """One recurrent step a slot that decodes; the other slots'
            tails and states stay as they lie."""
            from ..ops.ssm import mamba2_step

            y, tail, state = mamba2_step(
                lp, p["ssm"], xbc, dt, *cache, active,
                backend=self.attention_backend, scope=f"layer{i}/ssm")
            return y, (tail, state)

        self._count_kv_write(b)
        out, new_caches, moe_sizes = _stack.stack_layers(
            p, x, rope, caches, partial(self._scatter_kv, slots, False),
            attn, ssm=ssm, valid=active,
            mla=self._mla_cached(slots, lengths, table),
            backend=self.attention_backend)
        with jax.named_scope("head"):
            logits = _stack.head_logits(p, out).astype(jnp.float32)  # [B, V]
        with jax.named_scope("sample"):
            nxt = _gen._sample_slot_tokens(logits, temps, key)
        return nxt, new_caches, moe_sizes

    def _burst_impl(self, n, arrays, caches, tokens, lens, active,
                    tables, temps, eos_arr, keys):
        """``n`` decode ticks as ONE ``lax.scan`` that never leaves the
        chip: each tick runs the SAME ``_decode_core`` as the
        single-step path (per-token sampling included, with the same
        pre-split key schedule), then latches eos in-carry — a finished
        row keeps scanning but its state freezes: length stops
        advancing, its KV writes fence off the pool via the active
        mask, and its later sampled tokens are garbage the host never
        consumes. Per-slot emit counts ride out of the scan so the host
        can place every token (and the eos finish) at its true in-scan
        step boundary. ``n`` is STATIC: one trace per pow2 burst
        length, counted by ``serve.decode_traces``."""
        import jax.numpy as jnp
        from jax import lax

        self.decode_traces += 1
        _M_DECODE_TRACES.inc(engine=self.name)

        p = {**arrays, **self._static}

        def tick(carry, key):
            tokens, lens, active, emitted, caches = carry
            # (a sparse model's group sizes are not carried out of a burst)
            nxt, caches, _ = self._decode_core(
                caches, tokens, lens, active, tables, temps, key, p=p)
            hit = active & (eos_arr >= 0) & (nxt == eos_arr)
            carry = (jnp.where(active, nxt, tokens),
                     jnp.where(active, lens + 1, lens),
                     active & ~hit,
                     emitted + active.astype(jnp.int32),
                     caches)
            return carry, nxt

        emitted0 = jnp.zeros(self.max_slots, jnp.int32)
        (_, _, _, emitted, caches), ys = lax.scan(
            tick, (tokens, lens, active, emitted0, caches), keys,
            length=n)
        return ys, emitted, caches

    def _prefill_impl(self, arrays, caches, ids, n, table_rows):
        """Prompt prefill for ONE stream: causal self-attention over
        the (bucket-padded) prompt, K/V scattered into this stream's
        pool blocks (donated — updated in place), last real token's
        logits returned. Compiles once per power-of-two length bucket
        (``serve.prefill_traces``). A family whose view says
        ``prefill="flash"`` attends through the flash forward kernel
        (GQA by index map, a band on window layers) wherever kernels
        run; the others, and every family on the reference backend,
        through the float32 masked softmax."""
        import jax
        import jax.numpy as jnp

        self.prefill_traces += 1
        _M_PREFILL_TRACES.inc(engine=self.name,
                              bucket=int(ids.shape[1]))

        p = {**arrays, **self._static}
        tp = ids.shape[1]
        nh, kvh, dh = self._nh, self._nkv, self._dh
        bs = self.block_size
        group = nh // kvh
        if self._mamba:
            *table_rows, slot = table_rows
        table_row, *ring_row = table_rows

        positions = jnp.arange(tp, dtype=jnp.int32)
        valid = positions < n                              # [Tp]
        with jax.named_scope("embed"):
            x, rope = _stack.embed(p, ids, positions, self.max_seq_len)
        # causal within the prompt; pad rows see themselves only (their
        # K/V never reach the pool and their logits are never read)
        causal = (positions[None, :] <= positions[:, None]) \
            & valid[None, :]                               # [Tq, Tk]

        slots = {"full": self._full_slots(table_row, positions, valid)}
        if ring_row:
            # only what the ring can hold is written: the blocks from
            # ring_blocks - 1 before the prompt's last one on
            rows = min(tp, self.ring_blocks * bs)
            start = jnp.maximum(
                (n - 1) // bs - (self.ring_blocks - 1), 0) * bs
            kept = start + jnp.arange(rows, dtype=jnp.int32)
            slots["window"] = (start, rows, self._ring_slots(
                ring_row[0], kept, kept < n))
        flash = (p.get("prefill") == "flash"
                 and self.attention_backend != "reference")

        def flash_fwd(q, k, v, window=None):
            """[tp, heads, dv] through the flash forward kernel."""
            from ..ops.pallas.flash_attention import _flash_fwd_bhsd

            out, _ = _flash_fwd_bhsd(
                *(a.transpose(1, 0, 2)[None] for a in (q, k, v)),
                causal=True, scale=self._scale, window=window,
                interpret=self.attention_backend == "interpret")
            return out[0].transpose(1, 0, 2)

        def softmax_attn(q, k, v, seen):
            """[tp, heads, dv]: the float32 masked softmax."""
            scores = jnp.einsum(
                "qhd,khd->hqk", q.astype(jnp.float32),
                k.astype(jnp.float32)) * self._scale
            scores = jnp.where(seen[None], scores, -jnp.inf)
            probs = jax.nn.softmax(scores, axis=-1)
            return jnp.einsum("hqk,khd->qhd", probs, v.astype(jnp.float32))

        def attn(_i, spec, q, k, v, _kc, _vc):
            if flash:
                return flash_fwd(q, k, v, spec.window).reshape(tp, nh * dh)
            seen = causal
            if spec.window is not None:
                # (a pad row far past the prompt would see no key at
                # all; it keeps itself, so that no NaN reaches the V
                # rows the next layer multiplies by 0)
                seen = (seen & (positions[:, None] - positions[None, :]
                                < spec.window)) | (
                    positions[:, None] == positions[None, :])
            k_rep = jnp.repeat(k, group, axis=1) if group > 1 else k
            v_rep = jnp.repeat(v, group, axis=1) if group > 1 else v
            return softmax_attn(q, k_rep, v_rep, seen).reshape(tp, nh * dh)

        def mla(i, _spec, lp, q_nope, q_pe, latent, cache):
            """The prompt's rows into the pool as they are, and causal
            attention within the prompt with its own rows expanded."""
            from ..ops import mla as _mla

            with jax.named_scope(f"layer{i}/mla/scatter_latent"):
                pool = self._scatter_latent(slots, True, cache[0], latent)
            with jax.named_scope(f"layer{i}/mla/expand"):
                k, v = _mla.expand(lp, p["mla"], latent)
                q = jnp.concatenate([q_nope, q_pe], axis=-1)
            with jax.named_scope(f"layer{i}/mla/attn"):
                out = (flash_fwd(q, k, v) if flash
                       else softmax_attn(q, k, v, causal))
            return out.reshape(tp, -1), (pool,)

        def ssm(i, _spec, lp, xbc, dt, cache):
            """The prompt's chunked scan from a zero state; what its last
            real token leaves overwrites the slot's row."""
            from jax import lax

            from ..ops.ssm import mamba2_prefill

            y, tail, state = mamba2_prefill(lp, p["ssm"], xbc, dt, n,
                                            scope=f"layer{i}/ssm")
            tails, states = cache
            zero = jnp.int32(0)
            with jax.named_scope(f"layer{i}/ssm/scan"):
                tails = lax.dynamic_update_slice(
                    tails, tail[:, None, :].astype(tails.dtype),
                    (zero, slot, zero))
                states = lax.dynamic_update_slice(
                    states, state[None].astype(states.dtype),
                    (slot, zero, zero, zero))
            return y, (tails, states)

        self._count_kv_write(tp, fresh=True)
        out, new_caches, _ = _stack.stack_layers(
            p, x, rope, caches, partial(self._scatter_kv, slots, True),
            attn, ssm=ssm, mla=mla, valid=valid,
            backend=self.attention_backend)
        with jax.named_scope("head"):
            h_last = jnp.take(out, n - 1, axis=0)          # [H]
            logits = _stack.head_logits(p, h_last[None, :])[0]
            return new_caches, logits.astype(jnp.float32)

    def _suffix_prefill_impl(self, arrays, caches, ids, n, start,
                             table_row):
        """Prefill of the UNSHARED suffix only, for a stream whose
        first ``start`` tokens were mounted from the prefix cache:
        suffix K/V scatters into this stream's own blocks at absolute
        positions ``start + i``, then each suffix row attends THROUGH
        the block table (per-row lengths ``start + i + 1``) so it sees
        the shared resident prefix it never recomputed plus the
        just-written suffix rows — scatter precedes attention per
        layer, exactly as in decode. ``start`` is jit data, so this
        compiles once per pow2 suffix bucket."""
        import jax
        import jax.numpy as jnp

        self.prefill_traces += 1
        _M_PREFILL_TRACES.inc(engine=self.name,
                              bucket=int(ids.shape[1]))

        p = {**arrays, **self._static}
        tp = ids.shape[1]
        nh, dh = self._nh, self._dh

        offs = jnp.arange(tp, dtype=jnp.int32)
        positions = start + offs                           # absolute
        valid = offs < n
        with jax.named_scope("embed"):
            x, rope = _stack.embed(p, ids, positions, self.max_seq_len)

        slots = {"full": self._full_slots(table_row, positions, valid)}
        lengths = jnp.where(valid, positions + 1, 0)       # causal
        tables_rep = jnp.broadcast_to(
            table_row[None, :], (tp, table_row.shape[0]))

        def attn(_i, _spec, q, _k, _v, kc, vc):
            return self._paged_attn(q, kc, vc, lengths, tables_rep)

        self._count_kv_write(tp)
        out, new_caches, _ = _stack.stack_layers(
            p, x, rope, caches, partial(self._scatter_kv, slots, False),
            attn, valid=valid,
            mla=self._mla_cached(slots, lengths, tables_rep),
            backend=self.attention_backend)
        with jax.named_scope("head"):
            h_last = jnp.take(out, n - 1, axis=0)          # [H]
            logits = _stack.head_logits(p, h_last[None, :])[0]
            return new_caches, logits.astype(jnp.float32)

    def _cow_impl(self, caches, src, dst):
        """Copy-on-write: duplicate one physical block's K/V (or its
        latent rows) across every layer into a private block, so a stream
        can diverge inside a shared prefix block without mutating KV that
        other streams are reading. src/dst are jit data — one trace
        ever."""
        return [tuple(c.at[:, dst].set(c[:, src]) for c in cache)
                for cache in caches]
