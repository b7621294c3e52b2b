"""Overlapped continuous-batching serve engine: async packed prefill →
per-slot decode with batched sampling.

PackMamba's packing is applied to the SERVING path: instead of left-padding
every prompt to the batch max and decoding in synchronous waves (the padded
baseline the paper shows wasting 2-3×), prompts are packed back-to-back into
shape-bucketed prefill buffers (core/packing.py policies), ONE forward
harvests every segment's final (conv-tail, recurrent/KV) state at its
segment end (``model.prefill_packed``), and the states are scattered into
per-request decode slots (``model.scatter_into_cache``). Decode then runs
one fused step per token over all slots; a slot that hits EOS or its token
budget is released and refilled from the admission queue *mid-flight* —
the decode batch stays full without draining a wave.

Three serving-loop mechanisms on top of the PR-3 engine:

* **Prefill/decode overlap** (``overlap=True``): the packed prefill is
  dispatched asynchronously (JAX async dispatch; the decode-step jit donates
  its cache buffers) and the engine keeps issuing decode steps on the live
  slots while the prefill result is in flight. The target slots are merely
  *reserved* while pending; only when the device signals completion
  (``jax.Array.is_ready``) are the harvested states scattered into the
  decode cache — so the decode dependency chain never stalls on the packed
  forward. Per-slot token streams are identical either way: the engines
  differ only in *when* independent computations are enqueued.
* **Latency-aware admission** (``target_ttft_ms``): the fixed
  ``refill_threshold`` batches admissions for throughput (a decode step
  costs the same idle or full, so single-slot refills waste prefills). The
  TTFT policy overrides it: when the queue's *oldest* request has waited
  longer than the target, a prefill is issued even for a single free slot.
  ``ServeStats`` tracks per-request submit→first-token (TTFT) and
  inter-token latencies so the trade is measurable.
* **Batched sampling** (per-request ``temperature`` / ``top_k`` /
  ``top_p``): one fixed-shape jitted step (``model.decode_step_sample``)
  decodes AND samples every slot, with per-slot ``jax.random`` key streams
  derived from (engine seed, request id) — a request samples identically
  wherever its slot lands. ``temperature=0`` (the default) is exact greedy.

Scheduler v2 adds three levers on the admission path:

* **Chunked prefill** (``chunk_rows`` / ``chunk_size``): a prompt longer
  than the largest bucket is consumed in fixed-shape (chunk_rows,
  chunk_size) slabs that resume from the carried O(1) SSM/conv/KV state
  (``model.prefill_chunk``) on a side cache, then hand off to a decode
  slot through the same ``scatter_into_cache`` path — a 32k prompt can no
  longer head-of-line-block the queue, and short requests keep decoding
  through every chunk round. The old over-bucket ``ValueError`` in
  ``submit()`` is gone (``max_prompt_len`` is the explicit bound now).
* **Multi-prefill pipelining** (``max_inflight_prefills``): the single
  in-flight prefill generalizes to a bounded pool; each entry lands
  independently when its device result is ready. Token streams stay
  bit-identical — per-request sampling keys make them slot- and
  schedule-independent.
* **TTFT-aware bucket choice** (``bucket_policy="ttft"``): instead of
  always taking the smallest bucket that fits the head-of-line prompt,
  the engine upgrades to a larger bucket when that admits strictly more
  queued requests AND the head's wait still has slack against the TTFT
  allowance (``target_ttft_ms``, else the measured p50) — admit small
  early under latency pressure, wait to fill big when there is headroom.

``ServeStats`` additionally splits engine wall time into prefill / chunk /
decode / host phases (``*_ms``) so a throughput regression is attributable
to the scheduler vs the kernels.

Compile discipline: decode is one fixed shape; prefill shapes are bounded
by the bucket list (rows × bucket-capacity), NOT by the number of distinct
prompt lengths — ``stats.buckets`` counts the shapes actually compiled.
Chunked prefill adds ONE more shape, (chunk_rows, chunk_size).

On top of the overlap/latency/sampling engine sits a FAULT-TOLERANCE
layer (PackMamba's O(1) per-request state is what makes it cheap — a
session *is* a few KB of SSM/conv/KV state, not a paged KV region):

* **Request lifecycle**: ``submit(..., deadline_ms=)`` enforces a
  deadline at admission, at prefill landing, and per decode step;
  ``cancel(rid)`` revokes a request wherever it is (queued, reserved by
  an in-flight prefill, or decoding); when the admission queue exceeds
  ``max_queue`` entries or its head is older than ``max_queue_age_ms``,
  ``submit`` sheds the request (``ShedError`` with a reason) instead of
  queueing forever. ``engine.status[rid]`` is the explicit outcome:
  queued → active → done | failed | expired | cancelled (``errors[rid]``
  carries the diagnostic for failures).
* **Numerical guard rails** (``guard=True``): a per-step finiteness probe
  on decode logits (``model.decode_step_sample_guarded``) and a
  per-segment probe on harvested prefill states (``model.prefill_probe``).
  A non-finite slot is QUARANTINED — request failed with a diagnostic,
  slot freed for reuse — instead of silently streaming garbage; healthy
  slots' token streams are bit-identical to an unguarded run (the probe
  only reads the logits; the poison seam adds 0.0).
* **Fault injection** (``faults=FaultPlan(...)``, repro/faults.py): fail
  or delay the Nth prefill dispatch, poison decode logits or prefill
  states, kill the engine at step K — every failure mode above is
  deterministically testable on CPU (``make verify-faults``).
* **Crash recovery**: ``snapshot(manager)`` persists the whole engine —
  per-slot SSM/conv/KV states, sampling keys, generated-token tails,
  queue contents, statuses — through checkpoint.CheckpointManager;
  ``restore(manager)`` on a fresh engine resumes every in-flight request
  and completes it with exactly the tokens an uninterrupted run would
  have produced (decode is deterministic given the restored state, and
  per-request sampling keys make streams slot-independent).

Finally, two O(1)-state exploits ride on the same snapshot leaf layout
(full lifecycle walkthrough in docs/serving.md):

* **Prefix caching** (``state_cache=`` / ``cache_bytes=``,
  launch/state_cache.py): every landed prompt's post-prefill state (ONE
  cache row + end logits) is stored in a host-side LRU keyed by a prefix
  hash; ``submit(..., prefix_len=N)`` declares a shared system prompt so
  the capture boundary sits mid-prompt. A later request restores the
  longest cached prefix and prefills only its suffix (chunk-lane slabs,
  bucket-quantized widths) — or, on a whole-prompt hit, starts decoding
  with NO forward at all. Token streams are bit-identical to cold
  prefills (chunked ≡ unchunked + per-request key streams).
* **Speculative decode** (``spec_k=``): n-gram prompt-copy drafts are
  verified k-at-a-time by one scan-of-decode-steps forward
  (``model.decode_verify``); rejected suffixes roll back via the verify's
  own state trajectory (``model.spec_rollback``). Greedy streams are
  bit-identical to one-token-at-a-time decoding by construction;
  ``spec.accept_rate`` is the observable payoff.

  PYTHONPATH=src python -m repro.launch.serve --arch mamba-110m --tiny \
      --slots 8 --requests 24 --new-tokens 16 --temperature 0.8 --top-k 40
"""
import argparse
import collections
import dataclasses
import time
from typing import Callable, Dict, List, Optional

import numpy as np
import jax
import jax.numpy as jnp

from repro.compile_cache import use_compile_cache
from repro.configs.base import get_config
from repro.core import packing
from repro.faults import (EngineKilled, FaultPlan, poison_cache_rows,
                          poison_states)
from repro.launch.state_cache import (StateCache, cache_row, load_cache_row,
                                      row_finite, state_row)
from repro.models import blocks as B
from repro.models.lm import build_model
from repro.obs import (MetricsRegistry, Obs, percentiles, profiler_session)


class ShedError(RuntimeError):
    """Request rejected at admission (overload shedding). ``reason`` says
    which bound tripped; the request was never queued and has no rid."""

    def __init__(self, reason: str):
        super().__init__(reason)
        self.reason = reason


@dataclasses.dataclass
class Request:
    rid: int
    tokens: np.ndarray         # 1-D int32 prompt
    max_new: int
    eos: int = -1              # -1 = never matches (runs to budget)
    temperature: float = 0.0   # 0 = greedy
    top_k: int = 0             # 0 = full vocab
    top_p: float = 1.0         # 1 = full mass
    submit_t: float = 0.0      # engine clock at submit()
    deadline_ms: Optional[float] = None   # total budget from submit_t
    prefix_len: Optional[int] = None      # declared shared-prefix boundary
    #                                       (a StateCache capture/reuse hint)


class _HistList(list):
    """Per-sample latency list that ALSO feeds a registry histogram on
    append — ``stats.ttft_ms`` keeps its list API (indexing, len,
    ``np.percentile``-ability) while the obs registry sees every sample."""

    def __init__(self, hist):
        super().__init__()
        self.hist = hist

    def append(self, v):
        super().append(v)
        self.hist.observe(v)


# fixed histogram bounds (ms) for the registry view of per-request TTFT and
# per-token ITL — wide enough for CPU-compile-included demo runs
_TTFT_BUCKETS = (1, 2, 5, 10, 20, 50, 100, 200, 500, 1000, 2000, 5000)
_ITL_BUCKETS = (0.5, 1, 2, 5, 10, 20, 50, 100, 200, 500)


class ServeStats:
    """Engine counters/latencies as a thin view over a ``MetricsRegistry``
    (repro.obs): every attribute below is backed by a ``serve.*`` metric,
    so ``engine.stats.shed`` and the registry's ``serve.shed`` are the SAME
    number — one source for the CLI summary, the benchmark JSON, a
    Prometheus scrape, and the trace's embedded snapshot.

    ``ServeStats()`` stands alone (its own registry);
    ``ServeStats(registry)`` binds to an existing one (the engine passes
    its ``obs.metrics``). The attribute API is unchanged from the old
    dataclass: ``st.shed += 1`` works, ``st.buckets`` is a plain set,
    ``st.ttft_ms`` / ``st.itl_ms`` are lists (that also feed histograms).

    Counters:
      prefills            packed prefill rounds issued
      prefill_tokens      real prompt tokens prefilled
      decode_steps        fused all-slot decode steps
      generated           tokens handed back to requests
      midflight_refills   prefills issued while slots were decoding
      overlapped_prefills prefills in flight across ≥1 decode step
      early_admits        admissions forced by the TTFT policy
      shed                submits rejected by overload shedding
      expired             requests terminated by their deadline
      cancelled           requests revoked via cancel()
      quarantined         slots failed by the finiteness probes
      prefill_faults      prefill dispatches that raised
      chunk_rounds        chunked-prefill forwards issued
      chunk_tokens        prompt tokens consumed via chunk rounds
      chunked_prefills    requests whose prompt landed via chunks
      bucket_upgrades     TTFT policy took a bigger-than-fit bucket
      deferred_upgrades   upgrade declined: head wait too long
    Gauges:
      queue_depth_max     deepest the admission queue ever got
      prefill_ms / chunk_ms / decode_ms / host_ms
                          host wall time per engine phase (the satellite
                          diagnosis for packed_continuous vs padded_wave:
                          WHERE does a step spend time?)
    """

    _counters = ("prefills", "prefill_tokens", "decode_steps", "generated",
                 "midflight_refills", "overlapped_prefills", "early_admits",
                 "shed", "expired", "cancelled", "quarantined",
                 "prefill_faults", "chunk_rounds", "chunk_tokens",
                 "chunked_prefills", "bucket_upgrades", "deferred_upgrades")
    _gauges = ("queue_depth_max", "prefill_ms", "chunk_ms", "decode_ms",
               "host_ms")

    def __init__(self, registry: Optional[MetricsRegistry] = None):
        # bypass our __setattr__ until the metric map exists
        d = self.__dict__
        d["registry"] = registry if registry is not None \
            else MetricsRegistry()
        d["_m"] = {n: d["registry"].counter(f"serve.{n}")
                   for n in self._counters}
        d["_m"].update({n: d["registry"].gauge(f"serve.{n}")
                        for n in self._gauges})
        d["buckets"] = set()   # distinct (rows, L) prefill shapes used
        d["ttft_ms"] = _HistList(
            d["registry"].histogram("serve.ttft_ms", _TTFT_BUCKETS,
                                    help="submit to first token, ms"))
        d["itl_ms"] = _HistList(
            d["registry"].histogram("serve.itl_ms", _ITL_BUCKETS,
                                    help="inter-token latency, ms"))

    def __getattr__(self, name):
        m = self.__dict__.get("_m", {})
        if name in m:
            return m[name].value
        raise AttributeError(name)

    def __setattr__(self, name, value):
        m = self.__dict__.get("_m", {})
        if name in m:
            m[name].set(value)
        else:
            object.__setattr__(self, name, value)

    def __repr__(self):
        fields = ", ".join(f"{n}={self._m[n].value}"
                           for n in self._counters + self._gauges)
        return (f"ServeStats({fields}, buckets={self.buckets}, "
                f"ttft_n={len(self.ttft_ms)}, itl_n={len(self.itl_ms)})")

    def ttft_percentiles(self) -> Dict[str, float]:
        """{'p50': ms, 'p95': ms} over recorded TTFTs ({} when none)."""
        return percentiles(self.ttft_ms, (50, 95))

    def itl_percentiles(self) -> Dict[str, float]:
        """{'p50': ms, 'p95': ms} over inter-token latencies ({} = none)."""
        return percentiles(self.itl_ms, (50, 95))


# back-compat alias (pre-overlap name)
EngineStats = ServeStats


class ServeEngine:
    """Slot-based continuous batching with an async packed-prefill admission
    path and batched per-slot sampling.

    * ``submit()`` enqueues requests (each with its own budget, EOS and
      sampling knobs); ``run()`` drives admission + decode until everything
      drains (``step()`` exposes one iteration for custom loops).
    * Admission packs queued prompts (FIFO, ``policy``) into a
      (prefill_rows, bucket) buffer — the smallest bucket that fits the
      head-of-line prompt — capped by free slots and ``max_segments`` per
      row. The prefill is DISPATCHED and, with ``overlap=True``, left in
      flight while decode keeps stepping; its states land in the reserved
      slots once ready. Requests never wait for a wave boundary.
    * The decode batch is one jitted ``decode_step_sample`` over ALL slots
      (forward + temperature/top-k/top-p sampling fused; idle slots ride
      along — their state is fully overwritten at refill, so the garbage
      they accumulate is harmless and the shape never changes).
    * Per-slot termination: a slot is released the moment its request emits
      ``eos`` or exhausts ``max_new`` — the EOS token itself is kept.
    """

    def __init__(self, model, params, num_slots: int, max_len: int, *,
                 prefill_rows: int = 2, buckets=(64, 128, 256),
                 max_segments: int = 4, policy: str = "first_fit",
                 eos: int = -1, refill_threshold: Optional[int] = None,
                 overlap: bool = True,
                 target_ttft_ms: Optional[float] = None,
                 sample_seed: int = 0,
                 clock: Callable[[], float] = time.monotonic,
                 max_queue: Optional[int] = None,
                 max_queue_age_ms: Optional[float] = None,
                 guard: bool = False,
                 faults: Optional[FaultPlan] = None,
                 max_inflight_prefills: int = 1,
                 bucket_policy: str = "smallest_fit",
                 chunk_rows: int = 1,
                 chunk_size: Optional[int] = None,
                 max_prompt_len: Optional[int] = None,
                 obs: Optional[Obs] = None,
                 state_cache: Optional[StateCache] = None,
                 cache_bytes: Optional[int] = None,
                 spec_k: int = 0, spec_ngram: int = 3):
        self.model = model
        self.params = params
        # telemetry: metrics are always on (ServeStats is a view over
        # obs.metrics); span tracing records only when the caller passes
        # Obs.on() — the default NULL_TRACER makes every tracer call below
        # a no-op, so token streams and schedules are bit-identical
        self.obs = obs if obs is not None else Obs.off()
        self._tr = self.obs.tracer
        self._req_spans: Dict[int, Optional[int]] = {}
        self.num_slots = num_slots
        self.max_len = max_len
        self.prefill_rows = prefill_rows
        self.buckets = tuple(sorted(buckets))
        self.max_segments = max_segments
        self.policy = policy
        self.eos = eos
        self.overlap = overlap
        self.target_ttft_ms = target_ttft_ms
        self.sample_seed = sample_seed
        self._clock = clock
        self.max_queue = max_queue
        self.max_queue_age_ms = max_queue_age_ms
        self.faults = faults
        if bucket_policy not in ("smallest_fit", "ttft"):
            raise ValueError(f"bucket_policy must be 'smallest_fit' or "
                             f"'ttft', got {bucket_policy!r}")
        self.max_inflight_prefills = max(1, int(max_inflight_prefills))
        self.bucket_policy = bucket_policy
        self.max_prompt_len = max_prompt_len
        # prefix/state caching (launch/state_cache.py): a host-side LRU of
        # single-row post-prefix states. Pass a StateCache to share one
        # across engines (it survives crash-recovery), or just a byte
        # budget (``cache_bytes``) to have the engine build its own on the
        # obs metrics registry.
        if state_cache is None and cache_bytes is not None:
            state_cache = StateCache(cache_bytes, registry=self.obs.metrics)
        self.state_cache = state_cache
        self._cache_memo: Dict[int, int] = {}   # rid → miss generation
        # speculative decode: k-token n-gram/prompt-copy drafts verified by
        # ONE scan-of-decode-steps forward; rejects roll the per-slot state
        # back via the verify's own trajectory (greedy slots only)
        self.spec_k = max(0, int(spec_k))
        self.spec_ngram = max(1, int(spec_ngram))
        # chunked prefill: prompts longer than the largest bucket are fed
        # through a SIDE cache in fixed (chunk_rows, chunk_size) slabs —
        # the main decode cache can't host a partial prompt because the
        # fused all-slot decode step would advance (and corrupt) it
        self.chunk_rows = max(1, int(chunk_rows))
        self.chunk_size = int(chunk_size) if chunk_size is not None \
            else self.buckets[-1]
        if self.chunk_size < 1:
            raise ValueError(f"chunk_size must be >= 1, got {chunk_size}")
        self.chunk_enabled = chunk_rows > 0 and \
            getattr(model, "supports_chunked_prefill", False)
        # poison faults are only observable through the finiteness probes,
        # so a plan that injects them turns the guard on by itself
        self.guard = guard or (faults is not None and faults.needs_guard())
        # A decode step costs the same whether a slot is active or idle
        # (fixed batch), so single-slot refills waste a whole prefill
        # forward to activate one slot. Batch admissions: only refill once
        # this many slots are free (or nothing is decoding at all) — unless
        # the head-of-line wait blows the TTFT target (see _admission_due).
        self.refill_threshold = max(1, num_slots // 2) \
            if refill_threshold is None else refill_threshold

        self.cache = model.init_cache(num_slots, max_len)
        self.cache_len = jnp.zeros((num_slots,), jnp.int32)
        self.cur_tok = jnp.zeros((num_slots, 1), jnp.int32)
        # per-slot sampling state, scattered at refill like the cache
        self.slot_keys = jnp.zeros((num_slots, 2), jnp.uint32)
        self.slot_temp = jnp.zeros((num_slots,), jnp.float32)
        self.slot_topk = jnp.zeros((num_slots,), jnp.int32)
        self.slot_topp = jnp.ones((num_slots,), jnp.float32)
        # the decode chain and the scatter both rewrite the whole slot
        # cache every call — donate it so the engine holds ONE cache's
        # worth of device memory (and XLA can update in place), which is
        # what lets an overlapped prefill allocate its activations beside
        # the live decode loop instead of on top of two cache copies
        self._step = jax.jit(model.decode_step_sample, donate_argnums=(1,))

        # all-greedy steps skip the sampling tail (full-vocab sort + gumbel
        # per slot) — with temperature=0 the default, the common serving
        # regime decodes on the plain argmax step; slots only pay for
        # sampling on steps where some ACTIVE request actually samples
        # (key streams stay aligned: a sampling request keeps every one of
        # its steps on the sampled path)
        def greedy_step(params, cache, toks, clen):
            logits, cache = model.decode_step(params, cache, toks, clen,
                                              None)
            return jnp.argmax(logits, -1).astype(jnp.int32), cache

        self._step_greedy = jax.jit(greedy_step, donate_argnums=(1,))

        # guard-rail variants: same forward, plus the fused finiteness
        # probe and the additive poison seam (all-zero poison is a bitwise
        # no-op on the logits, so guarded streams match unguarded ones)
        def greedy_step_guarded(params, cache, toks, clen, poison):
            logits, cache = model.decode_step(params, cache, toks, clen,
                                              None)
            logits = logits + poison[:, None]
            return (jnp.argmax(logits, -1).astype(jnp.int32), cache,
                    jnp.all(jnp.isfinite(logits), axis=-1))

        self._step_greedy_guarded = jax.jit(greedy_step_guarded,
                                            donate_argnums=(1,))
        self._step_guarded = jax.jit(model.decode_step_sample_guarded,
                                     donate_argnums=(1,))
        self._probe = jax.jit(model.prefill_probe)
        self._poison0 = jnp.zeros((num_slots,), jnp.float32)
        self._scatter = jax.jit(model.scatter_into_cache,
                                donate_argnums=(0,))
        self._sample_flat = jax.jit(model.sample_tokens)
        # cached-lane row restore: ONE jitted writer shared by the decode
        # cache and the chunk cache (idx is traced; two cache shapes → two
        # compiles, independent of how many prefixes get restored)
        self._load_row = jax.jit(load_cache_row, donate_argnums=(0,))
        if self.spec_k:
            # no cache donation here: the verify's trajectory output keeps
            # K+1 cache copies alive, so in-place reuse is impossible
            self._spec_verify = jax.jit(model.decode_verify)
            self._spec_rollback = jax.jit(model.spec_rollback)
        m = self.obs.metrics
        self._spec_rounds = m.counter(
            "spec.rounds", help="speculative verify rounds issued")
        self._spec_proposed = m.counter(
            "spec.proposed", help="draft tokens proposed")
        self._spec_accepted = m.counter(
            "spec.accepted", help="draft tokens accepted by verify")
        self._spec_rate = m.gauge(
            "spec.accept_rate", help="accepted/proposed, cumulative")
        def prefill_packed(params, batch, ends):
            return model.prefill_packed(params, batch, max_len, ends)

        def prefill_wave(params, batch):
            return model.prefill(params, batch, max_len)

        self._prefill = jax.jit(prefill_packed)
        self._wave_prefill = jax.jit(prefill_wave)

        # chunked-prefill lane: a side cache of chunk_rows long prompts
        # being consumed slab by slab; handoff to a decode slot reuses the
        # packed scatter by viewing each row as a 1-segment harvest
        if self.chunk_enabled:
            self.chunk_cache = model.init_cache(self.chunk_rows, max_len)
            self.chunk_clen = jnp.zeros((self.chunk_rows,), jnp.int32)
            self._chunk_fn = jax.jit(model.prefill_chunk,
                                     donate_argnums=(1,))
            self._reset_rows = jax.jit(model.reset_cache_rows,
                                       donate_argnums=(0,))

            def chunk_handoff(cache, chunk_cache, src, dst):
                states = model.expand_chunk_states(chunk_cache)
                return model.scatter_into_cache(cache, states, src, dst)

            self._chunk_scatter = jax.jit(chunk_handoff, donate_argnums=(0,))

            def chunk_probe(chunk_cache, logits):
                states = model.expand_chunk_states(chunk_cache)
                return model.prefill_probe(states, logits[:, None])

            self._chunk_probe = jax.jit(chunk_probe)
        self.chunk_req: List[Optional[Request]] = [None] * self.chunk_rows
        self.chunk_off = [0] * self.chunk_rows    # prompt tokens consumed
        self.chunk_slot = [-1] * self.chunk_rows  # reserved decode slot
        self.chunk_capture = [-1] * self.chunk_rows  # StateCache boundary

        self.queue: collections.deque = collections.deque()
        self.slot_req: List[Optional[Request]] = [None] * num_slots
        self.slot_remaining = [0] * num_slots
        self.slot_pending = [False] * num_slots   # reserved by in-flight
        self.slot_last_t = [0.0] * num_slots      # last token host-observed
        self._prefill_pool: List[dict] = []       # pending packed prefills
        self.outputs: Dict[int, List[int]] = {}
        # explicit per-request lifecycle: queued → active → done | failed |
        # expired | cancelled; errors[rid] holds the failure diagnostic
        self.status: Dict[int, str] = {}
        self.errors: Dict[int, str] = {}
        self.resumed: set = set()     # rids restored from a snapshot
        self.stats = ServeStats(self.obs.metrics)
        self._next_rid = 0

    @property
    def spec_accept_rate(self) -> float:
        """Cumulative accepted/proposed draft-token ratio (0.0 before any
        speculative round) — also exported as the ``spec.accept_rate``
        gauge."""
        p = self._spec_proposed.value
        return self._spec_accepted.value / p if p else 0.0

    @property
    def _inflight(self) -> Optional[dict]:
        """Oldest pending prefill (None when the pool is empty) — the
        pre-pool engine exposed exactly one; tests and callers keep that
        view while the pool holds up to ``max_inflight_prefills``."""
        return self._prefill_pool[0] if self._prefill_pool else None

    # ------------------------------------------------------------ admission
    def submit(self, tokens, max_new: int, eos: Optional[int] = None,
               temperature: float = 0.0, top_k: int = 0,
               top_p: float = 1.0, deadline_ms: Optional[float] = None,
               prefix_len: Optional[int] = None,
               rid: Optional[int] = None) -> int:
        """Enqueue one request; returns its rid.

        ``prefix_len`` declares that ``tokens[:prefix_len]`` is a SHARED
        prefix (a system prompt): with a ``state_cache`` configured, the
        first such request's post-prefix state is captured at that exact
        boundary and every later request carrying the same prefix restores
        it and prefills only its suffix. Undeclared prompts still profit —
        any full prompt already decoded is itself a cached prefix — but
        only a declaration puts the capture boundary mid-prompt.

        ``deadline_ms`` bounds submit→completion: a request still queued,
        still in a prefill, or still decoding when its budget runs out is
        terminated with status "expired" (tokens generated so far are
        kept). ``rid`` lets a client pin its own id (e.g. resubmission
        with stable ids); duplicates of ANY known rid are rejected here
        rather than corrupting that request's output stream later.
        Prompts longer than the largest prefill bucket are accepted and
        served via chunked prefill (``max_prompt_len`` is the explicit
        length bound when configured).
        Raises ``ShedError`` — without queueing — when the admission queue
        is over its depth (``max_queue``) or age (``max_queue_age_ms``)
        bound: under overload a fast explicit reject beats an unbounded
        queue every client has already given up on."""
        tokens = np.asarray(tokens, np.int32)
        if tokens.ndim != 1 or len(tokens) == 0:
            raise ValueError(
                f"prompt must be a non-empty 1-D token array, got shape "
                f"{tokens.shape} — every request needs ≥ 1 prompt token")
        if max_new < 1:
            raise ValueError(f"max_new must be >= 1, got {max_new} — a "
                             f"request must generate at least one token")
        if self.max_prompt_len is not None and \
                len(tokens) > self.max_prompt_len:
            raise ValueError(
                f"prompt length {len(tokens)} exceeds max_prompt_len "
                f"{self.max_prompt_len} — raise the engine's bound or "
                f"truncate the prompt")
        if len(tokens) > self.buckets[-1] and not self.chunk_enabled:
            raise ValueError(
                f"prompt length {len(tokens)} exceeds largest prefill "
                f"bucket {self.buckets[-1]} and chunked prefill is "
                f"unavailable (chunk_rows=0, or the model has no "
                f"chunk-resume step) — enable chunking, split the prompt, "
                f"or configure a larger bucket")
        if len(tokens) + max_new > self.max_len:
            raise ValueError(f"prompt {len(tokens)} + max_new {max_new} "
                             f"exceeds slot capacity {self.max_len}")
        if temperature < 0.0:
            raise ValueError(f"temperature must be >= 0, got {temperature}")
        if top_k < 0:
            raise ValueError(f"top_k must be >= 0 (0 = full vocab), "
                             f"got {top_k}")
        if not 0.0 < top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {top_p}")
        if deadline_ms is not None and deadline_ms <= 0:
            raise ValueError(f"deadline_ms must be > 0, got {deadline_ms}")
        if prefix_len is not None and not 1 <= prefix_len <= len(tokens):
            raise ValueError(
                f"prefix_len {prefix_len} outside [1, {len(tokens)}] — it "
                f"marks how many LEADING prompt tokens form the shareable "
                f"prefix, so it must cover at least one token and at most "
                f"the whole prompt")
        if rid is not None:
            if rid < 0:
                raise ValueError(f"rid must be >= 0, got {rid}")
            if rid in self.outputs:
                raise ValueError(
                    f"duplicate request id {rid} (status "
                    f"{self.status.get(rid)!r}) — rids identify output "
                    f"streams and may never be reused")
        now = self._clock()
        if self.max_queue is not None and len(self.queue) >= self.max_queue:
            self.stats.shed += 1
            self._tr.instant("shed", track="engine", reason="max_queue")
            raise ShedError(f"shed: admission queue depth {len(self.queue)} "
                            f">= max_queue {self.max_queue}")
        if self.max_queue_age_ms is not None and self.queue:
            age_ms = (now - self.queue[0].submit_t) * 1e3
            if age_ms > self.max_queue_age_ms:
                self.stats.shed += 1
                self._tr.instant("shed", track="engine",
                                 reason="max_queue_age_ms")
                raise ShedError(
                    f"shed: head-of-line request has waited {age_ms:.0f}ms "
                    f"> max_queue_age_ms {self.max_queue_age_ms} — the "
                    f"engine is not keeping up")
        if rid is None:
            rid = self._next_rid
        self._next_rid = max(self._next_rid, rid + 1)
        self.queue.append(Request(rid, tokens, max_new,
                                  self.eos if eos is None else eos,
                                  temperature, int(top_k), top_p,
                                  now, deadline_ms, prefix_len))
        self.outputs[rid] = []
        self.status[rid] = "queued"
        self._span_to(rid, "queued", prompt=len(tokens), max_new=max_new)
        self.stats.queue_depth_max = max(self.stats.queue_depth_max,
                                         len(self.queue))
        return rid

    def _free_slots(self) -> List[int]:
        return [i for i, r in enumerate(self.slot_req)
                if r is None and not self.slot_pending[i]]

    def _active_slots(self) -> List[int]:
        return [i for i, r in enumerate(self.slot_req) if r is not None]

    def _finish_token(self, slot: int, tok: int):
        """Record one generated token; release the slot on EOS / budget."""
        req = self.slot_req[slot]
        self.outputs[req.rid].append(tok)
        self.stats.generated += 1
        self.slot_remaining[slot] -= 1
        if tok == req.eos or self.slot_remaining[slot] <= 0:
            self.slot_req[slot] = None
            self.status[req.rid] = "done"
            self._span_end(req.rid, "done",
                           tokens=len(self.outputs[req.rid]))

    # ------------------------------------------------------------ lifecycle
    def _span_to(self, rid: int, name: str, **attrs):
        """Advance a request's lifecycle span (queued → prefill/chunk →
        decode) on its own trace track — one Perfetto row per request."""
        self._tr.finish(self._req_spans.pop(rid, None))
        self._req_spans[rid] = self._tr.start(name, track=f"req{rid}",
                                              rid=rid, **attrs)

    def _span_end(self, rid: int, status: str, **attrs):
        """Close a request's lifecycle span at a terminal status and mark
        the terminal as an instant event on its track."""
        self._tr.finish(self._req_spans.pop(rid, None))
        self._tr.instant(status, track=f"req{rid}", rid=rid, **attrs)

    def _terminate(self, rid: int, status: str, reason: str):
        """Move a request to a terminal status with its diagnostic."""
        self.status[rid] = status
        self.errors[rid] = reason
        if status == "expired":
            self.stats.expired += 1
        elif status == "cancelled":
            self.stats.cancelled += 1
        self._span_end(rid, status, reason=reason)

    def _deadline_over(self, req: Request, now: float) -> bool:
        return req.deadline_ms is not None and \
            (now - req.submit_t) * 1e3 >= req.deadline_ms

    def _expire_queued(self):
        """Admission-side deadline enforcement: drop queued requests whose
        budget already ran out — prefilling them would waste a forward on
        an answer nobody is waiting for."""
        if not any(r.deadline_ms is not None for r in self.queue):
            return
        now = self._clock()
        kept = collections.deque()
        for r in self.queue:
            if self._deadline_over(r, now):
                self._terminate(r.rid, "expired",
                                f"deadline {r.deadline_ms:.0f}ms exceeded "
                                f"while queued")
            else:
                kept.append(r)
        self.queue = kept

    def cancel(self, rid: int) -> bool:
        """Revoke a request wherever it is: queued (dequeued now), reserved
        by an in-flight prefill (its slot is released when the prefill
        lands), or actively decoding (slot freed now). Tokens generated so
        far stay in ``outputs[rid]``. Returns False for unknown rids and
        requests already in a terminal state — cancelling twice, or after
        completion, is a harmless no-op."""
        st = self.status.get(rid)
        if st == "queued":
            self.queue = collections.deque(
                r for r in self.queue if r.rid != rid)
            self._terminate(rid, "cancelled", "cancelled while queued")
            return True
        if st == "active":
            for i, r in enumerate(self.slot_req):
                if r is not None and r.rid == rid:
                    self.slot_req[i] = None
                    self._terminate(rid, "cancelled", "cancelled mid-decode")
                    return True
            # reserved by an in-flight prefill (_land_prefill skips it) or
            # mid-chunked-prefill (_chunk_step frees the row next round)
            self._terminate(rid, "cancelled", "cancelled during prefill")
            return True
        return False

    def _packable(self) -> List[Request]:
        """Queued requests the PACKED prefill path serves, FIFO. Longer
        prompts stay queued for the chunk lane and never block these.
        Declared-prefix requests belong to the cached lane when a
        StateCache and the chunk lane are both available — only the chunk
        lane can cut the slab stream at the declared boundary to capture
        (or resume from) the prefix state."""
        Lmax = self.buckets[-1]
        cached_lane = self.state_cache is not None and self.chunk_enabled
        return [r for r in self.queue if len(r.tokens) <= Lmax
                and not (cached_lane and r.prefix_len)]

    def _admission_due(self, free: List[int],
                       head: Optional[Request]) -> bool:
        """Throughput rule (enough free slots, or nothing decoding) with a
        latency override: admit below the threshold when the head-of-line
        request's wait already exceeds ``target_ttft_ms``. ``head`` is the
        oldest PACKABLE request (chunk-lane prompts are admitted by
        ``_chunk_step`` and don't gate the packed path)."""
        if not free or head is None or \
                len(self._prefill_pool) >= self.max_inflight_prefills:
            return False
        if not self._active_slots():
            return True
        if len(free) >= self.refill_threshold:
            return True
        if self.target_ttft_ms is not None:
            wait_ms = (self._clock() - head.submit_t) * 1e3
            if wait_ms >= self.target_ttft_ms:
                self.stats.early_admits += 1
                return True
        return False

    def _admit_count(self, packq: List[Request], L: int,
                     nfree: int) -> int:
        """How many head-of-queue packable requests one (prefill_rows, L)
        round would admit — the dry-run of ``_try_refill``'s loop."""
        lens: List[int] = []
        for req in packq:
            if len(req.tokens) > L or len(lens) == nfree:
                break
            plan = packing.plan_packing(lens + [len(req.tokens)], L,
                                        self.policy)
            if len(plan) > self.prefill_rows or \
                    any(len(row) > self.max_segments for row in plan):
                break
            lens.append(len(req.tokens))
        return len(lens)

    def _choose_bucket(self, head: Request, packq: List[Request],
                       free: List[int]) -> int:
        """Pick the prefill bucket for this round. ``smallest_fit`` (the
        default, and the pre-v2 behaviour) takes the smallest bucket that
        holds the head-of-line prompt. ``ttft`` upgrades to a larger
        bucket when that admits strictly more queued requests AND the
        head's wait is still inside the TTFT allowance
        (``target_ttft_ms``, else the measured p50): wait to fill big
        while the head has slack, admit small immediately once the head
        is already late — a bigger forward would only make a blown
        deadline worse, while everyone behind the head still benefits
        from upgrades on later rounds."""
        fits = [b for b in self.buckets if b >= len(head.tokens)]
        L = fits[0]
        if self.bucket_policy != "ttft" or len(fits) == 1:
            return L
        allowance = self.target_ttft_ms
        if allowance is None:
            allowance = self.stats.ttft_percentiles().get("p50")
        if allowance is None or allowance <= 0:
            return L                 # no latency signal yet — stay small
        best_n, best_L = self._admit_count(packq, L, len(free)), L
        if best_n >= min(len(packq), len(free)):
            return L     # smallest fit already admits every admissible
            #              request — no bucket can admit strictly more,
            #              skip the bigger buckets' dry-runs entirely
        for b in fits[1:]:
            n = self._admit_count(packq, b, len(free))
            if n > best_n:
                best_n, best_L = n, b
        if best_L == L:
            return L
        wait_ms = (self._clock() - head.submit_t) * 1e3
        if wait_ms < allowance:
            self.stats.bucket_upgrades += 1
            return best_L
        self.stats.deferred_upgrades += 1
        return L

    def _try_refill(self) -> bool:
        """Admit queued prompts into free slots via one packed prefill.

        Bucket choice starts from the oldest packable prompt
        (``_choose_bucket``); younger prompts join only if they fit the
        chosen bucket (FIFO within a round, no starvation across rounds).
        The prefill is dispatched asynchronously; with ``overlap`` on and
        other slots decoding, it joins the in-flight pool (see
        _land_prefill)."""
        packq = self._packable()
        head = packq[0] if packq else None
        free = self._free_slots()
        if not self._admission_due(free, head):
            return False
        L = self._choose_bucket(head, packq, free)
        admitted: List[Request] = []
        lens: List[int] = []
        for req in packq:
            if len(req.tokens) > L or len(admitted) == len(free):
                break
            plan = packing.plan_packing(lens + [len(req.tokens)], L,
                                        self.policy)
            if len(plan) > self.prefill_rows or \
                    any(len(row) > self.max_segments for row in plan):
                break
            admitted.append(req)
            lens.append(len(req.tokens))
        if not admitted:
            return False
        if self._active_slots():
            self.stats.midflight_refills += 1
        adm = {r.rid for r in admitted}   # a prefix of packq, but possibly
        #                                   interleaved with chunk prompts
        self.queue = collections.deque(
            r for r in self.queue if r.rid not in adm)
        for req in admitted:
            self.status[req.rid] = "active"
            self._span_to(req.rid, "prefill", bucket=L)
        pidx = self.stats.prefills      # this dispatch's fault-plan index
        dsid = self._tr.start("serve.prefill_dispatch", track="engine",
                              bucket=L, rows=self.prefill_rows,
                              admitted=len(admitted), pidx=pidx)
        if self.faults is not None and self.faults.fails_prefill(pidx):
            # the packed forward died (injected stand-in for device OOM /
            # preemption): fail this round's requests with an explicit
            # status and keep serving — no slot was reserved, no state
            # landed, the live slots never notice
            self.stats.prefills += 1
            self.stats.prefill_faults += 1
            for req in admitted:
                self._terminate(req.rid, "failed",
                                f"prefill dispatch {pidx} failed "
                                f"(injected fault)")
            self._tr.finish(dsid, fault=True)
            return False
        pb = packing.pack([r.tokens for r in admitted], L,
                          policy=self.policy, num_rows=self.prefill_rows)
        ends = packing.segment_ends(pb, self.max_segments)
        batch = {"tokens": pb.tokens, "positions": pb.positions,
                 "segment_ids": pb.segment_ids}
        logits, states, seg_lens = self._prefill(self.params, batch,
                                                 ends=jnp.asarray(ends))
        if self.faults is not None:
            rs = self.faults.prefill_poison(pidx)
            if rs:
                states = poison_states(states, rs,
                                       self.faults.poison_value)
        # (row, seg) → admitted request → slot; fixed-size scatter with the
        # num_slots sentinel dropping unused entries (one compile per bucket)
        K = self.prefill_rows * self.max_segments
        src = np.zeros(K, np.int32)
        dst = np.full(K, self.num_slots, np.int32)
        rids = np.zeros(K, np.int32)
        temp = np.zeros(K, np.float32)
        topk = np.zeros(K, np.int32)
        topp = np.ones(K, np.float32)
        slot_of = {}
        for r, ids in enumerate(pb.seq_ids):
            for s, qi in enumerate(ids):
                slot = free[qi]
                k = len(slot_of)
                src[k] = r * self.max_segments + s
                dst[k] = slot
                slot_of[qi] = (slot, r, s)
                req = admitted[qi]
                fk = r * self.max_segments + s
                rids[fk] = req.rid
                temp[fk] = req.temperature
                topk[fk] = req.top_k
                topp[fk] = req.top_p
        # the prefill's own first token, sampled per segment with each
        # request's (seed, rid)-derived key stream — flat (K, V) so the
        # sample jit compiles once, independent of the bucket
        keys0 = B.request_keys(self.sample_seed, rids)
        flat_lg = logits.reshape(K, -1)
        flat_tok, keys1 = self._sample_flat(flat_lg, keys0,
                                            jnp.asarray(temp),
                                            jnp.asarray(topk),
                                            jnp.asarray(topp))
        for qi in slot_of:                       # reserve target slots
            self.slot_pending[slot_of[qi][0]] = True
        inf = {
            "tok": flat_tok, "keys": keys1, "states": states,
            "logits": flat_lg, "seg_lens": seg_lens, "src": jnp.asarray(src),
            "dst": jnp.asarray(dst), "admitted": admitted,
            "slot_of": slot_of, "temp": temp, "topk": topk, "topp": topp,
            "steps_waited": 0, "pidx": pidx, "probes": 0}
        if self.guard:
            # per-segment finiteness of the harvested states + end logits;
            # probed asynchronously with the prefill, read at land time
            inf["ok"] = self._probe(states, logits)
        self._prefill_pool.append(inf)
        self.stats.prefills += 1
        self.stats.prefill_tokens += sum(lens)
        self.stats.buckets.add((self.prefill_rows, L))
        self._tr.finish(dsid, tokens=sum(lens))
        if not self.overlap or not self._active_slots():
            self._land_prefill(block=True)
        return True

    def _prefill_ready(self, inflight: dict) -> bool:
        """Device-side completion probe for an in-flight prefill (split out
        so tests can script the overlap window). A fault plan can hold the
        answer at not-ready for the first N probes — a deterministic slow
        device stretching the overlap window."""
        if self.faults is not None and self.faults.prefill_not_ready(
                inflight.get("pidx", 0), inflight.get("probes", 0)):
            inflight["probes"] = inflight.get("probes", 0) + 1
            return False
        tok = inflight["tok"]
        ready = getattr(tok, "is_ready", None)
        return ready() if ready is not None else True

    def _land_prefill(self, block: bool = False) -> bool:
        """Scatter completed prefills' states into their reserved slots and
        activate them. With ``block=False`` only pool entries whose device
        result is ready land (a no-op while everything is still in flight —
        decode keeps the device busy and the states land on a later engine
        step); ``block=True`` drains the whole pool. Entries land in any
        order: they target disjoint reserved slots and per-request sampling
        keys keep token streams schedule-independent."""
        landed = False
        for inf in list(self._prefill_pool):
            if not block and not self._prefill_ready(inf):
                continue
            self._prefill_pool.remove(inf)
            self._land_one(inf)
            landed = True
        return landed

    def _land_one(self, inf: dict):
        """Land one dispatched prefill: scatter states, activate slots."""
        lsid = self._tr.start("serve.prefill_land", track="engine",
                              pidx=inf["pidx"],
                              steps_waited=inf["steps_waited"])
        src_j, dst_j = inf["src"], inf["dst"]
        self.cache = self._scatter(self.cache, inf["states"], src_j, dst_j)
        flat_lens = inf["seg_lens"].reshape(-1)
        self.cache_len = self.cache_len.at[dst_j].set(
            flat_lens[src_j], mode="drop")
        self.cur_tok = self.cur_tok.at[dst_j].set(
            inf["tok"][src_j][:, None], mode="drop")
        self.slot_keys = self.slot_keys.at[dst_j].set(
            inf["keys"][src_j], mode="drop")
        self.slot_temp = self.slot_temp.at[dst_j].set(
            jnp.asarray(inf["temp"])[src_j], mode="drop")
        self.slot_topk = self.slot_topk.at[dst_j].set(
            jnp.asarray(inf["topk"])[src_j], mode="drop")
        self.slot_topp = self.slot_topp.at[dst_j].set(
            jnp.asarray(inf["topp"])[src_j], mode="drop")
        # host bookkeeping + the prefill's own first token (the np.asarray
        # is the host sync point — TTFT is measured where the token becomes
        # observable, not where the prefill was dispatched)
        with self._tr.span("serve.prefill_wait", track="engine"):
            first = np.asarray(inf["tok"])
            ok = np.asarray(inf["ok"]).reshape(-1) if "ok" in inf else None
        esid = self._tr.start("serve.prefill_emit", track="engine")
        now = self._clock()
        for qi, req in enumerate(inf["admitted"]):
            slot, r, s = inf["slot_of"][qi]
            self.slot_pending[slot] = False
            if self.status.get(req.rid) == "cancelled":
                continue            # revoked while the prefill was in flight
            if self._deadline_over(req, now):
                self._terminate(req.rid, "expired",
                                f"deadline {req.deadline_ms:.0f}ms exceeded "
                                f"during prefill")
                continue
            k = r * self.max_segments + s
            if ok is not None and not ok[k]:
                # quarantine: the harvested state (or its end logits) went
                # non-finite — fail the request with a diagnostic and leave
                # the slot free (its cache row is fully overwritten at the
                # next refill, so the poison never propagates)
                self.stats.quarantined += 1
                self._tr.instant("quarantined", track=f"req{req.rid}",
                                 rid=req.rid)
                self._terminate(req.rid, "failed",
                                f"non-finite prefill state for request "
                                f"{req.rid} (prefill {inf['pidx']}, row "
                                f"{r}, segment {s}) — quarantined")
                continue
            if self.state_cache is not None:
                # every landed prompt doubles as a cached prefix — the
                # packed path's contribution to the StateCache
                self._insert_cache(req.tokens, len(req.tokens),
                                   state_row(inf["states"], r, s),
                                   inf["logits"][k])
            self.slot_req[slot] = req
            self.slot_remaining[slot] = req.max_new
            self.slot_last_t[slot] = now
            self.stats.ttft_ms.append((now - req.submit_t) * 1e3)
            self._span_to(req.rid, "decode", slot=slot)
            self._tr.instant("first_token", track=f"req{req.rid}",
                             rid=req.rid)
            self._finish_token(slot, int(first[k]))
        if inf["steps_waited"] > 0:
            self.stats.overlapped_prefills += 1
        self._tr.finish(esid)
        self._tr.finish(lsid)

    # ------------------------------------------------------- chunked prefill
    def _chunk_active(self) -> bool:
        return any(r is not None for r in self.chunk_req)

    def _free_chunk_row(self, row: int):
        """Release a chunk row and its reserved decode slot."""
        slot = self.chunk_slot[row]
        if slot >= 0:
            self.slot_pending[slot] = False
        self.chunk_req[row] = None
        self.chunk_slot[row] = -1
        self.chunk_capture[row] = -1

    def _chunk_claims(self):
        """Assign queued over-bucket prompts to free chunk rows (each also
        reserves the decode slot it will land in, so packed admission can't
        take it out from under a half-consumed prompt)."""
        claimed = np.zeros(self.chunk_rows, bool)
        Lmax = self.buckets[-1]
        cached_lane = self.state_cache is not None
        for row in range(self.chunk_rows):
            if self.chunk_req[row] is not None:
                continue
            # declared-prefix prompts are claimed by _cache_admit (which
            # also decides the capture boundary / restored offset)
            nxt = next((r for r in self.queue if len(r.tokens) > Lmax
                        and not (cached_lane and r.prefix_len)),
                       None)
            if nxt is None:
                break
            free = self._free_slots()
            if not free:
                break
            self.queue = collections.deque(
                r for r in self.queue if r.rid != nxt.rid)
            self.status[nxt.rid] = "active"
            self.slot_pending[free[0]] = True
            self.chunk_req[row] = nxt
            self.chunk_off[row] = 0
            self.chunk_slot[row] = free[0]
            claimed[row] = True
            self._span_to(nxt.rid, "chunk", row=row, slot=free[0],
                          prompt=len(nxt.tokens))
        if claimed.any():
            # wipe the claimed rows back to init_cache values — no stale
            # conv tail / attention ring / stabilizer state across tenants
            fr = jnp.asarray(claimed)
            self.chunk_cache = self._reset_rows(self.chunk_cache, fr)
            self.chunk_clen = jnp.where(fr, 0, self.chunk_clen)

    # --------------------------------------------------------- prefix cache
    def _insert_cache(self, tokens, prefix_len: int, row, logits):
        """Store one single-row state tree in the StateCache, with the
        insert-side guard: a non-finite state is never cached — a poisoned
        entry would turn one fault into a failure for every request that
        shares the prefix."""
        lg = np.asarray(logits, np.float32)
        row = jax.device_get(row)
        if not row_finite(row, lg):
            return
        e = self.state_cache.insert(tokens, prefix_len, row, lg)
        if e is not None:
            self._tr.instant("cache_insert", track="engine",
                             prefix=int(prefix_len), bytes=e.nbytes)

    def _claim_row(self, req: Request, row: int, slot: int, off: int,
                   capture: int = -1, state=None):
        """Claim a chunk row (and its reserved decode slot) for ``req``
        starting at prompt offset ``off`` — either cold (``state=None``:
        the row is wiped to init_cache values) or resuming from a restored
        cache entry (``state``: a single-row tree; the carried length
        starts at the prefix length). ``capture > off`` marks a declared
        prefix boundary: _chunk_step cuts the slab stream there and
        inserts the post-boundary state into the StateCache as it goes
        by."""
        self.queue = collections.deque(
            r for r in self.queue if r.rid != req.rid)
        self.status[req.rid] = "active"
        self.slot_pending[slot] = True
        self.chunk_req[row] = req
        self.chunk_off[row] = off
        self.chunk_slot[row] = slot
        self.chunk_capture[row] = capture if capture > off else -1
        mask = np.zeros(self.chunk_rows, bool)
        mask[row] = True
        mj = jnp.asarray(mask)
        if state is None:
            self.chunk_cache = self._reset_rows(self.chunk_cache, mj)
            self.chunk_clen = jnp.where(mj, 0, self.chunk_clen)
        else:
            self.chunk_cache = self._load_row(self.chunk_cache, state, row)
            self.chunk_clen = jnp.where(mj, off, self.chunk_clen)
        self._span_to(req.rid, "chunk", row=row, slot=slot,
                      prompt=len(req.tokens), cached_prefix=off)

    def _activate_full_hit(self, req: Request, slot: int, state, entry):
        """Zero-forward admission on a whole-prompt cache hit: restore the
        stored post-prompt state straight into a free decode slot and
        sample the first token from the STORED end-of-prompt logits with
        the request's own (seed, rid) key stream — bit-identical to what a
        cold prefill of the same prompt would emit, without running one."""
        self.queue = collections.deque(
            r for r in self.queue if r.rid != req.rid)
        now = self._clock()
        if self._deadline_over(req, now):
            self._terminate(req.rid, "expired",
                            f"deadline {req.deadline_ms:.0f}ms exceeded "
                            f"while queued")
            return
        self.cache = self._load_row(self.cache, state, slot)
        self.cache_len = self.cache_len.at[slot].set(entry.prefix_len)
        keys0 = B.request_keys(self.sample_seed,
                               np.asarray([req.rid], np.int32))
        tok, keys1 = self._sample_flat(
            jnp.asarray(entry.logits)[None], keys0,
            jnp.asarray([req.temperature], jnp.float32),
            jnp.asarray([req.top_k], jnp.int32),
            jnp.asarray([req.top_p], jnp.float32))
        self.cur_tok = self.cur_tok.at[slot].set(tok)
        self.slot_keys = self.slot_keys.at[slot].set(keys1[0])
        self.slot_temp = self.slot_temp.at[slot].set(req.temperature)
        self.slot_topk = self.slot_topk.at[slot].set(req.top_k)
        self.slot_topp = self.slot_topp.at[slot].set(req.top_p)
        self.status[req.rid] = "active"
        self.slot_req[slot] = req
        self.slot_remaining[slot] = req.max_new
        self.slot_last_t[slot] = now
        self.stats.ttft_ms.append((now - req.submit_t) * 1e3)
        self._span_to(req.rid, "decode", slot=slot)
        self._tr.instant("first_token", track=f"req{req.rid}", rid=req.rid)
        self._finish_token(slot, int(np.asarray(tok)[0]))

    def _cache_admit(self):
        """Cached-lane admission, run before packed refill each step.

        For every queued request (FIFO) the StateCache is consulted for
        its longest stored prefix:

        * FULL-prompt hit → ``_activate_full_hit`` (no forward at all);
        * partial hit → a chunk row is claimed seeded with the restored
          state at offset P, so only the suffix is prefilled;
        * declared-prefix miss → a chunk row is claimed cold with the
          capture boundary set, so the first request with a new system
          prompt populates the cache for everyone behind it;
        * undeclared miss → memoized against the cache's generation (no
          re-hashing until the cache changes) and left for the packed /
          chunk lanes.

        The fault seams live here too: ``drop_cache`` clears the cache
        before the indexed lookup, ``poison_cache_hit`` corrupts the
        restored state of the indexed hit (which the guard rails must
        quarantine downstream)."""
        sc = self.state_cache
        if sc is None or not self.queue:
            return
        for req in list(self.queue):
            free = self._free_slots()
            if not free:
                return
            if self.chunk_enabled:
                rows = [i for i in range(self.chunk_rows)
                        if self.chunk_req[i] is None]
                if not rows:
                    return
            else:
                rows = []
            declared = int(req.prefix_len or 0)
            if not declared and \
                    self._cache_memo.get(req.rid) == sc.generation:
                continue               # known miss and the cache unchanged
            if self.faults is not None and \
                    self.faults.drops_cache(sc.lookups):
                sc.clear()
            entry = sc.lookup(req.tokens)
            if entry is None:
                self._cache_memo[req.rid] = sc.generation
                if not declared or not self.chunk_enabled:
                    continue           # packed / chunk lanes serve it cold
                self._claim_row(req, rows[0], free[0], off=0,
                                capture=declared)
                continue
            hidx = sc.hits - 1         # the lookup above counted this hit
            state = sc.device_state(entry)
            if self.faults is not None and self.faults.cache_hit_poison(hidx):
                state = poison_cache_rows(state, [0],
                                          self.faults.poison_value)
            P = entry.prefix_len
            self._tr.instant("cache_hit", track=f"req{req.rid}",
                             rid=req.rid, prefix=P)
            if P == len(req.tokens):
                self._activate_full_hit(req, free[0], state, entry)
                continue
            if not self.chunk_enabled:
                # a suffix prefill needs the chunk lane's carried state;
                # without it the packed lane serves the request cold
                self._cache_memo[req.rid] = sc.generation
                continue
            self._claim_row(req, rows[0], free[0], off=P,
                            capture=declared if declared > P else -1,
                            state=state)

    def _chunk_step(self):
        """One chunked-prefill round: claim rows for queued over-bucket
        prompts, advance every occupied row by one fixed-shape
        (chunk_rows, chunk_size) slab resuming from the carried state, and
        hand finished prompts off to their reserved decode slots (first
        token sampled with the request's own key stream, guard probe on the
        carried state) — all while the decode slots keep stepping."""
        if not self.chunk_enabled:
            return
        self._chunk_claims()
        rows = [i for i, r in enumerate(self.chunk_req) if r is not None]
        if not rows:
            return
        # lifecycle sweep before spending a forward on a dead request
        now = self._clock()
        for i in rows:
            req = self.chunk_req[i]
            if self.status.get(req.rid) == "cancelled":
                self._free_chunk_row(i)
            elif self._deadline_over(req, now):
                self._terminate(req.rid, "expired",
                                f"deadline {req.deadline_ms:.0f}ms exceeded "
                                f"during chunked prefill")
                self._free_chunk_row(i)
        rows = [i for i, r in enumerate(self.chunk_req) if r is not None]
        if not rows:
            return
        cidx = self.stats.chunk_rounds
        if self.faults is not None and self.faults.fails_chunk(cidx):
            # the chunk forward died (injected stand-in for device OOM on
            # the slab): fail the rows' requests, keep serving — the decode
            # slots never notice
            self.stats.chunk_rounds += 1
            self.stats.prefill_faults += 1
            for i in rows:
                self._terminate(self.chunk_req[i].rid, "failed",
                                f"chunked-prefill round {cidx} failed "
                                f"(injected fault)")
                self._free_chunk_row(i)
            return
        # per-row consumption stop: a declared capture boundary CUTS the
        # slab stream so the carried state (and the slab's end logits) at
        # the cut are exactly the post-prefix artifacts the cache stores
        stops = {}
        for i in rows:
            cap = self.chunk_capture[i]
            stops[i] = cap if cap > self.chunk_off[i] \
                else len(self.chunk_req[i].tokens)
        # slab width is bucket-quantized to the round's real need (a warm
        # suffix round compiles/runs a small slab, not the full chunk_size
        # one) — compile shapes stay bounded by the bucket list
        need = max(min(self.chunk_size, stops[i] - self.chunk_off[i])
                   for i in rows)
        T = packing.slab_width(need, self.buckets, self.chunk_size)
        entries = {}
        took = {}
        for i in rows:
            off = self.chunk_off[i]
            n = min(T, stops[i] - off)
            entries[i] = (self.chunk_req[i].tokens, off, n)
            took[i] = n
        batch = packing.suffix_slab(entries, self.chunk_rows, T)
        csid = self._tr.start("serve.chunk_slab", track="engine",
                              round=cidx, rows=len(rows),
                              tokens=sum(took.values()))
        logits, self.chunk_cache, self.chunk_clen = self._chunk_fn(
            self.params, self.chunk_cache, batch, self.chunk_clen)
        self.stats.chunk_rounds += 1
        self.stats.chunk_tokens += sum(took.values())
        self._tr.finish(csid)
        if self.faults is not None:
            prs = self.faults.chunk_poison(cidx)
            if prs:
                self.chunk_cache = poison_cache_rows(
                    self.chunk_cache, prs, self.faults.poison_value)
        if self.state_cache is not None:
            for i in rows:
                cap = self.chunk_capture[i]
                if cap >= 0 and self.chunk_off[i] + took[i] >= cap:
                    # the slab stream was cut at the boundary, so row i's
                    # carried state IS the post-prefix state and logits[i]
                    # are the end-of-prefix logits — capture both
                    self._insert_cache(self.chunk_req[i].tokens, cap,
                                       cache_row(self.chunk_cache, i),
                                       logits[i])
                    self.chunk_capture[i] = -1
        finishing = []
        for i in rows:
            self.chunk_off[i] += took[i]
            if self.chunk_off[i] >= len(self.chunk_req[i].tokens):
                finishing.append(i)
        if not finishing:
            return
        hsid = self._tr.start("serve.chunk_handoff", track="engine",
                              handoffs=len(finishing))
        # handoff: sample each finished prompt's first token with its own
        # (seed, rid)-derived key stream, then scatter the carried state
        # into the reserved decode slot — fixed chunk_rows shapes, the
        # num_slots sentinel dropping the still-chunking rows
        rids = np.zeros(self.chunk_rows, np.int32)
        temp = np.zeros(self.chunk_rows, np.float32)
        topk = np.zeros(self.chunk_rows, np.int32)
        topp = np.ones(self.chunk_rows, np.float32)
        dst = np.full(self.chunk_rows, self.num_slots, np.int32)
        for i in finishing:
            req = self.chunk_req[i]
            rids[i] = req.rid
            temp[i] = req.temperature
            topk[i] = req.top_k
            topp[i] = req.top_p
            dst[i] = self.chunk_slot[i]
        keys0 = B.request_keys(self.sample_seed, rids)
        tok, keys1 = self._sample_flat(logits, keys0, jnp.asarray(temp),
                                       jnp.asarray(topk), jnp.asarray(topp))
        ok = None
        if self.guard:
            ok = np.asarray(self._chunk_probe(self.chunk_cache,
                                              logits)).reshape(-1)
        src_j = jnp.arange(self.chunk_rows, dtype=jnp.int32)
        dst_j = jnp.asarray(dst)
        self.cache = self._chunk_scatter(self.cache, self.chunk_cache,
                                         src_j, dst_j)
        self.cache_len = self.cache_len.at[dst_j].set(
            self.chunk_clen, mode="drop")
        self.cur_tok = self.cur_tok.at[dst_j].set(
            tok[:, None], mode="drop")
        self.slot_keys = self.slot_keys.at[dst_j].set(keys1, mode="drop")
        self.slot_temp = self.slot_temp.at[dst_j].set(
            jnp.asarray(temp), mode="drop")
        self.slot_topk = self.slot_topk.at[dst_j].set(
            jnp.asarray(topk), mode="drop")
        self.slot_topp = self.slot_topp.at[dst_j].set(
            jnp.asarray(topp), mode="drop")
        with self._tr.span("serve.chunk_wait", track="engine"):
            first = np.asarray(tok)     # host sync — TTFT observed here
        now = self._clock()
        for i in finishing:
            req = self.chunk_req[i]
            slot = self.chunk_slot[i]
            self._free_chunk_row(i)
            if self._deadline_over(req, now):
                self._terminate(req.rid, "expired",
                                f"deadline {req.deadline_ms:.0f}ms exceeded "
                                f"during chunked prefill")
                continue
            if ok is not None and not ok[i]:
                # quarantine: the carried state (or its end logits) went
                # non-finite — the slot stays free; its cache row is fully
                # overwritten at the next refill, so the poison never
                # reaches a live stream
                self.stats.quarantined += 1
                self._tr.instant("quarantined", track=f"req{req.rid}",
                                 rid=req.rid)
                self._terminate(req.rid, "failed",
                                f"non-finite chunked-prefill state for "
                                f"request {req.rid} (chunk round {cidx}, "
                                f"row {i}) — quarantined")
                continue
            if self.state_cache is not None:
                # the finished prompt is itself a cached prefix: a later
                # identical prompt becomes a zero-forward full hit
                self._insert_cache(req.tokens, len(req.tokens),
                                   cache_row(self.chunk_cache, i),
                                   logits[i])
            self.slot_req[slot] = req
            self.slot_remaining[slot] = req.max_new
            self.slot_last_t[slot] = now
            self.stats.ttft_ms.append((now - req.submit_t) * 1e3)
            self.stats.chunked_prefills += 1
            self._span_to(req.rid, "decode", slot=slot)
            self._tr.instant("first_token", track=f"req{req.rid}",
                             rid=req.rid)
            self._finish_token(slot, int(first[i]))
        self._tr.finish(hsid)

    # --------------------------------------------------- speculative decode
    def _spec_draft(self):
        """Propose up to ``spec_k`` draft tokens per active slot by n-gram
        prompt copy: find the most recent earlier occurrence of the
        context's trailing g-gram (g from ``spec_ngram`` down to 1, search
        capped at the last 512 context tokens) and copy the tokens that
        followed it. Free — no model call — and strong exactly where
        speculation pays: prompts that quote, template, or repeat.
        Returns ((num_slots, spec_k) int32 drafts, (num_slots,) bool
        have-a-draft)."""
        K = self.spec_k
        draft = np.zeros((self.num_slots, K), np.int32)
        have = np.zeros(self.num_slots, bool)
        for i in self._active_slots():
            req = self.slot_req[i]
            ctx = [int(t) for t in req.tokens] + self.outputs[req.rid]
            n = len(ctx)
            for g in range(min(self.spec_ngram, n - 1), 0, -1):
                pat = ctx[n - g:]
                hit = -1
                for e in range(n - 2, max(g - 2, n - 2 - 512), -1):
                    if ctx[e - g + 1:e + 1] == pat:
                        hit = e
                        break
                if hit >= 0:        # hit ≤ n-2, so ≥ 1 token follows it
                    cont = ctx[hit + 1:hit + 1 + K]
                    draft[i, :len(cont)] = cont
                    have[i] = True
                    break
        return draft, have

    def _spec_round(self, active: List[int], step_idx: int) -> bool:
        """One speculative round: draft ``spec_k`` tokens per slot, verify
        EVERY slot with one scan-of-decode-steps forward
        (``model.decode_verify`` — the same per-token computation as the
        plain greedy step, so token streams are bit-identical by
        construction), commit each slot's accepted draft prefix plus the
        verify's own next token, and roll each slot's state back to its
        post-commit trajectory entry (``model.spec_rollback``). Greedy
        slots only — the caller falls back to the plain step when any
        active slot samples, or when no slot has a draft (returns False:
        a verify round would then be pure overhead)."""
        draft, have = self._spec_draft()
        if not have.any():
            return False
        K = self.spec_k
        rsid = self._tr.start("serve.spec_round", track="engine",
                              step=step_idx, active=len(active), k=K)
        toks, fins, traj = self._spec_verify(
            self.params, self.cache, self.cur_tok, self.cache_len,
            jnp.asarray(draft))
        toks_np = np.asarray(toks)
        fin_np = np.asarray(fins) if self.guard else None
        cur_np = np.asarray(self.cur_tok[:, 0]).copy()
        idx = np.zeros(self.num_slots, np.int32)
        adv = np.zeros(self.num_slots, np.int32)
        commits: Dict[int, List[int]] = {}
        bad: Dict[int, bool] = {}
        proposed = accepted = 0
        for i in active:
            req = self.slot_req[i]
            a = 0
            while a < K and draft[i, a] == toks_np[i, a]:
                a += 1
            if have[i]:
                proposed += K
                accepted += a
            # commit t_1..t_{a+1}: the a verified draft tokens plus the
            # verify's own next token — truncated at EOS / the slot's
            # remaining budget / (guard on) the first non-finite step
            emit: List[int] = []
            bad[i] = False
            for t in toks_np[i, :a + 1]:
                if fin_np is not None and not fin_np[i, len(emit)]:
                    bad[i] = True
                    break
                emit.append(int(t))
                if int(t) == req.eos or len(emit) >= self.slot_remaining[i]:
                    break
            commits[i] = emit
            if emit:
                idx[i] = len(emit) - 1
                adv[i] = len(emit)
                cur_np[i] = emit[-1]
        # rollback: select each row's post-commit state from the verify's
        # cache trajectory — rejected draft suffixes never touch the cache
        self.cache = self._spec_rollback(traj, jnp.asarray(idx))
        self.cache_len = self.cache_len + jnp.asarray(adv)
        self.cur_tok = jnp.asarray(cur_np)[:, None]
        self.stats.decode_steps += 1
        for inf in self._prefill_pool:
            inf["steps_waited"] += 1
        self._spec_rounds.inc()
        self._spec_proposed.inc(proposed)
        self._spec_accepted.inc(accepted)
        if self._spec_proposed.value:
            self._spec_rate.set(self._spec_accepted.value
                                / self._spec_proposed.value)
        now = self._clock()
        for i in active:
            emit = commits[i]
            if emit:
                # one verify forward produced len(emit) tokens — one ITL
                # sample per slot per round (the latency the client saw)
                self.stats.itl_ms.append((now - self.slot_last_t[i]) * 1e3)
                self.slot_last_t[i] = now
                for t in emit:
                    if self.slot_req[i] is None:
                        break
                    self._finish_token(i, t)
            if bad[i] and self.slot_req[i] is not None:
                rid = self.slot_req[i].rid
                self.slot_req[i] = None
                self.stats.quarantined += 1
                self._tr.instant("quarantined", track=f"req{rid}", rid=rid)
                self._terminate(rid, "failed",
                                f"non-finite verify logits for request "
                                f"{rid} at spec round {step_idx} (slot {i})"
                                f" — quarantined")
        self._expire_active(now)
        self._tr.finish(rsid)
        return True

    # --------------------------------------------------------------- decode
    def _expire_active(self, now: float):
        """Per-step deadline enforcement over the live decode slots."""
        for i in self._active_slots():
            req = self.slot_req[i]
            if self._deadline_over(req, now):
                self.slot_req[i] = None
                self._terminate(req.rid, "expired",
                                f"deadline {req.deadline_ms:.0f}ms exceeded "
                                f"mid-decode (kept "
                                f"{len(self.outputs[req.rid])} tokens)")

    def _decode_step(self):
        """One fused decode+sample step over every slot; per-slot
        termination, inter-token latency accounting, and (guard on) the
        finiteness probe + quarantine + per-step deadline enforcement."""
        active = self._active_slots()
        if not active:
            return
        step_idx = self.stats.decode_steps
        if self.faults is not None and self.faults.kills(step_idx):
            # simulated process death at a step boundary: everything not
            # persisted by the last snapshot() is gone
            raise EngineKilled(f"fault plan killed the engine before "
                               f"decode step {step_idx}")
        sampling = any(self.slot_req[i].temperature > 0.0 for i in active)
        if self.spec_k and not sampling and \
                self._spec_round(active, step_idx):
            return
        dsid = self._tr.start("serve.decode_step", track="engine",
                              step=step_idx, active=len(active))
        fin = None
        if self.guard:
            pv = None if self.faults is None else \
                self.faults.decode_poison(step_idx, self.num_slots)
            poison = self._poison0 if pv is None else \
                jnp.asarray(pv, jnp.float32)
            if sampling:
                tok, _, self.cache, self.slot_keys, finite = \
                    self._step_guarded(
                        self.params, self.cache, self.cur_tok,
                        self.cache_len, self.slot_keys, self.slot_temp,
                        self.slot_topk, self.slot_topp, poison, None)
            else:
                tok, self.cache, finite = self._step_greedy_guarded(
                    self.params, self.cache, self.cur_tok, self.cache_len,
                    poison)
            fin = np.asarray(finite)
        elif sampling:
            tok, _, self.cache, self.slot_keys = self._step(
                self.params, self.cache, self.cur_tok, self.cache_len,
                self.slot_keys, self.slot_temp, self.slot_topk,
                self.slot_topp, None)
        else:
            tok, self.cache = self._step_greedy(
                self.params, self.cache, self.cur_tok, self.cache_len)
        act = np.zeros(self.num_slots, bool)
        act[active] = True
        self.cache_len = self.cache_len + jnp.asarray(act, jnp.int32)
        self.cur_tok = tok[:, None]
        self.stats.decode_steps += 1
        for inf in self._prefill_pool:
            inf["steps_waited"] += 1
        with self._tr.span("serve.decode_wait", track="engine"):
            toks = np.asarray(tok)
        esid = self._tr.start("serve.decode_emit", track="engine")
        now = self._clock()
        for i in active:
            if fin is not None and not fin[i]:
                # quarantine: fail the request with a diagnostic, free the
                # slot (fully overwritten at its next refill), never emit
                # the garbage token — the other slots' rows are untouched
                # by this row's values, so their streams stay bit-identical
                rid = self.slot_req[i].rid
                self.slot_req[i] = None
                self.stats.quarantined += 1
                self._tr.instant("quarantined", track=f"req{rid}", rid=rid)
                self._terminate(rid, "failed",
                                f"non-finite decode logits for request "
                                f"{rid} at step {step_idx} (slot {i}) — "
                                f"quarantined")
                continue
            self.stats.itl_ms.append((now - self.slot_last_t[i]) * 1e3)
            self.slot_last_t[i] = now
            self._finish_token(i, int(toks[i]))
        self._expire_active(now)             # per-step deadline enforcement
        self._tr.finish(esid)
        self._tr.finish(dsid)

    # ----------------------------------------------------------------- loop
    def step(self) -> bool:
        """One engine iteration: expire overdue queued requests, land
        finished prefills, refill free slots (up to the in-flight pool
        bound), advance one chunked-prefill round, then one decode step.
        Wall time is split per phase into ``stats.*_ms``. Returns True
        while work remains."""
        ssid = self._tr.start("serve.step", track="engine")
        t0 = time.perf_counter()
        self._expire_queued()
        t1 = time.perf_counter()
        self._land_prefill(block=False)
        self._cache_admit()           # cached lane first: hits skip prefill
        while self._try_refill():     # bounded by max_inflight_prefills
            pass                      # (and by the queue/slots draining)
        if self._prefill_pool and not self._active_slots() \
                and not self._chunk_active():
            self._land_prefill(block=True)    # nothing to overlap with
        t2 = time.perf_counter()
        self._chunk_step()
        t3 = time.perf_counter()
        self._decode_step()
        t4 = time.perf_counter()
        st = self.stats
        st.host_ms += (t1 - t0) * 1e3
        st.prefill_ms += (t2 - t1) * 1e3
        st.chunk_ms += (t3 - t2) * 1e3
        st.decode_ms += (t4 - t3) * 1e3
        self._tr.finish(ssid)
        return bool(self.queue or self._active_slots()
                    or self._prefill_pool or self._chunk_active())

    def run(self) -> Dict[int, List[int]]:
        """Drive until the queue and all slots drain; returns rid → tokens."""
        while self.step():
            pass
        return self.outputs

    # ------------------------------------------------------ crash recovery
    def _device_state(self) -> Dict[str, object]:
        """The engine's complete device-side state as one pytree. For an
        SSM serve engine this is TINY — each slot is a fixed-size
        (conv-tail, recurrent/KV) state plus a few per-slot scalars — which
        is exactly why snapshot/restore is almost free here where an
        attention server would checkpoint a paged KV region."""
        state = {"cache": self.cache, "cache_len": self.cache_len,
                 "cur_tok": self.cur_tok, "slot_keys": self.slot_keys,
                 "slot_temp": self.slot_temp, "slot_topk": self.slot_topk,
                 "slot_topp": self.slot_topp}
        if self.chunk_enabled:
            # a half-consumed long prompt is just chunk_rows more O(1)
            # states — snapshotting mid-chunked-prefill costs nothing extra
            state["chunk_cache"] = self.chunk_cache
            state["chunk_clen"] = self.chunk_clen
        return state

    def _engine_meta(self) -> Dict[str, object]:
        return {"num_slots": self.num_slots, "max_len": self.max_len,
                "prefill_rows": self.prefill_rows,
                "buckets": list(self.buckets),
                "max_segments": self.max_segments,
                "sample_seed": self.sample_seed,
                "chunk_rows": self.chunk_rows if self.chunk_enabled else 0,
                "chunk_size": self.chunk_size}

    @staticmethod
    def _req_meta(req: Request, now: float) -> Dict[str, object]:
        left = None if req.deadline_ms is None else \
            req.deadline_ms - (now - req.submit_t) * 1e3
        return {"rid": int(req.rid),
                "tokens": [int(t) for t in req.tokens],
                "max_new": int(req.max_new), "eos": int(req.eos),
                "temperature": float(req.temperature),
                "top_k": int(req.top_k), "top_p": float(req.top_p),
                "deadline_left_ms": left,
                "prefix_len": None if req.prefix_len is None
                else int(req.prefix_len)}

    @staticmethod
    def _meta_req(m: Dict, now: float) -> Request:
        return Request(m["rid"], np.asarray(m["tokens"], np.int32),
                       m["max_new"], m["eos"], m["temperature"],
                       m["top_k"], m["top_p"], now, m["deadline_left_ms"],
                       m.get("prefix_len"))

    def snapshot(self, manager, step: int = 0,
                 blocking: bool = False) -> int:
        """Persist the whole engine through ``CheckpointManager``: per-slot
        SSM/conv/KV states and sampling keys (device tree), plus queue
        contents, generated-token tails, statuses, and remaining deadline
        budgets (manifest metadata). An in-flight prefill is landed first
        so the snapshot sits at a clean step boundary; deadlines are stored
        as *remaining* budget so wall-clock downtime between crash and
        restore does not silently expire requests. The host copy is taken
        synchronously (the engine may keep stepping immediately); with
        ``blocking=False`` the disk write happens on the manager's
        background thread. Returns the checkpoint step."""
        self._land_prefill(block=True)
        now = self._clock()
        meta = {
            "engine": self._engine_meta(),
            "slots": [None if r is None else
                      dict(self._req_meta(r, now),
                           remaining=int(self.slot_remaining[i]))
                      for i, r in enumerate(self.slot_req)],
            "chunks": [None if r is None else
                       dict(self._req_meta(r, now),
                            off=int(self.chunk_off[i]),
                            slot=int(self.chunk_slot[i]),
                            capture=int(self.chunk_capture[i]))
                       for i, r in enumerate(self.chunk_req)],
            "queue": [self._req_meta(r, now) for r in self.queue],
            "outputs": {str(rid): [int(t) for t in toks]
                        for rid, toks in self.outputs.items()},
            "status": {str(rid): st for rid, st in self.status.items()},
            "errors": {str(rid): e for rid, e in self.errors.items()},
            "next_rid": int(self._next_rid),
        }
        manager.save(step, self._device_state(), meta=meta,
                     blocking=blocking)
        return step

    def restore(self, manager, step: Optional[int] = None) -> int:
        """Load a ``snapshot()`` into this (freshly constructed, idle)
        engine: every request that was decoding resumes from its exact
        per-slot state and completes with the same remaining tokens an
        uninterrupted run would have produced; queued requests are
        re-admitted in order. Restored rids are recorded in
        ``self.resumed`` (their terminal status is still "done" — resumed
        and completed). Returns the checkpoint step restored."""
        if self.queue or self._active_slots() or any(self.slot_pending) \
                or self._prefill_pool or self._chunk_active():
            raise RuntimeError("restore() requires an idle engine — it "
                               "overwrites every slot; use a freshly "
                               "constructed ServeEngine")
        step = step if step is not None else manager.latest_step()
        if step is None:
            raise FileNotFoundError(f"no snapshot to restore in "
                                    f"{manager.dir}")
        meta = manager.read_meta(step)["meta"]
        if meta.get("engine") != self._engine_meta():
            raise ValueError(
                f"snapshot step {step} was taken by an engine configured "
                f"as {meta.get('engine')} but this engine is "
                f"{self._engine_meta()} — slot shapes would not line up")
        got = manager.restore(self._device_state(), step=step)
        self.cache = got["cache"]
        self.cache_len = got["cache_len"]
        self.cur_tok = got["cur_tok"]
        self.slot_keys = got["slot_keys"]
        self.slot_temp = got["slot_temp"]
        self.slot_topk = got["slot_topk"]
        self.slot_topp = got["slot_topp"]
        now = self._clock()
        self.slot_req = [None if m is None else self._meta_req(m, now)
                         for m in meta["slots"]]
        self.slot_remaining = [0 if m is None else int(m["remaining"])
                               for m in meta["slots"]]
        self.slot_pending = [False] * self.num_slots
        self.slot_last_t = [now] * self.num_slots
        if self.chunk_enabled:
            self.chunk_cache = got["chunk_cache"]
            self.chunk_clen = got["chunk_clen"]
        for i, m in enumerate(meta.get("chunks", [])):
            if m is None:
                continue
            # a request mid-chunked-prefill resumes exactly where the slab
            # stream left off; its decode slot is re-reserved so packed
            # admission can't steal it before the handoff
            self.chunk_req[i] = self._meta_req(m, now)
            self.chunk_off[i] = int(m["off"])
            self.chunk_slot[i] = int(m["slot"])
            self.chunk_capture[i] = int(m.get("capture", -1))
            self.slot_pending[int(m["slot"])] = True
        self.queue = collections.deque(
            self._meta_req(m, now) for m in meta["queue"])
        self.outputs = {int(rid): list(toks)
                        for rid, toks in meta["outputs"].items()}
        self.status = {int(rid): st for rid, st in meta["status"].items()}
        self.errors = {int(rid): e for rid, e in meta["errors"].items()}
        self._next_rid = int(meta["next_rid"])
        self.resumed |= {r.rid for r in self.slot_req if r is not None}
        self.resumed |= {r.rid for r in self.chunk_req if r is not None}
        self.resumed |= {r.rid for r in self.queue}
        return step

    # ------------------------------------------------- padded-wave baseline
    def decode_batch(self, prompts, max_new, eos: int = -1,
                     temperature: float = 0.0, top_k: int = 0,
                     top_p: float = 1.0):
        """Padded-wave BASELINE (the paper's padding regime on the serving
        path): ≤num_slots prompts left-padded to the batch max, one prefill,
        synchronous decode. Kept for benchmarking against the continuous
        path — it shares the fused decode+sample step (uniform sampling
        knobs across the wave), so the two modes stay comparable under any
        sampling regime. ``max_new`` is an int or a per-prompt list; slots
        stop accumulating tokens at ``eos`` or their budget (the EOS token
        itself is kept) — but the WAVE only ends when every row is done,
        which is exactly the drain cost continuous batching removes."""
        Bz = self.num_slots
        if len(prompts) > Bz:
            raise ValueError(f"{len(prompts)} prompts > {Bz} slots")
        if self._active_slots() or self.queue or self._prefill_pool \
                or self._chunk_active():
            raise RuntimeError("decode_batch would clobber the live slot "
                               "cache; drain the continuous engine first "
                               "(or use a separate ServeEngine)")
        budgets = [max_new] * len(prompts) if isinstance(max_new, int) \
            else list(max_new)
        lens = [len(p) for p in prompts] + [1] * (Bz - len(prompts))
        maxp = max(lens)
        grid = np.zeros((Bz, maxp), np.int32)
        seg = np.zeros((Bz, maxp), np.int32)
        pos = np.zeros((Bz, maxp), np.int32)
        for b, p in enumerate(prompts):
            grid[b, :len(p)] = p
            seg[b, :len(p)] = 1
            pos[b, :len(p)] = np.arange(len(p))
        seg[len(prompts):, 0] = 1              # idle slots: 1-token dummy
        batch = {"tokens": jnp.asarray(grid), "positions": jnp.asarray(pos),
                 "segment_ids": jnp.asarray(seg)}
        logits, self.cache, lens_j = self._wave_prefill(self.params, batch)
        sampling = temperature > 0.0
        temp = jnp.full((Bz,), temperature, jnp.float32)
        topk = jnp.full((Bz,), int(top_k), jnp.int32)
        topp = jnp.full((Bz,), top_p, jnp.float32)
        keys = B.request_keys(self.sample_seed, np.arange(Bz))
        outs = [[] for _ in range(Bz)]
        done = [b >= len(prompts) for b in range(Bz)]
        if sampling:
            tok, keys = self._sample_flat(logits, keys, temp, topk, topp)
        else:
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
        tok = tok[:, None]
        for i in range(max(budgets, default=0)):
            toks = np.asarray(tok[:, 0])
            for b in range(len(prompts)):
                if done[b]:
                    continue
                outs[b].append(int(toks[b]))
                if int(toks[b]) == eos or len(outs[b]) >= budgets[b]:
                    done[b] = True
            if all(done):
                break
            if sampling:
                tok, _, self.cache, keys = self._step(
                    self.params, self.cache, tok, lens_j + i, keys, temp,
                    topk, topp, None)
            else:
                tok, self.cache = self._step_greedy(
                    self.params, self.cache, tok, lens_j + i)
            tok = tok[:, None]
        return outs[:len(prompts)]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mamba-110m")
    ap.add_argument("--tiny", action="store_true",
                    help="shrink the model for a CPU demo")
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--policy", default="first_fit",
                    choices=["first_fit", "sequential", "sorted_greedy"])
    ap.add_argument("--no-overlap", action="store_true",
                    help="block on each packed prefill instead of decoding "
                         "through it")
    ap.add_argument("--target-ttft-ms", type=float, default=None,
                    help="admit below the refill threshold once the oldest "
                         "queued request has waited this long")
    ap.add_argument("--max-inflight-prefills", type=int, default=1,
                    help="packed prefills allowed in flight at once "
                         "(the v2 prefill pipeline; 1 = pre-v2 behaviour)")
    ap.add_argument("--bucket-policy", default="smallest_fit",
                    choices=["smallest_fit", "ttft"],
                    help="ttft: upgrade to a bigger prefill bucket when it "
                         "admits more requests and TTFT has slack")
    ap.add_argument("--chunk-size", type=int, default=None,
                    help="chunked-prefill slab length (default: largest "
                         "bucket); prompts beyond the largest bucket are "
                         "consumed in slabs of this size")
    ap.add_argument("--chunk-rows", type=int, default=1,
                    help="long prompts chunk-prefilling concurrently "
                         "(0 disables chunked prefill)")
    ap.add_argument("--max-prompt-len", type=int, default=None,
                    help="hard bound on accepted prompt length "
                         "(default: unbounded — chunked prefill handles "
                         "any length that fits a slot)")
    ap.add_argument("--cache-mb", type=float, default=None,
                    help="enable the prefix StateCache with this byte "
                         "budget (MB); repeated prefixes restore an O(1) "
                         "state instead of re-prefilling")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="give every request the same N-token system "
                         "prefix, declared via submit(prefix_len=N) — the "
                         "prefix-cache demo workload")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="speculative decode: draft k tokens per round via "
                         "n-gram prompt copy, verify in one forward "
                         "(greedy slots only; 0 = off)")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request submit→completion deadline; overdue "
                         "requests are expired, not served late")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="shed submits once this many requests are queued")
    ap.add_argument("--guard", action="store_true",
                    help="numerical guard rails: per-step finiteness "
                         "probes; non-finite slots are quarantined")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature for every request (0=greedy)")
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--top-p", type=float, default=1.0)
    ap.add_argument("--obs-trace", default=None, metavar="PATH",
                    help="record request-lifecycle spans and export a "
                         "Chrome trace-event JSON here (open in "
                         "chrome://tracing or ui.perfetto.dev)")
    ap.add_argument("--profile-dir", default=None, metavar="DIR",
                    help="also capture an XLA profile (jax.profiler, "
                         "TensorBoard format) into this directory")
    args = ap.parse_args()

    use_compile_cache()
    cfg = get_config(args.arch)
    if args.tiny:
        cfg = dataclasses.replace(cfg, d_model=128, n_layers=4, vocab=512,
                                  dtype="float32", scan_chunk=64)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    obs = Obs.on() if args.obs_trace else Obs.off()
    engine = ServeEngine(model, params, args.slots, args.max_len,
                         policy=args.policy, overlap=not args.no_overlap,
                         target_ttft_ms=args.target_ttft_ms,
                         max_queue=args.max_queue, guard=args.guard,
                         max_inflight_prefills=args.max_inflight_prefills,
                         bucket_policy=args.bucket_policy,
                         chunk_size=args.chunk_size,
                         chunk_rows=args.chunk_rows,
                         max_prompt_len=args.max_prompt_len,
                         obs=obs,
                         cache_bytes=None if args.cache_mb is None
                         else int(args.cache_mb * 2**20),
                         spec_k=args.spec_k)

    rng = np.random.default_rng(0)
    lens = rng.integers(5, 40, size=args.requests)
    shared = rng.integers(1, cfg.vocab, size=args.shared_prefix) \
        if args.shared_prefix else None
    t0 = time.perf_counter()
    shed = 0
    with profiler_session(args.profile_dir) as profiling:
        for n in lens:
            toks = rng.integers(1, cfg.vocab, size=int(n))
            if shared is not None:
                toks = np.concatenate([shared, toks])
            try:
                engine.submit(toks, args.new_tokens,
                              temperature=args.temperature,
                              top_k=args.top_k, top_p=args.top_p,
                              deadline_ms=args.deadline_ms,
                              prefix_len=args.shared_prefix or None)
            except ShedError:
                shed += 1
        outs = engine.run()
    dt = time.perf_counter() - t0
    st = engine.stats
    if shed or st.expired or st.quarantined:
        print(f"fault-tolerance: {shed} shed at submit, {st.expired} "
              f"expired, {st.quarantined} quarantined")
    for rid in sorted(outs)[:4]:
        print(f"req{rid}: prompt[{lens[rid]}] -> {outs[rid][:8]}…")
    pct = st.ttft_percentiles()
    print(f"{len(outs)} requests, {st.generated} tokens in {dt:.2f}s "
          f"({st.generated / dt:.1f} tok/s incl. compile) — "
          f"{st.prefills} prefills ({st.midflight_refills} mid-flight, "
          f"{st.overlapped_prefills} overlapped, {st.early_admits} early), "
          f"{st.decode_steps} decode steps, "
          f"{len(st.buckets)} prefill shape(s) compiled")
    if st.chunk_rounds:
        print(f"chunked prefill: {st.chunked_prefills} request(s) over "
              f"{st.chunk_rounds} rounds ({st.chunk_tokens} tokens)")
    if engine.state_cache is not None:
        print(f"prefix cache: {engine.state_cache!r}")
    if args.spec_k:
        print(f"speculative decode: accept rate "
              f"{engine.spec_accept_rate:.2f} over "
              f"{engine._spec_rounds.value} verify rounds")
    print(f"time split: prefill {st.prefill_ms:.0f}ms, chunk "
          f"{st.chunk_ms:.0f}ms, decode {st.decode_ms:.0f}ms, host "
          f"{st.host_ms:.0f}ms")
    ipct = st.itl_percentiles()
    itl = f"{ipct['p50']:.2f}ms" if ipct else "n/a"
    print(f"TTFT p50 {pct.get('p50', 0):.1f}ms p95 {pct.get('p95', 0):.1f}ms "
          f"over {len(st.ttft_ms)} requests; "
          f"ITL p50 {itl} over {len(st.itl_ms)} decode tokens")
    if args.obs_trace:
        obs.export(args.obs_trace)
        print(f"obs: wrote {len(obs.tracer.chrome_events())} trace events "
              f"to {args.obs_trace} (open in chrome://tracing or "
              f"ui.perfetto.dev)")
    obs.close()
    if args.profile_dir and profiling:
        print(f"obs: XLA profile captured under {args.profile_dir}")


if __name__ == "__main__":
    main()
