"""Continuous-batching serve engine: orchestrator over scheduler / cache /
executor layers.

The engine used to be a monolith owning scheduling state, the dense slot
cache and every jit.  It is now wiring between three seams:

  * :mod:`repro.serve.scheduler` — admission + step policy.  Decode runs on
    the smallest power-of-two *bucketed* live-slot batch (one trace per
    bucket width), so a 64-slot engine with 3 live requests pays for a
    4-wide decode, not 64 — the slot-scaling cliff fix.  Long prompts
    prefill in fixed-size chunks interleaved between decode steps
    (``prefill_chunk=``), so TTFT of concurrent requests stops being
    hostage to the longest prompt.
  * :mod:`repro.serve.cache` — paged KV / recurrent-state pool (fixed-size
    pages, slot→page table as a jit-visible int32 array) with optional
    prompt-prefix sharing (``prefix_cache=True``): repeated prompt prefixes
    restore a page/state snapshot instead of recomputing, bit-exact vs a
    cold prefill.
  * :mod:`repro.serve.executor` — the compiled gather/compute/scatter entry
    points over the pool, riding the existing ``ExecContext`` execution
    path (quantized KMM policy, optional mesh).

Correctness on ragged prompts is unchanged from the dense-cache engine:
prompts are right-padded to bucket widths with ``pad_mask``/``last_idx``
threaded into :func:`repro.models.lm.prefill` (now with a resume offset
``start=`` for chunking), and decode runs a per-slot position vector.
Admission order and per-(request, step) sampling keys make output
token-identical to sequential single-request generation — independent of
slot count, decode-bucket width, prefill chunking and prefix-cache hits.

With quantization enabled the engine serves prequantized weights
(:func:`repro.quant.prequant.prequantize`): each quantizable weight leaf is
stored as ``{"q": intN, "scale"}`` once, at construction, and no GEMM call
re-quantizes a weight.

Pass ``mesh=`` to serve sharded: params take the ``repro.dist.sharding``
param rules, the page pools take the page-pool rules (pages over ``data``,
kv-heads over ``model``), and the executor's jits run under the mesh so
GSPMD partitions them (DESIGN.md §4.3, §13).  With the pallas quant
backend the mesh is *negotiated* per GEMM (:mod:`repro.dist.shard_gemm`).

Execution policy (backend / force_mode) is configured with ``context=`` (an
:class:`repro.core.context.ExecContext`); the legacy ``quant_backend=``
kwarg keeps working behind a ``DeprecationWarning`` (DESIGN.md §12).
"""
from __future__ import annotations

import contextlib
import logging
import math
import time
from typing import Any, Dict, List, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

from repro.core.context import ExecContext, resolve_context
from repro.dist import sharding as dist_sharding
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.quant.prequant import prequantize
from repro.serve.cache import PagedCachePool, PrefixCache, default_page_size
from repro.serve.executor import Executor
from repro.serve.scheduler import (MIN_BUCKET, Request, RequestStats,
                                   Scheduler, ServeStats, SlotState,
                                   prompt_buckets_for)

__all__ = ["Engine", "Request", "RequestStats", "ServeStats", "SlotState",
           "prompt_buckets_for", "MIN_BUCKET"]

log = logging.getLogger("repro.serve")

Params = Any

# Serve-path instruments (DESIGN.md §14).  All observations happen in host
# Python around the executor's compiled calls — never inside them — so
# enabling metrics/tracing cannot change a sampled token; disabled (the
# default) each site costs a flag test.
_TTFT = obs_metrics.histogram(
    "repro_serve_ttft_seconds", "arrival to first token, per request")
_DECODE_STEP = obs_metrics.histogram(
    "repro_serve_decode_step_seconds", "wall time of one bucketed decode step")
# One observation per engine step for each phase that ran (admit, prefill,
# decode_dispatch, decode_wait, finish): the top buckets catch host stalls
# that a profiled slice of the run would miss.
_STEP_PHASE = obs_metrics.histogram(
    "repro_serve_step_phase_seconds", "wall time of one phase of an engine step",
    labels=("phase",))
_OCCUPANCY = obs_metrics.gauge(
    "repro_serve_occupancy", "live slots / total slots at the last decode step")
_FINISHED = obs_metrics.counter(
    "repro_serve_finished_total", "finished requests by stop reason",
    labels=("reason",))
# Recurrence steps of the recurrent (rwkv / mamba) layers, one per token
# position and layer: a prefill chunk runs its padded width of them (the
# scan steps over pads too), a decode step one per layer for every lane at
# once.
_RECURRENT_STEPS = obs_metrics.counter(
    "repro_serve_recurrent_steps_total",
    "recurrence steps (token positions x recurrent layers) run by engine "
    "steps, by phase", labels=("phase",))


class Engine:
    """Continuous-batching engine over ``batch_size`` decode slots."""

    def __init__(self, cfg, params: Params, max_seq: int = 512,
                 batch_size: int = 4, rng_seed: int = 0,
                 mesh: Optional[Mesh] = None,
                 prompt_buckets: Optional[Sequence[int]] = None,
                 quant_backend: Optional[str] = None,
                 context: Optional[ExecContext] = None,
                 page_size: Optional[int] = None,
                 prefill_chunk: Optional[int] = None,
                 prefix_cache: bool = False,
                 prefix_snapshots: int = 4):
        if cfg.is_encdec:
            raise NotImplementedError(
                "continuous batching does not support encoder-decoder models")
        # Resolve the execution context.  Historical default: the model
        # config's own quant policy.  ``mesh=`` stays a first-class kwarg
        # (it also drives param/cache sharding, not just GEMMs) and is
        # folded into the context below.
        ctx = resolve_context(
            context, what="Engine", backend=quant_backend,
            _defaults=ExecContext(
                backend=getattr(cfg.quant, "backend", "xla"),
                force_mode=getattr(cfg.quant, "force_mode", "auto")))
        if mesh is not None:
            if ctx.mesh is not None and ctx.mesh is not mesh:
                raise ValueError("Engine: mesh= and context.mesh disagree; "
                                 "set one of them")
            ctx = ctx.replace(mesh=mesh)
        mesh = ctx.mesh
        if (ctx.backend != getattr(cfg.quant, "backend", "xla")
                or ctx.force_mode != getattr(cfg.quant, "force_mode", "auto")):
            # Rewrite the model's quantized-GEMM policy before any jit
            # traces: "pallas" serves through the fused single-pass kernel
            # (DESIGN.md §11), "xla" through plain dot_generals.
            import dataclasses
            cfg = cfg.with_quant(dataclasses.replace(
                cfg.quant, backend=ctx.backend, force_mode=ctx.force_mode))
        if mesh is not None and getattr(cfg.quant, "backend", "xla") == "pallas":
            log.info("serving with pallas quant backend under mesh %s: "
                     "GEMMs run shard-mapped where the mesh tiles them, "
                     "XLA otherwise (see repro.dist logs)", mesh)
        self.context = ctx
        self.cfg = cfg
        self._recurrent_layers = cfg.n_periods * sum(
            b.kind in ("rwkv", "mamba") for b in cfg.pattern)
        self.mesh = mesh
        if cfg.quant.enabled:
            # Weights do not change while serving: quantize each once here
            # instead of at every GEMM call.  The caller's tree is left as
            # it was.
            params = prequantize(params, cfg.quant)
        if mesh is not None:
            params = jax.device_put(
                params, dist_sharding.param_sharding(params, mesh))
        self.params = params
        self.max_seq = max_seq
        self.batch = batch_size
        self._key = jax.random.PRNGKey(rng_seed)
        if prompt_buckets is None:
            prompt_buckets = prompt_buckets_for(max_seq)
        self.prompt_buckets = tuple(sorted(set(prompt_buckets)))

        # -- chunked prefill / paging knobs ---------------------------------
        if page_size is None:
            page_size = default_page_size(max_seq)
        if max_seq % page_size:
            raise ValueError(f"page_size={page_size} must divide "
                             f"max_seq={max_seq}")
        if prefix_cache and prefill_chunk is None:
            # prefix restore resumes prefill mid-prompt, which needs the
            # chunked entry; pick a chunk covering at least one page
            prefill_chunk = max(page_size, MIN_BUCKET)
        if prefill_chunk is not None:
            if prefill_chunk < MIN_BUCKET or \
                    prefill_chunk & (prefill_chunk - 1):
                raise ValueError(
                    f"prefill_chunk={prefill_chunk} must be a power of two "
                    f">= {MIN_BUCKET} (the serve mamba-scan grid)")
        self.page_size = page_size
        self.prefill_chunk = prefill_chunk
        self._chunk_buckets = (prompt_buckets_for(prefill_chunk)
                               if prefill_chunk is not None else None)

        self.scheduler = Scheduler(batch_size, max_seq)
        self.pool = PagedCachePool(
            cfg, batch_size, max_seq, page_size,
            snapshot_slots=prefix_snapshots if prefix_cache else 0,
            mesh=mesh)
        self.executor = Executor(cfg, self.params, self.pool, mesh=mesh)
        self.prefix: Optional[PrefixCache] = None
        if prefix_cache:
            align = math.lcm(page_size, prefill_chunk, MIN_BUCKET)
            self.prefix = PrefixCache(self.pool, align)

        self._next_rid = 0
        self._clock0 = time.perf_counter()
        self._stats = ServeStats()
        self._admitted_done: List[Request] = []

    # -- infrastructure -----------------------------------------------------

    def _mesh_ctx(self):
        return (jax.set_mesh(self.mesh) if self.mesh is not None
                else contextlib.nullcontext())

    def _now(self) -> float:
        # perf_counter: the clock of repro.obs.trace, so every host record
        # of a run shares one clock
        return time.perf_counter() - self._clock0

    def _bucket(self, n: int, buckets: Sequence[int]) -> int:
        for b in buckets:
            if b >= n:
                return b
        raise ValueError(f"prompt length {n} exceeds max bucket "
                         f"{buckets[-1]}")

    def n_traces(self) -> Dict[str, int]:
        """Compiled-trace counts (retrace monitoring for the serve bench);
        -1 per entry if the jax version doesn't expose cache sizes.
        ``decode`` counts one trace per decode-bucket width."""
        return self.executor.n_traces()

    def warm(self):
        """Pre-trace every decode-bucket width and prefill chunk/bucket
        width so a measured run sees steady-state traces.  Warm calls run
        on the pool's parking rows only — no slot state is touched — and
        must happen while the engine is idle."""
        if self.scheduler.num_active or self.scheduler.num_pending:
            raise RuntimeError("warm() requires an idle engine")
        with self._mesh_ctx():
            for w in self.scheduler.decode_widths:
                lanes = [None] * w
                z = np.zeros((w,), np.int32)
                logits = self.executor.decode(lanes, z, z)
                self.executor.sample(self._key, logits,
                                     np.zeros((w,), np.float32), z, z)
            widths = self._chunk_buckets or self.prompt_buckets
            for w in widths:
                toks = np.zeros((1, w), np.int32)
                last = np.array([w - 1], np.int32)
                logits = self.executor.prefill(None, toks, 0, last)
                self.executor.sample(self._key, logits,
                                     np.zeros((1,), np.float32),
                                     np.zeros((1,), np.int32),
                                     np.zeros((1,), np.int32))

    # -- scheduling ---------------------------------------------------------

    def submit(self, req: Request, arrival_s: Optional[float] = None):
        """Enqueue a request; it is admitted when a slot frees up."""
        if req.max_new_tokens < 1:
            # the first token is sampled from the prefill logits at
            # admission, so a zero budget cannot be honored
            raise ValueError("max_new_tokens must be >= 1")
        if len(req.prompt) + req.max_new_tokens > self.max_seq:
            raise ValueError(
                f"prompt({len(req.prompt)}) + max_new({req.max_new_tokens}) "
                f"exceeds max_seq={self.max_seq}")
        if self.prefill_chunk is None \
                and len(req.prompt) > self.prompt_buckets[-1]:
            raise ValueError(
                f"prompt length {len(req.prompt)} exceeds max prompt "
                f"bucket {self.prompt_buckets[-1]}")
        rid = self._next_rid
        self._next_rid += 1
        req.stats = RequestStats(
            rid=rid, prompt_len=len(req.prompt),
            arrival_s=self._now() if arrival_s is None else arrival_s)
        req.generated = []
        obs_trace.begin_async("request", rid, prompt_len=len(req.prompt),
                              max_new=req.max_new_tokens)
        self.scheduler.enqueue(req)

    @property
    def num_active(self) -> int:
        return self.scheduler.num_active

    @property
    def num_pending(self) -> int:
        return self.scheduler.num_pending

    def _finish(self, idx: int, reason: str):
        slot = self.scheduler.slots[idx]
        req = slot.req
        req.stats.finish_s = self._now()
        req.stats.n_tokens = len(req.generated)
        req.stats.stop_reason = reason
        _FINISHED.inc(reason)
        obs_trace.end_async("request", req.stats.rid, reason=reason,
                            n_tokens=req.stats.n_tokens)
        self._stats.requests.append(req.stats)
        self.scheduler.finish(idx)

    def _check_done(self, slot: SlotState, tok: int) -> Optional[str]:
        req = slot.req
        if tok in req.stop_tokens:
            return "stop_token"
        if len(req.generated) >= req.max_new_tokens:
            return "length"
        if slot.pos >= self.max_seq:
            return "max_seq"
        return None

    # -- prefill ------------------------------------------------------------

    def _init_slot(self, idx: int, req: Request):
        """Initialize an admitted slot's pool rows and prefill plan."""
        slot = self.scheduler.slots[idx]
        with self._mesh_ctx():
            self.pool.zero_slot_state(idx)
            if self.prefix is not None:
                slot.prefill.snap_at = self.prefix.boundary_for(
                    len(req.prompt))
                hit_len, hit = self.prefix.lookup(req.prompt)
                if hit:
                    self.prefix.restore(idx, req.prompt, hit_len)
                    slot.prefill.off = hit_len
                    slot.prefill.from_prefix = True

    def _run_prefill_chunk(self, idx: int) -> Optional[Request]:
        """Advance one slot's prefill by one chunk (the whole remaining
        prompt when chunking is off).  Returns the request if it finished
        at admission (1-token budget or instant EOS)."""
        slot = self.scheduler.slots[idx]
        req, ps = slot.req, slot.prefill
        plen = len(req.prompt)
        if self.prefill_chunk is None:
            take = plen - ps.off
            width = self._bucket(take, self.prompt_buckets)
        else:
            take = min(self.prefill_chunk, plen - ps.off)
            width = self._bucket(take, self._chunk_buckets)
        toks = np.zeros((1, width), np.int32)
        toks[0, :take] = req.prompt[ps.off:ps.off + take]   # right-pad
        last = np.array([take - 1], np.int32)
        tok = None
        with self._mesh_ctx(), obs_trace.span(
                "serve.prefill", rid=req.stats.rid, slot=idx, off=ps.off,
                width=width, tokens=take):
            t0 = time.perf_counter()
            logits = self.executor.prefill(idx, toks, ps.off, last)
            if self._recurrent_layers:
                _RECURRENT_STEPS.inc("prefill",
                                     by=width * self._recurrent_layers)
            ps.off += take
            if ps.off < plen:
                jax.block_until_ready(logits)
            else:
                # prompt complete: sample the first token from the last
                # chunk's last-real-position logits
                tok = int(np.asarray(self.executor.sample(
                    self._key, logits,
                    np.asarray([req.temperature], np.float32),
                    np.asarray([req.stats.rid], np.int32),
                    np.asarray([0], np.int32)))[0])
            # synced: the clock stops after the device finished the chunk
            # (after the first token's readback on the last one)
            dt = time.perf_counter() - t0
            req.stats.prefill_s += dt
            if self.prefix is not None and ps.off == ps.snap_at \
                    and ps.snap_at > 0:
                self.prefix.store(idx, req.prompt, ps.snap_at)
        if tok is None:
            return None
        self.scheduler.prefill_done(idx, tok)
        req.generated.append(tok)
        req.stats.first_token_s = self._now()
        _TTFT.observe(req.stats.ttft_s)
        self._stats.generated_tokens += 1
        reason = self._check_done(slot, tok)
        if reason is not None:      # e.g. max_new_tokens=1 or instant EOS
            self._finish(idx, reason)
            return req
        return None

    def _prefill_step(self):
        """Prefill policy for one engine step: with chunking off, complete
        every admitted prompt (admission-time prefill, the dense-engine
        behavior); with chunking on, advance one prefilling slot by one
        chunk so prompts interleave with decode steps."""
        idxs = self.scheduler.prefilling()
        if not idxs:
            return
        if self.prefill_chunk is not None:
            idxs = idxs[:1]
        t0 = time.perf_counter()
        for idx in idxs:
            req = self._run_prefill_chunk(idx)
            if req is not None:
                self._admitted_done.append(req)
        _STEP_PHASE.observe(time.perf_counter() - t0, "prefill")

    # -- decode -------------------------------------------------------------

    def _decode_step(self) -> List[Request]:
        n_live, lanes = self.scheduler.decode_lanes()
        if not n_live:
            return []
        slots = self.scheduler.slots
        toks = np.array([slots[j].last_tok if j is not None else 0
                         for j in lanes], np.int32)
        # park free/padding lanes at a harmless position (their writes land
        # in dead slot rows or the pool's parking rows)
        pos = np.array([min(slots[j].pos, self.max_seq - 1)
                        if j is not None else 0 for j in lanes], np.int32)
        temps = np.array([slots[j].req.temperature
                          if j is not None and slots[j].decoding else 0.0
                          for j in lanes], np.float32)
        rids = np.array([slots[j].rid if j is not None else 0
                         for j in lanes], np.int32)
        steps = np.array([slots[j].n_tokens if j is not None else 0
                          for j in lanes], np.int32)
        stats = self._stats
        with self._mesh_ctx():
            t0 = time.perf_counter()
            with obs_trace.span("serve.decode.dispatch", n_live=n_live,
                                width=len(lanes)):
                logits = self.executor.decode(lanes, toks, pos)
                sampled = self.executor.sample(
                    self._key, logits, temps, rids, steps)
            if self._recurrent_layers:
                _RECURRENT_STEPS.inc("decode", by=self._recurrent_layers)
            t1 = time.perf_counter()
            with obs_trace.span("serve.decode.wait"):
                nxt = np.asarray(sampled)
            t2 = time.perf_counter()
        _STEP_PHASE.observe(t1 - t0, "decode_dispatch")
        _STEP_PHASE.observe(t2 - t1, "decode_wait")
        dt = t2 - t0
        stats.decode_s += dt
        stats.decode_steps += 1
        stats.occupancy_sum += n_live / self.batch
        _DECODE_STEP.observe(dt)
        _OCCUPANCY.set(n_live / self.batch)
        finished: List[Request] = []
        with obs_trace.span("serve.finish"):
            for lane, idx in enumerate(lanes[:n_live]):     # live lanes first
                slot = slots[idx]
                tok = int(nxt[lane])
                slot.pos += 1
                slot.last_tok = tok
                slot.n_tokens += 1
                slot.req.generated.append(tok)
                stats.generated_tokens += 1
                reason = self._check_done(slot, tok)
                if reason is not None:
                    req = slot.req
                    self._finish(idx, reason)
                    finished.append(req)
        _STEP_PHASE.observe(time.perf_counter() - t2, "finish")
        return finished

    # -- step / driver ------------------------------------------------------

    def step(self) -> List[Request]:
        """Admit what fits, advance prefill, then run one bucketed decode
        step.  Returns the requests that finished during this step —
        including those that finished at admission (first prefill token hit
        EOS or a 1-token budget)."""
        t0 = time.perf_counter()
        with obs_trace.span("serve.step"):
            with obs_trace.span("serve.admit"):
                for idx, req in self.scheduler.admit(self._now()):
                    self._init_slot(idx, req)
            _STEP_PHASE.observe(time.perf_counter() - t0, "admit")
            self._prefill_step()
            finished = self._admitted_done
            self._admitted_done = []
            finished += self._decode_step()
        self._stats.busy_s += time.perf_counter() - t0
        return finished

    def generate(self, requests: List[Request],
                 arrival_s: Optional[Sequence[float]] = None) -> ServeStats:
        """Serve ``requests`` to completion; fills ``req.generated`` and
        returns the run's :class:`ServeStats`.

        ``arrival_s`` (optional, seconds relative to now) replays an arrival
        trace: a request is only admitted once its arrival time has passed
        (TTFT then includes queueing delay)."""
        self._stats = ServeStats()
        self._clock0 = time.perf_counter()
        if arrival_s is None:
            for r in requests:
                self.submit(r)
        else:
            order = sorted(range(len(requests)), key=lambda i: arrival_s[i])
            for i in order:
                self.submit(requests[i], arrival_s=float(arrival_s[i]))
        sched = self.scheduler
        while sched.num_pending or sched.num_active:
            if not sched.num_active and sched.num_pending:
                wait = sched.next_arrival_s - self._now()
                if wait > 0:
                    time.sleep(min(wait, 0.01))
            self.step()
        return self._stats
