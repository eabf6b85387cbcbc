"""Continuous batching over the engine's fixed decode slots.

The decode executable always runs all ``slots`` sequences (its shape is
compiled once); *continuous batching* means requests are admitted into
and retired from those slots at step boundaries, so a long generation
never blocks a short one behind it — the batching lesson of the TPU-pod
scaling papers (arXiv:1909.09756 / 2011.03641) applied to a decode loop:
keep the chip-filling shape constant and move the *work* in and out.

Per step, in order:

  1. admit   — for every free slot, pop the oldest pending request,
               prefill it (its bucket's executable), insert into the
               slot.  TTFT is measured here: arrival -> first token.
  2. decode  — ONE decode step over all slots (active or not; inactive
               lanes compute garbage, which costs less than a recompile
               or a per-slot branch).
  3. retire  — requests that hit ``max_new_tokens`` or the EOS id leave
               their slot free for the next admit, and the engine is
               told (``release``): an idle slot's ring is not read.

Observability rides obs v2: a typed ``serve_step`` event per step and a
``serve_request`` event per retirement (TTFT/TPOT, token counts) — the
offline analyzer (``python -m tpuframe.obs summarize``) computes the
percentiles and tokens/sec/chip from these, beside the training MFU.
The step's host phases are ``obs.timeline`` spans (``sched.step`` over
``sched.admit``, the engine's own and ``sched.retire``; ``sched.queue``
per request), in the ring and in a profiler's trace.

This file is above the compile seam: it calls only the engine's AOT
executables (lint TF109 keeps ``jit``/``.apply`` out of here).
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field

from tpuframe.obs import events as obs_events
from tpuframe.obs import exporter as obs_exporter
from tpuframe.obs import metrics as obs_metrics
from tpuframe.obs import timeline, tracing
from tpuframe.obs.goodput import _pct
from tpuframe.serve.kv_cache import KV_BLOCK


@dataclass
class Request:
    """One generation request and its lifecycle record."""

    rid: int
    prompt: list
    max_new_tokens: int = 16
    arrival_t: float = 0.0            # scheduler clock, seconds
    # -- filled in by the scheduler --
    admit_t: float | None = None      # popped from pending, before prefill
    first_token_t: float | None = None
    done_t: float | None = None
    tokens: list = field(default_factory=list)   # generated tokens
    # Tracing context: trace id propagated in the /generate payload and
    # the replica-side "serve" span the scheduler's queue/prefill/decode
    # phase spans parent under.  None when the request is untraced.
    trace: str | None = None
    span: str | None = None

    @property
    def done(self) -> bool:
        return self.done_t is not None

    def ttft_ms(self) -> float | None:
        if self.first_token_t is None:
            return None
        return 1e3 * (self.first_token_t - self.arrival_t)

    def tpot_ms(self) -> float | None:
        """Time per output token AFTER the first (the decode cadence)."""
        if self.done_t is None or self.first_token_t is None \
                or len(self.tokens) < 2:
            return None
        return 1e3 * (self.done_t - self.first_token_t) \
            / (len(self.tokens) - 1)


class Scheduler:
    """Continuous-batching request loop over one :class:`LMEngine`.

    ``clock`` is injectable (fake-clock tests, the GoodputMeter idiom);
    the default is the host monotonic clock.
    """

    def __init__(self, engine, *, clock=time.monotonic):
        self.engine = engine
        self._clock = clock
        self.pending: list = []                 # FIFO of Request
        self.active: list = [None] * engine.slots
        self.completed: list = []
        self.step_count = 0
        self.tokens_generated = 0
        # Live telemetry (obs/exporter.py, env-gated no-op otherwise):
        # queue/slot/token gauges and TTFT/TPOT percentiles served
        # through pull collectors — a scrape between steps must see the
        # *current* pending depth (the router's admission signal), not
        # the last step's snapshot.
        self._exporter = obs_exporter.start_from_env()
        if self._exporter is not None:
            self._exporter.add_collector(self._latency_samples)
            self._exporter.add_collector(self._load_samples)

    def _load_samples(self):
        return [
            ("tpuframe_serve_queue_depth", {}, float(len(self.pending))),
            ("tpuframe_serve_active_slots", {},
             float(sum(r is not None for r in self.active))),
            ("tpuframe_serve_tokens_generated", {},
             float(self.tokens_generated)),
        ]

    def _latency_samples(self):
        ttft = sorted(v for v in (r.ttft_ms() for r in self.completed)
                      if v is not None)
        tpot = sorted(v for v in (r.tpot_ms() for r in self.completed)
                      if v is not None)
        out = []
        for name, vals in (("tpuframe_serve_ttft_ms", ttft),
                           ("tpuframe_serve_tpot_ms", tpot)):
            if vals:
                for q, frac in (("p50", 0.5), ("p90", 0.9), ("p99", 0.99)):
                    out.append((name, {"quantile": q}, _pct(vals, frac)))
        return out

    def submit(self, request: Request) -> None:
        if len(request.prompt) > max(self.engine.prompt_buckets):
            # Admission control: reject ahead of any shape decision —
            # never invent a new compile shape for an oversized prompt.
            raise ValueError(
                f"request {request.rid}: prompt {len(request.prompt)} "
                f"exceeds largest bucket "
                f"{max(self.engine.prompt_buckets)}")
        self.pending.append(request)

    def has_work(self) -> bool:
        return bool(self.pending) or any(r is not None
                                         for r in self.active)

    def step(self) -> int:
        """One scheduler step (admit + decode + retire + admit).
        Returns the number of live tokens produced this step.

        The trailing admit pass fills slots freed by *this step's*
        retires — their prefill (and first token, so TTFT) lands this
        step and their first decode token next step.  Without it a
        freed slot idles until the next step's leading admit."""
        t0 = self._clock()
        with timeline.span("sched.step") as step_span:
            admitted = self._admit()

            produced = kv_blocks = 0
            if any(r is not None for r in self.active):
                kv_blocks = self._count_kv_blocks()
                toks = self.engine.decode_step()
                now = self._clock()
                with timeline.span("sched.retire"):
                    for slot, req in enumerate(self.active):
                        if req is None:
                            continue
                        tok = int(toks[slot])
                        req.tokens.append(tok)
                        produced += 1
                        if self._finished(req, tok):
                            req.done_t = now
                            self._retire(slot)
            admitted += self._admit()
            self.step_count += 1
            self.tokens_generated += produced + admitted
            counts = dict(
                step=self.step_count,
                active=sum(r is not None for r in self.active),
                admitted=admitted, produced=produced,
                queued=len(self.pending), kv_blocks=kv_blocks)
            step_span.set(**counts)
        obs_events.emit(
            "serve_step", wall_ms=round(1e3 * (self._clock() - t0), 3),
            **counts)
        return produced + admitted

    # -- internals ----------------------------------------------------------

    def _count_kv_blocks(self) -> int:
        """The ``KV_BLOCK``-column blocks the coming decode step's
        attention finds in the live slots' rings (prompt plus tokens so
        far, the ring's capacity at most), summed; counted beside the
        blocks of all the rings, so a run's ratio of the two counters is
        the share of the cache its decode steps had to read.  (An idle
        slot costs the kernel one block more each.)"""
        capacity = self.engine.capacity
        live = sum(-(-min(len(r.prompt) + len(r.tokens), capacity)
                     // KV_BLOCK)
                   for r in self.active if r is not None)
        obs_metrics.bump("decode.kv_blocks_live", live)
        obs_metrics.bump("decode.kv_blocks_ring",
                         self.engine.slots * -(-capacity // KV_BLOCK))
        return live

    def _admit(self) -> int:
        """Fill free slots from the pending FIFO.  A request that
        finishes at prefill (max_new_tokens=1 or instant EOS) retires in
        place and its slot is reused without advancing — one admit pass
        never leaves a free slot behind while requests wait."""
        with timeline.span("sched.admit") as admit_span:
            admitted = 0
            slot = 0
            while self.pending and slot < self.engine.slots:
                if self.active[slot] is not None:
                    slot += 1
                    continue
                req = self.pending.pop(0)
                req.admit_t = t_adm = self._clock()
                timeline.record("sched.queue", req.arrival_t, t_adm,
                                rid=req.rid)
                first_tok, pcache, length = self.engine.prefill(req.prompt)
                prefill_span = timeline.last("engine.prefill")
                self.engine.insert(slot, pcache, length, first_tok)
                req.first_token_t = self._clock()
                req.tokens.append(first_tok)
                if req.trace is not None:
                    # Phase spans share clock reads with the TTFT record:
                    # arrival -> admit is queue, admit -> first token is
                    # prefill, so queue.ms + prefill.ms == ttft_ms exactly
                    # (modulo rounding) — the verify_traces invariant.
                    tracing.span(req.trace, "queue", parent=req.span,
                                 ms=1e3 * (t_adm - req.arrival_t))
                    tracing.span(req.trace, "prefill", parent=req.span,
                                 ms=1e3 * (req.first_token_t - t_adm),
                                 engine_ms=prefill_span and prefill_span.ms)
                self.active[slot] = req
                admitted += 1
                if self._finished(req, first_tok):
                    self._retire(slot)
                else:
                    slot += 1
            admit_span.set(admitted=admitted)
        return admitted

    def _finished(self, req: Request, tok: int) -> bool:
        return (len(req.tokens) >= req.max_new_tokens
                or (self.engine.eos_id is not None
                    and tok == self.engine.eos_id))

    def _retire(self, slot: int) -> None:
        req = self.active[slot]
        self.active[slot] = None
        self.engine.release(slot)
        if req.done_t is None:
            req.done_t = self._clock()
        self.completed.append(req)
        if req.trace is not None and req.first_token_t is not None:
            tracing.span(req.trace, "decode", parent=req.span,
                         ms=1e3 * (req.done_t - req.first_token_t),
                         tokens=len(req.tokens))
        obs_events.emit(
            "serve_request", id=req.rid, trace=req.trace,
            prompt_tokens=len(req.prompt),
            output_tokens=len(req.tokens),
            ttft_ms=round(req.ttft_ms() or 0.0, 3),
            tpot_ms=round(req.tpot_ms(), 3)
            if req.tpot_ms() is not None else None)
