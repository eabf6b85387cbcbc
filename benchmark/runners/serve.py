"""Runner kind ``serve``: open-loop traffic in wall time against
``Scheduler.submit``/``step`` over one ``LMEngine`` on the cell's chip.

The generator (``loadgen.py``) fixes when each request is due; the loop
submits what is due, then takes one scheduler step, so a slow step makes
later requests wait and their time to first token counts the wait: each
request is timed from when it was due, not from when it was sent.  Before
the window opens the same traffic has run for the mix's ``lead_s`` (the
generator's lead-in: the window's own requests, one turn earlier), as long
as the longest answer stays, so the window opens on the state this traffic
leaves the system in; the lead-in counts as set-up.  After the window
closes nothing more is sent and the loop drains what was sent, a minute at
most; a request that never finishes counts as failed and
as the worst latency.

Per-layer spans come from a thin proxy around the engine that times
``prefill``, ``insert`` and ``decode_step`` as the scheduler calls them.

``check`` runs the plain reference once over a sample of the finished
requests, the longest among them and one from every prompt bucket, each
prompt with its served tokens, and reads the widest gap by which a served
token's float32 logit lies below the reference's best at its position.

``python benchmark/runners/serve.py --sweep --workload <cell> --rates
a,b,c --seconds s`` offers each rate in turn in one process and prints
offered against completed tokens per second and the queue at the close:
the knee the traffic file's ``rate_rps`` is four fifths of.
"""

from __future__ import annotations

import gc
import math
import os
import sys
import time

import numpy as np


UNTRACED_SHARE = 0.6  # of --seconds, in a traced run, before the profiler
TRACED_SECONDS = 3.0


def percentile(values, q: float):
    """Nearest-rank percentile; None of nothing."""
    if not values:
        return None
    s = sorted(values)
    return s[min(max(math.ceil(q * len(s)) - 1, 0), len(s) - 1)]


class TimedEngine:
    """The engine as the scheduler sees it, with a span around each call
    into it."""

    def __init__(self, engine, spans):
        self._engine, self._spans = engine, spans
        self.prefill_starts: list[float] = []
        self.prefill_tokens: list[int] = []

    def __getattr__(self, name):
        return getattr(self._engine, name)

    def prefill(self, token_ids):
        self.prefill_starts.append(time.monotonic())
        self.prefill_tokens.append(len(token_ids))
        with self._spans.span("prefill"):
            return self._engine.prefill(token_ids)

    def insert(self, *a, **kw):
        with self._spans.span("insert"):
            return self._engine.insert(*a, **kw)

    def decode_step(self):
        with self._spans.span("decode_step"):
            return self._engine.decode_step()


class Cell:
    def __init__(self, ctx):
        self.ctx = ctx
        self.arch = ctx.config["arch"]
        self.engine = self.timed = None
        self.finished: list = []
        self.unfinished = 0
        self.attempted = 0

    # -- set-up ------------------------------------------------------------

    def _lm_config(self):
        from tpuframe.models.transformer_lm import LMConfig

        a = self.arch
        return LMConfig(vocab_size=a["vocab_size"],
                        hidden_size=a["hidden_size"],
                        num_layers=a["num_layers"],
                        num_heads=a["num_heads"],
                        intermediate_size=a["intermediate_size"],
                        max_seq=a["max_seq"], rope_theta=a["rope_theta"],
                        dtype=self.ctx.config["serve"]["dtype"])

    def setup(self) -> None:
        from tpuframe.serve.engine import LMEngine
        from tpuframe.serve.scheduler import Request, Scheduler

        ctx, e = self.ctx, self.ctx.traffic["engine"]
        params = ctx.reference.init_weights(self.arch, ctx.seed)["params"]
        self.engine = LMEngine(
            self._lm_config(), params, slots=int(e["slots"]),
            max_context=int(e["max_context"]),
            prompt_buckets=tuple(e["prompt_buckets"]))
        del params
        ctx.log(f"engine built: {self.engine.spec}")
        self.timed = TimedEngine(self.engine, ctx.spans)
        # every bucket, the insert and the decode step once before any
        # request is timed
        sched = Scheduler(self.timed)
        rng = np.random.default_rng([int(ctx.seed), 99])
        for i, b in enumerate(self.engine.prompt_buckets):
            ids = rng.integers(0, self.arch["vocab_size"], size=b)
            sched.submit(Request(rid=-1 - i, prompt=[int(t) for t in ids],
                                 max_new_tokens=3,
                                 arrival_t=time.monotonic()))
        while sched.has_work():
            sched.step()
        # no reset: every slot is free again, and a second ring beside the
        # first would double the peak that memory_peak_bytes reads

    # -- the window ---------------------------------------------------------

    def _offer(self, planned, t_open: float, t_close: float,
               drain_s: float, trace_from: float | None = None) -> dict:
        """Run the open loop.  ``planned`` are ``(due_abs, Planned,
        in_window)``; returns the requests and the per-step record.  The
        profiler, in a traced run, is on from ``trace_from`` to
        ``t_close``; the loop itself starts and stops it at a step
        boundary (a timer thread would share the GIL with the loop)."""
        from tpuframe.serve.scheduler import Request, Scheduler

        spans = self.ctx.spans
        sched = Scheduler(self.timed)
        reqs, late, steps = [], [], []
        i, n = 0, len(planned)
        give_up = t_close + drain_s
        traced_span, settle_until = None, 0.0
        while True:
            now = time.monotonic()
            while i < n and planned[i][0] <= now:
                due, p, in_win = planned[i]
                r = Request(rid=i, prompt=p.prompt,
                            max_new_tokens=p.max_new_tokens, arrival_t=due)
                sched.submit(r)
                reqs.append((r, in_win))
                late.append(now - due)
                i += 1
            if trace_from is not None and trace_from <= now < t_close:
                if not self.ctx.tracer.active:
                    self.ctx.tracer.start()
                    settle_until = time.monotonic() + 0.5
                elif traced_span is None and now >= settle_until:
                    # the profiler's start-up stalls the first steps
                    traced_span = spans.span("traced")
                    traced_span.__enter__()
            if now >= t_close and self.ctx.tracer.active:
                if traced_span is not None:
                    traced_span.__exit__(None, None, None)
                self.ctx.tracer.stop()
                # writing the trace out takes tens of seconds in which
                # nothing is stepped: the drain's minute starts after it
                give_up = time.monotonic() + drain_s
            if sched.has_work():
                if now > give_up:
                    break
                with spans.span("sched_step"):
                    made = sched.step()
                steps.append((time.monotonic(), made, len(sched.pending)))
            elif i < n:
                with spans.span("idle_wait"):
                    time.sleep(max(min(planned[i][0] - time.monotonic(),
                                       0.05), 0.0))
            else:
                break
        return {"requests": reqs, "lateness": late, "steps": steps,
                "t_end": time.monotonic(), "t_open": t_open}

    def measure(self, *, traffic=None, seconds=None) -> dict:
        ctx = self.ctx
        traffic = traffic or ctx.traffic
        loadgen = ctx.loadgen
        seconds = float(seconds if seconds is not None else ctx.seconds)
        lead = float(traffic["lead_s"])
        traced_s = TRACED_SECONDS if ctx.trace else 0.0
        if ctx.trace:
            seconds = max(seconds * UNTRACED_SHARE, 1.0)
        vocab = self.arch["vocab_size"]
        sent = loadgen.schedule(traffic, ctx.seed, seconds + traced_s, vocab,
                                lead_s=lead)
        mark = ctx.spans.mark()
        n_prefill0 = len(self.timed.prefill_starts)
        start = time.monotonic() + 0.05
        t_open = start + lead
        t_close = t_open + seconds   # end of the measured part
        t_stop = t_close + traced_s  # a traced run sends on until here
        planned = [(t_open + p.due_s, p, 0.0 <= p.due_s < seconds)
                   for p in sent]
        run = self._offer(planned, t_open, t_stop,
                          float(traffic["drain_s"]),
                          trace_from=t_close if ctx.trace else None)
        return self._reduce(run, seconds, mark, n_prefill0)

    def _reduce(self, run: dict, seconds: float, mark: dict,
                n_prefill0: int) -> dict:
        ctx = self.ctx
        t_open, t_close = run["t_open"], run["t_open"] + seconds
        t_end = run["t_end"]
        in_win = [r for r, w in run["requests"] if w]
        done = [r for r in in_win if r.done]
        self.finished = [r for r, _ in run["requests"] if r.done]
        self.unfinished = len(in_win) - len(done)
        self.attempted = len(in_win)
        ttft = [r.ttft_ms() if r.first_token_t is not None
                else 1e3 * (t_end - r.arrival_t) for r in in_win]
        tpot = []
        for r in in_win:
            if r.done and r.tpot_ms() is not None:
                tpot.append(r.tpot_ms())
            elif not r.done and r.first_token_t is not None:
                tpot.append(1e3 * (t_end - r.first_token_t)
                            / max(len(r.tokens) - 1, 1))
        tokens = sum(made for t, made, _ in run["steps"]
                     if t_open <= t <= t_close)
        spans = ctx.spans.since(mark)
        inside = lambda name: [  # noqa: E731
            (a, b) for a, b in spans.get(name, []) if t_open <= a < t_close]
        starts = self.timed.prefill_starts[n_prefill0:]
        ptoks = self.timed.prefill_tokens[n_prefill0:]
        # the scheduler admits in the order of submission, so the k-th
        # prefill belongs to the k-th request sent
        queue_ms = [1e3 * (s - r.arrival_t)
                    for s, (r, w) in zip(starts, run["requests"])
                    if w and t_open <= s < t_close]
        prompt_tokens = sum(n for s, n in zip(starts, ptoks)
                            if t_open <= s < t_close)
        pend_close = [p for t, _, p in run["steps"] if t <= t_close]
        return {
            "kind": "serve", "wall_s": seconds, "chips": ctx.chips,
            "opened_at": t_open,   # set-up ends here: the lead-in is set-up
            "requests_due": len(in_win), "requests_done": len(done),
            "tokens_in_window": tokens, "prompt_tokens_in_window":
            prompt_tokens, "queue_ms": queue_ms,
            "prefill_ms": [1e3 * (b - a) for a, b in inside("prefill")],
            "insert_ms": [1e3 * (b - a) for a, b in inside("insert")],
            "decode_step_ms": [1e3 * (b - a)
                               for a, b in inside("decode_step")],
            "lateness_ms_p95": percentile(
                [1e3 * x for x in run["lateness"]], 0.95),
            "pending_at_close": pend_close[-1] if pend_close else 0,
            "drain_s": t_end - t_close,
            "offered_tokens_per_s": sum(
                r.max_new_tokens for r in in_win) / seconds,
            "end_to_end": {
                "serve_ttft_p95_ms": percentile(ttft, 0.95),
                "serve_tpot_p95_ms": percentile(tpot, 0.95),
                "serve_tok_per_s": tokens / seconds,
            },
        }

    def release(self) -> None:
        import jax

        self.engine = self.timed = None
        gc.collect()
        jax.clear_caches()

    # -- correct -----------------------------------------------------------

    def _sample(self) -> list:
        """The longest finished request, one from every prompt bucket,
        and others drawn from the seed, ``sample_requests`` in all."""
        want = int(self.ctx.traffic["check"]["sample_requests"])
        buckets = sorted(self.ctx.traffic["engine"]["prompt_buckets"])
        size = lambda r: len(r.prompt) + len(r.tokens)  # noqa: E731
        pool = sorted(self.finished, key=lambda r: r.rid)
        if not pool:
            return []
        picked = [max(pool, key=size)]
        for b in buckets:
            lo = max([x for x in buckets if x < b], default=0)
            fits = [r for r in pool if lo < len(r.prompt) <= b
                    and r not in picked]
            if fits:
                picked.append(max(fits, key=size))
        rest = [r for r in pool if r not in picked]
        rng = np.random.default_rng([int(self.ctx.seed), 7])
        rng.shuffle(rest)
        return (picked + rest)[:max(want, 1)]

    def check(self, *, quant: str | None = None) -> dict:
        import jax.numpy as jnp

        ctx = self.ctx
        t0 = time.monotonic()
        limits = ctx.traffic["limits"]
        pad_to = int(ctx.traffic["engine"]["max_context"])
        wrong_length = sum(1 for r in self.finished
                           if len(r.tokens) != r.max_new_tokens)
        sample = self._sample()
        worst, worst_control, n_tokens = 0.0, None, 0
        if sample:
            params = ctx.reference.init_weights(self.arch,
                                                ctx.seed)["params"]
            gap_fn = ctx.reference.make_gap_fn(self.arch, quant=quant)
            for r in sample:
                seq = (list(r.prompt) + list(r.tokens))[:pad_to]
                ids = np.zeros((1, pad_to), np.int32)
                ids[0, :len(seq)] = seq
                gaps, control = gap_fn(params, jnp.asarray(ids))
                lo, hi = len(r.prompt) - 1, len(seq) - 1
                g = np.asarray(gaps)[lo:hi]
                n_tokens += len(g)
                worst = max(worst, float(np.max(g)))
                if quant is not None:
                    c = float(np.max(np.asarray(control)[lo:hi]))
                    worst_control = max(worst_control or 0.0, c)
        failed = self.unfinished + wrong_length
        compared = {
            "served_token_gap_max": {
                "value": worst if sample and math.isfinite(worst) else 1e30,
                "limit": limits["served_token_gap_max"]},
            "requests_failed": {"value": failed, "limit": 0},
        }
        ctx.log(f"reference read {n_tokens} served tokens of {len(sample)} "
                f"requests in {time.monotonic() - t0:.2f} s; widest gap "
                f"{worst:.4f}" + (f"; control ({quant}) {worst_control:.4f}"
                                  if quant else ""))
        ok = bool(sample) and all(v["value"] <= v["limit"]
                                  for v in compared.values())
        out = {"correct": ok, "attempted": self.attempted,
               "failed": failed, "compared": compared}
        if quant is not None:
            out["control_gap_max"] = worst_control
        return out


# --------------------------------------------------------------------------
# the knee sweep (run by hand on the chip; not the driver's command)
# --------------------------------------------------------------------------

def sweep(argv) -> int:
    import argparse
    import json

    here = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, os.path.dirname(here))
    import run as bench

    ap = argparse.ArgumentParser()
    ap.add_argument("--sweep", action="store_true")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--cpu-rehearsal", action="store_true")
    ap.add_argument("--manifest", default=None)
    args = ap.parse_args(argv)
    ctx = bench.make_context(
        argparse.Namespace(workload=args.workload, seed=args.seed,
                           seconds=args.seconds, trace=0),
        require_chip=not args.cpu_rehearsal, manifest_path=args.manifest)
    cell = Cell(ctx)
    cell.setup()
    for rate in [float(x) for x in args.rates.split(",")]:
        traffic = json.loads(json.dumps(ctx.traffic))
        traffic["arrivals"]["rate_rps"] = rate
        traffic["drain_s"] = 20.0
        w = cell.measure(traffic=traffic, seconds=args.seconds)
        cell.engine.reset()
        row = {"rate_rps": rate, **{k: w[k] for k in (
            "requests_due", "requests_done", "offered_tokens_per_s",
            "pending_at_close", "drain_s", "lateness_ms_p95")},
            **w["end_to_end"],
            "decode_step_ms_p50": percentile(w["decode_step_ms"], 0.5),
            "prefill_ms_p50": percentile(w["prefill_ms"], 0.5)}
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(sweep(sys.argv[1:]))
