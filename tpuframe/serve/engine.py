"""AOT-compiled prefill/decode engine — the one sanctioned compile seam
of ``tpuframe.serve``.

Every jitted program in the serving path lives HERE, compiled ahead of
time against the closed set of bucketed shapes from ``serve.kv_cache``:

  prefill[b]  (params, ids[1, b], length[1])        -> (tok[1], cache)
              one per prompt bucket ``b`` — causal attention over the
              left-aligned padded prompt (identical math to the training
              forward, so golden-logits parity is by construction) plus
              the KV write, sampling the first output token at
              ``length - 1``.
  decode      (params, toks[S, 1], lengths[S], cache) -> updated triple
              one program total — the query-length-1 step over all
              ``S`` slots at once: each layer stores every slot's new
              K/V column at the slot's own ring index in one pass
              (ops.ring_store), then attends over the ring
              (ops.attention.decode_attention).  Cache, lengths and
              token buffers are DONATED: the executable updates HBM in
              place.  A step's traffic is the params once, the lane
              blocks the store touches, and the lane blocks of each
              ring at or below its slot's length (on a chip, where the
              attention kernel runs; its einsum stand-in reads every
              ring whole).  A slot that holds no request has length 0,
              stays there, and costs one block.
  insert      (cache, lengths, toks, pcache, slot, len, tok) -> updated
              one program total — copies a finished prefill's
              single-slot cache into the shared decode cache at a
              traced slot index (continuous batching's admission op).
  release     (lengths, slot) -> lengths
              one program total — a retired slot's length back to 0,
              which is what keeps the decode step from advancing it
              and from attending over what the request left behind.

The scheduler/loadgen layers above call these executables and are
forbidden (lint TF109) from calling ``jit``/``.apply`` themselves — a
novel shape reaching the compiler mid-serving is a silent multi-second
stall, the serving analogue of the TF106 dead-env-write footgun.

Greedy argmax sampling keeps the engine deterministic.
"""

from __future__ import annotations

import numpy as np

from tpuframe.obs import timeline
from tpuframe.serve import kv_cache as kv


def make_prefill_fn(model, spec: kv.CacheSpec):
    """The prefill step program (shared with the analysis-gate strategy
    audit so the audited program IS the served program).  Batch 1: one
    request prefills at a time; the capacity is the full decode ring so
    insertion is a single batch-dim slice copy."""
    import jax.numpy as jnp

    shape = (1,) + spec.layer_shape()[1:]
    dtype = jnp.dtype(spec.dtype)

    def prefill_fn(params, ids, length):
        layers = tuple((jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))
                       for _ in range(spec.num_layers))
        logits, layers = model.apply(
            {"params": params}, ids, kv_cache=layers,
            cache_length=jnp.zeros((1,), jnp.int32), decode=False)
        last = jnp.take_along_axis(logits, (length - 1)[:, None, None],
                                   axis=1)  # [1, 1, V] at the true end
        tok = jnp.argmax(last[:, 0, :], axis=-1).astype(jnp.int32)
        return tok, layers

    return prefill_fn


def make_decode_fn(model):
    """The decode step program: one token for every slot, ring KV store,
    greedy argmax.  ``lengths`` advances where it is non-zero: a slot that
    holds a request (``insert`` writes a length of at least 1) moves on,
    one that holds none stays at 0 until the next ``insert`` (it still
    decodes garbage the scheduler ignores — branchless beats a per-slot
    cond on TPU — over a ring of one valid column)."""
    import jax.numpy as jnp

    def decode_fn(params, tokens, lengths, layers):
        logits, layers = model.apply(
            {"params": params}, tokens, kv_cache=layers,
            cache_length=lengths, decode=True)
        nxt = jnp.argmax(logits[:, 0, :], axis=-1).astype(jnp.int32)
        return nxt[:, None], lengths + (lengths > 0), layers

    return decode_fn


def make_insert_fn(num_layers: int):
    """Admission: copy a prefilled single-slot cache into the shared
    decode cache at a *traced* slot index — one compiled program serves
    every slot."""
    from jax import lax

    def insert_fn(layers, lengths, tokens, p_layers, slot, length, tok):
        out = []
        for (k, v), (pk, pv) in zip(layers, p_layers):
            out.append((lax.dynamic_update_slice(k, pk, (slot, 0, 0, 0)),
                        lax.dynamic_update_slice(v, pv, (slot, 0, 0, 0))))
        lengths = lax.dynamic_update_slice(lengths, length[None], (slot,))
        tokens = lax.dynamic_update_slice(tokens, tok[None, None],
                                          (slot, 0))
        return tuple(out), lengths, tokens

    if num_layers < 1:
        raise ValueError("need at least one layer")
    return insert_fn


def release_fn(lengths, slot):
    """Retirement: the slot's length back to 0, at a *traced* slot index."""
    import jax.numpy as jnp
    from jax import lax

    return lax.dynamic_update_slice(lengths, jnp.zeros((1,), jnp.int32),
                                    (slot,))


class LMEngine:
    """Bucketed AOT serving engine for :class:`TransformerLM`.

    Owns the decode cache (``slots`` concurrent sequences) and the AOT
    executable table.  All compilation happens in ``__init__`` — by the
    time ``prefill``/``decode_step`` run, every shape the engine will
    ever execute is already compiled, and with the persistent compile
    cache (PR 3) enabled, already on disk for the next restart.
    """

    def __init__(self, cfg, params=None, *, slots: int = 4,
                 max_context: int | None = None, prompt_buckets=None,
                 decode_block: int | None = None, eos_id: int | None = None,
                 seed: int = 0, enable_persistent_cache: bool = True):
        import jax
        import jax.numpy as jnp

        from tpuframe.models.transformer_lm import TransformerLM
        from tpuframe.utils import compile_cache

        if enable_persistent_cache:
            compile_cache.enable()
        self.cfg = cfg
        self.model = TransformerLM(cfg)
        self.eos_id = eos_id
        self.decode_block = (decode_block if decode_block is not None
                             else kv.resolve_decode_block())
        buckets = (tuple(prompt_buckets) if prompt_buckets is not None
                   else kv.resolve_buckets())
        self.prompt_buckets = tuple(sorted(set(buckets)))
        max_context = max_context or max(self.prompt_buckets)
        capacity = kv.capacity_for(max_context, self.decode_block)
        problems = kv.check_buckets(self.prompt_buckets, capacity)
        if problems:
            raise ValueError("; ".join(problems))
        self.spec = kv.spec_for_model(cfg, slots=slots, capacity=capacity)

        if params is None:
            params = self.model.init(
                jax.random.key(seed),
                jnp.zeros((1, min(self.prompt_buckets)), jnp.int32)
            )["params"]
        self.params = params

        # --- the AOT table -------------------------------------------------
        sds = jax.ShapeDtypeStruct
        p_sds = jax.tree.map(lambda a: sds(a.shape, a.dtype), params)
        cache_sds = tuple(
            (sds(self.spec.layer_shape(), jnp.dtype(self.spec.dtype)),
             sds(self.spec.layer_shape(), jnp.dtype(self.spec.dtype)))
            for _ in range(cfg.num_layers))
        pcache_sds = jax.tree.map(
            lambda s: sds((1,) + s.shape[1:], s.dtype), cache_sds)
        i32 = jnp.int32

        self._prefill = {}
        for b in self.prompt_buckets:
            fn = make_prefill_fn(self.model, self.spec)
            self._prefill[b] = jax.jit(fn).lower(
                p_sds, sds((1, b), i32), sds((1,), i32)).compile()

        decode_fn = make_decode_fn(self.model)
        self._decode = jax.jit(decode_fn, donate_argnums=(1, 2, 3)).lower(
            p_sds, sds((slots, 1), i32), sds((slots,), i32),
            cache_sds).compile()

        insert_fn = make_insert_fn(cfg.num_layers)
        self._insert = jax.jit(insert_fn, donate_argnums=(0, 1, 2)).lower(
            cache_sds, sds((slots,), i32), sds((slots, 1), i32),
            pcache_sds, sds((), i32), sds((), i32), sds((), i32)).compile()

        self._release = jax.jit(release_fn, donate_argnums=0).lower(
            sds((slots,), i32), sds((), i32)).compile()

        self.reset()

    # --- state -------------------------------------------------------------

    def reset(self) -> None:
        """Fresh (zeroed) decode cache; every slot becomes free."""
        import jax.numpy as jnp

        self._layers, self._lengths = kv.init_cache(self.spec)
        self._tokens = jnp.zeros((self.spec.slots, 1), jnp.int32)

    @property
    def slots(self) -> int:
        return self.spec.slots

    @property
    def capacity(self) -> int:
        """KV entries a slot's ring holds."""
        return self.spec.capacity

    def compiled_programs(self) -> dict:
        """The AOT table, for census/tests: name -> compiled."""
        table = {f"prefill_{b}": c for b, c in self._prefill.items()}
        table["decode"] = self._decode
        table["insert"] = self._insert
        table["release"] = self._release
        return table

    def swap_params(self, new_params) -> None:
        """The ONE sanctioned live weight-swap seam (lint TF121).

        Hot-swaps the served weights without touching the AOT table:
        every executable takes ``params`` as a call argument, so
        rebinding the attribute is the whole swap — zero recompiles by
        construction, which is exactly the compile-cache hit floor the
        rollout controller asserts.  The new tree must match the old one
        leaf-for-leaf in shape and dtype (a serving fleet's params are
        replicated, so a checkpoint written at a different world size
        reassembles to this same replicated tree — the world-size
        invariance the elastic restore path guarantees; only the flat
        ZeRO-1 *optimizer* moments ever reshard, and serving never
        loads those).  A mismatched tree means the checkpoint is for a
        different model: refuse loudly rather than serve garbage."""
        import jax

        old_leaves, old_def = jax.tree.flatten(self.params)
        new_leaves, new_def = jax.tree.flatten(new_params)
        if old_def != new_def:
            raise ValueError(
                "swap_params: new weights have a different tree "
                "structure — this checkpoint is not for this model")
        for i, (a, b) in enumerate(zip(old_leaves, new_leaves)):
            if a.shape != b.shape or a.dtype != b.dtype:
                raise ValueError(
                    f"swap_params: leaf {i} is {b.shape}/{b.dtype}, "
                    f"engine compiled for {a.shape}/{a.dtype} — a "
                    f"shape-changing update needs a new engine, not a "
                    f"hot swap")
        self.params = new_params

    # --- serving ops -------------------------------------------------------

    def prefill(self, token_ids) -> tuple:
        """Run one prompt through its bucket's prefill executable.
        Returns ``(first_token: int, prefill_cache, length: int)``."""
        import jax.numpy as jnp

        ids = list(int(t) for t in token_ids)
        if not ids:
            raise ValueError("empty prompt")
        bucket = kv.bucket_for(len(ids), self.prompt_buckets)
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :len(ids)] = ids
        # The span's two children split the bucket's dispatch (the
        # executable's call returns) from the wait for the device (the
        # first token materializes on the host).
        with timeline.span("engine.prefill", bucket=bucket,
                           tokens=len(ids)):
            with timeline.span("engine.prefill.dispatch"):
                tok, pcache = self._prefill[bucket](
                    self.params, jnp.asarray(padded),
                    jnp.asarray([len(ids)], jnp.int32))
            with timeline.span("engine.prefill.fetch"):
                first = int(tok[0])   # host sync
        return first, pcache, len(ids)

    def insert(self, slot: int, pcache, length: int,
               first_token: int) -> None:
        """Admit a prefilled request into ``slot`` of the decode batch."""
        import jax.numpy as jnp

        if not 0 <= slot < self.spec.slots:
            raise ValueError(f"slot {slot} out of range "
                             f"[0, {self.spec.slots})")
        with timeline.span("engine.insert", slot=slot):
            self._layers, self._lengths, self._tokens = self._insert(
                self._layers, self._lengths, self._tokens, pcache,
                jnp.asarray(slot, jnp.int32), jnp.asarray(length, jnp.int32),
                jnp.asarray(first_token, jnp.int32))

    def release(self, slot: int) -> None:
        """Give ``slot`` up: its request has retired.  The slot's length
        goes to 0, so decode steps neither advance it nor read its ring
        beyond one block, until the next ``insert``."""
        import jax.numpy as jnp

        if not 0 <= slot < self.spec.slots:
            raise ValueError(f"slot {slot} out of range "
                             f"[0, {self.spec.slots})")
        with timeline.span("engine.release", slot=slot):
            self._lengths = self._release(self._lengths,
                                          jnp.asarray(slot, jnp.int32))

    def decode_step(self) -> np.ndarray:
        """One decode step over every slot.  Returns the new token per
        slot (host numpy [slots]; inactive slots carry garbage the
        scheduler ignores)."""
        with timeline.span("engine.decode"):
            with timeline.span("engine.decode.dispatch"):
                self._tokens, self._lengths, self._layers = self._decode(
                    self.params, self._tokens, self._lengths, self._layers)
                column = self._tokens[:, 0]   # a device slice, enqueued
            with timeline.span("engine.decode.fetch"):
                return np.asarray(column)     # host sync


# ---------------------------------------------------------------------------
# Single-shot BERT classification — the non-autoregressive serving path.
# ---------------------------------------------------------------------------

class BertClassifier:
    """Bucketed AOT single-shot classifier: no cache, one executable per
    sequence bucket, batch 1 — the GLUE-style request/response shape."""

    def __init__(self, cfg, params=None, *, buckets=(64, 128),
                 seed: int = 0):
        import jax
        import jax.numpy as jnp

        from tpuframe.models.bert import BertForSequenceClassification

        self.cfg = cfg
        self.model = BertForSequenceClassification(cfg)
        self.buckets = tuple(sorted(set(buckets)))
        if max(self.buckets) > cfg.max_position:
            raise ValueError(f"bucket {max(self.buckets)} exceeds "
                             f"max_position {cfg.max_position}")
        if params is None:
            b0 = min(self.buckets)
            params = self.model.init(
                jax.random.key(seed), jnp.zeros((1, b0), jnp.int32)
            )["params"]
        self.params = params

        def classify_fn(params, ids, mask):
            logits = self.model.apply({"params": params}, ids,
                                      attention_mask=mask)
            return jax.nn.softmax(logits, axis=-1)

        sds = jax.ShapeDtypeStruct
        p_sds = jax.tree.map(lambda a: sds(a.shape, a.dtype), params)
        self._classify = {
            b: jax.jit(classify_fn).lower(
                p_sds, sds((1, b), jnp.int32),
                sds((1, b), jnp.int32)).compile()
            for b in self.buckets}

    def classify(self, token_ids) -> tuple:
        """-> ``(label: int, probs: np.ndarray[num_classes])``."""
        import jax.numpy as jnp

        ids = list(int(t) for t in token_ids)
        bucket = kv.bucket_for(len(ids), self.buckets)
        padded = np.zeros((1, bucket), np.int32)
        mask = np.zeros((1, bucket), np.int32)
        padded[0, :len(ids)] = ids
        mask[0, :len(ids)] = 1
        probs = np.asarray(self._classify[bucket](
            self.params, jnp.asarray(padded), jnp.asarray(mask))[0])
        return int(probs.argmax()), probs


def swap_parity_check(cfg, *, buckets, decode_tokens: int = 4,
                      seed: int = 0, decode_block: int = 16) -> list:
    """The hot-swap analogue of :func:`golden_parity_check`: an engine
    swapped onto new weights must produce, for every serve bucket (full
    and ragged prompt), exactly the token streams of an engine
    cold-started on those weights — AND the swap itself must cost zero
    compile-cache misses (the AOT table is untouched; params are call
    arguments).  Returns problem strings; [] means the swap is
    transparent."""
    import jax
    import jax.numpy as jnp

    from tpuframe.obs import metrics

    buckets = tuple(sorted(buckets))
    max_context = max(buckets) + decode_tokens + decode_block
    hot = LMEngine(cfg, slots=2, prompt_buckets=buckets,
                   decode_block=decode_block, max_context=max_context,
                   seed=seed)
    new_params = hot.model.init(
        jax.random.key(seed + 1),
        jnp.zeros((1, min(buckets)), jnp.int32))["params"]
    cold = LMEngine(cfg, new_params, slots=2, prompt_buckets=buckets,
                    decode_block=decode_block, max_context=max_context)

    misses_before = metrics.counters().get("compile_cache.misses", 0)
    hot.swap_params(new_params)

    problems = []

    def stream(engine, ids):
        engine.reset()
        first, pcache, length = engine.prefill(ids)
        engine.insert(0, pcache, length, first)
        toks = [first]
        for _ in range(decode_tokens):
            toks.append(int(engine.decode_step()[0]))
        return toks

    for bucket in buckets:
        for prompt_len in sorted({bucket, max(2, bucket - 3)}):
            ids = [int(t) for t in jax.random.randint(
                jax.random.key(seed + bucket + prompt_len),
                (prompt_len,), 0, cfg.vocab_size)]
            got, want = stream(hot, ids), stream(cold, ids)
            if got != want:
                problems.append(
                    f"bucket {bucket} prompt_len {prompt_len}: "
                    f"hot-swapped stream {got} != cold-start {want}")

    misses_after = metrics.counters().get("compile_cache.misses", 0)
    if misses_after != misses_before:
        problems.append(
            f"swap cost {misses_after - misses_before} compile-cache "
            f"miss(es) — the hot-swap path must never recompile")
    return problems


# ---------------------------------------------------------------------------
# Golden-logits parity — the correctness contract of the whole cache path.
# ---------------------------------------------------------------------------

def golden_parity_diffs(cfg, *, buckets, capacity: int,
                        decode_tokens: int = 4, seed: int = 0) -> list:
    """``(bucket, prompt_len, max |logit diff|)`` of prefill-then-decode
    against the training forward, position by position, for every prompt
    bucket (both a full bucket and a ragged prompt that exercises the
    length mask).  The diff is None where prompt + decode overruns
    ``capacity``.

    Uses raw ``model.apply`` on purpose — this file is the sanctioned
    compile seam, and the reference side must be the *training* path,
    not another serving program.
    """
    import jax
    import jax.numpy as jnp

    from tpuframe.models.transformer_lm import TransformerLM

    model = TransformerLM(cfg)
    rows = []
    params = None
    for bucket in buckets:
        for prompt_len in sorted({bucket, max(2, bucket - 3)}):
            total = prompt_len + decode_tokens
            if total > capacity:
                rows.append((bucket, prompt_len, None))
                continue
            ids = jax.random.randint(jax.random.key(seed + bucket),
                                     (1, total), 0, cfg.vocab_size)
            if params is None:
                params = model.init(jax.random.key(seed),
                                    jnp.zeros((1, 8), jnp.int32))["params"]
            ref = model.apply({"params": params}, ids)

            shape = kv.spec_for_model(cfg, slots=1,
                                      capacity=capacity).layer_shape()
            layers = tuple(
                (jnp.zeros(shape, cfg.jnp_dtype),
                 jnp.zeros(shape, cfg.jnp_dtype))
                for _ in range(cfg.num_layers))
            got_p, layers = model.apply(
                {"params": params}, ids[:, :prompt_len], kv_cache=layers,
                cache_length=jnp.zeros((1,), jnp.int32), decode=False)
            outs = [got_p]
            length = jnp.asarray([prompt_len], jnp.int32)
            for t in range(prompt_len, total):
                lg, layers = model.apply(
                    {"params": params}, ids[:, t:t + 1], kv_cache=layers,
                    cache_length=length, decode=True)
                outs.append(lg)
                length = length + 1
            got = jnp.concatenate(outs, axis=1)
            rows.append((bucket, prompt_len,
                         float(jnp.max(jnp.abs(ref - got)))))
    return rows


def golden_parity_check(cfg, *, buckets, capacity: int,
                        decode_tokens: int = 4, seed: int = 0,
                        atol: float = 2e-5) -> list:
    """:func:`golden_parity_diffs` held to ``atol`` (the f32 default; a
    bf16 config needs a bf16 tolerance).  Returns problem strings; []
    means parity holds."""
    problems = []
    for bucket, prompt_len, diff in golden_parity_diffs(
            cfg, buckets=buckets, capacity=capacity,
            decode_tokens=decode_tokens, seed=seed):
        if diff is None:
            problems.append(f"bucket {bucket}: prompt+decode "
                            f"{prompt_len + decode_tokens} exceeds "
                            f"capacity {capacity}")
        elif not diff <= atol:
            problems.append(
                f"bucket {bucket} prompt_len {prompt_len}: max "
                f"|logit diff| {diff:.2e} > {atol:.0e}")
    return problems
