"""Transformer train-step throughput on the real chip — BERT (config 4's
allreduce-stress model) and the TransformerLM long-context flagship.

VERDICT r2 #3: config 4 and the LM had zero on-chip evidence.  Measures
examples/s (BERT) and tokens/s (LM, both attention impls), bf16.  Results
go into BASELINE.md.

    python perf/bench_transformer.py           # both models
    MODEL=bert python perf/bench_transformer.py
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _common import make_log, setup

jax = setup()
import jax.numpy as jnp
import numpy as np
import optax

from tpuframe.models import losses
from tpuframe.parallel import step as step_lib

MODEL = os.environ.get("MODEL", "both")
STEPS = int(os.environ.get("N", "10"))
BERT_BATCH = int(os.environ.get("BERT_BATCH", "128"))
BERT_SEQ = int(os.environ.get("BERT_SEQ", "128"))
LM_BATCH = int(os.environ.get("LM_BATCH", "8"))
LM_SEQ = int(os.environ.get("LM_SEQ", "2048"))


log = make_log("tf-bench")


def run_chain(step, state, batch, steps=STEPS):
    state, m = step(state, batch)
    float(m["loss"])  # compile + settle
    t0 = time.perf_counter()
    for _ in range(steps):
        state, m = step(state, batch)
    float(m["loss"])
    return (time.perf_counter() - t0) / steps


def bench_bert():
    from tpuframe.models import bert as bert_lib

    cfg = bert_lib.BertConfig(dtype="bfloat16")  # base, MXU compute
    model = bert_lib.BertForSequenceClassification(cfg)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, size=(BERT_BATCH, BERT_SEQ)
                       ).astype(np.int32)
    batch = {"input_ids": jnp.asarray(ids),
             "attention_mask": jnp.ones((BERT_BATCH, BERT_SEQ), jnp.int32),
             "token_type_ids": jnp.zeros((BERT_BATCH, BERT_SEQ), jnp.int32),
             "label": jnp.asarray(rng.integers(0, 2, size=(BERT_BATCH,)),
                                  jnp.int32)}
    variables = model.init(jax.random.key(0), batch["input_ids"][:1],
                           batch["attention_mask"][:1],
                           batch["token_type_ids"][:1])
    tx = optax.adamw(2e-5)

    def loss_fn(params, model_state, b, rng):
        logits = model.apply({"params": params}, b["input_ids"],
                             b["attention_mask"], b["token_type_ids"],
                             train=True, rngs={"dropout": rng})
        return losses.softmax_cross_entropy(logits, b["label"]), ({}, {})

    state = step_lib.TrainState.create(variables["params"], tx)
    step = step_lib.make_train_step(loss_fn, tx, None, donate=True)
    dt = run_chain(step, state, batch)
    ex_s = BERT_BATCH / dt
    log(f"bert-base b={BERT_BATCH} s={BERT_SEQ}: {dt*1e3:.1f} ms/step, "
        f"{ex_s:.1f} examples/s, {ex_s*BERT_SEQ:.0f} tokens/s")
    return {"model": "bert-base", "batch": BERT_BATCH, "seq": BERT_SEQ,
            "ms_per_step": round(dt * 1e3, 1),
            "examples_per_s": round(ex_s, 1),
            "tokens_per_s": round(ex_s * BERT_SEQ)}


def bench_lm(attn_impl):
    from tpuframe.models.transformer_lm import LMConfig, TransformerLM

    remat = os.environ.get("REMAT", "1") == "1"
    cfg = LMConfig(vocab_size=32000, hidden_size=768, num_layers=12,
                   num_heads=12, intermediate_size=3072, max_seq=LM_SEQ,
                   dtype="bfloat16", attn_impl=attn_impl, remat=remat)
    model = TransformerLM(cfg)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, size=(LM_BATCH, LM_SEQ + 1)
                       ).astype(np.int32)
    batch = {"input_ids": jnp.asarray(ids[:, :-1]),
             "labels": jnp.asarray(ids[:, 1:])}
    variables = model.init(jax.random.key(0), batch["input_ids"][:1])
    tx = optax.adamw(1e-4)

    fused = os.environ.get("XENT", "dense") == "fused"
    if fused:
        # Chunked fused head+loss (tpuframe.ops.fused_xent): the [B,S,V]
        # logits never materialize in HBM.
        from tpuframe.ops import fused_xent as fx

        def loss_fn(params, model_state, b, rng):
            hidden = model.apply({"params": params}, b["input_ids"],
                                 train=True, rngs={"dropout": rng},
                                 hidden_only=True)
            w = params["lm_head"]["kernel"]
            loss = jnp.mean(fx.fused_softmax_xent(hidden, w, b["labels"]))
            return loss, ({}, {})
    else:
        def loss_fn(params, model_state, b, rng):
            logits = model.apply({"params": params}, b["input_ids"],
                                 train=True, rngs={"dropout": rng})
            return losses.softmax_cross_entropy(logits, b["labels"]), ({}, {})

    state = step_lib.TrainState.create(variables["params"], tx)
    step = step_lib.make_train_step(loss_fn, tx, None, donate=True)
    dt = run_chain(step, state, batch)
    tok_s = LM_BATCH * LM_SEQ / dt
    mods = (("" if remat else ",no-remat")
            + (",fused-xent" if fused else ""))
    tag = f"lm(124M,{attn_impl}{mods})"
    log(f"{tag} b={LM_BATCH} s={LM_SEQ}: {dt*1e3:.1f} ms/step,"
        f" {tok_s:.0f} tokens/s")
    return {"model": f"transformer-lm/{attn_impl}" + mods.replace(",", "/"),
            "batch": LM_BATCH, "seq": LM_SEQ,
            "ms_per_step": round(dt * 1e3, 1),
            "tokens_per_s": round(tok_s)}


def main():
    log(f"backend={jax.default_backend()}")
    rows = []
    if MODEL in ("both", "bert"):
        rows.append(bench_bert())
    if MODEL in ("both", "lm"):
        only = os.environ.get("ATTN_ONLY", "")
        impls = (only,) if only else ("xla", "pallas")
        # xla attention materializes [B,H,S,S] f32 scores; refuse shapes
        # that can't fit in HBM.
        score_gb = LM_BATCH * 12 * LM_SEQ * LM_SEQ * 4 / 1e9
        if "xla" in impls and score_gb > 4:
            log(f"skipping xla attention: scores ~{score_gb:.0f}GB")
            impls = tuple(i for i in impls if i != "xla")
        for impl in impls:
            try:
                rows.append(bench_lm(impl))
            except Exception as e:  # noqa: BLE001
                rows.append({"model": f"transformer-lm/{impl}",
                             "error": f"{type(e).__name__}: {e}"[:300]})
                log(rows[-1]["error"])
    import json
    print(json.dumps(rows, indent=1), flush=True)


if __name__ == "__main__":
    main()
