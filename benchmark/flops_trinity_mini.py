"""Work from shapes for the ``afmoe`` configurations (``trinity_mini``):
the operations and bytes a training step and its two kinds of kernel need.
Beside ``flops.py`` and by its rules: a fused multiply-add is 2
operations; recomputed operations (per-block remat, the flash backward's
second pass over QK^T) do not count; an operation on a masked pair of
positions, on a row picked for an expert held elsewhere, or on a padding
row of the expert buffer is not needed and does not count either.
"""

from __future__ import annotations

SLIDING = "sliding_attention"


def visible_pairs(seq: int, window: int | None) -> float:
    """Pairs (i, j) with ``0 <= i - j`` and, under a window, ``i - j <
    window``: what a causal (windowed) attention of ``seq`` positions has
    to score."""
    if window is None or window >= seq:
        return seq * (seq + 1) / 2.0
    return window * (window + 1) / 2.0 + (seq - window) * float(window)


def afmoe_matmul_params(cfg: dict) -> dict:
    """Weights every token multiplies, by kind: ``attention`` (q, k, v,
    gate, out) a layer, the ``dense`` MLP, a routed ``expert``, the
    ``shared`` expert, the ``router``, the ``head`` (the embedding is a
    lookup)."""
    h, nd = cfg["hidden_size"], cfg["num_heads"] * cfg["head_dim"]
    kvd = cfg["num_kv_heads"] * cfg["head_dim"]
    mi = cfg["moe_intermediate_size"]
    return {"attention": 3 * h * nd + 2 * h * kvd,
            "dense": 3 * h * cfg["intermediate_size"],
            "expert": 3 * h * mi,
            "shared": 3 * h * mi * cfg.get("num_shared_experts", 1),
            "router": h * cfg["num_experts"],
            "head": h * cfg["vocab_size"]}


def balanced_rows_here(cfg: dict, seq_len: int) -> float:
    """Rows an expert layer computes here when every expert has the mean
    load: tokens x experts a token x the share of the experts held."""
    return seq_len * cfg["num_experts_per_tok"] * cfg["experts_held"] \
        / cfg["num_experts"]


def afmoe_train_flops_per_sequence(cfg: dict, seq_len: int,
                                   rows_here: float | None = None) -> float:
    """Forward and backward of one sequence: 6 operations a weight a
    token for what every token multiplies, 6 a weight a row for the routed
    experts held here (``rows_here`` a layer, from the program's counter;
    the balanced load where none is given), and the attention's scores and
    values over the visible pairs, forward and twice that backward."""
    p = afmoe_matmul_params(cfg)
    n_dense = cfg["num_dense_layers"]
    n_moe = cfg["num_layers"] - n_dense
    if rows_here is None:
        rows_here = balanced_rows_here(cfg, seq_len)
    every_token = cfg["num_layers"] * p["attention"] + n_dense * p["dense"] \
        + n_moe * (p["shared"] + p["router"]) + p["head"]
    pairs = sum(visible_pairs(seq_len, cfg["sliding_window"]
                              if kind == SLIDING else None)
                for kind in cfg["layer_types"])
    attention_fwd = 2.0 * 2.0 * pairs * cfg["num_heads"] * cfg["head_dim"]
    return 6.0 * every_token * seq_len \
        + 6.0 * n_moe * p["expert"] * rows_here + 3.0 * attention_fwd


def window_attention_work(batch: int, seq: int, heads: int, kv_heads: int,
                          head_dim: int, window: int | None,
                          *, itemsize: int = 2) -> dict:
    """Operations and bytes one forward and one backward of causal
    (windowed) attention over grouped K/V heads need: QK^T and PV over the
    visible pairs forward, five such products backward; q read and o
    written per query head, k and v read once per K/V head forward; q, o,
    do read and dq written per query head, k, v read and dk, dv written
    per K/V head backward."""
    one = 2.0 * batch * heads * visible_pairs(seq, window) * head_dim
    q_tensor = batch * seq * heads * head_dim * itemsize
    kv_tensor = batch * seq * kv_heads * head_dim * itemsize
    return {"fwd": {"flops": 2 * one, "bytes": 2 * q_tensor + 2 * kv_tensor},
            "bwd": {"flops": 5 * one, "bytes": 4 * q_tensor + 4 * kv_tensor}}


def moe_experts_work(rows: float, hidden: int, inter: int, held: int,
                     *, itemsize: int = 2) -> dict:
    """Operations and bytes the grouped products of one expert layer need
    for ``rows`` rows over ``held`` SwiGLU experts of ``hidden -> inter``:
    gate, up and down forward (3 products), twice that backward; the
    experts' weights read once forward and once backward and their
    gradient written once, the rows in and out."""
    fwd = 2.0 * rows * 3 * hidden * inter
    weights = held * 3 * hidden * inter * itemsize
    io = rows * (2 * hidden + 3 * inter) * itemsize
    return {"fwd": {"flops": fwd, "bytes": weights + io},
            "bwd": {"flops": 2 * fwd, "bytes": 2 * weights + 2 * io}}
