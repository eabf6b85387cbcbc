"""Work from shapes for the ``deepseek_v3`` configurations
(``kanana2_30b_a3b``): the operations and bytes a training step and its
latent flash kernels need.  Beside ``flops.py`` and by its rules, which
``flops_trinity_mini.py`` spells out and whose counts of pairs, of a
balanced router's rows and of the grouped expert products this module
imports: a fused multiply-add is 2 operations; recomputed operations do
not count; an operation on a masked pair of positions, on a row picked for
an expert held elsewhere or on a padding row is not needed and does not
count either.  The attention's count is of the mathematics, products 192
(scores) and 128 (values) wide, whatever way a kernel lays the 192 out.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from flops_trinity_mini import balanced_rows_here, visible_pairs  # noqa: E402


def deepseek_v3_matmul_params(cfg: dict) -> dict:
    """Weights every token multiplies, by kind: ``attention`` (Wq, Wkva,
    Wkvb, Wo) a layer, the ``dense`` MLP, a routed ``expert``, the
    ``shared`` experts, the ``router``, the ``head`` (the embedding is a
    lookup)."""
    h, n = cfg["hidden_size"], cfg["num_heads"]
    d_nope, d_rope, d_v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                           cfg["v_head_dim"])
    rank, mi = cfg["kv_lora_rank"], cfg["moe_intermediate_size"]
    return {"attention": h * n * (d_nope + d_rope) + h * (rank + d_rope)
            + rank * n * (d_nope + d_v) + n * d_v * h,
            "dense": 3 * h * cfg["intermediate_size"],
            "expert": 3 * h * mi,
            "shared": 3 * h * mi * cfg["num_shared_experts"],
            "router": h * cfg["num_experts"],
            "head": h * cfg["vocab_size"]}


def deepseek_v3_train_flops_per_sequence(cfg: dict, seq_len: int,
                                         rows_here: float | None = None
                                         ) -> float:
    """Forward and backward of one sequence: 6 operations a weight a token
    for what every token multiplies, 6 a weight a row for the routed
    experts held here (``rows_here`` a layer, from the program's counter;
    the balanced load where none is given), and the attention's scores
    (192 wide) and values (128 wide) over the causal pairs, forward and
    twice that backward."""
    p = deepseek_v3_matmul_params(cfg)
    n_dense = cfg["num_dense_layers"]
    n_moe = cfg["num_layers"] - n_dense
    if rows_here is None:
        rows_here = balanced_rows_here(cfg, seq_len)
    every_token = cfg["num_layers"] * p["attention"] + n_dense * p["dense"] \
        + n_moe * (p["shared"] + p["router"]) + p["head"]
    attention_fwd = cfg["num_layers"] * 2.0 * visible_pairs(seq_len, None) \
        * cfg["num_heads"] * (cfg["qk_nope_head_dim"]
                              + cfg["qk_rope_head_dim"] + cfg["v_head_dim"])
    return 6.0 * every_token * seq_len \
        + 6.0 * n_moe * p["expert"] * rows_here + 3.0 * attention_fwd


def latent_attention_work(batch: int, seq: int, heads: int, d_nope: int,
                          d_rope: int, d_v: int, *, itemsize: int = 2
                          ) -> dict:
    """Operations and bytes one forward and one backward of causal latent
    attention need.  Forward two products over the causal pairs: the
    scores, ``d_nope + d_rope`` wide, and the values, ``d_v`` wide.
    Backward five: the scores again, dQ and dK (each ``d_nope + d_rope``
    wide), dV and dP (each ``d_v`` wide).  q, k_nope, v and o move once a
    head and the rotary key once a position forward; backward q, k_nope,
    v, o and do are read and dq, dk_nope, dv written once a head, the
    rotary key read and its gradient written once a position."""
    pairs = 2.0 * batch * heads * visible_pairs(seq, None)
    d_qk = d_nope + d_rope
    rows, shared = batch * seq * heads * itemsize, batch * seq * itemsize
    return {"fwd": {"flops": pairs * (d_qk + d_v),
                    "bytes": rows * (d_qk + d_nope + 2 * d_v)
                    + shared * d_rope},
            "bwd": {"flops": pairs * (3 * d_qk + 2 * d_v),
                    "bytes": rows * (2 * d_qk + 2 * d_nope + 4 * d_v)
                    + shared * 2 * d_rope}}
