"""Work from shapes: the operations and bytes a step or a kernel needs.

Kept with the benchmark so that no later PR can change the yardstick.  All
counts take a fused multiply-add as 2 operations, as the published peaks
do.  Recomputed operations (remat, the flash backward's second pass over
QK^T beyond the one the algorithm needs) do not count.
"""

from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks_for(device_kind: str) -> dict:
    """The chip's published peaks; an unknown kind raises."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(f"device kind {device_kind!r} is not in "
                       f"benchmark/peaks.json: no peak, no share of it")
    return table[device_kind]


# --------------------------------------------------------------------------
# ResNet (bottleneck family), from layer shapes
# --------------------------------------------------------------------------

def _same_out(size: int, stride: int) -> int:
    return -(-size // stride)


def resnet_conv_layers(cfg: dict) -> list[dict]:
    """Every convolution and the classifier of a bottleneck ResNet as
    ``{name, k, cin, cout, out_hw, needs_input_grad}``, for ``cfg`` with
    ``stage_sizes``, ``width``, ``image_size``, ``num_classes``."""
    size = _same_out(cfg["image_size"], 2)
    layers = [dict(name="stem_conv", k=7, cin=3, cout=cfg["width"],
                   out_hw=size, needs_input_grad=False)]
    size = _same_out(size, 2)  # 3x3/2 max pool
    cin = cfg["width"]
    idx = 0
    for i, n_blocks in enumerate(cfg["stage_sizes"]):
        f = cfg["width"] * 2 ** i
        for j in range(n_blocks):
            stride = 2 if i > 0 and j == 0 else 1
            out = _same_out(size, stride)
            pre = f"Bottleneck_{idx}"
            layers.append(dict(name=f"{pre}/Conv_0", k=1, cin=cin, cout=f,
                               out_hw=size, needs_input_grad=True))
            layers.append(dict(name=f"{pre}/Conv_1", k=3, cin=f, cout=f,
                               out_hw=out, needs_input_grad=True))
            layers.append(dict(name=f"{pre}/Conv_2", k=1, cin=f, cout=4 * f,
                               out_hw=out, needs_input_grad=True))
            if cin != 4 * f or stride != 1:
                layers.append(dict(name=f"{pre}/downsample_conv", k=1,
                                   cin=cin, cout=4 * f, out_hw=out,
                                   needs_input_grad=True))
            cin, size, idx = 4 * f, out, idx + 1
    layers.append(dict(name="Dense_0", k=1, cin=cin,
                       cout=cfg["num_classes"], out_hw=1,
                       needs_input_grad=True))
    return layers


def resnet_train_flops_per_image(cfg: dict) -> float:
    """Forward + backward of the convolutions and the classifier: the
    forward's multiply-adds, once more for the weight gradient and once
    more for the input gradient (which the stem does not need)."""
    total = 0.0
    for layer in resnet_conv_layers(cfg):
        fwd = 2.0 * layer["k"] ** 2 * layer["cin"] * layer["cout"] \
            * layer["out_hw"] ** 2
        total += fwd * (3.0 if layer["needs_input_grad"] else 2.0)
    return total


# --------------------------------------------------------------------------
# Decoder-only LM
# --------------------------------------------------------------------------

def lm_matmul_params(cfg: dict) -> int:
    """Parameters that every token multiplies: attention and MLP weights
    of every layer and the (untied) head; not the embedding table."""
    h, ff = cfg["hidden_size"], cfg["intermediate_size"]
    per_layer = 4 * h * h + 2 * h * ff
    return cfg["num_layers"] * per_layer + h * cfg["vocab_size"]


def lm_attention_flops_fwd(cfg: dict, seq_len: int) -> float:
    """Causal QK^T and PV of one sequence over all layers, forward."""
    h = cfg["hidden_size"]
    return cfg["num_layers"] * 2.0 * 2.0 * seq_len * seq_len * h / 2.0


def lm_train_flops_per_sequence(cfg: dict, seq_len: int) -> float:
    """6 * N_matmul * tokens, plus causal attention forward and twice
    that backward."""
    return 6.0 * lm_matmul_params(cfg) * seq_len \
        + 3.0 * lm_attention_flops_fwd(cfg, seq_len)


def lm_serve_flops(cfg: dict, prompt_tokens: int, output_tokens: int) -> float:
    """2 * N_matmul per token processed (prompt and output alike); the
    attention over the cache is bytes, not operations worth counting at
    these lengths, and is left out, so the share is a lower bound."""
    return 2.0 * lm_matmul_params(cfg) * (prompt_tokens + output_tokens)


# --------------------------------------------------------------------------
# Kernels
# --------------------------------------------------------------------------

def flash_attention_work(batch: int, seq: int, heads: int, head_dim: int,
                         *, causal: bool = True, itemsize: int = 2) -> dict:
    """Operations and bytes the flash algorithm needs for one forward and
    one backward at ``[batch, seq, heads, head_dim]``.

    Forward: QK^T and PV.  Backward: the one recomputation of QK^T that
    the algorithm is built on, then dV, dP, dQ, dK: five products.  A
    kernel split into a dq and a dkv pass recomputes more; that is its
    cost, not the algorithm's need.  Bytes: q, k, v read and o written
    forward; q, k, v, o, do read and dq, dk, dv written backward (row
    statistics are small and left out)."""
    one = 2.0 * batch * heads * seq * seq * head_dim
    if causal:
        one /= 2.0
    tensor = batch * seq * heads * head_dim * itemsize
    return {
        "fwd": {"flops": 2 * one, "bytes": 4 * tensor},
        "bwd": {"flops": 5 * one, "bytes": 8 * tensor},
    }


def roofline_seconds(work: dict, peaks: dict) -> tuple[float, str]:
    """Least time the chip could take for ``{flops, bytes}`` and which
    bound binds."""
    t_flops = work["flops"] / peaks["bf16_flops_per_s"]
    t_bytes = work["bytes"] / peaks["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "memory")
