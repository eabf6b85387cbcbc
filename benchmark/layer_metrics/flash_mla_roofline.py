"""Share of their roofline that the latent flash-attention kernels reach
in a ``deepseek_v3`` cell's traced steps, in %: the least time the chip
could take for one forward and one backward call a layer a step (scores
192 wide and values 128 wide over the causal pairs; q, k_nope, v, o once a
head and the rotary key once a position:
``flops_kanana2.latent_attention_work``) over the device time of the ops
named ``flash_mla_fwd``, ``flash_mla_bwd_dq`` and ``flash_mla_bwd_dkv``.
The forward calls that remat repeats are time spent, not work needed.
Nothing where no op carries those names (a program without the kernels)."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import _afmoe  # noqa: E402
import flops_kanana2  # noqa: E402

NAMES = ("flash_mla_fwd", "flash_mla_bwd_dq", "flash_mla_bwd_dkv")


def read(run: dict):
    peaks, traced = run["peaks"], run["window"].get("traced")
    spent = _afmoe.device_seconds(run, NAMES)
    if peaks is None or not traced or spent <= 0.0:
        return None
    arch, flops = run["config"]["arch"], run["flops"]
    work = flops_kanana2.latent_attention_work(
        run["traffic"]["job"]["global_batch"] // run["window"]["chips"],
        run["traffic"]["flops_args"]["seq_len"], arch["num_heads"],
        arch["qk_nope_head_dim"], arch["qk_rope_head_dim"],
        arch["v_head_dim"])
    least = arch["num_layers"] * (
        flops.roofline_seconds(work["fwd"], peaks)[0]
        + flops.roofline_seconds(work["bwd"], peaks)[0])
    return 100.0 * least * traced["steps"] / spent
