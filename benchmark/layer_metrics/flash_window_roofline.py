"""Share of their roofline that the flash-attention kernels reach in an
``afmoe`` cell's traced steps, in %: the least time the chip could take
for one forward and one backward call a layer a step (window layers over
the pairs inside the window, the full layer over the causal triangle; K
and V read once per K/V head: ``flops_trinity_mini.window_attention_work``)
over the device time of the ops named ``flash_fwd``, ``flash_bwd_dq`` and
``flash_bwd_dkv``.  The forward calls that remat repeats are time spent,
not work needed."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _afmoe  # noqa: E402

NAMES = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")


def read(run: dict):
    peaks, traced = run["peaks"], run["window"].get("traced")
    spent = _afmoe.device_seconds(run, NAMES)
    if peaks is None or not traced or spent <= 0.0:
        return None
    arch, flops = run["config"]["arch"], run["flops"]
    seq = run["traffic"]["flops_args"]["seq_len"]
    batch = run["traffic"]["job"]["global_batch"] // run["window"]["chips"]
    least = 0.0
    for kind in arch["layer_types"]:
        work = _afmoe.counts().window_attention_work(
            batch, seq, arch["num_heads"], arch["num_kv_heads"],
            arch["head_dim"], arch["sliding_window"]
            if kind == _afmoe.counts().SLIDING else None)
        least += flops.roofline_seconds(work["fwd"], peaks)[0] \
            + flops.roofline_seconds(work["bwd"], peaks)[0]
    return 100.0 * least * traced["steps"] / spent
