"""The whole step's share of the chip's bf16 peak, in %: the operations
the forward and backward passes need per example (``flops.py``, from
shapes, no recomputation) times examples per second per chip, over the
peak for the device kind.  From the untraced part of the window."""


def read(run: dict):
    w, peaks = run["window"], run["peaks"]
    if w.get("kind") != "train" or peaks is None:
        return None
    fn = getattr(run["flops"], run["config"]["train_flops_fn"])
    per_example = fn(run["config"]["arch"],
                     **run["traffic"].get("flops_args", {}))
    rate = w["examples_per_s_per_chip"]
    return 100.0 * per_example * rate / peaks["bf16_flops_per_s"]
