"""The serving step's share of the chip's bf16 peak, in %: 2 * N_matmul
operations for every prompt token prefilled and every output token made in
the measured part of the window, per second, over the peak for the device
kind.  Padding to a bucket and idle slots do no useful work and are not
counted."""


def read(run: dict):
    w, peaks = run["window"], run["peaks"]
    if w.get("kind") != "serve" or peaks is None or not w.get("wall_s"):
        return None
    work = run["flops"].lm_serve_flops(
        run["config"]["arch"], w["prompt_tokens_in_window"],
        w["tokens_in_window"])
    return 100.0 * work / w["wall_s"] / w["chips"] / peaks["bf16_flops_per_s"]
