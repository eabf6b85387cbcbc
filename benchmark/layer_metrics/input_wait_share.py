"""Share of the window's wall time that the host loop waited in
``next(data_iter)`` (the runner's ``data_wait`` span), in %."""


def read(run: dict):
    w = run["window"]
    spans = w.get("spans", {}).get("data_wait")
    if w.get("kind") != "train" or not spans or not w.get("wall_s"):
        return None
    return 100.0 * sum(b - a for a, b in spans) / w["wall_s"]
