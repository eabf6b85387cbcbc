"""Runner kind ``train``: one training job on the cell's chips.

Drives ``Harness.train_step`` fed by ``Harness.train_loader`` as built by
``tpuframe.train.build_harness``: the objects ``tpuframe.train.train``
itself loops over.  The job (optimizer, schedule, batch, data set, mesh)
is the traffic file's; the model is the configuration file's.  Weights are
the benchmark's own, made from ``--seed`` by the configuration's reference
module on the device in one jitted call, and put in place of the
program's.

Set-up builds one harness, drives it through its first steps (through the
window's own call and feed) and hands the same object to the window.  Of
those steps it keeps the batches, each loss, the optimizer's view of the
first gradient and the parameters' change after the last of them, which
``check`` compares with the plain reference once the window has closed
and the program's state is freed.
"""

from __future__ import annotations

import collections
import dataclasses
import gc
import math
import statistics
import time

import numpy as np

REFERENCE_STEPS = 3
WARMUP_STEPS = 3      # after the reference's steps, before the window
RUN_AHEAD = 2         # steps the host may be ahead of the device
UNTRACED_SHARE = 0.7  # of --seconds, in a traced run, before the profiler
TRACED_STEPS = 4


def _paths_and_leaves(tree):
    import jax

    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return ["/".join(str(getattr(k, "key", k)) for k in path)
            for path, _ in flat], [leaf for _, leaf in flat]


def _leaf_norms(tree) -> np.ndarray:
    """Per-leaf L2 norms, float64 on the host, in flattening order."""
    import jax
    import jax.numpy as jnp

    fn = jax.jit(lambda t: [jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))) for x in jax.tree.leaves(t)])
    return np.asarray([float(x) for x in fn(tree)], np.float64)


def _delta_norms(after, before) -> np.ndarray:
    import jax
    import jax.numpy as jnp

    fn = jax.jit(lambda a, b: [jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32) - y.astype(jnp.float32))))
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))])
    return np.asarray([float(x) for x in fn(after, before)], np.float64)


def first_gradient(opt_state, params, optimizer: str, adam_b1: float):
    """The first gradient as the optimizer got it, from its state after
    one step: SGD's momentum buffer, or Adam's first moment over
    ``1 - b1`` (``adam_b1`` is the reference's own ``B1``)."""
    import jax

    want = jax.tree.structure(params)
    same = lambda x: jax.tree.structure(x) == want  # noqa: E731
    found = [x for x in jax.tree.leaves(opt_state, is_leaf=same) if same(x)]
    if not found:
        raise RuntimeError("no parameter-shaped moment in the optimizer "
                           "state")
    if optimizer == "sgd":
        return found[0]
    if optimizer == "adamw":
        return jax.tree.map(lambda m: m / (1.0 - adam_b1), found[0])
    raise ValueError(f"no rule to read the first gradient of {optimizer!r}")


def leaf_gaps(prog: np.ndarray, ref: np.ndarray) -> np.ndarray:
    """Per leaf, the gap between the program's and the reference's norm,
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger."""
    return np.abs(prog - ref) / np.maximum(ref, float(np.median(ref)))


def gap_report(observed: dict, ref: dict, names: list[str]) -> str:
    """The three widest leaves and the quartiles of both comparisons, for
    the log: what a look at a limit starts from."""
    out = []
    for key in ("grad_norms", "delta_norms"):
        g = leaf_gaps(observed[key], ref[key])
        top = ", ".join(f"{names[i]}={g[i]:.4f}"
                        for i in np.argsort(-g)[:3])
        q = np.quantile(g, [0.5, 0.9])
        out.append(f"{key}: median {q[0]:.5f} p90 {q[1]:.5f} widest {top}")
    return "; ".join(out)


def compare(observed: dict, ref: dict, limits: dict) -> dict:
    """``{name: {"value", "limit"}}`` for every number this runner
    compares that the traffic file gives a limit."""
    out = {}
    for i, (a, b) in enumerate(zip(observed["losses"], ref["losses"])):
        out[f"loss_step{i + 1}_rel"] = abs(a - b) / max(abs(b), 1e-30)
    grad = leaf_gaps(observed["grad_norms"], ref["grad_norms"])
    delta = leaf_gaps(observed["delta_norms"], ref["delta_norms"])
    # A leaf whose gradient is nought to rounding in the reference moves
    # by round-off alone: out of the change's comparison by a rule on the
    # reference's gradient, not by name.
    moved = ref["grad_norms"] >= 1e-3 * float(np.median(ref["grad_norms"]))
    out["grad1_norm_gap"] = float(np.max(grad))
    out["delta3_norm_gap"] = float(np.max(delta[moved]))
    out["grad1_norm_gap_median"] = float(np.median(grad))
    out["delta3_norm_gap_median"] = float(np.median(delta[moved]))
    return {k: {"value": (v if math.isfinite(v) else 1e30),
                "limit": limits[k]} for k, v in out.items() if k in limits}


def reference_readings(ref_mod, arch, job, weights, batches, **kw) -> dict:
    out = ref_mod.train_steps(arch, job, weights["params"], batches, **kw)
    return {"losses": out["losses"],
            "grad_norms": _leaf_norms(out["opt_grad"]),
            "delta_norms": _leaf_norms(out["delta"])}


class Cell:
    def __init__(self, ctx):
        self.ctx = ctx
        self.job = ctx.traffic["job"]
        self.arch = ctx.config["arch"]
        self.h = self.state = self.it = None
        self.observed: dict = {}
        self.batches: list = []
        self.steps_in_window = 0
        self.last_loss = None

    # -- set-up ------------------------------------------------------------

    def _train_config(self):
        from tpuframe.parallel.mesh import MeshSpec
        from tpuframe.utils.config import TrainConfig

        prog, t = self.ctx.config["program"], self.ctx.traffic
        fields = dict(prog)
        fields.update(self.job)
        fields.update(t.get("program_fields", {}))
        fields["mesh"] = MeshSpec(**t.get("mesh", {}))
        fields["seed"] = int(self.ctx.seed) % (2 ** 31 - 1)
        return TrainConfig(**fields)

    def setup(self) -> None:
        import jax

        from tpuframe.train import build_harness

        ctx = self.ctx
        cfg = self._train_config()
        ctx.log("program imported")
        self.h = h = build_harness(cfg)
        ctx.log(f"harness built: mesh "
                f"{dict(h.mesh.shape) if h.mesh is not None else None}")
        weights = ctx.reference.init_weights(self.arch, ctx.seed)
        names_w, leaves_w = _paths_and_leaves(weights["params"])
        names_p, leaves_p = _paths_and_leaves(h.state.params)
        if names_w != names_p or any(
                a.shape != b.shape for a, b in zip(leaves_w, leaves_p)):
            raise RuntimeError("the reference's parameters are not the "
                               "program's: " + str(
                                   set(names_w) ^ set(names_p) or "shapes"))
        place = lambda w, old: jax.device_put(  # noqa: E731
            w.astype(old.dtype), old.sharding)
        state = dataclasses.replace(
            h.state, params=jax.tree.map(place, weights["params"],
                                         h.state.params))
        if jax.tree.leaves(h.state.model_state):
            state = dataclasses.replace(state, model_state=jax.tree.map(
                place, weights["model_state"], h.state.model_state))
        del weights
        ctx.log("weights from the seed in place")
        self.state = state
        self.it = iter(h.train_loader)
        self.leaf_names = names_p

        losses = []
        for i in range(REFERENCE_STEPS):
            batch = next(self.it)
            self.batches.append({k: np.asarray(v) for k, v in batch.items()})
            self.state, metrics = h.train_step(self.state, batch)
            losses.append(float(metrics["loss"]))
            ctx.log(f"step {i + 1} done")
            if i == 0:
                grad_norms = _leaf_norms(first_gradient(
                    self.state.opt_state, self.state.params,
                    self.job["optimizer"],
                    getattr(ctx.reference, "B1", None)))
        start = ctx.reference.init_weights(self.arch, ctx.seed)["params"]
        self.observed = {"losses": losses, "grad_norms": grad_norms,
                         "delta_norms": _delta_norms(self.state.params,
                                                     start)}
        del start
        ctx.log(f"first {REFERENCE_STEPS} losses {losses}")
        for _ in range(WARMUP_STEPS):
            self.state, metrics = h.train_step(self.state, next(self.it))
        float(metrics["loss"])

    # -- the window ---------------------------------------------------------

    def _drive(self, until) -> dict:
        """Steps until ``until(elapsed, steps)`` says stop; the host runs
        at most ``RUN_AHEAD`` steps ahead of the device."""
        import jax

        spans, step = self.ctx.spans, self.h.train_step
        pending: collections.deque = collections.deque()
        mark = spans.mark()
        t0 = time.monotonic()
        steps = 0
        while True:
            with spans.span("data_wait"):
                batch = next(self.it)
            with spans.span("dispatch"):
                self.state, metrics = step(self.state, batch)
            pending.append(metrics["loss"])
            steps += 1
            if len(pending) > RUN_AHEAD:
                with spans.span("device_wait"):
                    pending.popleft().block_until_ready()
            if until(time.monotonic() - t0, steps):
                break
        with spans.span("device_wait"):
            jax.block_until_ready(self.state)
        t1 = time.monotonic()
        self.last_loss = float(pending[-1])
        return {"t0": t0, "t1": t1, "wall_s": t1 - t0, "steps": steps,
                "spans": spans.since(mark)}

    def measure(self) -> dict:
        ctx = self.ctx
        batch = int(self.job["global_batch"])
        seconds = float(ctx.seconds)
        if ctx.trace:
            seconds *= UNTRACED_SHARE
        part = self._drive(lambda el, n: el >= seconds)
        self.steps_in_window = part["steps"]
        rate = part["steps"] * batch / part["wall_s"] / ctx.chips
        window = {"kind": "train", "wall_s": part["wall_s"],
                  "steps": part["steps"], "examples_per_step": batch,
                  "chips": ctx.chips, "spans": part["spans"],
                  "examples_per_s_per_chip": rate,
                  "end_to_end": {ctx.traffic["rate_metric"]: rate}}
        if ctx.trace:
            ctx.tracer.start()
            # the profiler's start-up stalls the first traced step
            self._drive(lambda el, k: k >= 2)
            with ctx.spans.span("traced"):
                traced = self._drive(lambda el, k: k >= TRACED_STEPS)
            ctx.tracer.stop()
            window["traced"] = {"steps": traced["steps"],
                                "wall_s": traced["wall_s"]}
        return window

    def release(self) -> None:
        import jax

        if self.h is not None:
            self.h.train_loader.close()
            self.h.eval_loader.close()
        self.it = self.state = self.h = None
        gc.collect()
        jax.clear_caches()

    # -- correct -----------------------------------------------------------

    def check(self) -> dict:
        import jax.numpy as jnp

        ctx = self.ctx
        t0 = time.monotonic()
        weights = ctx.reference.init_weights(self.arch, ctx.seed)
        batches = [{k: jnp.asarray(v) for k, v in b.items()}
                   for b in self.batches]
        ref = reference_readings(ctx.reference, self.arch, self.job,
                                 weights, batches)
        compared = compare(self.observed, ref, ctx.traffic["limits"])
        ctx.log(gap_report(self.observed, ref, self.leaf_names))
        finite = self.last_loss is not None and math.isfinite(self.last_loss)
        ctx.log(f"reference followed {REFERENCE_STEPS} steps in "
                f"{time.monotonic() - t0:.2f} s: losses {ref['losses']} "
                f"(median leaf gradient norm "
                f"{statistics.median(ref['grad_norms']):.3e})")
        ok = finite and all(v["value"] <= v["limit"]
                            for v in compared.values())
        return {"correct": ok, "attempted": self.steps_in_window,
                "failed": 0 if finite else self.steps_in_window,
                "compared": compared}
