"""Which implementation each Pallas-backed op resolved to, and why.

An op with a kernel has up to three implementations: the Mosaic kernel
(``mosaic``), the same kernel under the Pallas interpreter
(``interpret``), and the plain-XLA composition it replaces (``xla``).
The choice is made from the backend and the shapes at trace time, so a
step program can hold any of the three without its config saying so.
Every dispatch point reports its choice here; the first time an
``(op, impl, why)`` is seen in a run it is printed and written to the
run-event log as a ``kernel_impl`` record.  A caller that must hold a run
to its choice reads those records (``chip_smoke.py`` fails a phase that
asked for a kernel and got anything but ``mosaic``).
"""

from __future__ import annotations

import os

_resolved: dict[str, dict[str, str]] = {}


def interpret_env() -> bool | None:
    """What ``TPUFRAME_PALLAS_INTERPRET`` says, or None when it is unset."""
    env = os.environ.get("TPUFRAME_PALLAS_INTERPRET")
    return None if env is None else env == "1"


def interpret_default() -> tuple[bool, str]:
    """``(interpret, why)`` for a kernel whose caller did not say.

    ``TPUFRAME_PALLAS_INTERPRET`` overrides the backend check: compiling
    FOR a described TPU FROM a CPU host must lower Mosaic, where the
    backend alone would pick the interpreter."""
    env = interpret_env()
    if env is not None:
        return env, f"TPUFRAME_PALLAS_INTERPRET={int(env)}"
    import jax

    backend = jax.default_backend()
    return backend != "tpu", f"backend={backend}"


def no_mosaic() -> str | None:
    """``backend=<name>`` where there is no Mosaic and nobody asked for the
    interpreter, else None.  For an op whose XLA composition is that
    backend's faster program, and the one the partitioner can split over
    sharded slots: it runs the composition there and records this reason."""
    if interpret_env() is not None:
        return None
    import jax

    backend = jax.default_backend()
    return None if backend == "tpu" else f"backend={backend}"


def resolve_interpret(op: str, interpret: bool | None,
                      detail: str = "") -> bool:
    """Whether ``op``'s kernel runs under the interpreter: the caller's
    explicit choice, else :func:`interpret_default`; recorded either way,
    with ``detail`` (what the kernel chose for itself: its tiling and
    grid) after the reason."""
    why = "explicit"
    if interpret is None:
        interpret, why = interpret_default()
    if detail:
        why = f"{why}; {detail}"
    record(op, "interpret" if interpret else "mosaic", why)
    return interpret


def record(op: str, impl: str, why: str) -> None:
    """Note that ``op`` resolved to ``impl`` (mosaic | interpret | xla).
    Called at trace time, so once per compiled program, not per step."""
    seen = _resolved.setdefault(op, {})
    if seen.get(impl) == why:
        return
    seen[impl] = why
    print(f"[tpuframe] kernel {op} -> {impl} ({why})", flush=True)
    from tpuframe.obs import events

    events.emit("kernel_impl", op=op, impl=impl, why=why)


def reset() -> None:
    """Forget what was recorded — the start of a run (``train()`` calls
    it), so each run logs its own resolutions once."""
    _resolved.clear()
