"""Regenerate the golden optimized-HLO fixtures for the shardflow tests.

Usage (from the repo root — the same scrubbed CPU child env the gate
uses):

    JAX_PLATFORMS=cpu \
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python tests/fixtures/regen_hlo.py

Writes, per *compilable* registered strategy:

    tests/fixtures/hlo/<name>.hlo.gz     optimized-HLO module text
    tests/fixtures/hlo/goldens.json      parsed-graph shape pins + meta

The fixtures let ``tests/test_shardflow.py`` exercise the whole parser +
detector stack without compiling anything (no jax import at test time),
and the goldens pin the graph *shape* (computation/node/parameter/
collective counts) so a parser regression that silently drops nodes
fails loudly.  Regenerate on a jax upgrade; the goldens record the jax
version so the pin test skips rather than lies when the compiler moved.
"""

import gzip
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
OUT = os.path.join(REPO, "tests", "fixtures", "hlo")


def main() -> int:
    if os.environ.get("JAX_PLATFORMS") != "cpu":
        print("set JAX_PLATFORMS=cpu (and the 8-device XLA_FLAGS) first",
              file=sys.stderr)
        return 2
    sys.path.insert(0, REPO)
    import jax

    from tpuframe.analysis import shardflow, strategies
    from tpuframe.analysis.collective_graph import graph_of_compiled

    os.makedirs(OUT, exist_ok=True)
    goldens = {"jax": jax.__version__, "n_devices": 8, "strategies": {}}
    for audit in strategies.audit_all(8):
        if audit.compiled is None:
            print(f"skip {audit.name}: {audit.reason or audit.status}")
            continue
        txt = audit.compiled.as_text()
        graph = graph_of_compiled(audit.compiled)
        fname = f"{audit.name}.hlo.gz"
        with gzip.open(os.path.join(OUT, fname), "wt",
                       compresslevel=9) as f:
            f.write(txt)
        goldens["strategies"][audit.name] = {
            "file": fname,
            "summary": graph.summary(),
            "mesh_shape": list(list(p) for p in audit.meta.mesh_shape),
            "wire_dtype": audit.meta.wire_dtype,
            "n_declared_leaves": len(audit.meta.declared_leaves),
            # analysis v3: the integer schedule/liveness record — must
            # stay byte-identical to the strategy's derived_schedule.json
            # entry (tests cross-check the two files against each other).
            "schedule": shardflow.derive_schedule_entry(
                graph, ignore_below=audit.budget.ignore_below),
        }
        print(f"wrote {fname}: {graph.summary()}")
    with open(os.path.join(OUT, "goldens.json"), "w") as f:
        json.dump(goldens, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"wrote goldens.json ({len(goldens['strategies'])} strategies)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
