"""Adding ``lm124m.train_b32_s2048_dp4`` needs new files and new entries
only: shown at toy size on four virtual CPU devices.  The test writes a
traffic file and a manifest that adds one cell (``chips: 4``, ``mesh
{"data": 4}``) and runs it with the code as it is."""

import json
import os
import subprocess
import sys

from conftest import BENCH, ROOT, TOY


def test_dp4_cell_from_data_files_alone(tmp_path):
    with open(os.path.join(BENCH, "tests", "toy", "traffic",
                           "toy_train_b4_s128.json")) as f:
        traffic = json.load(f)
    traffic["job"]["global_batch"] = 8
    traffic["mesh"] = {"data": 4}
    (tmp_path / "traffic").mkdir()
    with open(tmp_path / "traffic" / "toy_train_b8_s128_dp4.json", "w") as f:
        json.dump(traffic, f)
    with open(TOY) as f:
        manifest = json.load(f)
    name = "lm_toy.toy_train_b8_s128_dp4"
    manifest["paths"] = [str(tmp_path)] + manifest["paths"]
    manifest["workloads"].append({
        "name": name, "config": "lm_toy",
        "traffic": "toy_train_b8_s128_dp4", "chips": 4,
        "why": "rehearsal of the dp4 cell: gradient all-reduce over data=4"})
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if "lm_toy.toy_train_b4_s128" in m.get("workloads", []):
            m["workloads"].append(name)
    path = tmp_path / "BENCHMARK.json"
    with open(path, "w") as f:
        json.dump(manifest, f)
    code = (
        "import sys, json; sys.path.insert(0, %r); import run\n"
        "rc = run.main(['--workload', %r, '--seed', '9', '--seconds', '1',"
        " '--trace', '0'], require_chip=False, manifest_path=%r)\n"
        "sys.exit(rc)" % (BENCH, name, str(path)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    result = json.loads(p.stdout.strip().splitlines()[-1])
    assert result["correct"] is True, result["compared"]
    assert result["device"]["count"] == 4
    assert "'data': 4" in p.stderr
    assert result["metrics"]["train_seq_per_s_per_chip"]["value"] > 0
