"""The benchmark's own tests run on the CPU: `python -m pytest bench/tests`
from the root of a checkout."""
import json
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)


# ------------------------------------------------ a checkout with tiny cells
# m4 at small widths on a small fabric. Its limit is set as the paper
# configuration's is, from readings at its own size (CPU, seeds 1-16):
# the program read at most 8.1e-9, the control at least 5.2e-8 on all
# seeds but 7, where three bfloat16 passes moved no FCT at these widths.
TINY_M4 = {"system": "m4", "source": "test",
           "model": {"hidden": 16, "gnn_dim": 12, "mlp_hidden": 8,
                     "gnn_layers": 2, "snap_flows": 64, "snap_links": 128,
                     "max_path": 8, "cfg_dim": 9, "dense_sldn": True},
           "correct": {"precision": "highest", "control": "high",
                       "limits": {"fct_gap_mean": 2e-8, "unfinished": 0}}}
TINY_ONE = {"base_seed": 3, "scenario": {
    "racks": 8, "hosts_per_rack": 4, "spines": 2, "link_gbps": 10.0,
    "prop_delay_s": 1e-6, "net": {"cc": "dctcp"}, "size_dist": "Hadoop",
    "theta": 2e4, "sigma": 1.0, "max_load": 0.8, "matrix": "A",
    "num_flows": 200}}
METRIC = '''"""Events of the traced call (test metric)."""


def read(ctx):
    return float(ctx["events"])
'''


@pytest.fixture(scope="session")
def checkout(tmp_path_factory):
    """A copy of the benchmark with one cell added as files only."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    os.symlink(os.path.join(ROOT, "src"), root / "src")
    bench = json.loads(open(os.path.join(ROOT, "BENCHMARK.json")).read())
    (root / "bench/configs/m4-tiny.json").write_text(json.dumps(TINY_M4))
    (root / "bench/traffic/one.json").write_text(json.dumps(TINY_ONE))
    (root / "bench/metrics/traced_events.py").write_text(METRIC)
    bench["configs"].append({"name": "m4-tiny", "source": "test",
                             "file": "bench/configs/m4-tiny.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "m4.tiny", "config": "m4-tiny",
                               "traffic": "one", "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "traced_events", "unit": "events",
                               "better": "higher", "source": "device_trace",
                               "layer": "test", "moves": "events_per_s",
                               "workloads": ["m4.tiny"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(root)
