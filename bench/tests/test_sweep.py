"""The sweep system (`bench/systems/m4_sweep.py`) and its readers, driven
through the harness on the CPU at tiny sizes.

A tiny sweep cell is added to a copy of the checkout as files only: three
Table-2 points on the 32-host fabric that differ in CC scheme, spines,
flow count and link degree, at small widths. Its limit is set as the
paper cells' are, from readings at its own size (CPU, seeds 1-16): the
worst lane of the program read at most 3.0e-8, of the control at least
9.6e-8.
"""
import dataclasses
import json
import os
import shutil
from collections import Counter

import numpy as np
import pytest

from bench import run, traffic
from bench.systems import m4_sweep
from test_layers import ctx_of, write, xspace

TINY_SWEEP = {"system": "m4_sweep", "source": "test", "batch": 3,
              "model": {"hidden": 16, "gnn_dim": 12, "mlp_hidden": 8,
                        "gnn_layers": 2, "snap_flows": 64, "snap_links": 128,
                        "max_path": 8, "cfg_dim": 9, "dense_sldn": True},
              "correct": {"precision": "highest", "control": "high",
                          "limits": {"fct_gap_worst": 5e-8,
                                     "unfinished": 0}}}


def _point(spines, cc, dist, sigma, load, matrix, n):
    return {"racks": 8, "hosts_per_rack": 4, "spines": spines,
            "link_gbps": 10.0, "prop_delay_s": 1e-6, "net": {"cc": cc},
            "size_dist": dist, "theta": 2e4, "sigma": sigma,
            "max_load": load, "matrix": matrix, "num_flows": n}


TINY_B3 = {"base_seed": 3, "scenarios": [
    _point(1, "dcqcn", "gaussian", 2.0, 0.7, "C", 120),
    _point(2, "dctcp", "pareto", 1.0, 0.5, "A", 80),
    _point(4, "timely", "lognormal", 1.0, 0.6, "B", 100)]}
SWEEP_READERS = ("scan_us_per_event.sweep", "departure_us_per_event.sweep",
                 "snapshot_us_per_event.sweep", "temporal_us_per_event.sweep",
                 "spatial_us_per_event.sweep", "heads_us_per_event.sweep",
                 "scatter_us_per_event.sweep", "host_build_ms.sweep",
                 "k_pad_share.sweep", "fused_gru_roofline.sweep",
                 "bipartite_round_roofline.sweep", "mfu.m4.sweep",
                 "device_idle.sweep")


@pytest.fixture(scope="module")
def sweep_checkout(checkout, tmp_path_factory):
    """The tiny checkout with a sweep cell added as files only; the sweep
    readers and the test metric of traced events read it too."""
    root = tmp_path_factory.mktemp("sweep") / "checkout"
    shutil.copytree(checkout, root, symlinks=True)
    (root / "bench/configs/m4-tiny-sweep.json").write_text(
        json.dumps(TINY_SWEEP))
    (root / "bench/traffic/tiny-b3.json").write_text(json.dumps(TINY_B3))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "m4-tiny-sweep", "source": "test",
                             "file": "bench/configs/m4-tiny-sweep.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "m4.sweep.tiny",
                               "config": "m4-tiny-sweep",
                               "traffic": "tiny-b3", "chips": 1,
                               "why": "test"})
    for m in bench["per_layer"]:
        if m["name"] in SWEEP_READERS + ("traced_events",):
            m["workloads"].append("m4.sweep.tiny")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(root)


def result(root, capsys, trace=0, seed=4000000001):
    import jax
    jax.clear_caches()            # a patched program must be traced anew
    capsys.readouterr()
    rc = run.main(["--workload", "m4.sweep.tiny", "--seed", str(seed),
                   "--seconds", "0.5", "--trace", str(trace)],
                  root=root, require_tpu=False)
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    return json.loads(out[-1])


def test_paper_sweep_points_are_the_table2_draw():
    """The traffic file's 8 points, generated by the benchmark's own
    generator, give the link degrees the configuration states; their
    structure is the same on every run seed."""
    mix = traffic.load_mix(run.ROOT, "table2-b8")
    config = json.load(open(os.path.join(run.ROOT, "bench", "configs",
                                         "m4-table2-sweep.json")))
    assert len(mix["scenarios"]) == config["batch"] == 8
    degrees = [[max(Counter(l for p in s.paths for l in p).values())
                for s in m4_sweep.scenarios(mix, seed)]
               for seed in (1, 3150000001)]
    assert degrees[0] == degrees[1] == [167, 95, 270, 162, 262, 168, 94,
                                        307]
    assert sorted(p["net"]["cc"] for p in mix["scenarios"]) == (
        ["dcqcn"] * 4 + ["dctcp"] * 2 + ["timely"] * 2)


def test_sweep_cell_is_correct_with_all_its_events(sweep_checkout, capsys):
    r = result(sweep_checkout, capsys, trace=1)
    assert r["correct"] is True, r["checks"]
    assert r["failed"] == 0
    assert r["metrics"]["traced_events"]["value"] == 2.0 * (120 + 80 + 100)
    checks = r["checks"]
    assert set(checks) == {"fct_gap_worst", "unfinished",
                           "compiles_in_window"}
    # the gauge reader reads the padding of the three points' link degrees
    k = [max(Counter(l for p in s.paths for l in p).values())
         for s in m4_sweep.scenarios(TINY_B3, 4000000001)]
    assert r["metrics"]["k_pad_share.sweep"]["value"] == pytest.approx(
        100.0 * (1 - sum(k) / (3 * max(k))))
    assert 0 < r["metrics"]["host_build_ms.sweep"]["value"] < 1e4
    # the CPU has no TPU plane: the scope, scan and kernel readers and the
    # shares of a peak read nothing there
    assert not set(r["metrics"]) & {"snapshot_us_per_event.sweep",
                                    "scan_us_per_event.sweep",
                                    "fused_gru_roofline.sweep",
                                    "mfu.m4.sweep"}


def _one_lane_altered(monkeypatch):
    from repro.sim import backends
    run_many = backends.M4Backend.run_many

    def altered(self, requests, **kw):
        res = run_many(self, requests, **kw)
        fcts = np.array(res[1].fcts)
        fcts[len(fcts) // 2] *= 1.01
        res[1] = dataclasses.replace(res[1], fcts=fcts)
        return res
    monkeypatch.setattr(backends.M4Backend, "run_many", altered)


def _control_in_place(monkeypatch):
    load = run.load_module

    def with_control(path, name):
        mod = load(path, name)
        if hasattr(mod, "Cell"):
            monkeypatch.setattr(mod.Cell, "call", lambda self: self.reference(
                self.config["correct"]["control"]))
        return mod
    monkeypatch.setattr(run, "load_module", with_control)


def _step_unchanged(monkeypatch):
    from repro.core import simulate
    monkeypatch.setattr(simulate, "make_event_step",
                        lambda *a, **k: lambda params, state, *e:
                        (state, None, None))


FAULTS = {"one_lane_altered": (_one_lane_altered, "fct_gap_worst"),
          "control_in_place": (_control_in_place, "fct_gap_worst"),
          "step_unchanged": (_step_unchanged, "unfinished")}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_sweep_fault_is_not_correct(sweep_checkout, capsys, monkeypatch,
                                    fault):
    plant, number = FAULTS[fault]
    plant(monkeypatch)
    r = result(sweep_checkout, capsys)
    assert r["correct"] is False, r["checks"]
    assert r["checks"][number]["value"] > r["checks"][number]["limit"]


def test_sweep_runs_on_a_program_without_devices(sweep_checkout, capsys,
                                                 monkeypatch):
    """A program whose `run_many` takes no `devices` (the parent of the
    argument) runs the cell all the same."""
    from repro.sim import backends
    run_many = backends.M4Backend.run_many
    monkeypatch.setattr(backends.M4Backend, "run_many",
                        lambda self, requests: run_many(self, requests))
    r = result(sweep_checkout, capsys)
    assert r["correct"] is True, r["checks"]


def _readers(root=run.ROOT):
    return {n: run.load_module(os.path.join(root, "bench", "metrics",
                                            n + ".py"), "m_" + n)
            for n in SWEEP_READERS}


def test_sweep_readers_give_none_where_nothing_is_recorded(tmp_path,
                                                           monkeypatch):
    """No scope, no `m4.build` span, no batch gauge, no matching kernel
    calls, no peak: every sweep reader but the idle share reads nothing."""
    from repro import obs
    monkeypatch.setattr(obs, "get_registry", lambda: obs.MetricsRegistry())
    path = write(tmp_path, monkeypatch,
                 xspace(scoped=False, host=[("bench.run", 0.0, 35.0)]))
    ctx = dict(ctx_of(path, events=6), config=TINY_SWEEP, peak=None,
               window_s=35e-6, busy_s=30e-6)
    got = {n: r.read(ctx) for n, r in _readers().items()}
    assert got.pop("device_idle.sweep") == pytest.approx(100 * 5 / 35)
    assert got.pop("scan_us_per_event.sweep") == pytest.approx(31 / 6)
    assert set(got.values()) == {None}


def test_sweep_scope_readers_divide_by_the_batch(tmp_path, monkeypatch):
    """Each recorded iteration of the vmapped body advances every lane:
    a scope's time per lane-event is its time per iteration over the
    batch."""
    ctx = dict(ctx_of(write(tmp_path, monkeypatch, xspace()), events=9),
               config=TINY_SWEEP)
    readers = _readers()
    assert readers["snapshot_us_per_event.sweep"].read(ctx) == (
        pytest.approx(2.0 / 3))
    assert readers["temporal_us_per_event.sweep"].read(ctx) == (
        pytest.approx(3.0 / 3))
    assert readers["host_build_ms.sweep"].read(ctx) == pytest.approx(0.5e-3)
