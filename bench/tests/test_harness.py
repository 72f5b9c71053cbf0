"""The harness, driven on the CPU at tiny sizes in a scratch checkout.

A configuration, a traffic mix and a per-layer metric are added to the
copy as files only, with their entries in its `BENCHMARK.json`; the
harness finds them by name. The same cell then runs with the timed path
broken underneath (the look for a chip skipped), and `correct` has to
come out false for each fault such a cell can have.
"""
import dataclasses
import json
import os
import shutil

import numpy as np
import pytest

from bench import run

ROOT = run.ROOT


def result(root, capsys, workload, trace=0, seed=5):
    import jax
    jax.clear_caches()            # a patched program must be traced anew
    capsys.readouterr()
    rc = run.main(["--workload", workload, "--seed", str(seed),
                   "--seconds", "0.5", "--trace", str(trace)],
                  root=root, require_tpu=False)
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    return json.loads(out[-1])


def test_cell_added_as_files(checkout, capsys):
    r = result(checkout, capsys, "m4.tiny")
    assert r["correct"] is True, r["checks"]
    assert list(r)[-1] == "checks"
    assert set(r["metrics"]) == {"events_per_s", "setup_s"}
    assert r["metrics"]["events_per_s"]["value"] > 0
    assert r["checks"]["compiles_in_window"]["value"] == 0
    assert r["device"]["platform"] == "cpu"


def test_added_metric_in_traced_run(checkout, capsys):
    r = result(checkout, capsys, "m4.tiny", trace=1)
    assert r["correct"] is True
    assert r["metrics"]["traced_events"] == {"value": 400.0, "unit": "events"}
    assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}
    assert r["device"]["window_s"] > 0


def test_no_tpu_no_result(checkout, capsys):
    rc = run.main(["--workload", "m4.tiny", "--seed", "1", "--seconds", "1"],
                  root=checkout)
    assert rc != 0
    assert capsys.readouterr().out == ""


def test_outside_a_checkout_no_result(tmp_path, capsys):
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    rc = run.main(["--workload", "m4.single.ft32x16x8-n4096", "--seed", "1",
                   "--seconds", "1"], root=str(tmp_path), require_tpu=False)
    assert rc != 0
    assert capsys.readouterr().out == ""


# ------------------------------------------------------------------ faults
def _m4_step_unchanged(monkeypatch):
    from repro.core import simulate
    monkeypatch.setattr(simulate, "make_event_step",
                        lambda *a, **k: lambda params, state, *e:
                        (state, None, None))


def _answer_altered(monkeypatch):
    from repro.sim import backends
    run_one = backends.M4Backend.run

    def altered(self, request):
        res = run_one(self, request)
        fcts = np.array(res.fcts)
        fcts[len(fcts) // 2] *= 1.01
        return dataclasses.replace(res, fcts=fcts)
    monkeypatch.setattr(backends.M4Backend, "run", altered)


def _control_in_place(monkeypatch):
    """The reference one precision lower in the program's place."""
    load = run.load_module

    def with_control(path, name):
        mod = load(path, name)
        if hasattr(mod, "Cell"):
            monkeypatch.setattr(mod.Cell, "call", lambda self: self.reference(
                self.config["correct"]["control"]))
        return mod
    monkeypatch.setattr(run, "load_module", with_control)


# each fault, and the number compared that it has to fail
FAULTS = {"step_unchanged": (_m4_step_unchanged, "unfinished"),
          "answer_altered": (_answer_altered, "fct_gap_mean"),
          "control_in_place": (_control_in_place, "fct_gap_mean")}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_fault_is_not_correct(checkout, capsys, monkeypatch, fault):
    plant, number = FAULTS[fault]
    plant(monkeypatch)
    r = result(checkout, capsys, "m4.tiny")
    assert r["correct"] is False, r["checks"]
    assert r["checks"][number]["value"] > r["checks"][number]["limit"]
