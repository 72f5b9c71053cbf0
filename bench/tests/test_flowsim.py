"""flowSim's cell: the run seed's relabelling, the plain reference and its
control, a tiny cell through the harness, and the readers of flowSim's
layers (`bench.flowsim_layers` and the `*.flowsim` metrics).

The small trace written here from a text XSpace has hand-worked answers.
Program 77 (`_event_scan`) runs one event every 20 us from 1 us; event
k's water-filling loop (a `while`, scope `flowsim.waterfill`) runs
`ROUNDS[k]` rounds of 4 us: link sums 2 us (`flowsim.linksum`), the
incidence copy 1 us (no name stack), the row-min 0.5 us
(`flowsim.rowmin`) and a reduction 0.5 us (`flowsim.waterfill` only);
then two `flowsim.advance` ops of 1 and 0.5 us. Program 88 holds a
`copy-done` too, at 70 us, which is not the scan's.
"""
import dataclasses
import gzip
import json
import os
import shutil

import numpy as np
import pytest

from bench import flowsim_layers, run, trace, traffic
from bench.systems import common, flowsim_fast as fs, flowsim_ref

ROOT = run.ROOT
CONFIG = json.load(open(os.path.join(ROOT, "bench", "configs",
                                     "flowsim-fast.json")))
TINY = {"base_seed": 3, "perm_seed": 4, "scenario": {
    "racks": 8, "hosts_per_rack": 4, "spines": 2, "link_gbps": 10.0,
    "prop_delay_s": 1e-6, "net": {"cc": "dctcp"}, "size_dist": "WebServer",
    "theta": 2e4, "sigma": 1.0, "max_load": 0.7, "matrix": "A",
    "num_flows": 300}}
FLOWSIM_METRICS = ("scan_us_per_event.flowsim",
                   "waterfill_us_per_event.flowsim",
                   "linksum_us_per_event.flowsim",
                   "incidence_copy_us_per_event.flowsim",
                   "rowmin_us_per_event.flowsim",
                   "waterfill_rounds_per_event.flowsim",
                   "device_idle.flowsim")


def base_scenario(mix=TINY):
    return traffic.scenario(mix, mix["perm_seed"])


def program_fcts(scen):
    from repro.core.flowsim_fast import run_flowsim_fast
    req = common.to_request(scen)
    return run_flowsim_fast(req.topo, list(req.flows)).fcts


# ------------------------------------------------------------ relabelling
def _check_paths(scen):
    R, h, S = scen.racks, scen.hosts_per_rack, scen.spines
    H = R * h
    for s, d, path in zip(scen.src, scen.dst, scen.paths):
        rs, rd = s // h, d // h
        if rs == rd:
            assert path == [s, H + d]
            continue
        up, down = path[1] - 2 * H, path[2] - 2 * H - R * S
        assert path[0] == s and path[3] == H + d
        assert up // S == rs and down // S == rd
        assert up % S == down % S                  # through one spine


@pytest.mark.parametrize("mix", ["tiny", "cell"])
@pytest.mark.parametrize("seed", [0, 7, 3180000105])
def test_relabel_is_a_fat_tree_automorphism(mix, seed):
    """Every relabelled path runs host -> ToR -> spine -> ToR -> host (or
    host -> ToR -> host within a rack) between the relabelled hosts; the
    maps are permutations; sizes and arrival times are the same multiset,
    each flow keeping its own."""
    m = TINY if mix == "tiny" else traffic.load_mix(
        ROOT, "single.ft32x16x8-n4096.relabel")
    base = base_scenario(m)
    _check_paths(base)
    host, links = fs.link_map(base.racks, base.hosts_per_rack, base.spines,
                              np.random.default_rng([seed, 20]))
    assert sorted(host) == list(range(base.num_hosts))
    assert sorted(links) == list(range(base.num_links))
    new = fs.relabel(base, np.random.default_rng([seed, 20]))
    _check_paths(new)
    assert sorted(new.t_arrival) == sorted(base.t_arrival)
    order = np.argsort(new.t_arrival, kind="stable")
    np.testing.assert_array_equal(new.size[order], base.size)
    assert [len(new.paths[i]) for i in order] == [len(p) for p in base.paths]
    np.testing.assert_array_equal(new.src[order], host[base.src])


def test_relabelled_fcts_equal_mapped_back():
    """The program's FCTs on a relabelled scenario are the unrelabelled
    scenario's, flow for flow, bit for bit: the seed moves rows and
    columns of the incidence, not the work."""
    base = base_scenario()
    want = program_fcts(base)
    for seed in (1, 2, 3):
        new = fs.relabel(base, np.random.default_rng([seed, 20]))
        got = program_fcts(new)
        order = np.argsort(new.t_arrival, kind="stable")
        np.testing.assert_array_equal(got[order], want)


def test_cell_seeds_share_one_trajectory():
    mix = traffic.load_mix(ROOT, "single.ft32x16x8-n4096.relabel")
    a = fs.Cell(CONFIG, mix, 11).scen
    b = fs.Cell(CONFIG, mix, 3180000105).scen
    assert a.num_flows == b.num_flows == 4096
    np.testing.assert_array_equal(np.sort(a.t_arrival), np.sort(b.t_arrival))
    np.testing.assert_array_equal(a.size[np.argsort(a.t_arrival)],
                                  b.size[np.argsort(b.t_arrival)])
    assert not np.array_equal(a.src, b.src)


# -------------------------------------------------- reference and control
@pytest.fixture(scope="module")
def readings():
    """The program, the reference and the control on two relabellings of
    the tiny scenario."""
    out = []
    for seed in (5, 6):
        scen = fs.relabel(base_scenario(), np.random.default_rng([seed, 20]))
        prog = program_fcts(scen)
        ref, stats = flowsim_ref.simulate(scen, "float32")
        low, _ = flowsim_ref.simulate(scen, "bfloat16")
        out.append((prog, ref, low, stats))
    return out


def _judge(fcts, ref):
    checks = {"fct_gap_mean": common.fct_gap_mean(fcts, ref),
              "unfinished": common.unfinished(fcts) + common.unfinished(ref),
              "compiles_in_window": 0}
    return run.judge(checks, CONFIG)


def test_reference_agrees_with_program(readings):
    for prog, ref, _, stats in readings:
        ok, checks = _judge(prog, ref)
        assert ok, checks
        assert stats["events"] == 2 * len(prog)
        assert 0 < checks["fct_gap_mean"]["value"]


def test_control_fails_the_limit(readings):
    for _, ref, low, _ in readings:
        ok, checks = _judge(low, ref)
        assert not ok
        gap = checks["fct_gap_mean"]
        assert gap["value"] > gap["limit"]
        assert checks["unfinished"]["value"] == 0


def test_reference_single_bottleneck():
    """Three flows share one host uplink from t=0; the reference's max-min
    FCTs are worked by hand: 1/3, then 1/2, then all of the link."""
    scen = base_scenario()
    n = 3
    bits = np.array([1e4, 2e4, 4e4])              # bytes
    one = traffic.Scenario(
        racks=scen.racks, hosts_per_rack=scen.hosts_per_rack,
        spines=scen.spines, link_gbps=10.0, prop_delay_s=1e-6, net=scen.net,
        src=np.zeros(n, np.int64), dst=np.ones(n, np.int64),
        size=bits.astype(np.int64), t_arrival=np.zeros(n),
        paths=[[0, scen.num_hosts + 1]] * n)
    fcts, stats = flowsim_ref.simulate(one, "float32")
    c = 10e9
    t1 = 3 * 8e4 / c
    t2 = t1 + 2 * 8e4 / c
    t3 = t2 + 8e4 * 2 / c
    np.testing.assert_allclose(fcts, [t1, t2, t3], rtol=1e-7)
    assert stats == {"events": 6, "rounds": 5, "rounds_max": 1}


# ------------------------------------------------- a tiny cell, end to end
@pytest.fixture(scope="module")
def fs_checkout(tmp_path_factory):
    """A copy of the benchmark with a tiny flowSim cell added as files."""
    root = tmp_path_factory.mktemp("fs_checkout")
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    os.symlink(os.path.join(ROOT, "src"), root / "src")
    bench = json.loads(open(os.path.join(ROOT, "BENCHMARK.json")).read())
    (root / "bench/traffic/fs-tiny.json").write_text(json.dumps(TINY))
    bench["workloads"].append({"name": "fs.tiny", "config": "flowsim-fast",
                               "traffic": "fs-tiny", "chips": 1,
                               "why": "test"})
    for m in bench["per_layer"]:
        if m["name"] in FLOWSIM_METRICS:
            m["workloads"].append("fs.tiny")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(root)


@pytest.mark.parametrize("traced", [0, 1])
def test_tiny_cell_prints_a_result(fs_checkout, capsys, traced):
    import jax
    jax.clear_caches()
    capsys.readouterr()
    rc = run.main(["--workload", "fs.tiny", "--seed", "3180000105",
                   "--seconds", "0.5", "--trace", str(traced)],
                  root=fs_checkout, require_tpu=False)
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    r = json.loads(out[-1])
    assert r["correct"] is True, r["checks"]
    assert list(r)[-1] == "checks"
    assert set(r["checks"]) == {"fct_gap_mean", "unfinished",
                                "compiles_in_window"}
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert r["device"]["platform"] == "cpu"
    if not traced:
        assert set(r["metrics"]) == {"events_per_s", "setup_s"}
        assert r["metrics"]["events_per_s"]["value"] > 0
        return
    # the CPU has no TPU plane: only the gauge and the idle share read
    metrics = r["metrics"]
    assert set(metrics) == {"waterfill_rounds_per_event.flowsim",
                            "device_idle.flowsim"}
    rounds = metrics["waterfill_rounds_per_event.flowsim"]
    assert rounds["unit"] == "rounds" and 1 < rounds["value"] < 10
    assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}


# ------------------------------------------------------- the layer readers
US = 1000000                # ps per us
SCAN = "jit(_event_scan)/while/body/closed_call/"
WF = SCAN + "flowsim.waterfill/while/body/"
ROUNDS = (2, 3, 1)
# metadata id: (HLO text, name stack or None)
META = {
    2: ("%while.3 = (f32[8]{0}) while((f32[8]{0}) %t), body=%b",
        SCAN + "flowsim.waterfill/while:"),
    3: ("%multiply_reduce_fusion.3 = (f32[4]{0}, f32[4]{0}) fusion(f32[8,4]"
        "{1,0} %a), kind=kLoop", WF + "flowsim.linksum/dot_general:"),
    4: ("%copy-done = f32[8,4]{1,0} copy-done((f32[8,4]{1,0}) %c)", None),
    5: ("%masked_rowmin_pallas.3 = f32[8,1]{1,0} custom-call(f32[8,4]{1,0} "
        "%c)", WF + "flowsim.rowmin/jit(masked_rowmin_pallas)/pallas_call:"),
    6: ("%reduce.32 = f32[] reduce(f32[8]{0} %s), dimensions={0}",
        WF + "reduce_min:"),
    7: ("%fusion.5 = f32[8]{0} fusion(f32[8]{0} %r), kind=kLoop",
        SCAN + "flowsim.advance/scatter:"),
    8: ("%argmin.1 = s32[] reduce(f32[8]{0} %t), dimensions={0}",
        SCAN + "flowsim.advance/argmin:"),
}
# us per event, over the three events' 6 rounds
EXPECT = {"flowsim.waterfill": 4 * 6 / 3, "flowsim.linksum": 2 * 6 / 3,
          "flowsim.rowmin": 0.5 * 6 / 3, "flowsim.advance": 1.5}
COPY = 1 * 6 / 3


def _stat(sid, value):
    kind = "uint64_value" if isinstance(value, int) else "str_value"
    return f"stats {{ metadata_id: {sid} {kind}: {json.dumps(value)} }}"


def flowsim_xspace(rounds=ROUNDS, scoped=True):
    ops = []
    for k, r in enumerate(rounds):
        t0 = 1 + 20 * k
        ops.append((2, t0, 4 * r))
        for j in range(r):
            t = t0 + 4 * j
            ops += [(3, t, 2), (4, t + 2, 1), (5, t + 3, 0.5),
                    (6, t + 3.5, 0.5)]
        ops += [(7, t0 + 4 * r, 1), (8, t0 + 4 * r + 1, 0.5)]
    ops.append((9, 70, 1))
    ops.sort(key=lambda o: o[1])
    ev = "\n".join(f"events {{ metadata_id: {m} offset_ps: {int(s * US)} "
                   f"duration_ps: {int(d * US)} }}" for m, s, d in ops)
    meta = dict(META)
    meta[9] = ("%copy-done = f32[4]{0} copy-done((f32[4]{0}) %x)", None)
    md = []
    for mid, (text, stack) in meta.items():
        stats = [_stat(35, 88 if mid == 9 else 77)]
        if stack and scoped:
            stats.append(_stat(26, stack))
        md.append(f"event_metadata {{ key: {mid} value {{ id: {mid} "
                  f"name: {json.dumps(text)} {' '.join(stats)} }} }}")
    md = "\n".join(md)
    return f"""
planes {{
  name: "/device:TPU:0"
  lines {{ name: "XLA Modules" timestamp_ns: 10000
    events {{ metadata_id: 1 offset_ps: 0 duration_ps: {62 * US} }}
    events {{ metadata_id: 11 offset_ps: {70 * US} duration_ps: {US} }} }}
  lines {{ name: "XLA Ops" timestamp_ns: 10000
{ev} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "jit__event_scan(77)" }} }}
  event_metadata {{ key: 11 value {{ id: 11 name: "jit_copy(88)" }} }}
{md}
  stat_metadata {{ key: 26 value {{ id: 26 name: "tf_op" }} }}
  stat_metadata {{ key: 35 value {{ id: 35 name: "program_id" }} }}
}}
planes {{
  name: "/host:CPU"
  lines {{ name: "python" timestamp_ns: 0
    events {{ metadata_id: 1 offset_ps: {10 * US} duration_ps: {75 * US} }} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "bench.run" }} }}
}}
"""


def write(tmp_path, monkeypatch, text=None, raw=None, name="bench-trace-f"):
    """The file where the harness traces: a `bench-trace-*` directory of
    the temporary directory."""
    from jax.profiler import ProfileData
    monkeypatch.setattr("tempfile.tempdir", str(tmp_path))
    d = tmp_path / name / "plugins" / "profile" / "1"
    d.mkdir(parents=True)
    path = d / "host.xplane.pb"
    path.write_bytes(raw if raw is not None else
                     ProfileData.text_proto_to_serialized_xspace(text))
    return str(path)


def read_metrics(ctx):
    return {n: run.load_module(os.path.join(ROOT, "bench", "metrics",
                                            n + ".py"),
                               "m_" + n.replace(".", "_")).read(ctx)
            for n in FLOWSIM_METRICS}


def ctx_of(path, events=3):
    t = trace.load(path)
    busy = trace.busy_s(t)
    return {"trace": t, "events": events, "busy_s": busy,
            "window_s": t.window_s}


@pytest.mark.parametrize("scope", sorted(EXPECT))
def test_scope_time_per_event(tmp_path, monkeypatch, scope):
    ctx = ctx_of(write(tmp_path, monkeypatch, flowsim_xspace()))
    got = flowsim_layers.scope_us_per_event(ctx, scope)
    assert got == pytest.approx(EXPECT[scope])


def test_copy_time_per_event(tmp_path, monkeypatch):
    # program 88's copy-done (1 us) is not the scan's
    ctx = ctx_of(write(tmp_path, monkeypatch, flowsim_xspace()))
    assert flowsim_layers.copy_us_per_event(ctx) == pytest.approx(COPY)


def test_time_per_event_with_events_unrecorded(tmp_path, monkeypatch):
    # the tracer left out the last of three events, each of two rounds:
    # the time per recorded event reads as it did in full
    ctx = ctx_of(write(tmp_path, monkeypatch, flowsim_xspace((2, 2, 2))))
    full = {s: flowsim_layers.scope_us_per_event(ctx, s) for s in EXPECT}
    t = ctx["trace"]
    ids, start, dur = t.ops[0]
    keep = start < t.window[0] + 41000.0
    part = dict(ctx, trace=dataclasses.replace(
        t, ops=[(ids[keep], start[keep], dur[keep])]))
    for scope, value in full.items():
        assert flowsim_layers.scope_us_per_event(part, scope) == \
            pytest.approx(value)
    assert full["flowsim.linksum"] == pytest.approx(4.0)


def test_metric_readers_on_the_scoped_trace(tmp_path, monkeypatch):
    from repro import obs
    reg = obs.MetricsRegistry()
    reg.set_gauge("flowsim.waterfill.rounds_per_event", 2.0)
    monkeypatch.setattr(obs, "get_registry", lambda: reg)
    got = read_metrics(ctx_of(write(tmp_path, monkeypatch,
                                    flowsim_xspace())))
    assert got["waterfill_us_per_event.flowsim"] == pytest.approx(8.0)
    assert got["linksum_us_per_event.flowsim"] == pytest.approx(4.0)
    assert got["rowmin_us_per_event.flowsim"] == pytest.approx(1.0)
    assert got["incidence_copy_us_per_event.flowsim"] == pytest.approx(2.0)
    assert got["scan_us_per_event.flowsim"] == pytest.approx(62 / 3)
    assert got["waterfill_rounds_per_event.flowsim"] == 2.0
    # the window is bench.run, 75 us; the chip ran 6 rounds of 4 us, 3
    # advances of 1.5 us and program 88's copy, 1 us
    assert got["device_idle.flowsim"] == pytest.approx(
        100 * (1 - (6 * 4 + 3 * 1.5 + 1) / 75))


def test_readers_read_nothing_without_scopes(tmp_path, monkeypatch):
    from repro import obs
    monkeypatch.setattr(obs, "get_registry", lambda: obs.MetricsRegistry())
    got = read_metrics(ctx_of(write(tmp_path, monkeypatch,
                                    flowsim_xspace(scoped=False))))
    assert {n for n, v in got.items() if v is None} == set(
        FLOWSIM_METRICS) - {"scan_us_per_event.flowsim",
                            "device_idle.flowsim"}


def test_readers_on_the_recorded_trace(tmp_path, monkeypatch):
    """The committed trace (a TPU v5 lite; m4 and flowSim on 6 flows, 12
    events each) predates the `flowsim.` scopes and the gauge: those
    readers read nothing; its `_event_scan` program and its window still
    read."""
    from repro import obs
    monkeypatch.setattr(obs, "get_registry", lambda: obs.MetricsRegistry())
    with gzip.open(os.path.join(os.path.dirname(__file__), "data",
                                "small.xplane.pb.gz"), "rb") as fh:
        path = write(tmp_path, monkeypatch, raw=fh.read())
    got = read_metrics(ctx_of(path, events=12))
    assert {n for n, v in got.items() if v is None} == set(
        FLOWSIM_METRICS) - {"scan_us_per_event.flowsim",
                            "device_idle.flowsim"}
    assert got["scan_us_per_event.flowsim"] > 0
    assert 0 <= got["device_idle.flowsim"] < 100
