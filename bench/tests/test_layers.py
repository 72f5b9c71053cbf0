"""m4's layers from the profiler trace: the name-stack reader, the six
event-step scopes and the host spans (`bench.layers`).

A small trace is written here from a text XSpace, so every number has a
hand-worked answer. On one chip, program 77 (`_open_loop_scan`) runs
three iterations of its loop body, one every 10 us from 1 us; in each,
one op per scope: departure 1 us, snapshot 2 us, temporal 3 us (a GRU
kernel), spatial 1 us, heads 0.5 us, scatter 1 us, then a copy with no
name stack, 0.5 us. Program 88 runs at 32-33 us and holds an instruction
named as one of program 77's, `fusion.3`. The host holds `bench.run`
(0-35 us) and, inside it, `m4.run` (0.05-34.45 us) > `m4.build` (0.1-0.6
us), `m4.scan` (0.9-33.5 us), `m4.result` (33.6-34.4 us); an `m4.build`
of an earlier call lies before the window. (Both clocks start at 10 us
in the file, so that the earlier call's span has a time.)
"""
import dataclasses
import json
import os
import shutil

import numpy as np
import pytest

from bench import layers, run, trace

US = 1000000                # ps per us
SCAN = "jit(_open_loop_scan)/while/body/closed_call/"
# (metadata id, HLO text, name stack or None, stack as a ref, start, dur)
BODY = [
    (3, "%argmin.1 = s32[] reduce(f32[8]{0} %t), dimensions={0}",
     SCAN + "m4.departure/argmin:", False, 0.0, 1.0),
    (4, "%fusion.277 = s32[512]{0} fusion(s32[128]{0} %a), kind=kLoop",
     SCAN + "m4.snapshot/gather:", True, 1.0, 2.0),
    (5, "%gru_cell_pallas.42 = f32[128,512]{1,0} custom-call(f32[1]{0} %p)",
     SCAN + "m4.temporal/jit(gru_cell_pallas)/pallas_call:", False, 3.0,
     3.0),
    (6, "%bipartite_round_pallas.30 = f32[64,384]{1,0} custom-call(f32[1] %q)",
     SCAN + "m4.spatial/jit(bipartite_round_pallas)/pallas_call:", False,
     6.0, 1.0),
    (7, "%dot.9 = f32[64]{0} dot(f32[64,200]{1,0} %h, f32[200]{0} %w)",
     SCAN + "m4.heads/dot_general:", False, 7.0, 0.5),
    (8, "%fusion.3 = f32[65,400]{1,0} fusion(f32[64,400]{1,0} %f), kind=kLoop",
     SCAN + "m4.scatter/scatter:", False, 7.5, 1.0),
    (9, "%copy.5 = f32[6]{0} copy(f32[6]{0} %c)", None, False, 8.5, 0.5),
]
EXPECT = {"m4.departure": 1.0, "m4.snapshot": 2.0, "m4.temporal": 3.0,
          "m4.spatial": 1.0, "m4.heads": 0.5, "m4.scatter": 1.0}
HOST = [("bench.run", 0.0, 35.0), ("m4.build", -9.0, 0.5),
        ("m4.run", 0.05, 34.4), ("m4.build", 0.1, 0.5),
        ("m4.scan", 0.9, 32.6), ("m4.result", 33.6, 0.8)]


def _stat(sid, value):
    kind = "uint64_value" if isinstance(value, int) else "str_value"
    return f"stats {{ metadata_id: {sid} {kind}: {json.dumps(value)} }}"


def xspace(iterations=3, scoped=True, host=HOST):
    ops, meta = [], []
    for mid, text, stack, as_ref, start, dur in BODY:
        for k in range(iterations):
            ops.append((mid, 1 + 10 * k + start, dur))
        stats = [_stat(35, 77)]
        if stack and scoped:
            stats.append(f"stats {{ metadata_id: 26 ref_value: {100 + mid} }}"
                         if as_ref else _stat(26, stack))
        meta.append((mid, text, stats))
    ops.append((2, 0.5, 31.0))
    meta.append((2, "%while.134 = (s32[]) while((s32[]) %t), body=%b",
                 [_stat(35, 77), _stat(26, "jit(_open_loop_scan)/while:")]))
    ops.append((10, 32.0, 1.0))
    meta.append((10, "%fusion.3 = f32[4]{0} fusion(f32[4]{0} %x), kind=kLoop",
                 [_stat(35, 88), _stat(26, "jit(convert)/convert:")]))
    ops.sort(key=lambda o: o[1])
    ev = "\n".join(f"events {{ metadata_id: {m} offset_ps: {int(s * US)} "
                   f"duration_ps: {int(d * US)} }}" for m, s, d in ops)
    md = "\n".join(f"event_metadata {{ key: {m} value {{ id: {m} "
                   f"name: {json.dumps(t)} {' '.join(st)} }} }}"
                   for m, t, st in meta)
    sm = "\n".join(
        [f'stat_metadata {{ key: 26 value {{ id: 26 name: "tf_op" }} }}',
         f'stat_metadata {{ key: 35 value {{ id: 35 name: "program_id" }} }}']
        + [f"stat_metadata {{ key: {100 + m} value {{ id: {100 + m} "
           f"name: {json.dumps(s)} }} }}"
           for m, _, s, r, _, _ in BODY if r])
    names = sorted({h[0] for h in host})
    hev = "\n".join(f"events {{ metadata_id: {names.index(n) + 1} "
                    f"offset_ps: {int((s + 10) * US)} "
                    f"duration_ps: {int(d * US)} }}" for n, s, d in host)
    hmd = "\n".join(f'event_metadata {{ key: {i + 1} value {{ id: {i + 1} '
                    f'name: "{n}" }} }}' for i, n in enumerate(names))
    return f"""
planes {{
  name: "/device:TPU:0"
  lines {{ name: "XLA Modules" timestamp_ns: 10000
    events {{ metadata_id: 1 offset_ps: 0 duration_ps: {31 * US} }}
    events {{ metadata_id: 11 offset_ps: {32 * US} duration_ps: {US} }} }}
  lines {{ name: "XLA Ops" timestamp_ns: 10000
{ev} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "jit__open_loop_scan(77)" }} }}
  event_metadata {{ key: 11 value {{ id: 11 name: "jit_convert(88)" }} }}
{md}
{sm}
}}
planes {{
  name: "/host:CPU"
  lines {{ name: "python" timestamp_ns: 0
{hev} }}
{hmd}
}}
"""


def write(tmp_path, monkeypatch, text, name="bench-trace-a"):
    """The file where the harness traces: a `bench-trace-*` directory of
    the temporary directory."""
    from jax.profiler import ProfileData
    monkeypatch.setattr("tempfile.tempdir", str(tmp_path))
    d = tmp_path / name / "plugins" / "profile" / "1"
    d.mkdir(parents=True)
    path = d / "host.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return str(path)


def ctx_of(path, events=3):
    return {"trace": trace.load(path), "events": events}


def test_name_stacks_from_str_and_ref_values(tmp_path, monkeypatch):
    x = layers.read_xplane(write(tmp_path, monkeypatch, xspace()))
    assert x.stacks[(77, "argmin.1")] == SCAN + "m4.departure/argmin:"
    assert x.stacks[(77, "fusion.277")] == SCAN + "m4.snapshot/gather:"
    assert x.stacks[(77, "fusion.3")] == SCAN + "m4.scatter/scatter:"
    assert x.stacks[(88, "fusion.3")] == "jit(convert)/convert:"
    assert (77, "copy.5") not in x.stacks       # no name stack
    assert [s[0] for s in x.spans] == [
        "m4.build", "bench.run", "m4.run", "m4.build", "m4.scan",
        "m4.result"]


@pytest.mark.parametrize("scope", sorted(EXPECT))
def test_scope_time_per_step(tmp_path, monkeypatch, scope):
    ctx = ctx_of(write(tmp_path, monkeypatch, xspace()))
    # program 88's fusion.3 (1 us) is not program 77's scatter
    assert layers.scope_us_per_step(ctx, scope) == pytest.approx(
        EXPECT[scope])


@pytest.mark.parametrize("scope", sorted(EXPECT))
def test_scope_time_with_iterations_unrecorded(tmp_path, monkeypatch,
                                               scope):
    # the tracer left out the last of three iterations: the time per
    # recorded iteration reads as it did in full
    ctx = ctx_of(write(tmp_path, monkeypatch, xspace()))
    t = ctx["trace"]
    ids, start, dur = t.ops[0]
    keep = start < t.window[0] + 21000.0
    part = dataclasses.replace(t, ops=[(ids[keep], start[keep], dur[keep])])
    got = layers.scope_us_per_step(dict(ctx, trace=part), scope)
    assert got == pytest.approx(EXPECT[scope])


def test_six_scopes_and_the_rest(tmp_path, monkeypatch):
    ctx = ctx_of(write(tmp_path, monkeypatch, xspace()))
    total = sum(layers.scope_us_per_step(ctx, s) for s in EXPECT)
    assert total == pytest.approx(8.5)   # of 9 us a step: the copy is left


def read_all(ctx, root=run.ROOT):
    bench = json.load(open(os.path.join(root, "BENCHMARK.json")))
    names = [m["name"] for m in bench["per_layer"]
             if m["name"].endswith(("_us_per_event.sim", "_ms.sim"))
             and m["name"] != "scan_us_per_event.sim"]
    assert len(names) == 8
    return {n: run.load_module(os.path.join(root, "bench", "metrics",
                                            n + ".py"), "m_" + n).read(ctx)
            for n in names}


def test_readers_give_none_without_scopes_or_spans(tmp_path, monkeypatch):
    path = write(tmp_path, monkeypatch,
                 xspace(scoped=False, host=[("bench.run", 0.0, 35.0)]))
    assert set(read_all(ctx_of(path)).values()) == {None}


def test_readers_read_the_traced_call(tmp_path, monkeypatch):
    got = read_all(ctx_of(write(tmp_path, monkeypatch, xspace())))
    assert got["host_build_ms.sim"] == pytest.approx(0.5e-3)
    assert got["host_result_ms.sim"] == pytest.approx(0.8e-3)
    assert got["snapshot_us_per_event.sim"] == pytest.approx(2.0)


def test_file_found_by_its_window(tmp_path, monkeypatch):
    # a file of another run in the same temporary directory is passed over
    path = write(tmp_path, monkeypatch, xspace())
    other = write(tmp_path, monkeypatch, xspace(host=[
        ("bench.run", 0.0, 20.0), ("m4.build", 0.2, 9.0)]), "bench-trace-b")
    os.utime(other, (2e9, 2e9))                      # the newer one
    ctx = ctx_of(path)
    assert layers.span_ms(ctx, "m4.build") == pytest.approx(0.5e-3)
    # no file with that window: nothing is read
    shutil.rmtree(os.path.dirname(path))
    assert layers.span_ms({"trace": dataclasses.replace(ctx["trace"])},
                          "m4.build") is None


def test_window_is_still_the_bench_spans(tmp_path, monkeypatch):
    ctx = ctx_of(write(tmp_path, monkeypatch, xspace()))
    t = ctx["trace"]
    assert t.window == (10000.0, 45000.0)
    assert [s[0] for s in t.spans] == ["bench.run"]
    x, _ = layers._reading(ctx)
    assert x.window == pytest.approx(t.window)
    # the earlier call's m4.build, outside the window, is not counted
    assert layers.span_ms(ctx, "m4.build") == pytest.approx(0.5e-3)


def test_idle_gap_labelled_by_innermost_m4_span(tmp_path, monkeypatch):
    ctx = ctx_of(write(tmp_path, monkeypatch, xspace()))
    gaps = layers.idle_gaps(ctx)
    # the chip idles 33-35 us (m4.result holds 33.6-34.4 us's middle, 34),
    # 0-0.5 us (m4.build) and 31.5-32 us (m4.scan)
    assert gaps[0] == ["m4.result", pytest.approx(2e-6)]
    assert sorted(label for label, _ in gaps[1:]) == ["m4.build", "m4.scan"]
    assert [g for _, g in gaps[1:]] == pytest.approx([0.5e-6, 0.5e-6])
    assert trace.idle_gaps(ctx["trace"])[0][0] == "bench.run"
    assert np.isclose(sum(g for _, g in gaps),
                      ctx["trace"].window_s - trace.busy_s(ctx["trace"]))


def test_harness_reads_the_host_spans(checkout, tmp_path, capsys):
    """A traced run of the tiny cell on the CPU: the host-span readers
    find the harness's trace file and read the call's build and result;
    the CPU has no TPU plane, so the scope readers read nothing."""
    root = tmp_path / "checkout"
    shutil.copytree(checkout, root, symlinks=True)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for m in bench["per_layer"]:
        if m["name"] in ("host_build_ms.sim", "host_result_ms.sim",
                         "snapshot_us_per_event.sim"):
            m["workloads"].append("m4.tiny")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    capsys.readouterr()
    assert run.main(["--workload", "m4.tiny", "--seed", "5", "--seconds",
                     "0.5", "--trace", "1"], root=str(root),
                    require_tpu=False) == 0
    r = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert r["correct"] is True
    metrics = r["metrics"]
    assert 0 < metrics["host_build_ms.sim"]["value"] < 1e4
    assert 0 < metrics["host_result_ms.sim"]["value"] < 1e4
    assert metrics["host_build_ms.sim"]["unit"] == "ms"
    assert "snapshot_us_per_event.sim" not in metrics
