"""The trace reduction: busy, idle and kernel time from an `.xplane.pb`.

A small trace is written here from a text XSpace, so every number has a
hand-worked answer: on one chip, inside a `bench.run` span of 10 us,
a loop runs 1-5 us and holds a GRU kernel (1.5-2.5 us) and a fusion
(3-4 us); a row-min kernel runs 6-7 us. A second `bench.run` span is
open at 8-9 us.
"""
import gzip
import os

import numpy as np
import pytest

from bench import trace

XSPACE = """
planes {
  name: "/device:TPU:0"
  lines { name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 4000000 }
    events { metadata_id: 2 offset_ps: 6000000 duration_ps: 1000000 } }
  lines { name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 3 offset_ps: 1000000 duration_ps: 4000000 }
    events { metadata_id: 4 offset_ps: 1500000 duration_ps: 1000000 }
    events { metadata_id: 5 offset_ps: 3000000 duration_ps: 1000000 }
    events { metadata_id: 6 offset_ps: 6000000 duration_ps: 1000000 } }
  event_metadata { key: 1 value { id: 1 name: "jit__open_loop_scan(1)" } }
  event_metadata { key: 2 value { id: 2 name: "jit__event_scan(2)" } }
  event_metadata { key: 3 value { id: 3
    name: "%while.134 = (f32[4]{0}, s32[]) while((f32[4]{0}, s32[]) %t), condition=%c, body=%b" } }
  event_metadata { key: 4 value { id: 4
    name: "%gru_cell_pallas.42 = f32[128,512]{1,0:T(8,128)} custom-call(f32[128,128]{1,0:T(8,128)} %pad.1), custom_call_target=\\"tpu_custom_call\\"" } }
  event_metadata { key: 5 value { id: 5
    name: "%fusion.277 = s32[512]{0:T(512)S(1)} fusion(s32[128]{0} %a), kind=kCustom" } }
  event_metadata { key: 6 value { id: 6
    name: "%masked_rowmin_pallas.3.clone = f32[128,1]{1,0} custom-call(f32[128,96]{1,0} %pad.8)" } }
}
planes {
  name: "/host:CPU"
  lines { name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 1 offset_ps: 8000000 duration_ps: 1000000 }
    events { metadata_id: 2 offset_ps: 2000000 duration_ps: 1000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.run" } }
  event_metadata { key: 2 value { id: 2 name: "$topology.py:77 <genexpr>" } }
}
"""


@pytest.fixture(scope="module")
def small(tmp_path_factory):
    from jax.profiler import ProfileData
    path = tmp_path_factory.mktemp("trace") / "small.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(XSPACE))
    return trace.load(str(path))


def test_window_is_the_bench_spans(small):
    assert small.window == (0.0, 10000.0)
    assert small.window_s == pytest.approx(10e-6)
    assert [s[0] for s in small.spans] == ["bench.run", "bench.run"]


def test_busy_is_the_union_of_ops(small):
    # 1-5 us (the loop covers the ops inside it) and 6-7 us
    assert trace.busy_s(small) == pytest.approx(5e-6)


def test_kernel_time_by_pallas_name(small):
    gru = trace.op_time_s(small, lambda base, name, op:
                          base == "gru_cell_pallas")
    assert gru == (pytest.approx(1e-6), 1)
    rowmin = trace.op_time_s(small, lambda base, name, op:
                             base == "masked_rowmin_pallas")
    assert rowmin == (pytest.approx(1e-6), 1)


def test_parse_op():
    assert trace.parse_op(
        "%gru_cell_pallas.42 = f32[128,512]{1,0} custom-call(f32[1]{0} %p)"
    ) == ("gru_cell_pallas", "gru_cell_pallas.42", "custom-call")
    assert trace.parse_op(
        "%copy-start = (f32[64,96]{1,0}, u32[]{:S(2)}) copy-start(f32[64,96]"
        "{1,0} %x)") == ("copy-start", "copy-start", "copy-start")


def test_program_busy(small):
    assert trace.module_busy_s(small, lambda n: "open_loop_scan" in n) \
        == pytest.approx(4e-6)
    assert trace.module_busy_s(small, lambda n: "scan" in n) \
        == pytest.approx(5e-6)


def test_top_ops_leave_out_loops(small):
    top = trace.top_ops(small)
    assert [n for n, _ in top] == ["gru_cell_pallas.42", "fusion.277",
                                   "masked_rowmin_pallas.3"]
    assert all(s == pytest.approx(1e-6) for _, s in top)


def test_idle_gaps_labelled_by_host_span(small):
    gaps = trace.idle_gaps(small)
    # 7-10 us, 0-1 us, 5-6 us; the innermost span at 8.5 us is the second
    # bench.run, and every gap lies inside the outer one
    assert [g for _, g in gaps] == pytest.approx([3e-6, 1e-6, 1e-6])
    assert {label for label, _ in gaps} == {"bench.run"}


# A trace recorded on a TPU v5 lite: one `bench.run` span around m4 at the
# paper's widths on a 6-flow scenario (12 events), one around flowSim on
# the same scenario.
RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "small.xplane.pb.gz")
PAPER = {"hidden": 400, "gnn_dim": 300, "mlp_hidden": 200, "gnn_layers": 3,
         "snap_flows": 64, "snap_links": 128, "max_path": 8, "cfg_dim": 9}


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    path = tmp_path_factory.mktemp("recorded") / "small.xplane.pb"
    with gzip.open(RECORDED, "rb") as fh:
        path.write_bytes(fh.read())
    return trace.load(str(path))


def test_recorded_busy_and_idle(recorded):
    assert len(recorded.ops) == 1                  # one chip
    busy = trace.busy_s(recorded)
    assert 0 < busy < recorded.window_s
    gaps = trace.idle_gaps(recorded)
    assert {label for label, _ in gaps} == {"bench.run"}
    # every gap lies between the device's busy stretches
    assert sum(g for _, g in gaps) <= recorded.window_s - busy + 1e-9


def test_recorded_kernel_calls(recorded):
    # m4: 4 GRU cells and 3 GraphSAGE rounds in each of the 12 event steps
    gru_s, gru_n = trace.op_time_s(recorded, lambda base, name, op:
                                   base == "gru_cell_pallas")
    gnn_s, gnn_n = trace.op_time_s(recorded, lambda base, name, op:
                                   base == "bipartite_round_pallas")
    assert (gru_n, gnn_n) == (48, 36)
    assert gru_s > 0 and gnn_s > 0
    _, rowmin_n = trace.op_time_s(recorded, lambda base, name, op:
                                  base == "masked_rowmin_pallas")
    assert rowmin_n > 0
    assert trace.module_busy_s(recorded, lambda n: "open_loop_scan" in n) > 0
    assert trace.module_busy_s(recorded, lambda n: "event_scan" in n) > 0


def test_recorded_rooflines_below_100(recorded):
    from bench import flops
    from bench.roofline import m4_kernel_share
    ctx = {"trace": recorded, "events": 12, "config": {"model": PAPER},
           "flops": flops,
           "peak": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}}
    for kernel, op in (("fused_gru", "gru_cell_pallas"),
                       ("bipartite_round", "bipartite_round_pallas")):
        share = m4_kernel_share(ctx, kernel, op)
        assert 0 < share < 100
    # a call count the step does not account for reads nothing
    assert m4_kernel_share(dict(ctx, events=11), "fused_gru",
                           "gru_cell_pallas") is None



def test_recorded_rooflines_with_calls_unrecorded(recorded):
    # the tracer may leave out a stretch of a long call's events: the
    # share is taken per recorded call, so it reads as it did in full
    import dataclasses
    from bench import flops
    from bench.roofline import m4_kernel_share
    ctx = {"trace": recorded, "events": 12, "config": {"model": PAPER},
           "flops": flops,
           "peak": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}}
    ids, start, dur = recorded.ops[0]
    gru = np.array([recorded.names[i][0] == "gru_cell_pallas" for i in ids])
    cut = np.sort(start[gru])[gru.sum() // 2]
    keep = start < cut
    part = dataclasses.replace(recorded,
                               ops=[(ids[keep], start[keep], dur[keep])])
    _, n = trace.op_time_s(part, lambda base, name, op:
                           base == "gru_cell_pallas")
    assert 0 < n < 48
    for kernel, op in (("fused_gru", "gru_cell_pallas"),
                       ("bipartite_round", "bipartite_round_pallas")):
        full = m4_kernel_share(ctx, kernel, op)
        assert m4_kernel_share(dict(ctx, trace=part), kernel, op) == \
            pytest.approx(full, rel=0.01)
