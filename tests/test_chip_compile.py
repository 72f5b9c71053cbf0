"""The main path compiles for a TPU v5e chip, with no chip attached.

The TPU compiler is installed beside JAX, and it compiles for a chip that
is only described (`jax.experimental.topologies`). Each test lowers one
program at the paper's widths (`M4Config()`: hidden 400, GNN 300, 64/128
snapshot slots) with `interpret=False` and compiles it for one v5e chip:
the three Pallas kernels, and the whole open-loop event scans that
`chip_smoke.py` runs. Mosaic then refuses what interpret mode accepts —
unaligned slices, more VMEM than a kernel may use — at no chip time.
Nothing runs, so these say nothing about results or speed.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process may load the TPU library, and under
pytest-xdist every worker imports this file.
"""
import dataclasses
import os
import re
from collections import Counter

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import flowsim_fast as ff
from repro.core import simulate as sim
from repro.core.model import M4Config, init_m4
from repro.kernels import dispatch
from repro.kernels.bipartite.kernel import bipartite_round_pallas
from repro.kernels.fused_gru.kernel import gru_cell_pallas
from repro.kernels.waterfill.kernel import masked_rowmin_pallas

PAPER = M4Config(kernel_mode="pallas")
# chip_smoke.py's sizes: the single m4 scenario (ft-32x16x8, 4096 flows),
# the batch of 8 Table-2 scenarios at 1500 flows, and flowsim_fast on one
# of those (paper topology, at most 96 links)
SINGLE = dict(N=4096, L=1536, K=128)
BATCH = dict(B=8, N=1500, L=96, K=256)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _sds(one_chip, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _compile(fn, *args, **kw):
    compiled = fn.lower(*args, **kw).compile()
    return compiled.as_text()


def _pad128(n):
    return n + (-n) % 128


@pytest.mark.parametrize("stage, din", [
    ("gru1", 1 + PAPER.flow_feat + PAPER.cfg_dim),     # temporal GRU-1
    ("gru2", PAPER.gnn_dim + PAPER.cfg_dim),           # post-GNN GRU-2
])
def test_fused_gru_compiles(one_chip, stage, din):
    B, Dp, Hp = 128, _pad128(din), _pad128(PAPER.hidden)
    hlo = _compile(gru_cell_pallas, _sds(one_chip, (B, Dp)),
                   _sds(one_chip, (B, Hp)), _sds(one_chip, (Dp, 3 * Hp)),
                   _sds(one_chip, (Hp, 3 * Hp)), _sds(one_chip, (3 * Hp,)),
                   _sds(one_chip, (3 * Hp,)), tile_b=128, interpret=False)
    assert "tpu_custom_call" in hlo


def test_bipartite_compiles(one_chip):
    SF, SL, G = PAPER.snap_flows, PAPER.snap_links, _pad128(PAPER.gnn_dim)
    hlo = _compile(bipartite_round_pallas, _sds(one_chip, (SF, G)),
                   _sds(one_chip, (SL, G)), _sds(one_chip, (SF, SL)),
                   _sds(one_chip, (2 * G, G)), _sds(one_chip, (2 * G, G)),
                   _sds(one_chip, (G,)), _sds(one_chip, (G,)),
                   interpret=False)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("F, L", [
    (_pad128(BATCH["N"]), BATCH["L"]),        # the smoke's flowsim_fast
    (SINGLE["N"], SINGLE["L"]),               # ft-32x16x8 at 4096 flows
])
def test_masked_rowmin_compiles(one_chip, F, L):
    hlo = _compile(masked_rowmin_pallas, _sds(one_chip, (F, L)),
                   _sds(one_chip, (L,)), interpret=False)
    assert "tpu_custom_call" in hlo


def _params(one_chip, cfg):
    shapes = jax.eval_shape(lambda: init_m4(jax.random.PRNGKey(0), cfg))
    return jax.tree.map(lambda s: _sds(one_chip, s.shape, s.dtype), shapes)


def _static(one_chip, cfg, N, L, K, lead=()):
    P = cfg.max_path
    i32 = jnp.int32
    return {
        "flow_links": _sds(one_chip, lead + (N, P), i32),
        "flow_feat": _sds(one_chip, lead + (N, cfg.flow_feat)),
        "link_feat": _sds(one_chip, lead + (L, cfg.link_feat)),
        "ideal_fct": _sds(one_chip, lead + (N,)),
        "t_arrival": _sds(one_chip, lead + (N,)),
        "cfg_vec": _sds(one_chip, lead + (cfg.cfg_dim,)),
        "link_members": _sds(one_chip, lead + (L + 1, K), i32),
        "occ_rows": _sds(one_chip, lead + (N, P), i32),
        "occ_slots": _sds(one_chip, lead + (N, P), i32),
    }


@pytest.fixture(scope="module")
def scan_hlo(one_chip):
    """The compiled text of the whole m4 event scan in pallas mode, single
    or vmapped (`scan_hlo(batched)`), each compiled once for the module.
    The dispatch asks `jax.default_backend()`, which sees the CPU here;
    the fixture steers it to the described chip's platform."""
    texts = {}

    def compiled(batched):
        if batched not in texts:
            with pytest.MonkeyPatch.context() as mp:
                mp.setattr(dispatch, "_platform", lambda: "tpu")
                cfg = dispatch.canonicalize_cfg(PAPER)
                assert cfg.kernel_mode == "pallas"
                size = BATCH if batched else SINGLE
                lead = (size["B"],) if batched else ()
                N, L = size["N"], size["L"]
                fn = (sim._open_loop_scan_batched if batched
                      else sim._open_loop_scan)
                texts[batched] = _compile(
                    fn, _params(one_chip, cfg), cfg, L,
                    _static(one_chip, cfg, N, L, size["K"], lead),
                    _sds(one_chip, lead + (N,), jnp.int32),
                    _sds(one_chip, lead + (N,)))
        return texts[batched]
    return compiled


@pytest.mark.parametrize("batched", [False, True])
def test_open_loop_scan_compiles(scan_hlo, batched):
    """The whole m4 event scan in pallas mode, single and vmapped."""
    assert "tpu_custom_call" in scan_hlo(batched)


def _close(text, i):
    """Index of the parenthesis that closes the one at `text[i]`."""
    depth = 0
    for j in range(i, len(text)):
        depth += {"(": 1, ")": -1}.get(text[j], 0)
        if depth == 0:
            return j
    raise ValueError(text)


def _hlo_computations(text):
    """Compiled HLO text -> ({computation: {instruction: dict(shape, op,
    operands, called)}}, entry name). Shapes lose their layouts."""
    comps, cur, entry = {}, None, None
    for line in text.splitlines():
        if not line.startswith(" ") and line.endswith("{"):
            head = line.split(" (")[0].split()
            cur = comps.setdefault(head[-1].lstrip("%"), {})
            if head[0] == "ENTRY":
                entry = head[-1].lstrip("%")
        elif cur is not None and line.lstrip().startswith(("%", "ROOT %")):
            lhs, rhs = line.strip().removeprefix("ROOT ").split(" = ", 1)
            end = _close(rhs, 0) + 1 if rhs.startswith("(") else rhs.index(" ")
            rest = rhs[end:].lstrip()
            op = rest[:rest.index("(")]
            close = _close(rest, len(op))
            cur[lhs.lstrip("%")] = dict(
                shape=re.sub(r"\{[^}]*\}", "", rhs[:end]), op=op,
                operands=re.findall(r"%([\w.\-]+)", rest[len(op):close]),
                called=re.findall(r"%([\w.\-]+)", rest[close:]))
    return comps, entry


def _loop_computations(comps, entry):
    """Every computation that the entry's while loops run, nested ones
    (fusions, inner loops) included."""
    todo = [c for ins in comps[entry].values() if ins["op"] == "while"
            for c in ins["called"] if c in comps]
    seen = set()
    while todo:
        c = todo.pop()
        if c not in seen:
            seen.add(c)
            todo += [d for ins in comps[c].values() for d in ins["called"]
                     if d in comps]
    return seen


# the staged kernel layouts at paper widths (H 400 -> 512, G 300 -> 384,
# Din 13 and 309 -> 128 and 384) and the per-gate slices of unstaged GRU
# weights
STAGED_SHAPES = {"f32[512,1536]", "f32[128,1536]", "f32[384,1536]",
                 "f32[768,384]", "f32[1,1536]", "f32[400,400]"}
# ops that hand a value on without computing it: the loop's own carry,
# and the async copies that move it between memory spaces
PASS_ON = {"parameter", "get-tuple-element", "tuple", "copy-start",
           "copy-done"}
# kernel -> positions of its weight and bias operands
WEIGHT_OPERANDS = {"gru_cell_pallas": range(2, 6),
                   "bipartite_round_pallas": range(3, 7)}


@pytest.mark.parametrize("batched", [False, True])
def test_open_loop_scan_stages_weights_once(scan_hlo, batched):
    """The kernels' weight layout is built before the event loop, not in
    it: no op in the loop computes a staged weight or bias shape, and
    each weight operand of the seven kernel calls per event is a value
    the loop carries unchanged."""
    comps, entry = _hlo_computations(scan_hlo(batched))
    loop = _loop_computations(comps, entry)
    built = sorted(f"{name} = {ins['shape']} {ins['op']}"
                   for c in loop for name, ins in comps[c].items()
                   if ins["shape"] in STAGED_SHAPES
                   and ins["op"] not in PASS_ON)
    assert not built, built[:8]
    calls = Counter()
    for c in loop:
        body = comps[c]
        for name, ins in body.items():
            kernel = name.rsplit(".", 1)[0]
            if kernel not in WEIGHT_OPERANDS:
                continue
            calls[kernel] += 1
            for i in WEIGHT_OPERANDS[kernel]:
                src = ins["operands"][i]
                while body[src]["op"] in ("copy-start", "copy-done"):
                    src = body[src]["operands"][0]
                assert body[src]["op"] == "get-tuple-element", (name, i, src)
                carry = body[body[src]["operands"][0]]
                assert carry["op"] == "parameter", (name, i, src)
    assert calls == {"gru_cell_pallas": 4, "bipartite_round_pallas": 3}


@pytest.mark.parametrize("batched", [False, True])
def test_open_loop_scan_has_no_nested_loop(scan_hlo, batched):
    """The event loop's body runs no loop of its own: the snapshot's edge
    slots are a comparison rank, not a binary search (a `while` of
    gathers per event)."""
    text = scan_hlo(batched)
    comps, entry = _hlo_computations(text)
    loop = _loop_computations(comps, entry)
    nested = sorted(f"{c}: {name}" for c in loop
                    for name, ins in comps[c].items() if ins["op"] == "while")
    assert not nested, nested[:8]
    named = [n for n in re.findall(r'op_name="([^"]*)"', text)
             if "searchsorted" in n]
    assert not named, named[:4]


def _incidence_shapes(batched):
    """The GNN incidence M (SF, SL) as the compiled scan holds it: (B, SF,
    SL) under `vmap`, or flattened to (B·SF·SL,)."""
    SF, SL = PAPER.snap_flows, PAPER.snap_links
    if not batched:
        return {f"f32[{SF},{SL}]"}
    B = BATCH["B"]
    return {f"f32[{B},{SF},{SL}]", f"f32[{B * SF * SL}]"}


@pytest.mark.parametrize("batched", [False, True])
def test_open_loop_scan_builds_incidence_once(scan_hlo, batched):
    """The GNN incidence is a one-hot contraction built once per event: no
    scatter in the event loop's body produces it (the TPU applies a
    scatter's updates one at a time), and the three
    `bipartite_round_pallas` calls of an event take the same M."""
    comps, entry = _hlo_computations(scan_hlo(batched))
    loop = _loop_computations(comps, entry)
    shapes = _incidence_shapes(batched)
    scatters = sorted(f"{c}: {name} = {ins['shape']}" for c in loop
                      for name, ins in comps[c].items()
                      if ins["op"] == "scatter" and ins["shape"] in shapes)
    assert not scatters, scatters[:4]
    m_operands = []
    for c in loop:
        body = comps[c]
        for name, ins in body.items():
            if name.rsplit(".", 1)[0] != "bipartite_round_pallas":
                continue
            src = ins["operands"][2]
            while body[src]["op"] in ("copy-start", "copy-done"):
                src = body[src]["operands"][0]
            assert body[src]["shape"] in shapes, (name, src)
            m_operands.append((c, src))
    assert len(m_operands) == 3 and len(set(m_operands)) == 1, m_operands


@pytest.mark.parametrize("batched", [False, True])
def test_flowsim_fast_scan_compiles(one_chip, batched):
    B, N, L = BATCH["B"], BATCH["N"], BATCH["L"]
    lead = (B,) if batched else ()
    args = (_sds(one_chip, lead + (N, L)), _sds(one_chip, lead + (L,)),
            _sds(one_chip, lead + (N,)), _sds(one_chip, lead + (N,)),
            _sds(one_chip, lead + (N,), jnp.int32))
    fn = ff._event_scan_batched if batched else ff._event_scan
    hlo = _compile(fn, *args, mode="pallas")
    assert "tpu_custom_call" in hlo


def test_xla_mode_scan_compiles(one_chip):
    """The comparison path of the smoke (`kernel_mode="xla"`) compiles
    for the chip too, and holds no Pallas kernel."""
    cfg = dataclasses.replace(PAPER, kernel_mode="xla")
    N, L = SINGLE["N"], SINGLE["L"]
    hlo = _compile(sim._open_loop_scan, _params(one_chip, cfg), cfg, L,
                   _static(one_chip, cfg, N, L, SINGLE["K"]),
                   _sds(one_chip, (N,), jnp.int32), _sds(one_chip, (N,)))
    assert "tpu_custom_call" not in hlo
