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


@pytest.mark.parametrize("batched", [False, True])
def test_open_loop_scan_compiles(one_chip, monkeypatch, batched):
    """The whole m4 event scan in pallas mode, single and vmapped. The
    dispatch asks `jax.default_backend()`, which sees the CPU here; the
    test steers it to the described chip's platform."""
    monkeypatch.setattr(dispatch, "_platform", lambda: "tpu")
    cfg = dispatch.canonicalize_cfg(PAPER)
    assert cfg.kernel_mode == "pallas"
    size = BATCH if batched else SINGLE
    lead = (size["B"],) if batched else ()
    N, L = size["N"], size["L"]
    fn = sim._open_loop_scan_batched if batched else sim._open_loop_scan
    hlo = _compile(fn, _params(one_chip, cfg), cfg, L,
                   _static(one_chip, cfg, N, L, size["K"], lead),
                   _sds(one_chip, lead + (N,), jnp.int32),
                   _sds(one_chip, lead + (N,)))
    assert "tpu_custom_call" in hlo


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
