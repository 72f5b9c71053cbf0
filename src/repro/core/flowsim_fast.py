"""TPU-resident flowSim (beyond-paper): the entire max-min event loop as a
single `lax.scan` of 2N flow-level events over dense incidence matmuls.
The per-round masked row-min executes through `repro.kernels.dispatch`:
the Pallas kernel (`repro.kernels.waterfill`) on TPU (or under
REPRO_KERNELS=pallas|interpret), the jnp reference otherwise — the
resolved mode is a static jit argument, so flipping it retraces instead
of reusing a stale executable. This gives classical flowSim the same
accelerator-friendly execution model that m4's learned step enjoys — the
paper's Table-4 scaling argument applied back to the baseline.

`run_flowsim_fast_batch` pads B scenarios to one incidence shape and vmaps
the scan, so a benchmark sweep costs one compile instead of B (exposed as
`repro.sim.get_backend("flowsim_fast").run_many`); with more than one
local device the batch is `jax.pmap`-sharded (devices x B/devices) so the
sweep also divides across accelerators.

The event step names its layers with `jax.named_scope`:
`flowsim.waterfill` (the filling loop), inside it `flowsim.linksum` (the
two link sums) and `flowsim.rowmin` (the row-min), and `flowsim.advance`
(the departure argmin, the time advance and the state scatters). The
single-scenario scan also returns the water-filling rounds it ran, which
`run_flowsim_fast` publishes in the `repro.obs` registry; the batched and
sharded scans drop that count, so their programs compute nothing for it.

Equivalence with the numpy event-driven reference is tested in
tests/test_flowsim_fast.py; batched-vs-looped in tests/test_sim_api.py.
"""
from __future__ import annotations

import time
from collections import Counter

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels import dispatch
from ..obs.registry import get_registry
from ..obs.trace import get_tracer
from .probes import (FLOWSIM_CHANNELS, ProbeConfig,
                     finalize as _probe_finalize, init_buffers as _probe_init,
                     normalize_probes, record as _probe_record)

BIG = 1e30

# compile counters (incremented at trace time only — see simulate.TRACE_COUNTS)
TRACE_COUNTS = Counter()


def _waterfill_masked(a, cap, active, *, max_rounds=32, mode="xla"):
    """Max-min rates for the active subset. a: (N, L) incidence; returns
    (rates (N,) with zeros for inactive flows, the number of filling
    rounds run). The inner masked row-min (each flow's bottleneck share)
    runs via `repro.kernels.dispatch` — the Pallas kernel in
    pallas/interpret mode, jnp otherwise; parity is tested in
    tests/test_kernels.py. Scopes: `flowsim.waterfill` (the loop),
    inside it `flowsim.linksum` (the two link sums) and `flowsim.rowmin`."""
    N, L = a.shape

    def cond(st):
        rates, frozen, i = st
        return (i < max_rounds) & ~jnp.all(frozen)

    def body(st):
        rates, frozen, i = st
        u = jnp.where(frozen, 0.0, 1.0)
        with jax.named_scope("flowsim.linksum"):
            n_l = u @ a
            used = (rates * frozen) @ a
        avail = jnp.maximum(cap - used, 0.0)
        share = jnp.where(n_l > 0, avail / jnp.maximum(n_l, 1.0), BIG)
        with jax.named_scope("flowsim.rowmin"):
            f_share = dispatch.masked_rowmin(a, share, mode=mode)
        theta = jnp.min(jnp.where(u > 0, f_share, BIG))
        newly = (u > 0) & (f_share <= theta * (1 + 1e-9))
        rates = jnp.where(newly, f_share, rates)
        return rates, frozen | newly, i + 1

    frozen0 = ~active
    with jax.named_scope("flowsim.waterfill"):
        rates, _, rounds = jax.lax.while_loop(
            cond, body, (jnp.zeros((N,), jnp.float32), frozen0, 0))
        return jnp.where(active, rates, 0.0), rounds


def _event_scan_core(a, cap, sizes_bits, arr_times, arr_order, mode="xla",
                     num_events=None, probes=None):
    N = sizes_bits.shape[0]

    def step(carry):
        remaining, active, done, ptr, t, fct, rounds = carry
        rates, n = _waterfill_masked(a, cap, active, mode=mode)
        with jax.named_scope("flowsim.advance"):
            tta = jnp.where(active & (rates > 0),
                            remaining / jnp.maximum(rates, 1e-9), BIG)
            dep_i = jnp.argmin(tta)
            next_dep = t + tta[dep_i]
            next_arr = jnp.where(ptr < N, arr_times[jnp.minimum(ptr, N - 1)],
                                 BIG)
            is_arr = next_arr <= next_dep
            t_ev = jnp.where(is_arr, next_arr, next_dep)
            dt = jnp.maximum(t_ev - t, 0.0)
            remaining = jnp.where(active, remaining - rates * dt, remaining)
            fid = jnp.where(is_arr, arr_order[jnp.minimum(ptr, N - 1)], dep_i)
            # arrival: activate; departure: deactivate + record FCT
            active = active.at[fid].set(is_arr)
            done = done.at[fid].set(done[fid] | ~is_arr)
            fct = fct.at[fid].set(jnp.where(is_arr, fct[fid], t_ev))
            remaining = remaining.at[fid].set(
                jnp.where(is_arr, sizes_bits[fid], 0.0))
            ptr = ptr + is_arr.astype(jnp.int32)
        return (remaining, active, done, ptr, t_ev, fct, rounds + n)

    def body(carry, _):
        return step(carry), None

    init = (jnp.zeros((N,), jnp.float32), jnp.zeros((N,), bool),
            jnp.zeros((N,), bool), jnp.int32(0), 0.0,
            jnp.zeros((N,), jnp.float32), jnp.int32(0))
    length = 2 * N if num_events is None else num_events
    if probes is None:
        carry, _ = jax.lax.scan(body, init, None, length=length)
        # completion TIMES (absolute; the caller subtracts arrivals) and
        # the water-filling rounds of all events
        return carry[5], carry[6]

    bufs0 = _probe_init(probes, num_flows=N, num_links=a.shape[1])

    def body_probed(carry, ev_idx):
        inner, bufs = carry
        inner = step(inner)
        remaining, active, done, ptr, t_ev, fct, _ = inner
        vals = {
            # instantaneous max-min rates of the post-event active set —
            # an extra waterfill, but only inside the cond's taken branch
            "flow_rate": lambda: _waterfill_masked(a, cap, active,
                                                   mode=mode)[0],
            "flow_remaining": lambda: remaining / 8.0,          # bits -> bytes
            "link_active": lambda: jnp.where(active, 1.0, 0.0) @ a,
        }
        bufs = _probe_record(probes, bufs, ev_idx, t_ev, vals)
        return (inner, bufs), None

    (carry, bufs), _ = jax.lax.scan(
        body_probed, (init, bufs0), jnp.arange(length, dtype=jnp.int32))
    return carry[5], bufs


@partial(jax.jit, static_argnames=("mode", "num_events", "probes"))
def _event_scan(a, cap, sizes_bits, arr_times, arr_order, mode="xla",
                num_events=None, probes=None):
    TRACE_COUNTS["event_scan"] += 1
    return _event_scan_core(a, cap, sizes_bits, arr_times, arr_order, mode,
                            num_events, probes)


@partial(jax.jit, static_argnames=("mode", "probes"))
def _event_scan_batched(a, cap, sizes_bits, arr_times, arr_order, mode="xla",
                        probes=None):
    TRACE_COUNTS["event_scan_batched"] += 1

    def one(*leaves):
        out = _event_scan_core(*leaves, mode, None, probes)
        return out if probes is not None else out[0]   # no rounds count

    return jax.vmap(one)(a, cap, sizes_bits, arr_times, arr_order)


@partial(jax.pmap, static_broadcasted_argnums=(5,))
def _event_scan_sharded(a, cap, sizes_bits, arr_times, arr_order, mode):
    """pmap(vmap(scan)): leading axis = local devices, second = scenarios
    per device. One compile serves the whole sharded sweep chunk."""
    TRACE_COUNTS["event_scan_sharded"] += 1

    def one(*leaves):
        return _event_scan_core(*leaves, mode)[0]     # no rounds count

    return jax.vmap(one)(a, cap, sizes_bits, arr_times, arr_order)


def _pack(topo, flows, n_total=None, l_total=None):
    """Dense incidence + arrival schedule, optionally padded to shared shape.
    Padded flows have empty paths and arrive at t=BIG (strictly after every
    real event), padded links carry no flow."""
    n = len(flows)
    N = n if n_total is None else n_total
    L = topo.num_links if l_total is None else l_total
    a = np.zeros((N, L), np.float32)
    for f in flows:
        a[f.fid, f.path] = 1.0
    sizes = np.full(N, 8.0, np.float64)
    sizes[:n] = [float(f.size) * 8.0 for f in flows]
    cap = np.ones(L, np.float64)
    cap[:topo.num_links] = topo.capacity
    t_arr = np.full(N, BIG, np.float32)
    t_arr[:n] = [f.t_arrival for f in flows]
    order = np.argsort(t_arr, kind="stable").astype(np.int32)
    return a, cap, sizes, t_arr[order], order


def _result(topo, flows, fct_abs, wall, series=None):
    from .flowsim import FlowSimResult
    arr = np.array([f.t_arrival for f in flows])
    fcts = fct_abs[:len(flows)] - arr
    ideal = np.array([topo.ideal_fct(f.size, f.path) for f in flows])
    return FlowSimResult(fcts=fcts, slowdowns=fcts / ideal,
                         event_times=np.zeros(0, np.float64),
                         event_types=np.zeros(0, np.float64),
                         event_fids=np.zeros(0, np.float64), wallclock=wall,
                         probes=series)


def _finalize_fs_series(probes, bufs, topo, flows, *, num_flows, num_links):
    series = _probe_finalize(probes, bufs, num_flows=num_flows,
                             num_links=num_links, trim_flows=len(flows),
                             trim_links=topo.num_links)
    series["meta"] = {"backend": "flowsim_fast",
                      "units": {"flow_rate": "bits/s",
                                "flow_remaining": "bytes",
                                "link_active": "flows"}}
    return series


def run_flowsim_fast(topo, flows, probes: ProbeConfig = None):
    """Drop-in fast path for `run_flowsim` (fcts + slowdowns only).
    `probes` records exact remaining-size / waterfill-rate / link-occupancy
    series into `FlowSimResult.probes`; None is the probe-free program.

    The call is the `flowsim_fast.run` span, with the children
    `flowsim_fast.build`, `flowsim_fast.scan` and `flowsim_fast.result`
    (`repro.obs`). A probe-free call adds its water-filling rounds to the
    counter `flowsim.waterfill.rounds` and sets the gauge
    `flowsim.waterfill.rounds_per_event` (rounds over the call's 2N
    events)."""
    tracer = get_tracer()
    with tracer.span("flowsim_fast.run"):
        probes = normalize_probes(probes, FLOWSIM_CHANNELS)
        with tracer.span("flowsim_fast.build"):
            args = [jnp.asarray(x) for x in _pack(topo, flows)]
        mode = dispatch.resolve_mode()
        with tracer.span("flowsim_fast.scan"):
            t0 = time.perf_counter()
            out = jax.block_until_ready(_event_scan(*args, mode=mode,
                                                    probes=probes))
            wall = time.perf_counter() - t0
        with tracer.span("flowsim_fast.result"):
            series = None
            if probes is None:
                fct_abs, rounds = jax.device_get(out)
                registry = get_registry()
                registry.inc("flowsim.waterfill.rounds", int(rounds))
                registry.set_gauge("flowsim.waterfill.rounds_per_event",
                                   int(rounds) / (2 * len(flows)))
            else:
                fct_abs = np.asarray(out[0])
                series = _finalize_fs_series(probes, out[1], topo, flows,
                                             num_flows=len(flows),
                                             num_links=topo.num_links)
            return _result(topo, flows, fct_abs, wall, series)


def stack_scenarios(scenarios):
    """Pad (topo, flows) scenarios to one incidence shape and stack the
    `_event_scan` inputs along a leading scenario axis."""
    n_max = max(len(flows) for _, flows in scenarios)
    l_max = max(topo.num_links for topo, _ in scenarios)
    packed = [_pack(topo, flows, n_total=n_max, l_total=l_max)
              for topo, flows in scenarios]
    return [jnp.asarray(np.stack(col)) for col in zip(*packed)]


def run_flowsim_fast_batch(scenarios, probes: ProbeConfig = None):
    """One vmapped compile over B (topo, flows) scenarios padded to the
    largest flow/link count. Returns a list of FlowSimResult. Probed
    batches stay on the vmapped (single-device) path."""
    probes = normalize_probes(probes, FLOWSIM_CHANNELS)
    scenarios = list(scenarios)
    if not scenarios:
        return []
    n_max = max(len(flows) for _, flows in scenarios)
    l_max = max(topo.num_links for topo, _ in scenarios)
    stacked = stack_scenarios(scenarios)
    mode = dispatch.resolve_mode()
    D = jax.local_device_count()
    t0 = time.perf_counter()
    bufs = None
    if D > 1 and len(scenarios) >= D and probes is None:
        from .sharding import shard_leaves, unshard
        fct_abs = unshard(np.asarray(_event_scan_sharded(
            *shard_leaves(stacked, D), mode)), len(scenarios))
    else:
        out = jax.block_until_ready(
            _event_scan_batched(*stacked, mode=mode, probes=probes))
        if probes is None:
            fct_abs = np.asarray(out)
        else:
            fct_abs = np.asarray(out[0])
            bufs = out[1]
    wall = time.perf_counter() - t0
    results = []
    for b, (topo, flows) in enumerate(scenarios):
        series = None
        if bufs is not None:
            series = _finalize_fs_series(
                probes, {k: v[b] for k, v in bufs.items()}, topo, flows,
                num_flows=n_max, num_links=l_max)
        results.append(_result(topo, flows, fct_abs[b],
                               wall / len(scenarios), series))
    return results
