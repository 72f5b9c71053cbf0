"""Backend protocol + string-keyed registry for the four simulators.

`Backend.run` executes one `SimRequest`; `run_many` executes a batch — the
jax backends ("flowsim_fast", "m4") override it to pad all scenarios to a
shared arena shape and `jax.vmap` one compiled `lax.scan` across them,
turning a Python loop of per-scenario retraces into a single XLA call.
Backends that can consume arrivals dynamically also expose
`closed_loop(...)` sessions (see `repro.sim.closedloop`).

Registry usage:

    from repro.sim import get_backend, list_backends

    get_backend("flowsim").run(req)
    get_backend("m4", params=params, cfg=cfg).run_many(reqs)
"""
from __future__ import annotations

import copy
import hashlib
import time
from typing import Callable, Dict, List, Sequence

import numpy as np

from .api import SimRequest, SimResult

# ----------------------------------------------------------------- registry
_REGISTRY: Dict[str, Callable[..., "Backend"]] = {}


def register_backend(name: str, factory: Callable[..., "Backend"] = None):
    """Register a backend factory under `name` (usable as a decorator)."""
    def _add(f):
        _REGISTRY[name] = f
        return f
    return _add(factory) if factory is not None else _add


def get_backend(name: str, **kwargs) -> "Backend":
    """Instantiate the backend registered under `name`.

    kwargs are forwarded to the factory — e.g. the learned backend needs
    its parameters: `get_backend("m4", params=params, cfg=cfg)`.
    """
    if name not in _REGISTRY:
        raise KeyError(f"unknown backend {name!r}; "
                       f"available: {sorted(_REGISTRY)}")
    return _REGISTRY[name](**kwargs)


def list_backends() -> List[str]:
    return sorted(_REGISTRY)


# ----------------------------------------------------------------- protocol
class Backend:
    """A simulator behind the unified request/response API."""

    name: str = "?"

    def run(self, request: SimRequest) -> SimResult:
        raise NotImplementedError

    def run_many(self, requests: Sequence[SimRequest]) -> List[SimResult]:
        """Batch execution; default is a loop, jax backends vmap (and shard
        the vmapped batch across local devices when more than one exists)."""
        return [self.run(r) for r in requests]

    def run_chunked(self, requests: Sequence[SimRequest],
                    chunk_size: int = None) -> List[SimResult]:
        """Chunked sharded dispatch: partition `requests` into shape-
        compatible chunks and `run_many` each.

        Requests are sorted by arena footprint (flow count, then link
        count) before slicing so each chunk pads to near-uniform shapes —
        a shape-diverse N-request sweep costs at most ceil(N/chunk_size)
        batched compiles instead of N retraces (chunks that land on the
        same padded shape reuse one executable). Results come back in
        input order. `chunk_size=None` runs everything as one chunk.
        This is what `repro.scenarios.SweepRunner` dispatches through.
        """
        requests = list(requests)
        if chunk_size is None or chunk_size >= len(requests):
            return self.run_many(requests)
        if chunk_size < 1:
            raise ValueError(f"chunk_size must be >= 1, got {chunk_size}")
        order = sorted(range(len(requests)),
                       key=lambda i: (requests[i].num_flows,
                                      requests[i].topo.num_links))
        out: List[SimResult] = [None] * len(requests)
        for lo in range(0, len(order), chunk_size):
            chunk = order[lo:lo + chunk_size]
            for i, res in zip(chunk, self.run_many([requests[i]
                                                    for i in chunk])):
                out[i] = res
        return out

    def fingerprint(self) -> str:
        """Identity string for result caching: two backends with the same
        fingerprint must produce identical results for the same request.
        Parameterized backends (m4) extend this with a weights hash."""
        return self.name

    def closed_loop(self, topo, config, flows):
        """Open a `ClosedLoopSession` (dynamic arrivals); optional."""
        raise NotImplementedError(
            f"backend {self.name!r} has no closed-loop session")


def _batch_probes(requests: Sequence[SimRequest]):
    """A vmapped batch shares one compiled program, so every request must
    carry the same (static) ProbeConfig."""
    probes = {r.probes for r in requests}
    if len(probes) > 1:
        raise ValueError(
            "run_many requires a uniform `probes` setting across the batch")
    return probes.pop() if probes else None


# ------------------------------------------------------------------- packet
@register_backend("packet")
class PacketBackend(Backend):
    """Reduced packet-level DES (the ns-3 stand-in): ground truth."""

    name = "packet"

    def run(self, request: SimRequest) -> SimResult:
        from ..net.packetsim import PacketSim
        flows = copy.deepcopy(list(request.flows))   # DES mutates flow state
        t0 = time.perf_counter()
        trace = PacketSim(request.topo, request.config,
                          seed=request.seed).run(flows, until=request.until)
        wall = time.perf_counter() - t0
        done = np.array([f.done for f in trace.flows])
        fcts = np.where(done, trace.fcts, np.nan)
        sldn = np.where(done, trace.slowdowns, np.nan)
        kw = {}
        if request.record_events:
            ev = trace.events
            kw = dict(event_times=np.array([e.time for e in ev]),
                      event_types=np.array([e.etype for e in ev]),
                      event_fids=np.array([e.fid for e in ev]),
                      event_remaining=tuple(tuple(e.remaining) for e in ev),
                      event_queues=tuple(tuple(e.path_queues) for e in ev))
        if request.probes is not None:
            # the DES has no device arenas; synthesize the same series
            # schema host-side from its ground-truth event records
            from ..obs.timeseries import series_from_packet_trace
            kw["probes"] = series_from_packet_trace(
                trace, request.probes, num_flows=len(flows))
        return SimResult(fcts=fcts, slowdowns=sldn, wall_time=wall,
                         backend=self.name, raw=trace, **kw)

    def closed_loop(self, topo, config, flows):
        from .closedloop import PacketSession
        return PacketSession(topo, config, flows)


# ------------------------------------------------------------------ flowsim
@register_backend("flowsim")
class FlowSimBackend(Backend):
    """Classical max-min flowSim, numpy event loop (paper §2.1 baseline)."""

    name = "flowsim"

    def run(self, request: SimRequest) -> SimResult:
        from ..core.flowsim import run_flowsim
        r = run_flowsim(request.topo, list(request.flows),
                        until=request.until,
                        record_events=request.record_events)
        kw = {}
        if request.record_events:
            kw = dict(event_times=r.event_times, event_types=r.event_types,
                      event_fids=r.event_fids)
        return SimResult(fcts=r.fcts, slowdowns=r.slowdowns,
                         wall_time=r.wallclock, backend=self.name, raw=r, **kw)

    def closed_loop(self, topo, config, flows):
        from .closedloop import FlowSimSession
        return FlowSimSession(topo, flows)


# ------------------------------------------------------------- flowsim_fast
@register_backend("flowsim_fast")
class FlowSimFastBackend(Backend):
    """flowSim as one jitted `lax.scan`; `run_many` vmaps across scenarios."""

    name = "flowsim_fast"

    def fingerprint(self) -> str:
        """"flowsim_fast-k<mode>": the resolved kernel mode (Pallas vs jnp
        row-min, see repro.kernels.dispatch) is part of the identity so
        cached sweep results never mix kernel paths."""
        from ..kernels.dispatch import resolve_mode
        return f"{self.name}-k{resolve_mode()}"

    def run(self, request: SimRequest) -> SimResult:
        from ..core.flowsim_fast import run_flowsim_fast
        self._check(request)
        r = run_flowsim_fast(request.topo, list(request.flows),
                             probes=request.probes)
        return SimResult(fcts=r.fcts, slowdowns=r.slowdowns,
                         wall_time=r.wallclock, backend=self.name,
                         probes=r.probes, raw=r)

    def run_many(self, requests: Sequence[SimRequest]) -> List[SimResult]:
        from ..core.flowsim_fast import run_flowsim_fast_batch
        for r in requests:
            self._check(r)
        probes = _batch_probes(requests)
        results = run_flowsim_fast_batch(
            [(r.topo, list(r.flows)) for r in requests], probes=probes)
        return [SimResult(fcts=r.fcts, slowdowns=r.slowdowns,
                          wall_time=r.wallclock, backend=self.name,
                          probes=r.probes, raw=r)
                for r in results]

    def closed_loop(self, topo, config, flows):
        # incremental closed-loop stepping is inherently event-at-a-time;
        # reuse the numpy max-min session (identical fluid semantics).
        from .closedloop import FlowSimSession
        return FlowSimSession(topo, flows)

    @staticmethod
    def _check(request: SimRequest):
        if request.until is not None:
            raise NotImplementedError(
                "flowsim_fast runs the full trace; `until` unsupported")


# ----------------------------------------------------------------------- m4
@register_backend("m4")
class M4Backend(Backend):
    """The learned flow-level simulator. Needs trained `params` + `M4Config`;
    `run_many` pads scenarios to one arena and vmaps the open-loop scan."""

    name = "m4"

    def __init__(self, params=None, cfg=None):
        if params is None or cfg is None:
            raise ValueError(
                'm4 backend needs model parameters: '
                'get_backend("m4", params=params, cfg=cfg)')
        from ..kernels.dispatch import canonicalize_cfg
        self.params, self.cfg = params, canonicalize_cfg(cfg)
        self._fingerprint = None

    def fingerprint(self) -> str:
        """"m4-<weights hash>-k<mode>": cached results are only valid for
        the exact parameters (and model shape) that produced them, and for
        the resolved kernel mode (Pallas vs jnp execution paths are not
        bitwise identical). The mode is pinned at backend construction
        (`canonicalize_cfg`). The weights hash is the same `tree_digest`
        the training pipeline reports (`TrainState.weights_hash`), so a
        checkpoint-resumed model and the uninterrupted run it bitwise
        reproduces share one sweep-cache identity, while any retrained
        weights get their own."""
        if self._fingerprint is None:
            from ..runtime.checkpoint import tree_digest
            h = hashlib.sha256(
                (repr(self.cfg) + tree_digest(self.params)).encode())
            self._fingerprint = \
                f"m4-{h.hexdigest()[:16]}-k{self.cfg.kernel_mode}"
        return self._fingerprint

    def run(self, request: SimRequest) -> SimResult:
        from ..core.simulate import simulate_open_loop
        self._check(request)
        r = simulate_open_loop(self.params, self.cfg, request.topo,
                               request.config, list(request.flows),
                               probes=request.probes)
        return SimResult(fcts=r.fcts, slowdowns=r.slowdowns,
                         wall_time=r.wallclock, backend=self.name,
                         probes=r.probes, raw=r)

    def run_many(self, requests: Sequence[SimRequest],
                 devices=None) -> List[SimResult]:
        """One vmapped scan over the batch. `devices` holds it to those
        devices (one chip of a host: the vmapped scan there, whatever
        the host's chip count); None shards across every local device
        when there is more than one (`simulate_open_loop_batch`)."""
        from ..core.simulate import simulate_open_loop_batch
        for r in requests:
            self._check(r)
        probes = _batch_probes(requests)
        results = simulate_open_loop_batch(
            self.params, self.cfg,
            [(r.topo, r.config, list(r.flows)) for r in requests],
            probes=probes, devices=devices)
        return [SimResult(fcts=r.fcts, slowdowns=r.slowdowns,
                          wall_time=r.wallclock, backend=self.name,
                          probes=r.probes, raw=r)
                for r in results]

    def closed_loop(self, topo, config, flows):
        from ..core.simulate import M4Simulator
        return M4Simulator(self.params, self.cfg, topo, config, list(flows))

    @staticmethod
    def _check(request: SimRequest):
        if request.until is not None:
            raise NotImplementedError(
                "m4 predicts the full trace; `until` unsupported")
