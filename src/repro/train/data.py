"""Ground-truth dataset generation + the content-hash shard store.

Training data is expensive: every sim is a full packet-level DES run
(`PacketSim`) followed by host-side event-tensor assembly
(`build_event_batch`). This module makes that a build system, not a
script: a corpus is declared as a `repro.scenarios` suite (or any list of
`ScenarioSpec`s), each spec becomes one on-disk *shard* keyed by the
content hash of everything that determines its bytes — the materialized
`SimRequest` (topology, NetConfig, full flow list, packet seed) plus the
event-tensor layout (`snap_flows`/`snap_links`/`max_path`, the event cap)
— and a re-build of an overlapping corpus touches only the missing keys.
CI caches the store directory under the aggregate `dataset_key`.

Cache misses fan out across worker *processes* (the DES is pure-Python
and CPU-bound, so threads won't do); workers are fresh interpreters, not
forks — the parent usually has JAX initialized, and forking a live XLA
runtime is undefined behaviour — and they run on the CPU, so any number
of them can start beside a process that owns the chip. The pool is a
`repro.fleet` run (one task per missing shard, the store as result
channel), so multi-worker builds inherit lease-based claiming,
crash/straggler reaping and retry with backoff instead of dying with the
first worker exception — and a
killed build resumes from whatever shards completed. Storage is
`runtime.blobstore.BlobStore` — the same sharded content-addressed
directory scheme, compression and atomic-write discipline as
`repro.scenarios.ResultCache` — so concurrent builds of overlapping
corpora are safe.
"""
from __future__ import annotations

import hashlib
import time
from dataclasses import dataclass, field
from typing import List, Optional, Sequence, Tuple

import numpy as np

from ..core.events import EventBatch, build_event_batch
from ..core.model import M4Config
from ..runtime.blobstore import BlobStore

_FORMAT_VERSION = 1   # bump to invalidate every shard (layout change)


def shard_key(spec, m4cfg: M4Config, *, max_events: Optional[int] = None,
              request_seed: int = 0) -> str:
    """Content hash of one training shard.

    Keyed on the *materialized request* (flows + topology + NetConfig +
    packet seed — `SimRequest.content_hash()`), not the spec's name or
    field spelling, so two specs that generate the same scenario share
    one shard; plus the `EventBatch` layout knobs that change the tensor
    bytes. Generating the flows costs a little per call — the same
    deliberate trade as the sweep result cache (stale-proof keys).
    """
    req = spec.to_request(seed=request_seed)
    layout = (f"v{_FORMAT_VERSION}|sf:{m4cfg.snap_flows}"
              f"|sl:{m4cfg.snap_links}|p:{m4cfg.max_path}"
              f"|ev:{'all' if max_events is None else int(max_events)}")
    return hashlib.sha256(
        f"{req.content_hash()}|{layout}".encode()).hexdigest()


def dataset_key_from_shards(keys: Sequence[str]) -> str:
    """Aggregate corpus hash from already-computed shard keys
    (order-independent). `DatasetReport.corpus_key` uses this so callers
    that just ran `build_dataset` don't re-materialize every spec's flow
    list a second time."""
    return hashlib.sha256("|".join(sorted(keys)).encode()).hexdigest()


def dataset_key(specs: Sequence, m4cfg: M4Config, *,
                max_events: Optional[int] = None,
                request_seed: int = 0) -> str:
    """Aggregate content hash of a whole corpus (order-independent).

    This is what CI keys the cached store directory on: it changes iff
    at least one shard's content key changes.
    """
    return dataset_key_from_shards(
        [shard_key(s, m4cfg, max_events=max_events,
                   request_seed=request_seed) for s in specs])


class DatasetStore(BlobStore):
    """Blob store of compressed `EventBatch` shards addressed by content
    key (the `to_arrays`/`from_arrays` contract in `core.events`)."""

    def _encode(self, batch: EventBatch) -> dict:
        return {
            name: (arr.dtype.str, list(arr.shape),
                   np.ascontiguousarray(arr).tobytes())
            for name, arr in batch.to_arrays().items()}

    def _decode(self, payload: dict) -> EventBatch:
        # .copy(): frombuffer views are read-only — a cache hit must be
        # as mutable as a freshly built batch
        arrays = {
            name: np.frombuffer(buf, np.dtype(dt)).reshape(shape).copy()
            for name, (dt, shape, buf) in payload.items()}
        return EventBatch.from_arrays(arrays)


def _build_one(spec, m4cfg: M4Config, max_events, request_seed) -> EventBatch:
    """One spec -> packet ground truth -> event tensors (pure numpy; this
    is the function the worker pool runs)."""
    from ..sim import get_backend
    req = spec.to_request(seed=request_seed)
    trace = get_backend("packet").run(req).raw
    return build_event_batch(trace, m4cfg, max_events=max_events)


def _worker(args) -> Tuple[str, str]:
    """Build + persist one shard inline; returns (key, path). Kept as the
    single-process path (workers<=1) — multi-worker builds go through
    `repro.fleet.DatasetJob`, which calls the same `_build_one`."""
    root, key, spec, m4cfg, max_events, request_seed = args
    batch = _build_one(spec, m4cfg, max_events, request_seed)
    path = DatasetStore(root).put(key, batch)
    return key, path


@dataclass
class DatasetReport:
    """What one `build_dataset` call did (the cache-hit acceptance
    numbers come from here)."""
    keys: List[str]
    hits: int
    misses: int
    wall_s: float
    root: str
    built_paths: List[str] = field(default_factory=list)
    fleet: Optional[dict] = None   # FleetMetrics of a multi-worker build

    @property
    def hit_rate(self) -> float:
        return self.hits / max(len(self.keys), 1)

    @property
    def corpus_key(self) -> str:
        """The aggregate dataset hash (== `dataset_key` of the specs)."""
        return dataset_key_from_shards(self.keys)


def build_dataset(specs: Sequence, m4cfg: M4Config, root: str, *,
                  max_events: Optional[int] = None, workers: int = 0,
                  request_seed: int = 0, fleet=None,
                  log=None) -> Tuple[List[EventBatch], DatasetReport]:
    """Materialize the corpus: serve hits from the store, fan misses
    across `workers` supervised fleet processes (0/1 = build inline),
    return batches in spec order plus a `DatasetReport`.

    Multi-worker builds run as a `repro.fleet` job — one task per
    missing shard, the store as the result channel — so a crashed or
    wedged worker costs a retry, not the build, and a killed build
    resumes from completed shards. A shard that fails deterministically
    is quarantined to the fleet's poison manifest and reported here as
    an IOError naming it (a corpus with holes can't train). `fleet`
    accepts a `repro.fleet.FleetConfig` to override supervision knobs
    (its `workers` wins over the `workers` argument).

    Determinism: a spec's shard bytes depend only on its content key —
    flow generation is seeded by `spec.seed`, the DES by `request_seed` —
    so inline and fleet builds of the same corpus are identical
    (asserted in tests/test_train.py), and every miss is reproducible
    in isolation.
    """
    specs = list(specs)
    store = DatasetStore(root)
    t0 = time.perf_counter()
    keys = [shard_key(s, m4cfg, max_events=max_events,
                      request_seed=request_seed) for s in specs]
    batches: List[Optional[EventBatch]] = [store.get(k) for k in keys]
    miss = [i for i, b in enumerate(batches) if b is None]
    hits = len(specs) - len(miss)
    built_paths = []
    fleet_metrics = None
    if miss:
        if log:
            log(f"[train.data] {hits} cached, building {len(miss)} shard(s)"
                f" with {max(workers, 1)} worker(s)")
        use_pool = (fleet is not None or (workers and workers > 1)) \
            and len(miss) > 1
        if use_pool:
            from ..fleet import (DatasetJob, FleetConfig, dataset_tasks,
                                 default_coord_dir, run_fleet)
            job = DatasetJob(root=root, m4cfg=m4cfg, max_events=max_events,
                             request_seed=request_seed)
            tasks = dataset_tasks([specs[i] for i in miss],
                                  [keys[i] for i in miss])
            config = fleet if fleet is not None \
                else FleetConfig(workers=min(workers, len(miss)))
            if config.coord_dir is None:
                config = config.with_coord_dir(
                    default_coord_dir(root, tasks))
            fleet_metrics = run_fleet(tasks, job, config, log=log).as_dict()
            for i in miss:
                batches[i] = store.get(keys[i])
                if batches[i] is None:
                    raise IOError(
                        f"shard {keys[i][:12]} missing after fleet build "
                        f"({fleet_metrics['poisoned']} shard(s) poisoned — "
                        f"see {config.coord_dir}/poison/)")
                built_paths.append(store._path(keys[i]))
        else:
            jobs = [(root, keys[i], specs[i], m4cfg, max_events,
                     request_seed) for i in miss]
            for job in jobs:
                key, path = _worker(job)
                built_paths.append(path)
            for i in miss:
                batches[i] = store.get(keys[i])
                if batches[i] is None:
                    raise IOError(
                        f"freshly built shard {keys[i][:12]} unreadable")
    report = DatasetReport(keys=keys, hits=hits, misses=len(miss),
                           wall_s=time.perf_counter() - t0, root=root,
                           built_paths=built_paths, fleet=fleet_metrics)
    if log:
        log(f"[train.data] corpus ready: {len(specs)} shard(s), "
            f"{report.hits} hit / {report.misses} built, "
            f"{report.wall_s:.1f}s")
    return batches, report
