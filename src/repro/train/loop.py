"""Resumable, bucketed, multi-device training of m4 (§3.3, §5.1).

One `fit()` call owns the whole regime the seed scattered across ad-hoc
host loops:

- **Bucketed compilation.** The corpus is shape-bucketed
  (`train.batching`) and each bucket trains through ONE jitted step —
  `TRACE_COUNTS` counts the compiles, and a 16-sim shape-diverse corpus
  costs at most ceil(16/bucket_size) of them (the seed cost one per sim).
- **Two step semantics.** `step_mode="per_sim"` (default) `lax.scan`s
  over the bucket's sim axis applying one optimizer update per sim —
  the seed trainer's exact update schedule, compiled. `step_mode="batch"`
  averages gradients across the bucket in a single update (`jax.vmap`),
  and with more than one local device shards the bucket `jax.pmap`-style
  across them with `lax.psum` gradient averaging — the data-parallel
  mirror of `core/flowsim_fast.py`'s pmap(vmap(scan)) inference path.
- **Resume.** `TrainState` (params + AdamW moments + step + RNG) is
  checkpointed through `runtime.checkpoint` every `ckpt_every` epochs;
  a killed run re-invoked with the same `TrainConfig` restores the last
  committed epoch and walks the identical bucket sequence, reproducing
  the uninterrupted run's final parameters bitwise (asserted in
  tests/test_train.py).
- **Schedules & history.** Warmup+cosine LR over the true update count
  (`optim.schedules`), structured per-head/per-epoch history, and an
  optional held-out eval callback — `evaluate_m4` reports the paper's
  per-flow slowdown error against the flowSim baseline through the
  `repro.sim` registry.
"""
from __future__ import annotations

import dataclasses
import json
import os
import time
from collections import Counter
from dataclasses import dataclass
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.events import EventBatch
from ..core.model import M4Config, init_m4
from ..core.training import event_scan_losses
from ..obs.registry import get_registry
from ..obs.trace import get_tracer
from ..optim import adamw_init, adamw_update, clip_by_global_norm
from ..optim.schedules import linear_warmup_cosine
from ..runtime import checkpoint as ckpt
from ..runtime.checkpoint import tree_digest
from ..runtime.guards import check_finite, no_retrace
from .batching import make_buckets

# Compiles of the training step, by entry point — the training mirror of
# `core.simulate.TRACE_COUNTS`: Python side effects inside jit/pmap run
# only while tracing, so these count XLA programs, not calls.
TRACE_COUNTS = Counter()


@dataclass
class TrainState:
    """Everything a resumed run needs: parameters, AdamW moments (with
    the update counter inside), and the run's root RNG key — `rng`
    seeded the parameter init and drives the per-epoch bucket shuffle
    (folded by absolute epoch index, so resume replays the same walk)."""
    params: dict
    opt: dict
    rng: jax.Array

    @property
    def step(self) -> int:
        """Optimizer updates applied so far."""
        return int(self.opt["step"])

    def weights_hash(self) -> str:
        """Content digest of the parameters — the identity the m4
        backend fingerprint embeds (`runtime.checkpoint.tree_digest`),
        so resumed-vs-fresh models alias in the sweep cache iff they are
        bitwise identical."""
        return tree_digest(self.params)

    def tree(self) -> dict:
        return {"params": self.params, "opt": self.opt, "rng": self.rng}


def init_state(m4cfg: M4Config, seed: int = 0) -> TrainState:
    rng = jax.random.PRNGKey(seed)
    params = init_m4(rng, m4cfg)
    return TrainState(params=params, opt=adamw_init(params), rng=rng)


def load_state(ckpt_dir: Optional[str], m4cfg: M4Config, seed: int = 0,
               ) -> Tuple[Optional[TrainState], Optional[int]]:
    """Restore the latest committed `TrainState` from `ckpt_dir`.

    Returns (state, completed_epochs), or (None, None) when no committed
    checkpoint exists. A corrupt latest checkpoint falls back to the
    newest older one that loads (`restore_latest_loadable`); raises only
    when *no* committed checkpoint is readable — callers that can
    retrain should catch and start fresh."""
    if not ckpt_dir or ckpt.latest_step(ckpt_dir) is None:
        return None, None
    tree, step, _ = ckpt.restore_latest_loadable(
        ckpt_dir, init_state(m4cfg, seed).tree())
    return TrainState(**tree), step


@dataclass(frozen=True)
class TrainConfig:
    """Declarative knobs of one training run (safe to log verbatim)."""
    epochs: int = 10
    lr: float = 3e-4
    warmup_frac: float = 0.05     # fraction of total updates spent warming
    min_lr_frac: float = 0.05     # cosine floor as a fraction of lr
    schedule: str = "warmcos"     # "warmcos" | "const"
    bucket_size: int = 8          # sims padded+stacked per compiled step
    step_mode: str = "per_sim"    # "per_sim" (seed-faithful SGD) | "batch"
    w_sldn: float = 1.0           # per-head loss weights (0 = ablate)
    w_size: float = 1.0
    w_queue: float = 1.0
    clip_norm: float = 1.0
    weight_decay: float = 1e-4
    seed: int = 0
    shuffle: bool = True          # bucket order per epoch (seeded, stable)
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 1           # epochs between checkpoints
    keep_last: int = 3


def _make_schedule(tc: TrainConfig, total_updates: int):
    if tc.schedule == "const":
        return lambda step: jnp.asarray(tc.lr, jnp.float32)
    if tc.schedule == "warmcos":
        warm = max(1, int(tc.warmup_frac * total_updates))
        fn = linear_warmup_cosine(tc.lr, warm, max(total_updates, 2),
                                  min_frac=tc.min_lr_frac)
        # opt["step"] counts *applied* updates, so the i-th update sees
        # step == i; evaluate at i+1 so warmup starts at lr/warm instead
        # of a wasted lr=0 first update
        return lambda step: fn(step + 1)
    raise ValueError(f"unknown schedule {tc.schedule!r} "
                     "(want 'warmcos' or 'const')")


def _sim_loss(params, m4cfg: M4Config, tc: TrainConfig, b):
    """Weighted three-head loss of one sim (per-head means as aux)."""
    l = event_scan_losses(params, m4cfg, b)
    tot = tc.w_sldn * l["sldn"] + tc.w_size * l["size"] \
        + tc.w_queue * l["queue"]
    return tot, l


def _pack(tot, parts, lr, gn):
    return jnp.stack([tot, parts["sldn"], parts["size"], parts["queue"],
                      lr, gn])


def make_bucket_step(m4cfg: M4Config, tc: TrainConfig, schedule) -> Callable:
    """The compiled training step for one bucket.

    Returns `step(params, opt, arrays) -> (params, opt, outs)` where
    `outs` is (updates, 6): [total, sldn, size, queue, lr, grad_norm]
    per optimizer update. jit caches by bucket shape, so distinct padded
    shapes — not distinct sims — cost compiles.
    """
    def update(params, opt, grads):
        grads, gn = clip_by_global_norm(grads, tc.clip_norm)
        lr = schedule(opt["step"])
        params, opt = adamw_update(params, grads, opt, lr=lr,
                                   weight_decay=tc.weight_decay)
        return params, opt, lr, gn

    if tc.step_mode == "per_sim":
        @jax.jit
        def step(params, opt, bb):
            TRACE_COUNTS["train_step"] += 1

            def body(carry, b):
                params, opt = carry
                (tot, parts), grads = jax.value_and_grad(
                    _sim_loss, has_aux=True)(params, m4cfg, tc, b)
                params, opt, lr, gn = update(params, opt, grads)
                return (params, opt), _pack(tot, parts, lr, gn)

            (params, opt), outs = jax.lax.scan(body, (params, opt), bb)
            return params, opt, outs
        return step

    if tc.step_mode != "batch":
        raise ValueError(f"unknown step_mode {tc.step_mode!r} "
                         "(want 'per_sim' or 'batch')")

    def batch_loss(params, bb, w):
        """Weighted-mean bucket loss; `w` zeroes padded device lanes."""
        tots, parts = jax.vmap(
            lambda b: _sim_loss(params, m4cfg, tc, b))(bb)
        wsum = jnp.maximum(w.sum(), 1e-9)
        mean = lambda x: (x * w).sum() / wsum
        return mean(tots), {k: mean(v) for k, v in parts.items()}

    D = jax.local_device_count()

    @jax.jit
    def single_device_step(params, opt, bb):
        TRACE_COUNTS["train_step"] += 1
        w = jnp.ones((bb["t"].shape[0],), jnp.float32)
        (tot, parts), grads = jax.value_and_grad(
            batch_loss, has_aux=True)(params, bb, w)
        params, opt, lr, gn = update(params, opt, grads)
        return params, opt, _pack(tot, parts, lr, gn)[None]

    if D == 1:
        return single_device_step

    # pmap(vmap(·)) data parallelism, mirroring flowsim_fast's inference
    # sharding: the bucket's sim axis splits across local devices (padded
    # by repeating the last sim with weight 0), per-device weighted grad
    # *sums* are psum'd and normalized by the global weight — exact
    # gradient averaging regardless of pad lanes — and every device
    # applies the identical update, so out_axes=None returns one replica.
    from ..core.sharding import shard_leaves

    @partial_pmap
    def _pstep(params, opt, bb, w):
        TRACE_COUNTS["train_step_sharded"] += 1

        def local_sums(p):
            tots, parts = jax.vmap(
                lambda b: _sim_loss(p, m4cfg, tc, b))(bb)
            return (tots * w).sum(), {k: (v * w).sum()
                                      for k, v in parts.items()}
        (lsum, psums), gsums = jax.value_and_grad(
            local_sums, has_aux=True)(params)
        wsum = jnp.maximum(jax.lax.psum(w.sum(), "dev"), 1e-9)
        grads = jax.tree.map(lambda g: jax.lax.psum(g, "dev") / wsum, gsums)
        tot = jax.lax.psum(lsum, "dev") / wsum
        parts = {k: jax.lax.psum(v, "dev") / wsum for k, v in psums.items()}
        params, opt, lr, gn = update(params, opt, grads)
        return params, opt, _pack(tot, parts, lr, gn)[None]

    def step(params, opt, bb):
        B = int(bb["t"].shape[0])
        if B < D:   # tiny tail bucket: one device is plenty (still jitted)
            return single_device_step(params, opt, bb)
        w = jnp.ones((B,), jnp.float32)
        per = -(-B // D)
        w = jnp.concatenate([w, jnp.zeros((per * D - B,), jnp.float32)])
        return _pstep(params, opt, shard_leaves(bb, D), shard_leaves(w, D))
    # the same update on one device: the reference the sharded step is
    # checked against (`chip_smoke.py --chips 4`)
    step.single_device = single_device_step
    return step


def partial_pmap(fn):
    return jax.pmap(fn, axis_name="dev", in_axes=(None, None, 0, 0),
                    out_axes=None)


def _history_path(ckpt_dir: str) -> str:
    return os.path.join(ckpt_dir, "history.json")


def _write_history(ckpt_dir: str, history: List[dict]):
    """Atomic (tmp + rename) like the checkpoint itself — a kill mid-write
    must never leave a file that wedges the next resume."""
    path = _history_path(ckpt_dir)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(history, f, indent=1)
    os.replace(tmp, path)


def _read_history(ckpt_dir: str, epochs: int) -> List[dict]:
    """Best-effort: the checkpoint is the source of truth, so a missing
    or corrupt history file costs the loss log, never the resume."""
    try:
        return json.load(open(_history_path(ckpt_dir)))[:epochs]
    except (OSError, ValueError):
        return []


def fit(batches: Sequence[EventBatch], m4cfg: M4Config,
        tc: TrainConfig = TrainConfig(), *, state: Optional[TrainState] = None,
        log=print, eval_fn: Optional[Callable] = None, eval_every: int = 0,
        ) -> Tuple[TrainState, List[dict]]:
    """Train m4 on a corpus of `EventBatch`es; returns (state, history).

    history is one dict per epoch: {epoch, loss, sldn, size, queue, lr,
    grad_norm, wall_s, compile_s, step_s, compiles[, eval]} — `loss` is
    the sim-weighted epoch mean of the combined objective, the per-head
    entries its components. `wall_s` splits into `compile_s` (bucket
    steps that triggered an XLA trace, i.e. cold shapes) and `step_s`
    (steady-state steps); both include the device->host sync, so they
    sum to the loop's true wall. The same split streams into the
    process `repro.obs` registry (`train.compile_wall_s` /
    `train.step_wall_s` histograms) for `train_suite`'s report.

    With `tc.ckpt_dir` set, the run checkpoints every `ckpt_every`
    epochs and AUTO-RESUMES: if a committed checkpoint exists, training
    continues from it (same bucket walk, bitwise-identical outcome to an
    uninterrupted run). A finished run restores and returns immediately.
    """
    batches = list(batches)
    if not batches:
        raise ValueError("empty training corpus")
    buckets = make_buckets(batches, tc.bucket_size)
    updates_per_epoch = len(batches) if tc.step_mode == "per_sim" \
        else len(buckets)
    schedule = _make_schedule(tc, tc.epochs * updates_per_epoch)
    step_fn = make_bucket_step(m4cfg, tc, schedule)

    if state is None:
        state = init_state(m4cfg, tc.seed)
    params, opt, rng = state.params, state.opt, state.rng
    history: List[dict] = []
    start_epoch = 0
    if tc.ckpt_dir and ckpt.latest_step(tc.ckpt_dir) is not None:
        if state is not None:
            log(f"[train] NOTE: ckpt_dir {tc.ckpt_dir} has a committed "
                "checkpoint — it takes precedence over the passed `state` "
                "(use a fresh ckpt_dir to warm-start from `state`)")
        try:
            tree, start_epoch, skipped = ckpt.restore_latest_loadable(
                tc.ckpt_dir, {"params": params, "opt": opt, "rng": rng})
        except FileNotFoundError as exc:
            # every committed checkpoint is unreadable: worth a loud
            # warning, but a fresh start beats failing the whole run
            log(f"[train] WARNING: {exc} — starting fresh")
            tree, start_epoch, skipped = None, 0, []
        if tree is not None:
            for bad_step, why in skipped:
                log(f"[train] skipping corrupt checkpoint "
                    f"step {bad_step}: {why}")
            params, opt, rng = tree["params"], tree["opt"], tree["rng"]
            history = _read_history(tc.ckpt_dir, start_epoch)
            log(f"[train] resumed from {tc.ckpt_dir} at epoch "
                f"{start_epoch} (step {int(opt['step'])})"
                + (f" — recovered past {len(skipped)} corrupt "
                   "checkpoint(s)" if skipped else ""))

    shapes = sorted({b.shape for b in buckets})
    if start_epoch < tc.epochs:
        log(f"[train] {len(batches)} sims -> {len(buckets)} bucket(s) "
            f"{shapes}, {updates_per_epoch} update(s)/epoch x "
            f"{tc.epochs} epochs [{tc.step_mode}]")

    # compile budget for the whole run: one executable per distinct bucket
    # shape per step path (tiny tail buckets fall back to the single-device
    # jit, so a shape can hit two targets). eval_fn compiles in the
    # simulate counter family, which this guard deliberately excludes —
    # those are budgeted where the sweep wraps them.
    reg = get_registry()
    tracer = get_tracer()
    with no_retrace(allowed=2 * len(shapes),
                    counters={"train.loop": TRACE_COUNTS}, label="fit"):
        for ep in range(start_epoch, tc.epochs):
            ep_span = tracer.span("train.epoch", attrs={"epoch": ep})
            t0 = time.perf_counter()
            order = np.arange(len(buckets), dtype=np.int64)
            if tc.shuffle:
                # derived from the state's root RNG key by *absolute* epoch
                # (fold_in, not sequential draws), so a resumed run replays
                # the identical bucket walk — part of the bitwise guarantee
                order = np.asarray(jax.random.permutation(
                    jax.random.fold_in(rng, ep), len(buckets)))
            outs_all, weights = [], []
            compile_s = step_s = 0.0
            ep_compiles = 0
            for bi in order:
                b = buckets[int(bi)]
                c0 = sum(TRACE_COUNTS.values())
                ts = time.perf_counter()
                params, opt, outs = step_fn(params, opt, b.arrays)
                # the host transfer blocks on the device computation, so
                # keeping it inside the window times the true step wall
                outs = np.asarray(outs)
                dt = time.perf_counter() - ts
                new_traces = sum(TRACE_COUNTS.values()) - c0
                if new_traces:
                    compile_s += dt
                    ep_compiles += new_traces
                else:
                    step_s += dt
                check_finite(f"train step outs (epoch {ep})", outs)
                outs_all.append(outs)
                # per_sim: one row per sim; batch: one bucket-mean row
                weights.append(np.full(len(outs), b.size / len(outs),
                                       np.float64))
            reg.inc("train.steps", len(order))
            if ep_compiles:
                reg.inc("train.compiles", ep_compiles)
                reg.observe("train.compile_wall_s", compile_s)
            reg.observe("train.step_wall_s", step_s)
            outs = np.concatenate(outs_all)
            w = np.concatenate(weights)
            mean = (outs * w[:, None]).sum(0) / w.sum()
            entry = {"epoch": ep, "loss": float(mean[0]),
                     "sldn": float(mean[1]), "size": float(mean[2]),
                     "queue": float(mean[3]), "lr": float(outs[-1, 4]),
                     "grad_norm": float(mean[5]),
                     "wall_s": round(time.perf_counter() - t0, 3),
                     "compile_s": round(compile_s, 3),
                     "step_s": round(step_s, 3),
                     "compiles": ep_compiles}
            if eval_fn is not None and eval_every and \
                    ((ep + 1) % eval_every == 0 or ep + 1 == tc.epochs):
                entry["eval"] = eval_fn(params)
            history.append(entry)
            log(f"[train] epoch {ep}: loss={entry['loss']:.4f} "
                f"(sldn={entry['sldn']:.4f} size={entry['size']:.4f} "
                f"queue={entry['queue']:.4f}) lr={entry['lr']:.2e} "
                f"{entry['wall_s']:.1f}s"
                + (f" (compile {entry['compile_s']:.1f}s)"
                   if ep_compiles else ""))
            ep_span.end(loss=entry["loss"], compiles=ep_compiles,
                        compile_s=entry["compile_s"],
                        step_s=entry["step_s"])
            if tc.ckpt_dir and ((ep + 1) % tc.ckpt_every == 0
                                or ep + 1 == tc.epochs):
                tree = {"params": params, "opt": opt, "rng": rng}
                ckpt.save(tc.ckpt_dir, ep + 1, tree, keep_last=tc.keep_last)
                _write_history(tc.ckpt_dir, history)
                # test hook: deterministic "kill" right after a checkpoint
                # commits — os._exit skips every cleanup path, so the
                # resume test exercises exactly what a SIGKILL mid-run
                # leaves behind
                if os.environ.get("REPRO_TRAIN_ABORT_AFTER_EPOCH") \
                        == str(ep + 1):
                    os._exit(17)

    return TrainState(params=params, opt=opt, rng=rng), history


# ---------------------------------------------------------------- evaluation
def evaluate_m4(params, m4cfg: M4Config, specs: Sequence, *,
                cache_dir: Optional[str] = None, request_seed: int = 0,
                chunk_size: int = 8, baseline: str = "flowsim") -> dict:
    """Held-out eval through the `repro.sim` registry: per-flow slowdown
    error of m4 vs the packet ground truth, against the `baseline`
    backend (the paper's headline metric, §5.2).

    Ground truth and the baseline go through `SweepRunner` so a
    `cache_dir` makes repeated evals (every epoch, every resume) pay the
    packet DES once; m4 runs uncached (`run_chunked` -> one batched
    compile per shape bucket) because its params change between calls.
    """
    from ..scenarios import SweepRunner
    from ..sim import get_backend
    specs = list(specs)
    gt_rep = SweepRunner(get_backend("packet"), cache_dir=cache_dir,
                         chunk_size=chunk_size).run(specs,
                                                    seed=request_seed)
    base_rep = SweepRunner(get_backend(baseline), cache_dir=cache_dir,
                           chunk_size=chunk_size).run(specs,
                                                      seed=request_seed)
    m4 = get_backend("m4", params=params, cfg=m4cfg)
    m4_res = m4.run_chunked([s.to_request(seed=request_seed) for s in specs],
                            chunk_size)

    def err(res, gt):
        e = np.abs(res.slowdowns - gt) / gt
        return float(np.nanmean(e))

    rows = []
    for spec, g, b, m in zip(specs, gt_rep.entries, base_rep.entries, m4_res):
        gt = g.result.slowdowns
        rows.append({"scenario": spec.label,
                     "m4_err": err(m, gt),
                     f"{baseline}_err": err(b.result, gt)})
    m4_err = float(np.mean([r["m4_err"] for r in rows]))
    base_err = float(np.mean([r[f"{baseline}_err"] for r in rows]))
    return {"m4_err_mean": m4_err, f"{baseline}_err_mean": base_err,
            "baseline": baseline, "m4_beats_baseline": m4_err < base_err,
            "rows": rows}


# ------------------------------------------------------------- one-call API
def train_suite(suite, m4cfg: M4Config, tc: TrainConfig = TrainConfig(), *,
                data_root: str, workers: int = 0,
                max_events: Optional[int] = None,
                eval_specs: Optional[Sequence] = None,
                eval_cache_dir: Optional[str] = None,
                log=print) -> Tuple[TrainState, dict]:
    """Suite -> cached dataset -> fit -> (optional) held-out eval.

    The one-call pipeline the CLI (`python -m repro.train`), the
    benchmark artifact (`benchmarks.common.trained_m4`) and the
    quickstart all share. Returns (TrainState, report) where `report` is
    the structured payload written to results/train_log.json.
    """
    from .data import build_dataset
    t0 = time.perf_counter()
    specs = list(suite)
    batches, data_report = build_dataset(specs, m4cfg, data_root,
                                         max_events=max_events,
                                         workers=workers, log=log)
    c0 = sum(TRACE_COUNTS.values())
    state, history = fit(batches, m4cfg, tc, log=log)
    compiles = sum(TRACE_COUNTS.values()) - c0
    report = {
        "suite": getattr(suite, "name", "corpus"),
        "num_sims": len(specs),
        "model": dataclasses.asdict(m4cfg),
        "train_config": dataclasses.asdict(tc),
        "dataset": {"key": data_report.corpus_key,
                    "hits": data_report.hits, "misses": data_report.misses,
                    "root": data_root},
        "train": {"epochs": history, "compiles": compiles,
                  "updates": state.step,
                  # run-level compile-vs-steady wall split (sums of the
                  # per-epoch entries; epochs resumed from a checkpoint
                  # contribute their recorded walls)
                  "compile_s": round(sum(e.get("compile_s", 0.0)
                                         for e in history), 3),
                  "step_s": round(sum(e.get("step_s", 0.0)
                                      for e in history), 3)},
        "weights_hash": state.weights_hash(),
    }
    if eval_specs:
        report["eval"] = evaluate_m4(state.params, m4cfg, eval_specs,
                                     cache_dir=eval_cache_dir)
        e = report["eval"]
        log(f"[train] held-out eval: m4 err {e['m4_err_mean']:.3f} vs "
            f"{e['baseline']} {e[e['baseline'] + '_err_mean']:.3f} "
            f"({'beats' if e['m4_beats_baseline'] else 'LOSES TO'} baseline)")
    report["wall_s"] = round(time.perf_counter() - t0, 2)
    # the process repro.obs snapshot (train.* histograms + any sweep/eval
    # counters) rides along in train_log.json, so
    # `python -m repro.obs --merge results/train_log.json` just works
    report["obs"] = get_registry().snapshot()
    return state, report


def write_train_log(report: dict, path: str = "results/train_log.json"):
    """Persist the `train_suite` report (what
    `benchmarks/make_experiments.py` renders)."""
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w") as f:
        json.dump(report, f, indent=1)
    return path
